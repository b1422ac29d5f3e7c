"""Public wrappers for the Pallas kernels — the only place that picks
``interpret``.

Where JAX's default backend is a TPU the kernels compile to Mosaic. On any
other backend (the CPU test runs, ``JAX_PLATFORMS=cpu``) they run with
``interpret=True``: the kernel body executes exactly, block by block, which
checks the TPU program's logic but says nothing about its speed. The kernel
modules have no ``interpret`` default, so every caller on the training path
comes through here. ``use_pallas=True`` paths in the models route here too.

Autodiff: ``pallas_call`` with carried VMEM scratch has no JVP rule, so each
kernel is wrapped in ``jax.custom_vjp`` whose backward differentiates the
mathematically-identical XLA path (models/layers.blocked_attention,
models/rwkv6.wkv6_chunked, models/mamba2.ssd_chunked) — forward speed from
the kernel, exact gradients from XLA. A fused backward kernel is the
next step for TPU performance work.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import shard_codec as _codec
from repro.kernels import ssd as _ssd
from repro.kernels import wkv6 as _wkv6
from repro.models.layers import MaskSpec


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# Flash attention.
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _fa_op(q, k, v, static):
    spec, scale, softcap, q_offset = static
    window = spec.window
    return _fa.flash_attention_kernel(
        q, k, v, scale=scale, softcap=softcap, kind=spec.kind, window=window,
        prefix_len=spec.prefix_len, q_offset=q_offset, interpret=_interpret())


def _fa_fwd(q, k, v, static):
    return _fa_op(q, k, v, static), (q, k, v)


def _fa_bwd(static, res, g):
    from repro.models.layers import blocked_attention

    spec, scale, softcap, q_offset = static
    q, k, v = res

    def xla(q, k, v):
        return blocked_attention(q, k, v, spec, scale=scale, softcap=softcap,
                                 q_offset=q_offset,
                                 is_local=True if spec.window else None,
                                 use_pallas=False)

    _, vjp = jax.vjp(xla, q, k, v)
    return vjp(g)


_fa_op.defvjp(_fa_fwd, _fa_bwd)


def flash_attention(q, k, v, spec: MaskSpec, *, scale, softcap=0.0,
                    q_offset=0, is_local=None, block_q=128, block_k=128):
    """Contract-compatible with models.layers.blocked_attention.

    ``is_local`` must be static here (None/True/False): a traced per-layer
    flag (gemma2 inside lax.scan) stays on the XLA path — see DESIGN.md §6.
    """
    if is_local is not None and not isinstance(is_local, bool):
        raise ValueError("pallas path needs a static is_local; use the XLA path")
    if is_local is False:
        spec = MaskSpec(spec.kind, window=0, prefix_len=spec.prefix_len)
    return _fa_op(q, k, v, (spec, float(scale), float(softcap), int(q_offset)))


# ---------------------------------------------------------------------------
# WKV6.
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _wkv6_op(r, k, v, lw, u_state, chunk):
    u, state = u_state
    return _wkv6.wkv6_kernel(r, k, v, lw, u, state=state, chunk=chunk,
                             interpret=_interpret())


def _wkv6_fwd(r, k, v, lw, u_state, chunk):
    return _wkv6_op(r, k, v, lw, u_state, chunk), (r, k, v, lw, u_state)


def _wkv6_bwd(chunk, res, g):
    from repro.models.rwkv6 import wkv6_chunked

    r, k, v, lw, (u, state) = res

    def xla(r, k, v, lw, u, state):
        return wkv6_chunked(r, k, v, lw, u, state=state, chunk=min(chunk, 32))

    state_in = state if state is not None else jnp.zeros(
        (r.shape[0], r.shape[2], r.shape[3], r.shape[3]), jnp.float32)
    _, vjp = jax.vjp(lambda *a: xla(*a), r, k, v, lw, u, state_in)
    dr, dk, dv, dlw, du, dstate = vjp(g)
    return dr, dk, dv, dlw, (du, None if state is None else dstate)


_wkv6_op.defvjp(_wkv6_fwd, _wkv6_bwd)


def wkv6(r, k, v, lw, u, state=None, *, chunk=64):
    return _wkv6_op(r, k, v, lw, (u, state), chunk)


# ---------------------------------------------------------------------------
# SSD (Mamba2).
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _ssd_op(x, dt, A_log, BC, state, chunk):
    Bm, Cm = BC
    return _ssd.ssd_kernel(x, dt, A_log, Bm, Cm, state=state, chunk=chunk,
                           interpret=_interpret())


def _ssd_fwd(x, dt, A_log, BC, state, chunk):
    return _ssd_op(x, dt, A_log, BC, state, chunk), (x, dt, A_log, BC, state)


def _ssd_bwd(chunk, res, g):
    from repro.models.mamba2 import ssd_chunked

    x, dt, A_log, (Bm, Cm), state = res
    state_in = state if state is not None else jnp.zeros(
        (x.shape[0], x.shape[2], x.shape[3], Bm.shape[-1]), jnp.float32)

    def xla(x, dt, A_log, Bm, Cm, st):
        return ssd_chunked(x, dt, A_log, Bm, Cm, state=st, chunk=min(chunk, 32))

    _, vjp = jax.vjp(xla, x, dt, A_log, Bm, Cm, state_in)
    dx, ddt, dA, dB, dC, dstate = vjp(g)
    return dx, ddt, dA, (dB, dC), (None if state is None else dstate)


_ssd_op.defvjp(_ssd_fwd, _ssd_bwd)


def ssd(x, dt, A_log, Bm, Cm, state=None, *, chunk=64):
    return _ssd_op(x, dt, A_log, (Bm, Cm), state, chunk)


# ---------------------------------------------------------------------------
# Shard codec.
# ---------------------------------------------------------------------------


@jax.jit
def shard_encode(x_blocks):
    """(nb, 256) fp32 → (int8 codes (nb, 256), fp32 scales (nb,))."""
    return _codec.shard_encode_kernel(x_blocks, interpret=_interpret())


@jax.jit
def shard_decode(codes, scales):
    """Inverse of :func:`shard_encode`: ``codes * scales`` in fp32."""
    return _codec.shard_decode_kernel(codes, scales, interpret=_interpret())
