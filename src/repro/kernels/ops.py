"""Public wrappers for the Pallas kernels — the only place that picks
``interpret``.

Where JAX's default backend is a TPU the kernels compile to Mosaic. On any
other backend (the CPU test runs, ``JAX_PLATFORMS=cpu``) they run with
``interpret=True``: the kernel body executes exactly, block by block, which
checks the TPU program's logic but says nothing about its speed. The kernel
modules have no ``interpret`` default, so every caller on the training path
comes through here. ``use_pallas=True`` paths in the models route here too.

Autodiff: ``pallas_call`` with carried VMEM scratch has no JVP rule, so each
kernel is wrapped in ``jax.custom_vjp``. Flash attention's backward is its
own pair of kernels (dK/dV, then dQ, from the forward's output and row
logsumexp), so attention trains with no S×S tensor in HBM in either
direction. WKV6 and SSD keep a forward kernel whose backward differentiates
the mathematically identical XLA path (models/rwkv6.wkv6_chunked,
models/mamba2.ssd_chunked).
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import flash_attention as _fa
from repro.kernels import shard_codec as _codec
from repro.kernels import ssd as _ssd
from repro.kernels import wkv6 as _wkv6
from repro.models.layers import MaskSpec, blocked_attention, note_attention_site


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# Flash attention.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class _FaArgs:
    """The static half of one attention call."""

    scale: float
    softcap: float
    kind: str
    window: int
    prefix_len: int
    q_offset: int
    blocks: tuple  # ((bq, bk) of the forward, the dK/dV and the dQ kernel)

    def kw(self):
        return dict(scale=self.scale, softcap=self.softcap, kind=self.kind,
                    window=self.window, prefix_len=self.prefix_len,
                    q_offset=self.q_offset, interpret=_interpret())


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _fa_op(q, k, v, a: _FaArgs):
    return _fa_fwd(q, k, v, a)[0]


def _fa_fwd(q, k, v, a: _FaArgs):
    (bq, bk), _, _ = a.blocks
    o, lse = _fa.flash_attention_fwd(q, k, v, block_q=bq, block_k=bk,
                                     **a.kw())
    return o, (q, k, v, o, lse)


def _fa_bwd(a: _FaArgs, res, do):
    _, dkv, dq = a.blocks
    return _fa.flash_attention_bwd(*res, do, blocks_dkv=dkv, blocks_dq=dq,
                                   **a.kw())


_fa_op.defvjp(_fa_fwd, _fa_bwd)


def _per_shard(q, k):
    """How the kernel meets the traced program's mesh: ``None`` on one
    device (or inside a ``shard_map`` already), ``(mesh, spec)`` to map it
    per shard, batch over every axis but ``model`` and heads over
    ``model``, or ``False`` where the batch or the heads do not divide."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.size == 1 or mesh.are_all_axes_manual:
        return None
    if mesh.manual_axes:
        return False
    heads = "model" if "model" in mesh.axis_names else None
    batch = tuple(a for a in mesh.axis_names if a != heads)
    nb = math.prod(mesh.shape[a] for a in batch)
    nm = mesh.shape[heads] if heads else 1
    if q.shape[0] % nb or q.shape[2] % nm or k.shape[2] % nm:
        return False
    return mesh, P(batch, None, heads, None)


def flash_attention(q, k, v, spec: MaskSpec, *, scale, softcap=0.0,
                    q_offset=0, is_local=None):
    """Contract-compatible with models.layers.blocked_attention.

    Runs the Pallas kernel, forward and backward, where its contract holds
    for the input: a static ``is_local`` (None/True/False) and
    ``q_offset``, lengths that divide into blocks of at least 128
    (``flash_attention.choose_blocks``, which also picks the block sizes),
    and a batch and heads that divide the traced program's mesh. Any other
    input, Gemma2's traced per-layer ``is_local`` among them, takes the XLA
    path for forward and backward alike.

    Where the traced program's mesh (``jax.set_mesh``, or the abstract mesh
    of ``jax.sharding.use_abstract_mesh`` as the trainer sets it) spans more
    than one device, the kernel runs per shard under ``jax.shard_map``, so
    the ``pallas_call`` never sees a global array.
    """
    blocks = _fa.choose_blocks(q.shape[1], k.shape[1])
    shard = _per_shard(q, k)
    if (blocks is None or shard is False or not isinstance(q_offset, int)
            or not (is_local is None or isinstance(is_local, bool))):
        return blocked_attention(q, k, v, spec, scale=scale, softcap=softcap,
                                 q_offset=q_offset, is_local=is_local)
    note_attention_site("pallas")
    a = _FaArgs(float(scale), float(softcap), spec.kind,
                0 if is_local is False else spec.window, spec.prefix_len,
                q_offset, tuple(blocks[n] for n in ("fwd", "dkv", "dq")))

    def call(q, k, v):
        return _fa_op(q, k, v, a)

    if shard is not None:
        mesh, pspec = shard
        # Every operand and the output are split over every mesh axis, so
        # no value is replicated across shards and the vma check has
        # nothing to add (it would need each pallas_call output typed).
        call = jax.shard_map(call, mesh=mesh, in_specs=(pspec,) * 3,
                             out_specs=pspec, check_vma=False)
    return call(q, k, v)


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
