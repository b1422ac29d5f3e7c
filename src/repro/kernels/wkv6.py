"""WKV6 (RWKV-6 recurrence) Pallas-TPU kernel — chunked linear attention with
data-dependent per-channel decay.

TPU adaptation (DESIGN.md §3): the official RWKV CUDA kernel assigns one
thread per channel and serializes over time; on TPU we instead use the
numerically-stable *chunked* form (see models/rwkv6.wkv6_chunked): per chunk
of C steps all exponentials take non-positive arguments (cumulative log-decay
differences), the O(C²·hd) intra-chunk term is vectorized in VMEM, and the
(hd×hd) state is carried in fp32 VMEM scratch across the sequential chunk
grid axis. Grid: (B·H parallel, n_chunks sequential).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl


def _wkv6_kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, s0_ref, o_ref, sf_ref,
                 state_scr, *, chunk, n_chunks):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = s0_ref[0].astype(jnp.float32)

    r = r_ref[0].astype(jnp.float32)  # (C, hd)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    lw = lw_ref[0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)  # (1, hd) -> broadcast
    S = state_scr[...]  # (hd, hd) [key-channel, value-channel]

    C = r.shape[0]
    Lc = jnp.cumsum(lw, axis=0)  # inclusive
    Lx = Lc - lw  # exclusive

    # Intra-chunk: A[t,j] = Σ_c r[t,c] k[j,c] exp(Lx[t,c] − Lc[j,c]) (j<t).
    D = jnp.exp(jnp.minimum(Lx[:, None, :] - Lc[None, :, :], 0.0))  # (C,C,hd)
    A = jnp.sum(r[:, None, :] * k[None, :, :] * D, axis=-1)  # (C,C)
    tri = lax.broadcasted_iota(jnp.int32, (C, C), 0) > lax.broadcasted_iota(
        jnp.int32, (C, C), 1)
    A = jnp.where(tri, A, 0.0)
    diag = jnp.sum(r * k * u, axis=-1)  # (C,)
    o = jax.lax.dot_general(A, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    o = o + diag[:, None] * v
    # Inter-chunk: o += (r ⊙ exp(Lx)) @ S.
    o = o + jax.lax.dot_general(r * jnp.exp(Lx), S, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    o_ref[0] = o.astype(o_ref.dtype)

    # State update: S' = exp(L_C) ⊙ S + Σ_j (k_j ⊙ exp(L_C − L_j)) v_jᵀ.
    Llast = Lc[-1:, :]  # (1, hd)
    kk = k * jnp.exp(Llast - Lc)  # (C, hd)
    S_new = jnp.exp(Llast).T * S + jax.lax.dot_general(
        kk, v, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    state_scr[...] = S_new

    @pl.when(ci == n_chunks - 1)
    def _final():
        sf_ref[0] = S_new


def wkv6_kernel(r, k, v, lw, u, state=None, *, interpret: bool,
                chunk: int = 64):
    """r,k,v,lw: (B,S,H,hd); u: (H,hd); state: (B,H,hd,hd) fp32 or None.
    Returns (out (B,S,H,hd) fp32, final_state (B,H,hd,hd) fp32)."""
    B, S, H, hd = r.shape
    C = min(chunk, S)
    assert S % C == 0
    NC = S // C

    def fold(x):
        return x.transpose(0, 2, 1, 3).reshape(B * H, S, hd)

    rf, kf, vf, lwf = map(fold, (r, k, v, lw))
    uf = jnp.broadcast_to(u[None], (B, H, hd)).reshape(B * H, 1, hd)
    s0 = (jnp.zeros((B, H, hd, hd), jnp.float32) if state is None else
          state.astype(jnp.float32)).reshape(B * H, hd, hd)

    grid = (B * H, NC)

    def seq_map(bh, ci):
        return (bh, ci, 0)

    def bh_map(bh, ci):
        return (bh, 0, 0)

    kernel = functools.partial(_wkv6_kernel, chunk=C, n_chunks=NC)
    out, sf = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, C, hd), seq_map),
            pl.BlockSpec((1, C, hd), seq_map),
            pl.BlockSpec((1, C, hd), seq_map),
            pl.BlockSpec((1, C, hd), seq_map),
            pl.BlockSpec((1, 1, hd), bh_map),
            pl.BlockSpec((1, hd, hd), bh_map),
        ],
        out_specs=[
            pl.BlockSpec((1, C, hd), seq_map),
            pl.BlockSpec((1, hd, hd), bh_map),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, S, hd), jnp.float32),
            jax.ShapeDtypeStruct((B * H, hd, hd), jnp.float32),
        ],
        scratch_shapes=[_vmem((hd, hd), jnp.float32)],
        interpret=interpret,
    )(rf, kf, vf, lwf, uf, s0)
    return (out.reshape(B, H, S, hd).transpose(0, 2, 1, 3),
            sf.reshape(B, H, hd, hd))


def _vmem(shape, dtype):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.VMEM(shape, dtype)
