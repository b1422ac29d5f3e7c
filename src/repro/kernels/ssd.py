"""SSD (Mamba2 state-space duality) Pallas-TPU kernel — chunked scan with
scalar-per-head decay.

Same blocking as models/mamba2.ssd_chunked: per chunk the intra-term is a
(C×C) masked "attention" matrix CBᵀ ⊙ decay built from cumulative log-decays
(all exponent arguments ≤ 0), evaluated on the MXU; the (P×N) state is fp32
VMEM scratch carried across the sequential chunk axis.
Grid: (B·H parallel, n_chunks sequential).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl


def _ssd_kernel(x_ref, dt_ref, l_ref, b_ref, c_ref, h0_ref, y_ref, hf_ref,
                h_scr, *, n_chunks):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = h0_ref[0].astype(jnp.float32)

    x = x_ref[0].astype(jnp.float32)  # (C, P)
    dt = dt_ref[0].astype(jnp.float32)  # (C, 1)
    l = l_ref[0].astype(jnp.float32)  # (C, 1) log-decay ≤ 0
    Bm = b_ref[0].astype(jnp.float32)  # (C, N)
    Cm = c_ref[0].astype(jnp.float32)  # (C, N)
    h = h_scr[...]  # (P, N)

    C = x.shape[0]
    Lc = jnp.cumsum(l, axis=0)  # (C,1) inclusive

    cb = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (C,C)
    decay = jnp.exp(jnp.minimum(Lc - Lc.T, 0.0))  # (C,C): exp(L_t - L_j)
    M = cb * decay * dt.T  # (t, j): includes dt_j
    tri = lax.broadcasted_iota(jnp.int32, (C, C), 0) >= lax.broadcasted_iota(
        jnp.int32, (C, C), 1)
    M = jnp.where(tri, M, 0.0)
    y = jax.lax.dot_general(M, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (C,P)
    # Inter-chunk: y += exp(Lc_t) · C_t hᵀ.
    ch = jax.lax.dot_general(Cm, h, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (C,P)
    y = y + jnp.exp(Lc) * ch
    y_ref[0] = y.astype(y_ref.dtype)

    # State: h' = exp(L_last) h + Σ_j x_jᵀ (exp(L_last − L_j) dt_j B_j).
    Llast = Lc[-1:, :]  # (1,1)
    w = jnp.exp(Llast - Lc) * dt  # (C,1)
    h_new = jnp.exp(Llast) * h + jax.lax.dot_general(
        x, Bm * w, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)  # (P,N)
    h_scr[...] = h_new

    @pl.when(ci == n_chunks - 1)
    def _final():
        hf_ref[0] = h_new


def ssd_kernel(x, dt, A_log, Bm, Cm, state=None, *, interpret: bool,
               chunk: int = 64):
    """x: (B,S,H,P); dt: (B,S,H) > 0; A_log: (H,); Bm,Cm: (B,S,N).
    Returns (y (B,S,H,P) fp32, final_state (B,H,P,N) fp32)."""
    B, S, H, P = x.shape
    N = Bm.shape[-1]
    C = min(chunk, S)
    assert S % C == 0
    NC = S // C

    lA = -jnp.exp(A_log.astype(jnp.float32))
    l = dt.astype(jnp.float32) * lA[None, None, :]  # (B,S,H)

    xf = x.transpose(0, 2, 1, 3).reshape(B * H, S, P)
    dtf = dt.transpose(0, 2, 1).reshape(B * H, S, 1)
    lf = l.transpose(0, 2, 1).reshape(B * H, S, 1)
    bf = jnp.broadcast_to(Bm[:, None], (B, H, S, N)).reshape(B * H, S, N)
    cf = jnp.broadcast_to(Cm[:, None], (B, H, S, N)).reshape(B * H, S, N)
    h0 = (jnp.zeros((B, H, P, N), jnp.float32) if state is None else
          state.astype(jnp.float32)).reshape(B * H, P, N)

    grid = (B * H, NC)

    def seq_map(bh, ci):
        return (bh, ci, 0)

    def bh_map(bh, ci):
        return (bh, 0, 0)

    kernel = functools.partial(_ssd_kernel, n_chunks=NC)
    y, hf = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, C, P), seq_map),
            pl.BlockSpec((1, C, 1), seq_map),
            pl.BlockSpec((1, C, 1), seq_map),
            pl.BlockSpec((1, C, N), seq_map),
            pl.BlockSpec((1, C, N), seq_map),
            pl.BlockSpec((1, P, N), bh_map),
        ],
        out_specs=[
            pl.BlockSpec((1, C, P), seq_map),
            pl.BlockSpec((1, P, N), bh_map),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, S, P), jnp.float32),
            jax.ShapeDtypeStruct((B * H, P, N), jnp.float32),
        ],
        scratch_shapes=[_vmem((P, N), jnp.float32)],
        interpret=interpret,
    )(xf, dtf, lf, bf, cf, h0)
    return (y.reshape(B, H, S, P).transpose(0, 2, 1, 3),
            hf.reshape(B, H, P, N))


def _vmem(shape, dtype):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.VMEM(shape, dtype)
