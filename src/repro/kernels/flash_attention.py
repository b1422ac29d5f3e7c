"""Flash attention Pallas-TPU kernel (FA2-style online softmax).

TPU-native design (DESIGN.md §3): MXU-aligned (block_q × head_dim) and
(block_k × head_dim) tiles resident in VMEM; fp32 running max / denominator /
accumulator in VMEM scratch carried across the sequential kv-block grid axis;
bf16 inputs, fp32 math. Supports GQA (kv-head folding via the index map),
causal / full / bidirectional-prefix masks, sliding windows, and Gemma2
attention-logit softcapping — the same contract as the XLA path
(models/layers.blocked_attention) and the oracle (kernels/ref.attention_ref).

Scope: train/prefill (Sq ≥ block). Decode (Sq = 1) stays on the XLA path
where GSPMD's sequence-sharded partial softmax already implements
flash-decoding semantics at the collective level.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

NEG_INF = -2.0e38


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                 scale, softcap, kind, window, prefix_len, q_offset,
                 block_q, block_k, n_kv_blocks):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -1e30)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32) * scale  # (block_q, hd)
    k = k_ref[0].astype(jnp.float32)  # (block_k, hd)
    v = v_ref[0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    if softcap > 0.0:
        s = softcap * jnp.tanh(s / softcap)

    q_pos = q_offset + qi * block_q + lax.broadcasted_iota(jnp.int32,
                                                           (block_q, block_k), 0)
    kv_pos = ki * block_k + lax.broadcasted_iota(jnp.int32,
                                                 (block_q, block_k), 1)
    if kind == "full":
        mask = jnp.ones((block_q, block_k), jnp.bool_)
    else:
        mask = kv_pos <= q_pos
        if kind == "prefix" and prefix_len > 0:
            mask = mask | ((q_pos < prefix_len) & (kv_pos < prefix_len))
        if window > 0:
            w_ok = (q_pos - kv_pos) < window
            if kind == "prefix" and prefix_len > 0:
                w_ok = w_ok | (kv_pos < prefix_len)
            mask = mask & w_ok
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(ki == n_kv_blocks - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def flash_attention_kernel(
    q, k, v, *,
    interpret: bool,
    scale: float,
    softcap: float = 0.0,
    kind: str = "causal",
    window: int = 0,
    prefix_len: int = 0,
    q_offset: int = 0,
    block_q: int = 128,
    block_k: int = 128,
):
    """q: (B,Sq,H,hd); k,v: (B,Skv,K,hd) with H % K == 0. Returns (B,Sq,H,hd)."""
    B, Sq, H, hd = q.shape
    Skv, K = k.shape[1], k.shape[2]
    G = H // K
    block_q = min(block_q, Sq)
    block_k = min(block_k, Skv)
    assert Sq % block_q == 0 and Skv % block_k == 0, (Sq, block_q, Skv, block_k)
    nq, nk = Sq // block_q, Skv // block_k

    # Layout: fold (B,H) into the leading parallel grid axis.
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(B * K, Skv, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(B * K, Skv, hd)

    grid = (B * H, nq, nk)

    def q_map(bh, qi, ki):
        return (bh, qi, 0)

    def kv_map(bh, qi, ki):
        b = bh // H
        h = bh % H
        return (b * K + h // G, ki, 0)

    kernel = functools.partial(
        _attn_kernel, scale=scale, softcap=softcap, kind=kind, window=window,
        prefix_len=prefix_len, q_offset=q_offset, block_q=block_q,
        block_k=block_k, n_kv_blocks=nk)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, hd), q_map),
            pl.BlockSpec((1, block_k, hd), kv_map),
            pl.BlockSpec((1, block_k, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), q_map),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, hd), q.dtype),
        scratch_shapes=[
            _vmem((block_q, 1), jnp.float32),
            _vmem((block_q, 1), jnp.float32),
            _vmem((block_q, hd), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, H, Sq, hd).transpose(0, 2, 1, 3)


def _vmem(shape, dtype):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.VMEM(shape, dtype)
