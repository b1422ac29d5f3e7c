"""Pure-jnp oracles for every Pallas kernel (the correctness ground truth).

These are deliberately naive — O(S²) attention, O(S) sequential recurrences —
so they are unarguably correct; kernel tests sweep shapes/dtypes and
``assert_allclose`` against these.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.models.layers import MaskSpec, _mask_block
from repro.optim.compression import round_quotient


# ---------------------------------------------------------------------------
# Attention.
# ---------------------------------------------------------------------------


def attention_ref(q, k, v, spec: MaskSpec, *, scale, softcap=0.0, q_offset=0,
                  is_local=None):
    """q: (B,Sq,H,hd); k,v: (B,Skv,K,hd). Dense softmax attention in fp32."""
    B, Sq, H, hd = q.shape
    Skv, K = k.shape[1], k.shape[2]
    G = H // K
    qr = (q.astype(jnp.float32) * scale).reshape(B, Sq, K, G, hd)
    s = jnp.einsum("bqkgd,bjkd->bkgqj", qr, k.astype(jnp.float32))
    if softcap > 0:
        s = softcap * jnp.tanh(s / softcap)
    q_pos = q_offset + jnp.arange(Sq, dtype=jnp.int32)
    kv_pos = jnp.arange(Skv, dtype=jnp.int32)
    m = _mask_block(spec, q_pos, kv_pos, is_local=is_local)
    s = jnp.where(m[None, None, None], s, -2.0e38)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqj,bjkd->bqkgd", p, v.astype(jnp.float32))
    return o.reshape(B, Sq, H, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# WKV6 recurrence (RWKV-6).
# ---------------------------------------------------------------------------


def wkv6_ref(r, k, v, lw, u, state=None):
    """Sequential oracle of  S_t = diag(w_t) S_{t-1} + k_t v_tᵀ,
    o_t = r_t·(diag(u) k_t v_tᵀ + S_t).  r,k,v,lw: (B,S,H,hd); u: (H,hd)."""
    B, S, H, hd = r.shape
    f32 = jnp.float32
    r, k, v, lw = (x.astype(f32) for x in (r, k, v, lw))
    S0 = jnp.zeros((B, H, hd, hd), f32) if state is None else state.astype(f32)

    def step(Sst, xs):
        rt, kt, vt, lwt = xs  # (B,H,hd)
        kv = kt[..., :, None] * vt[..., None, :]  # (B,H,hd,hd)
        ot = jnp.einsum("bhc,bhcd->bhd", rt, u[None, :, :, None] * kv + Sst)
        Snew = jnp.exp(lwt)[..., None] * Sst + kv
        return Snew, ot

    xs = tuple(x.transpose(1, 0, 2, 3) for x in (r, k, v, lw))  # (S,B,H,hd)
    Sf, outs = lax.scan(step, S0, xs)
    return outs.transpose(1, 0, 2, 3), Sf  # (B,S,H,hd), (B,H,hd,hd)


# ---------------------------------------------------------------------------
# SSD recurrence (Mamba2).
# ---------------------------------------------------------------------------


def ssd_ref(x, dt, A_log, Bm, Cm, state=None):
    """Sequential oracle of  h_t = exp(A dt_t) h_{t-1} + dt_t B_t x_t,
    y_t = C_t·h_t.  x: (B,S,H,P); dt: (B,S,H); Bm,Cm: (B,S,N); A_log: (H,)."""
    Bb, S, H, P = x.shape
    N = Bm.shape[-1]
    f32 = jnp.float32
    x, dt, Bm, Cm = (t.astype(f32) for t in (x, dt, Bm, Cm))
    lA = -jnp.exp(A_log.astype(f32))
    h0 = jnp.zeros((Bb, H, P, N), f32) if state is None else state.astype(f32)

    def step(h, xs):
        xt, dtt, bt, ct = xs  # (B,H,P), (B,H), (B,N), (B,N)
        a = jnp.exp(dtt * lA[None, :])  # (B,H)
        inject = dtt[..., None, None] * xt[..., :, None] * bt[:, None, None, :]
        h = a[..., None, None] * h + inject
        y = jnp.einsum("bn,bhpn->bhp", ct, h)
        return h, y

    xs = (x.transpose(1, 0, 2, 3), dt.transpose(1, 0, 2), Bm.transpose(1, 0, 2),
          Cm.transpose(1, 0, 2))
    hf, ys = lax.scan(step, h0, xs)
    return ys.transpose(1, 0, 2, 3), hf  # (B,S,H,P), (B,H,P,N)


# ---------------------------------------------------------------------------
# Shard codec (int8 block quantization).
# ---------------------------------------------------------------------------


def shard_codec_ref(x_blocks):
    """x_blocks: (nb, block) fp32 → (codes int8, scales fp32 (nb,))."""
    # Reciprocal multiply, not "/ 127.0": matches the quantizer and the
    # Pallas kernel bit-for-bit regardless of how a lowering handles the
    # division (see optim/compression.int8_quantize).
    scale = jnp.maximum(jnp.max(jnp.abs(x_blocks), axis=1), 1e-12) * (1.0 / 127.0)
    codes = jnp.clip(round_quotient(x_blocks, scale[:, None]), -127,
                     127).astype(jnp.int8)
    return codes, scale


def shard_decode_ref(codes, scales):
    return codes.astype(jnp.float32) * scales[:, None]
