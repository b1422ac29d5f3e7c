"""Shared model layers: norms, RoPE, blocked (flash-style) attention, MLPs.

All layers are pure functions over param dicts so they compose with
``jax.lax.scan`` over stacked layer parameters and with GSPMD sharding rules
keyed on parameter paths (see ``repro/models/sharding.py``).

The attention here is the **XLA path**: an online-softmax scan over KV blocks
(O(Sq·Bk) live memory, never materializing the S×S score matrix) so that 32k
prefill compiles with bounded temps. The Pallas TPU kernel in
``repro/kernels/flash_attention.py`` implements the same contract for the
hot path on real hardware; both are checked against ``kernels/ref.py``.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.models.shardctx import constrain

NEG_INF = -2.0e38


# ---------------------------------------------------------------------------
# Norms.
# ---------------------------------------------------------------------------


def rmsnorm(x, scale, eps=1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    # Gemma-style (1 + scale); scale initialized at zero.
    return (x * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(jnp.float32))).astype(dt)


def layernorm(x, scale, bias, eps=1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + scale) + bias).astype(dt)


def apply_norm(params, x, kind, eps=1e-6):
    if kind == "rmsnorm":
        return rmsnorm(x, params["scale"], eps)
    return layernorm(x, params["scale"], params["bias"], eps)


def init_norm(d, kind):
    p = {"scale": jnp.zeros((d,), jnp.float32)}
    if kind == "layernorm":
        p["bias"] = jnp.zeros((d,), jnp.float32)
    return p


# ---------------------------------------------------------------------------
# RoPE.
# ---------------------------------------------------------------------------


def rope(x, positions, theta):
    """x: (..., S, H, hd); positions: (..., S) int32."""
    hd = x.shape[-1]
    half = hd // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freq  # (..., S, half)
    cos = jnp.cos(ang)[..., None, :]  # (..., S, 1, half)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Mask spec — evaluated blockwise, never materialized at S×S.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class MaskSpec:
    kind: str = "causal"  # causal | full | prefix
    window: int = 0  # sliding window size (0 = unlimited)
    prefix_len: int = 0  # bidirectional prefix (vlm)


def _mask_block(spec: MaskSpec, q_pos, kv_pos, is_local=None):
    """Boolean mask (Sq, Bk) for given absolute positions."""
    q = q_pos[:, None]
    k = kv_pos[None, :]
    if spec.kind == "full":
        return jnp.ones((q_pos.shape[0], kv_pos.shape[0]), jnp.bool_)
    m = k <= q
    if spec.kind == "prefix" and spec.prefix_len > 0:
        m = m | ((q < spec.prefix_len) & (k < spec.prefix_len))
    if spec.window > 0:
        w_ok = (q - k) < spec.window
        if spec.kind == "prefix" and spec.prefix_len > 0:
            w_ok = w_ok | (k < spec.prefix_len)
        if is_local is None:
            m = m & w_ok
        else:
            m = m & jnp.where(is_local, w_ok, True)
    return m


# ---------------------------------------------------------------------------
# Blocked flash-style attention (XLA path).
# ---------------------------------------------------------------------------


def blocked_attention(
    q,
    k,
    v,
    spec: MaskSpec,
    *,
    scale: float,
    softcap: float = 0.0,
    q_offset=0,
    kv_block: int = 1024,
    is_local=None,
    use_pallas: bool = False,
):
    """Online-softmax attention.

    q: (B, Sq, H, hd); k, v: (B, Skv, K, hd) with H % K == 0 (GQA).
    ``q_offset``: absolute position of q[0] (decode: cache write position).
    Returns (B, Sq, H, hd) in q.dtype.
    """
    if use_pallas:
        from repro.kernels import ops as kernel_ops

        return kernel_ops.flash_attention(
            q, k, v, spec, scale=scale, softcap=softcap, q_offset=q_offset,
            is_local=is_local,
        )

    B, Sq, H, hd = q.shape
    Skv, K = k.shape[1], k.shape[2]
    G = H // K
    qr = (q.astype(jnp.float32) * scale).reshape(B, Sq, K, G, hd)
    q_pos = q_offset + jnp.arange(Sq, dtype=jnp.int32)

    # Direct (single-block) softmax for short-to-moderate KV: under per-layer
    # remat this keeps the S×S scores transient, and avoids the kv-block
    # scan's stacked backward residuals. The scan path handles long KV
    # (32k prefill / decode reads), which is inference-only (no backward).
    if Skv <= 8192:
        kv_block = Skv
    kv_block = min(kv_block, Skv)
    if Skv % kv_block:
        kv_block = math.gcd(Skv, kv_block) or Skv
    nb = Skv // kv_block

    def block_scores(kb, kv_pos):
        s = jnp.einsum("bqkgd,bjkd->bkgqj", qr, kb.astype(jnp.float32))
        if softcap > 0.0:
            s = softcap * jnp.tanh(s / softcap)
        m = _mask_block(spec, q_pos, kv_pos, is_local=is_local)
        return jnp.where(m[None, None, None], s, NEG_INF)

    if nb == 1:
        # Direct path: single block. Scores/max/denominator in fp32; the
        # probability matrix is cast to bf16 for the PV matmul (fp32 MXU
        # accumulation) — §Perf iteration C1 halves the dominant S×S HBM
        # traffic with <1e-3 relative output error (validated vs ref).
        s = block_scores(k, jnp.arange(Skv, dtype=jnp.int32))
        mmax = jnp.max(s, axis=-1, keepdims=True)
        mmax = jnp.maximum(mmax, -1e30)
        p = jnp.exp(s - mmax)
        denom = jnp.sum(p, axis=-1)  # (B,K,G,Sq)
        o = jnp.einsum("bkgqj,bjkd->bqkgd", p.astype(q.dtype), v,
                       preferred_element_type=jnp.float32)
        o = o / jnp.transpose(denom, (0, 3, 1, 2))[..., None]
        return o.reshape(B, Sq, H, hd).astype(q.dtype)

    def step(carry, i):
        m_run, l_run, acc = carry
        kb = lax.dynamic_slice_in_dim(k, i * kv_block, kv_block, axis=1)
        vb = lax.dynamic_slice_in_dim(v, i * kv_block, kv_block, axis=1)
        kv_pos = i * kv_block + jnp.arange(kv_block, dtype=jnp.int32)
        s = block_scores(kb, kv_pos)  # (B,K,G,Sq,Bk)
        m_blk = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_run, m_blk)
        corr = jnp.exp(m_run - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l_run * corr + jnp.sum(p, axis=-1)
        o_blk = jnp.einsum("bkgqj,bjkd->bkgqd", p, vb.astype(jnp.float32))
        acc = acc * corr[..., None] + o_blk
        return (m_new, l_new, acc), None

    m0 = jnp.full((B, K, G, Sq), -1e30, jnp.float32)
    l0 = jnp.zeros((B, K, G, Sq), jnp.float32)
    a0 = jnp.zeros((B, K, G, Sq, hd), jnp.float32)
    (m_f, l_f, acc), _ = lax.scan(step, (m0, l0, a0), jnp.arange(nb))
    o = acc / jnp.maximum(l_f, 1e-30)[..., None]  # (B,K,G,Sq,hd)
    return jnp.transpose(o, (0, 3, 1, 2, 4)).reshape(B, Sq, H, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# Attention sublayer (projections + rope + cache handling).
# ---------------------------------------------------------------------------


def init_attention(key, cfg, d_in=None):
    d = d_in or cfg.d_model
    kq, kk, kv, ko = jax.random.split(key, 4)
    s = 1.0 / math.sqrt(d)
    return {
        "wq": jax.random.normal(kq, (d, cfg.q_dim), jnp.float32) * s,
        "wk": jax.random.normal(kk, (d, cfg.kv_dim), jnp.float32) * s,
        "wv": jax.random.normal(kv, (d, cfg.kv_dim), jnp.float32) * s,
        "wo": jax.random.normal(ko, (cfg.q_dim, d), jnp.float32) * s / math.sqrt(2 * max(cfg.n_layers, 1)),
    }


def attention_sublayer(
    params,
    x,
    cfg,
    spec: MaskSpec,
    *,
    positions,
    kv_x=None,
    cache_kv=None,
    cache_pos=None,
    static_kv=False,
    is_local=None,
    use_pallas=False,
):
    """Full attention sublayer.

    x: (B, S, d) normed input. ``kv_x``: source for K/V (cross-attention).
    ``cache_kv``: (k, v) arrays (B, Smax, K, hd); with ``static_kv=False``
    they are updated at ``cache_pos`` (decode self-attn); with
    ``static_kv=True`` they are used as-is (precomputed cross-attn cache).
    """
    B, S, _ = x.shape
    dt = x.dtype
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = constrain((x @ params["wq"].astype(dt)).reshape(B, S, H, hd),
                  "batch", None, "model", None)

    scale = cfg.query_scale if cfg.query_scale else 1.0 / math.sqrt(hd)

    if cfg.positions == "rope" and kv_x is None:
        q = rope(q, positions, cfg.rope_theta)

    new_cache = None
    if cache_kv is not None and static_kv:
        k, v = cache_kv
        new_cache = cache_kv
        q_offset = 0
    else:
        src = x if kv_x is None else kv_x
        k = constrain(
            (src @ params["wk"].astype(dt)).reshape(B, src.shape[1], K, hd),
            "batch", None, "model", None)
        v = constrain(
            (src @ params["wv"].astype(dt)).reshape(B, src.shape[1], K, hd),
            "batch", None, "model", None)
        if cfg.positions == "rope" and kv_x is None:
            k = rope(k, positions, cfg.rope_theta)
        if cache_kv is not None:
            ck, cv = cache_kv
            ck = lax.dynamic_update_slice_in_dim(ck, k.astype(ck.dtype), cache_pos, axis=1)
            cv = lax.dynamic_update_slice_in_dim(cv, v.astype(cv.dtype), cache_pos, axis=1)
            new_cache = (ck, cv)
            if S == ck.shape[1]:
                # Prefill fills the whole cache: attend over the freshly
                # computed K/V (identical values, but keeps attention reads on
                # the head-sharded activations instead of the possibly
                # seq-sharded cache layout).
                q_offset = 0
            else:
                k, v = ck, cv
                q_offset = cache_pos
        else:
            q_offset = 0

    # The scope names every device op of the core (scores, softmax, the
    # products with V), XLA's or the Pallas kernel's, forward and backward.
    with jax.named_scope("attention_core"):
        o = blocked_attention(
            q, k, v, spec, scale=scale, softcap=cfg.attn_softcap,
            q_offset=q_offset, is_local=is_local, use_pallas=use_pallas,
        )
    o = constrain(o, "batch", None, "model", None)
    out = o.reshape(B, S, H * hd) @ params["wo"].astype(dt)
    return constrain(out, "batch", None, None), new_cache


# ---------------------------------------------------------------------------
# MLPs.
# ---------------------------------------------------------------------------


def init_mlp(key, d, ff, kind):
    k1, k2, k3 = jax.random.split(key, 3)
    s1, s2 = 1.0 / math.sqrt(d), 1.0 / math.sqrt(ff)
    p = {
        "w1": jax.random.normal(k1, (d, ff), jnp.float32) * s1,
        "w2": jax.random.normal(k2, (ff, d), jnp.float32) * s2,
    }
    if kind in ("swiglu", "geglu"):
        p["w3"] = jax.random.normal(k3, (d, ff), jnp.float32) * s1
    return p


def mlp_sublayer(params, x, kind):
    dt = x.dtype
    h = constrain(x @ params["w1"].astype(dt), "batch", None, "model")
    if kind == "swiglu":
        h = jax.nn.silu(h) * (x @ params["w3"].astype(dt))
    elif kind == "geglu":
        h = jax.nn.gelu(h, approximate=True) * (x @ params["w3"].astype(dt))
    else:  # gelu2
        h = jax.nn.gelu(h, approximate=True)
    return constrain(h @ params["w2"].astype(dt), "batch", None, None)


# ---------------------------------------------------------------------------
# Embedding / unembedding.
# ---------------------------------------------------------------------------


def init_embed(key, cfg):
    p = {"tok": jax.random.normal(key, (cfg.vocab, cfg.d_model), jnp.float32) * 0.02}
    if not cfg.tie_embeddings:
        key2 = jax.random.fold_in(key, 1)
        p["unembed"] = (
            jax.random.normal(key2, (cfg.d_model, cfg.vocab), jnp.float32)
            / math.sqrt(cfg.d_model)
        )
    if cfg.positions == "learned":
        key3 = jax.random.fold_in(key, 2)
        n_pos = 32_768  # covers decode_32k; train_4k/prefill_32k are subsets
        p["pos"] = jax.random.normal(key3, (n_pos, cfg.d_model), jnp.float32) * 0.02
    return p


def embed_tokens(params, tokens, cfg, positions=None, dtype=jnp.bfloat16):
    x = params["tok"].astype(dtype)[tokens]
    x = constrain(x, "batch", None, None)
    if cfg.embed_scale:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), dtype)
    if cfg.positions == "learned" and positions is not None:
        x = x + params["pos"].astype(dtype)[positions]
    return x


def unembed(params, x, cfg):
    dt = x.dtype
    if cfg.tie_embeddings:
        logits = x @ params["tok"].astype(dt).T
    else:
        logits = x @ params["unembed"].astype(dt)
    if cfg.final_softcap > 0.0:
        logits = cfg.final_softcap * jnp.tanh(logits / cfg.final_softcap)
    return logits


def chunked_cross_entropy(embed_params, x, labels, cfg, chunk: int = 1024):
    """Mean next-token CE computed in sequence chunks so the full (B,S,V)
    logits tensor never materializes (§Perf iteration C2 — at 128k vocab the
    logits buffer + fp32 softmax temps dominate train-step peak memory).
    x: final hidden states (B,S,d); labels (B,S)."""
    B, S, d = x.shape
    if S % chunk or S <= chunk:
        return cross_entropy(unembed(embed_params, x, cfg), labels)
    nc = S // chunk
    xs = x.reshape(B, nc, chunk, d).transpose(1, 0, 2, 3)
    ls = labels.reshape(B, nc, chunk).transpose(1, 0, 2)

    @jax.checkpoint
    def step(acc, xs_):
        xc, lc = xs_
        logits = unembed(embed_params, xc, cfg).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
        return acc + jnp.sum(lse - ll), None

    total, _ = lax.scan(step, jnp.zeros((), jnp.float32), (xs, ls))
    return total / (B * S)


def cross_entropy(logits, labels, mask=None):
    """Mean next-token CE in fp32. logits (B,S,V), labels (B,S)."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - ll
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)
