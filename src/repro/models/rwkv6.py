"""RWKV-6 "Finch": attention-free time mixing with data-dependent per-channel
decay [arXiv:2404.05892].

The WKV recurrence  S_t = diag(w_t)·S_{t-1} + k_t v_tᵀ,  o_t = r_t·(diag(u)·k_t v_tᵀ + S_t)
is evaluated with a **numerically-stable chunked algorithm**: all exponentials
take non-positive arguments (log-decay cumulative differences), so no overflow
for any decay — see the derivation in kernels/wkv6.py which mirrors this
blocking on TPU. The pure recurrence oracle lives in kernels/ref.py.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from repro.models import layers as L

LORA_MIX = 32
LORA_DECAY = 64


# ---------------------------------------------------------------------------
# Chunked WKV6 (XLA path).
# ---------------------------------------------------------------------------


def wkv6_chunked(r, k, v, lw, u, state=None, chunk: int = 32):
    """r,k,v,lw: (B, S, H, hd); lw = log-decay (≤ 0); u: (H, hd) bonus.

    Returns (out (B,S,H,hd) fp32, final_state (B,H,hd,hd) fp32).
    state axes: [key_channel c, value_channel d].

    Perf (§Perf iteration B1/B2): the chunk step is wrapped in
    ``jax.checkpoint`` so the scan backward re-derives the O(C²·hd) decay
    tensor instead of stacking it per step (the stacked residuals dominated
    HBM traffic); stacked chunk inputs stream in bf16 (they were computed in
    bf16 upstream anyway) while all accumulation math stays fp32.
    """
    B, S, H, hd = r.shape
    C = min(chunk, S)
    assert S % C == 0, (S, C)
    NC = S // C
    f32 = jnp.float32
    bf16 = jnp.bfloat16

    def to_chunks(x, dt):
        return x.astype(dt).reshape(B, NC, C, H, hd).transpose(1, 0, 3, 2, 4)

    # Stream chunk inputs in the caller's dtype (bf16 from the model path —
    # halves stacked-input traffic; fp32 callers stay exact vs the oracle).
    stream_dt = r.dtype if r.dtype in (bf16, jnp.float16) else f32
    rc, kc, vc = (to_chunks(x, stream_dt) for x in (r, k, v))
    lwc = to_chunks(lw, f32)  # log-decays stay fp32 (cumsums feed exponents)
    if state is None:
        state = jnp.zeros((B, H, hd, hd), f32)

    tri = jnp.tril(jnp.ones((C, C), jnp.bool_), k=-1)  # strict lower: j < t

    @jax.checkpoint
    def step(S_in, xs):
        rb, kb, vb, lwb = xs  # (B,H,C,hd)
        rb, kb, vb = (x.astype(f32) for x in (rb, kb, vb))
        Lc = jnp.cumsum(lwb, axis=2)  # inclusive
        Lx = Lc - lwb  # exclusive
        # Intra-chunk: D[t,j,c] = exp(Lx[t,c] - Lc[j,c]), j<t (arg ≤ 0: stable).
        D = jnp.exp(jnp.minimum(Lx[:, :, :, None, :] - Lc[:, :, None, :, :], 0.0))
        A = jnp.einsum("bhtc,bhjc,bhtjc->bhtj", rb, kb, D)
        A = jnp.where(tri[None, None], A, 0.0)
        diag = jnp.sum(rb * kb * u[None, :, None, :], axis=-1)  # (B,H,C)
        o = jnp.einsum("bhtj,bhjd->bhtd", A, vb) + diag[..., None] * vb
        # Inter-chunk: o += (r ⊙ exp(Lx)) @ S_in.
        o = o + jnp.einsum("bhtc,bhcd->bhtd", rb * jnp.exp(Lx), S_in)
        # State update: S' = exp(L_C) ⊙ S + Σ_j (k_j ⊙ exp(L_C − L_j)) v_jᵀ.
        Llast = Lc[:, :, -1:, :]  # (B,H,1,hd)
        S_out = jnp.exp(Llast.squeeze(2))[..., None] * S_in + jnp.einsum(
            "bhjc,bhjd->bhcd", kb * jnp.exp(Llast - Lc), vb
        )
        return S_out, o

    final, outs = lax.scan(step, state, (rc, kc, vc, lwc))
    out = outs.transpose(1, 0, 3, 2, 4).reshape(B, S, H, hd)
    return out, final


def wkv6_decode(r, k, v, lw, u, state):
    """Single-token WKV. r,k,v,lw: (B, H, hd); state (B,H,hd,hd) fp32."""
    f32 = jnp.float32
    r, k, v, lw = (x.astype(f32) for x in (r, k, v, lw))
    kv = k[..., :, None] * v[..., None, :]  # (B,H,hd,hd)
    o = jnp.einsum("bhc,bhcd->bhd", r, u[None, :, :, None] * kv + state)
    new_state = jnp.exp(lw)[..., None] * state + kv
    return o, new_state


# ---------------------------------------------------------------------------
# Layer.
# ---------------------------------------------------------------------------


def init_layer(key, cfg):
    d, ff, H, hd = cfg.d_model, cfg.d_ff, cfg.n_heads, cfg.head_dim
    ks = jax.random.split(key, 10)
    s = 1.0 / math.sqrt(d)
    return {
        "ln1": L.init_norm(d, "layernorm"),
        "ln2": L.init_norm(d, "layernorm"),
        "tm": {
            "mu_base": jnp.zeros((d,), jnp.float32),
            "mus": jnp.zeros((5, d), jnp.float32),
            "lora_A": jax.random.normal(ks[0], (d, 5 * LORA_MIX), jnp.float32) * s,
            "lora_B": jax.random.normal(ks[1], (5, LORA_MIX, d), jnp.float32) * 0.01,
            "w0": jnp.full((d,), -0.6, jnp.float32),  # decay ≈ exp(-exp(-0.6))
            "wA": jax.random.normal(ks[2], (d, LORA_DECAY), jnp.float32) * s,
            "wB": jax.random.normal(ks[3], (LORA_DECAY, d), jnp.float32) * 0.01,
            "u": jax.random.normal(ks[4], (H, hd), jnp.float32) * 0.1,
            "wr": jax.random.normal(ks[5], (d, d), jnp.float32) * s,
            "wk": jax.random.normal(ks[6], (d, d), jnp.float32) * s,
            "wv": jax.random.normal(ks[7], (d, d), jnp.float32) * s,
            "wg": jax.random.normal(ks[8], (d, d), jnp.float32) * s,
            "wo": jax.random.normal(ks[9], (d, d), jnp.float32) * s / math.sqrt(cfg.n_layers),
            "gn_scale": jnp.ones((d,), jnp.float32),
            "gn_bias": jnp.zeros((d,), jnp.float32),
        },
        "cm": {
            "mu_k": jnp.zeros((d,), jnp.float32),
            "mu_r": jnp.zeros((d,), jnp.float32),
            "wk": jax.random.normal(jax.random.fold_in(key, 11), (d, ff), jnp.float32) * s,
            "wv": jax.random.normal(jax.random.fold_in(key, 12), (ff, d), jnp.float32) / math.sqrt(ff),
            "wr": jax.random.normal(jax.random.fold_in(key, 13), (d, d), jnp.float32) * s,
        },
    }


def init_rwkv6(cfg, key):
    ke, kl = jax.random.split(key)
    stacked = jax.vmap(lambda k: init_layer(k, cfg))(jax.random.split(kl, cfg.n_layers))
    return {
        "embed": L.init_embed(ke, cfg),
        "layers": stacked,
        "final_norm": L.init_norm(cfg.d_model, "layernorm"),
    }


def _shift(x, x_last=None):
    """Token shift: x_prev[t] = x[t-1]; first slot from x_last (decode) or 0."""
    prev = jnp.pad(x, ((0, 0), (1, 0), (0, 0)))[:, :-1]
    if x_last is not None:
        prev = prev.at[:, 0].set(x_last)
    return prev


def _ddlerp(tm, x, prev):
    """Data-dependent interpolation producing the 5 mixed inputs (w,k,v,r,g)."""
    sx = prev - x
    base = x + sx * tm["mu_base"]
    lora = jnp.tanh(base @ tm["lora_A"].astype(x.dtype))
    lora = lora.reshape(*x.shape[:-1], 5, LORA_MIX)
    adj = jnp.einsum("...fc,fcd->...fd", lora, tm["lora_B"].astype(x.dtype))
    mixed = x[..., None, :] + sx[..., None, :] * (tm["mus"].astype(x.dtype) + adj)
    return [mixed[..., i, :] for i in range(5)]  # w,k,v,r,g


def _decay(tm, xw):
    dw = jnp.tanh(xw.astype(jnp.float32) @ tm["wA"]) @ tm["wB"]
    lw = -jnp.exp(jnp.clip(tm["w0"] + dw, -8.0, 3.0))  # log-decay ≤ 0
    return jnp.clip(lw, -60.0, -1e-6)


def _group_norm(x, scale, bias, H, hd):
    B, S = x.shape[:2]
    xh = x.reshape(B, S, H, hd).astype(jnp.float32)
    mu = xh.mean(-1, keepdims=True)
    var = xh.var(-1, keepdims=True)
    xh = (xh - mu) * lax.rsqrt(var + 1e-5)
    return (xh.reshape(B, S, H * hd) * scale + bias).astype(x.dtype)


def time_mix(tm, x, cfg, state=None, x_last=None, use_pallas=False):
    """state: (B,H,hd,hd) or None. Returns (out, new_state, new_x_last)."""
    B, S, d = x.shape
    H, hd = cfg.n_heads, cfg.head_dim
    prev = _shift(x, x_last)
    xw, xk, xv, xr, xg = _ddlerp(tm, x, prev)
    dt = x.dtype
    r = (xr @ tm["wr"].astype(dt)).reshape(B, S, H, hd)
    k = (xk @ tm["wk"].astype(dt)).reshape(B, S, H, hd)
    v = (xv @ tm["wv"].astype(dt)).reshape(B, S, H, hd)
    g = jax.nn.silu(xg @ tm["wg"].astype(dt))
    lw = _decay(tm, xw).reshape(B, S, H, hd)
    # ``use_pallas="attention"`` (the trainer on a TPU) selects attention's
    # kernel alone: the wkv6 recurrence then stays on its XLA path.
    if use_pallas is True:
        from repro.kernels import ops as kernel_ops

        o, new_state = kernel_ops.wkv6(r, k, v, lw, tm["u"], state=state)
    else:
        o, new_state = wkv6_chunked(r, k, v, lw, tm["u"], state=state)
    o = _group_norm(o.reshape(B, S, d), tm["gn_scale"], tm["gn_bias"], H, hd)
    out = ((o.astype(dt) * g) @ tm["wo"].astype(dt)).astype(dt)
    return out, new_state, x[:, -1]


def channel_mix(cm, x, x_last=None):
    prev = _shift(x, x_last)
    dt = x.dtype
    xk = x + (prev - x) * cm["mu_k"].astype(dt)
    xr = x + (prev - x) * cm["mu_r"].astype(dt)
    kk = jnp.square(jax.nn.relu(xk @ cm["wk"].astype(dt)))
    return jax.nn.sigmoid(xr @ cm["wr"].astype(dt)) * (kk @ cm["wv"].astype(dt)), x[:, -1]


def forward(cfg, params, tokens, *, state=None, n_groups=1, use_pallas=False,
            last_only=False, return_hidden=False, dtype=jnp.bfloat16, **_):
    """state: {"wkv": (L,B,H,hd,hd), "tm_x": (L,B,d), "cm_x": (L,B,d)} or None."""
    B, S = tokens.shape
    x = L.embed_tokens(params["embed"], tokens, cfg, dtype=dtype)

    def body(carry, xs):
        x = carry
        if state is None:
            lp = xs
            st = xl_tm = xl_cm = None
        else:
            lp, st, xl_tm, xl_cm = xs
        h = L.apply_norm(lp["ln1"], x, "layernorm")
        tmo, new_st, new_xl = time_mix(lp["tm"], h, cfg, state=st, x_last=xl_tm,
                                       use_pallas=use_pallas)
        x = x + tmo
        h = L.apply_norm(lp["ln2"], x, "layernorm")
        cmo, new_xl_cm = channel_mix(lp["cm"], h, xl_cm)
        x = x + cmo
        ys = (new_st, new_xl, new_xl_cm) if state is not None else None
        return x, ys

    if cfg.remat:
        body = jax.checkpoint(body, prevent_cse=False)

    xs = params["layers"] if state is None else (
        params["layers"], state["wkv"], state["tm_x"], state["cm_x"]
    )
    x, ys = lax.scan(body, x, xs)
    x = L.apply_norm(params["final_norm"], x, "layernorm")
    if last_only:
        x = x[:, -1:]
    if return_hidden and state is None:
        return x, jnp.zeros((), jnp.float32)
    logits = L.unembed(params["embed"], x, cfg)
    aux = jnp.zeros((), jnp.float32)
    if state is not None:
        new_state = {"wkv": ys[0], "tm_x": ys[1], "cm_x": ys[2]}
        return logits, new_state, aux
    return logits, aux


def make_state(cfg, batch):
    H, hd, d = cfg.n_heads, cfg.head_dim, cfg.d_model
    return {
        "wkv": jnp.zeros((cfg.n_layers, batch, H, hd, hd), jnp.float32),
        "tm_x": jnp.zeros((cfg.n_layers, batch, d), jnp.bfloat16),
        "cm_x": jnp.zeros((cfg.n_layers, batch, d), jnp.bfloat16),
    }


def state_specs(cfg, batch):
    H, hd, d = cfg.n_heads, cfg.head_dim, cfg.d_model
    return {
        "wkv": jax.ShapeDtypeStruct((cfg.n_layers, batch, H, hd, hd), jnp.float32),
        "tm_x": jax.ShapeDtypeStruct((cfg.n_layers, batch, d), jnp.bfloat16),
        "cm_x": jax.ShapeDtypeStruct((cfg.n_layers, batch, d), jnp.bfloat16),
    }
