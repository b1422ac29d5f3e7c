"""Mamba2 (SSD — state-space duality) block, used by the Zamba2 hybrid.

Recurrence (per head h, head-channel p, state-channel n):
    h_t = exp(A·dt_t) · h_{t-1} + dt_t · B_t[n] · x_t[p]
    y_t[p] = Σ_n C_t[n] · h_t[p,n] + D · x_t[p]
Chunked evaluation with all exponentials of non-positive arguments (A < 0,
dt > 0), scanned across chunks. Pure recurrence oracle in kernels/ref.py;
the TPU kernel in kernels/ssd.py mirrors this blocking.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from repro.models import layers as L

CONV_K = 4  # causal conv kernel size


def ssd_chunked(x, dt, A_log, Bm, Cm, state=None, chunk: int = 32):
    """x: (B,S,H,P); dt: (B,S,H) >0; A_log: (H,); Bm, Cm: (B,S,N).

    Returns (y (B,S,H,P) fp32, final_state (B,H,P,N) fp32).
    """
    Bb, S, H, P = x.shape
    N = Bm.shape[-1]
    C = min(chunk, S)
    assert S % C == 0
    NC = S // C
    f32 = jnp.float32
    x, dt, Bm, Cm = (t.astype(f32) for t in (x, dt, Bm, Cm))
    lA = -jnp.exp(A_log.astype(f32))  # (H,) < 0
    l = dt * lA[None, None, :]  # (B,S,H) log-decay ≤ 0

    def to_chunks(t, feat):
        return t.reshape(Bb, NC, C, *feat).transpose(1, 0, 2, *range(3, 3 + len(feat)))

    xc = x.reshape(Bb, NC, C, H, P).transpose(1, 0, 2, 3, 4)
    dtc = dt.reshape(Bb, NC, C, H).transpose(1, 0, 2, 3)
    lc = l.reshape(Bb, NC, C, H).transpose(1, 0, 2, 3)
    Bc = Bm.reshape(Bb, NC, C, N).transpose(1, 0, 2, 3)
    Cc = Cm.reshape(Bb, NC, C, N).transpose(1, 0, 2, 3)

    if state is None:
        state = jnp.zeros((Bb, H, P, N), f32)

    tri = jnp.tril(jnp.ones((C, C), jnp.bool_))  # inclusive: j ≤ t

    @jax.checkpoint
    def step(h_in, xs):
        xb, dtb, lb, Bb_, Cb_ = xs  # (B,C,H,P) (B,C,H) (B,C,H) (B,C,N) (B,C,N)
        Lc = jnp.cumsum(lb, axis=1)  # (B,C,H) inclusive
        # Intra: M[t,j,h] = exp(Lc[t,h]-Lc[j,h]) * (C_t·B_j) * dt_j, j ≤ t.
        cb = jnp.einsum("btn,bjn->btj", Cb_, Bb_)
        decay = jnp.exp(jnp.minimum(Lc[:, :, None, :] - Lc[:, None, :, :], 0.0))
        M = cb[..., None] * decay * dtb[:, None, :, :]  # (B,t,j,H)
        M = jnp.where(tri[None, :, :, None], M, 0.0)
        y = jnp.einsum("btjh,bjhp->bthp", M, xb)
        # Inter: y += exp(Lc_t) · C_t · h_in.
        y = y + jnp.einsum("btn,bhpn,bth->bthp", Cb_, h_in, jnp.exp(Lc))
        # State: h' = exp(L_last) h + Σ_j exp(L_last - L_j) dt_j B_j x_j.
        Llast = Lc[:, -1:, :]  # (B,1,H)
        w = jnp.exp(Llast - Lc) * dtb  # (B,C,H)
        h_out = jnp.exp(Llast.squeeze(1))[:, :, None, None] * h_in + jnp.einsum(
            "bjn,bjhp,bjh->bhpn", Bb_, xb, w
        )
        return h_out, y

    final, ys = lax.scan(step, state, (xc, dtc, lc, Bc, Cc))
    y = ys.transpose(1, 0, 2, 3, 4).reshape(Bb, S, H, P)
    return y, final


def init_block(key, cfg):
    d = cfg.d_model
    d_in = cfg.ssm_expand * d
    H = cfg.ssm_heads or (d_in // 64)
    N = cfg.ssm_state
    conv_dim = d_in + 2 * N
    ks = jax.random.split(key, 4)
    s = 1.0 / math.sqrt(d)
    return {
        "norm": L.init_norm(d, "rmsnorm"),
        "in_proj": jax.random.normal(ks[0], (d, 2 * d_in + 2 * N + H), jnp.float32) * s,
        "conv_w": jax.random.normal(ks[1], (CONV_K, conv_dim), jnp.float32) * 0.1,
        "conv_b": jnp.zeros((conv_dim,), jnp.float32),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, H).astype(jnp.float32)),
        "D": jnp.ones((H,), jnp.float32),
        "dt_bias": jnp.full((H,), -2.0, jnp.float32),
        "gate_norm": L.init_norm(d_in, "rmsnorm"),
        "out_proj": jax.random.normal(ks[2], (d_in, d), jnp.float32) / math.sqrt(d_in),
    }


def causal_conv(x, w, b, conv_state=None):
    """x: (B,S,D); w: (K,D) depthwise. conv_state: (B,K-1,D) left context."""
    K = w.shape[0]
    if conv_state is None:
        pad = jnp.zeros((x.shape[0], K - 1, x.shape[2]), x.dtype)
    else:
        pad = conv_state.astype(x.dtype)
    xp = jnp.concatenate([pad, x], axis=1)
    out = sum(xp[:, i : i + x.shape[1]] * w[i].astype(x.dtype) for i in range(K))
    new_state = xp[:, -(K - 1) :] if K > 1 else pad
    return out + b.astype(x.dtype), new_state


def block_apply(p, x, cfg, state=None, use_pallas=False):
    """One Mamba2 block. state: {"h": (B,H,P,N), "conv": (B,K-1,conv_dim)}.

    Returns (out (B,S,d), new_state or None).
    """
    B, S, d = x.shape
    d_in = cfg.ssm_expand * d
    H = cfg.ssm_heads or (d_in // 64)
    P = d_in // H
    N = cfg.ssm_state
    dt_ = x.dtype

    h = L.apply_norm(p["norm"], x, "rmsnorm")
    zxbcdt = h @ p["in_proj"].astype(dt_)
    z, xbc, dt_raw = jnp.split(zxbcdt, [d_in, 2 * d_in + 2 * N], axis=-1)
    conv_in_state = None if state is None else state["conv"]
    xbc, new_conv = causal_conv(xbc, p["conv_w"], p["conv_b"], conv_in_state)
    xbc = jax.nn.silu(xbc)
    xs, Bm, Cm = jnp.split(xbc, [d_in, d_in + N], axis=-1)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])  # (B,S,H)
    xh = xs.reshape(B, S, H, P)
    ssm_state = None if state is None else state["h"]
    # ``use_pallas="attention"`` (the trainer on a TPU) selects attention's
    # kernel alone: the ssd recurrence then stays on its XLA path.
    if use_pallas is True:
        from repro.kernels import ops as kernel_ops

        y, new_h = kernel_ops.ssd(xh, dt, p["A_log"], Bm, Cm, state=ssm_state)
    else:
        y, new_h = ssd_chunked(xh, dt, p["A_log"], Bm, Cm, state=ssm_state)
    y = y + p["D"][None, None, :, None] * xh.astype(jnp.float32)
    y = y.reshape(B, S, d_in).astype(dt_)
    y = L.apply_norm(p["gate_norm"], y * jax.nn.silu(z), "rmsnorm")
    out = y @ p["out_proj"].astype(dt_)
    new_state = None if state is None else {"h": new_h, "conv": new_conv.astype(jnp.bfloat16)}
    return out, new_state


def block_state(cfg, batch):
    d_in = cfg.ssm_expand * cfg.d_model
    H = cfg.ssm_heads or (d_in // 64)
    P = d_in // H
    conv_dim = d_in + 2 * cfg.ssm_state
    return {
        "h": jnp.zeros((batch, H, P, cfg.ssm_state), jnp.float32),
        "conv": jnp.zeros((batch, CONV_K - 1, conv_dim), jnp.bfloat16),
    }


def block_state_specs(cfg, batch):
    d_in = cfg.ssm_expand * cfg.d_model
    H = cfg.ssm_heads or (d_in // 64)
    P = d_in // H
    conv_dim = d_in + 2 * cfg.ssm_state
    return {
        "h": jax.ShapeDtypeStruct((batch, H, P, cfg.ssm_state), jnp.float32),
        "conv": jax.ShapeDtypeStruct((batch, CONV_K - 1, conv_dim), jnp.bfloat16),
    }
