"""Plain float32 GPT-2 train step: the reference that decides `correct`.

Written from the GPT-2 description (Radford et al. 2019) and the
configuration file beside it, in straightforward `jax.numpy`: token and
learned position embeddings, pre-norm blocks of causal multi-head attention
and a tanh-GELU MLP, a final layer norm, a head tied to the token embedding,
next-token cross-entropy, gradients, clipping by the global norm and AdamW.
It imports nothing of the program under test and takes nothing it made.

The parameter tree uses the names and stacking of the trainer's state
(`params` / `opt`, layers stacked on a leading axis), so the benchmark can
hand the same seeded weights to both and compare leaf by leaf. Departures
from the published model that the program makes, and that this reference
therefore follows, are listed in the configuration files (`departures`).

Every matmul runs at `highest` precision, so on a TPU it is float32 and
not one bfloat16 pass. `quant="fp8"` gives the control one precision step
below the configuration's bfloat16 compute: the operands of every matmul
are rounded (straight through in the backward pass) to float8 e4m3 with one
scale per tensor. (A bfloat16 rounding written as two converts is no
control: with XLA's excess precision the TPU compiles it away.)

The rest of the family interface (`chipbench/reference/__init__.py`): the
program's fields, the rehearsal size, the PaLM FLOP count, the size of a
row's activations, and the leaves the faults and `check.py` name.
"""
from __future__ import annotations

import math
from dataclasses import replace
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

F8_MAX = 448.0  # largest finite float8_e4m3fn
UPDATE_LEAF = ("layers", "mlp", "w1")  # the MLP input matrix
STACKED = ("layers",)


def arch(cfg: dict) -> dict:
    """The sizes and training settings this module reads from a config file."""
    t = cfg["train"]
    d = cfg["n_embd"]
    return {
        "L": cfg["n_layer"], "d": d, "H": cfg["n_head"],
        "ff": cfg["n_inner"] or 4 * d, "V": cfg["vocab_size"],
        "P": cfg["n_positions"], "eps": cfg["layer_norm_epsilon"],
        "init_std": cfg["initializer_range"],
        "lr": t["learning_rate"], "b1": t["adam_b1"], "b2": t["adam_b2"],
        "adam_eps": t["adam_eps"], "wd": t["weight_decay"],
        "clip": t["grad_clip"],
    }


def program_fields(cfg: dict) -> dict:
    """The registry's `ArchConfig` model fields that must equal the file's."""
    return {"n_layers": cfg["n_layer"], "d_model": cfg["n_embd"],
            "n_heads": cfg["n_head"], "n_kv_heads": cfg["n_head"],
            "d_ff": cfg["n_inner"] or 4 * cfg["n_embd"],
            "vocab": cfg["vocab_size"], "norm_eps": cfg["layer_norm_epsilon"],
            "tie_embeddings": True, "positions": "learned",
            "norm": "layernorm", "mlp": "gelu2"}


def rehearse_cfg(cfg: dict, small):
    """(config file, `ArchConfig`) at the size of the registry's reduced
    config `small`, which GPT-2's lack of GQA keeps at n_kv_heads = n_heads."""
    small = replace(small, n_kv_heads=small.n_heads)
    return dict(cfg, n_layer=small.n_layers, n_embd=small.d_model,
                n_head=small.n_heads, n_inner=small.d_ff,
                vocab_size=small.vocab), small


def matmul_params(cfg: dict) -> int:
    d = cfg["n_embd"]
    ff = cfg["n_inner"] or 4 * d
    return cfg["n_layer"] * (4 * d * d + 2 * d * ff) + cfg["vocab_size"] * d


def train_flops_per_token(cfg: dict) -> int:
    """The PaLM count (Chowdhery et al. 2022, App. B): 6 N for the forward
    and backward matmuls over the N matmul parameters, plus 12 L S d for the
    attention scores and their weighted sums (causal masking not
    subtracted). N counts the four attention projections and the two MLP
    matrices of every layer and the head, which is tied to the token
    embedding but still a matmul; the learned position table and the
    embedding lookup do no matmul and are left out, as are layer norms.
    Recomputed work (remat) is not counted: it is not required."""
    seq = cfg["train"]["seq_len"]
    return (6 * matmul_params(cfg)
            + 12 * cfg["n_layer"] * seq * cfg["n_embd"])


def activation_bytes_per_row(a: dict, seq_len: int) -> int:
    """f32 bytes live for one row in `train_step`'s blocks: per position,
    each layer's input, one layer's ten d-wide and two S-wide arrays, and
    the logits with their softmax and its gradient."""
    return 4 * seq_len * (a["L"] * a["d"] + 10 * a["d"]
                          + 2 * a["H"] * seq_len + 3 * a["V"])


# ---------------------------------------------------------------------------
# Weights from the seed.
# ---------------------------------------------------------------------------


def seed_key(seed: int):
    """A PRNG key for any non-negative seed (wider than 32 bits too)."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


def param_shapes(a: dict) -> dict:
    L, d, ff, V, P = a["L"], a["d"], a["ff"], a["V"], a["P"]
    ln = {"scale": (L, d), "bias": (L, d)}
    return {
        "embed": {"tok": (V, d), "pos": (P, d)},
        "layers": {
            "ln1": ln,
            "attn": {"wq": (L, d, d), "wk": (L, d, d), "wv": (L, d, d),
                     "wo": (L, d, d)},
            "ln2": dict(ln),
            "mlp": {"w1": (L, d, ff), "w2": (L, ff, d)},
        },
        "final_norm": {"scale": (d,), "bias": (d,)},
    }


def init_params(a: dict, key):
    """GPT-2's initialisation: N(0, 0.02) for embeddings and matrices, the
    residual projections scaled by 1/sqrt(2L); layer-norm gain offsets and
    biases at zero (gain is stored as an offset from 1)."""
    shapes = param_shapes(a)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    out = []
    for i, (path, shape) in enumerate(flat):
        name = jax.tree_util.keystr(path)
        if "ln" in name or "final_norm" in name:
            out.append(jnp.zeros(shape, jnp.float32))
            continue
        std = a["init_std"]
        if name.endswith("['wo']") or name.endswith("['w2']"):
            std = std / math.sqrt(2 * a["L"])
        out.append(std * jax.random.normal(jax.random.fold_in(key, i), shape,
                                           jnp.float32))
    return jax.tree.unflatten(treedef, out)


def init_state(a: dict, key):
    """Parameters plus zeroed AdamW moments and step, as the trainer holds
    them. Call it under `jax.jit` so it runs as one program on the device."""
    params = init_params(a, key)
    zeros = jax.tree.map(jnp.zeros_like, params)
    return {"params": params,
            "opt": {"m": zeros, "v": jax.tree.map(jnp.zeros_like, params),
                    "step": jnp.zeros((), jnp.int32)}}


# ---------------------------------------------------------------------------
# Forward and loss.
# ---------------------------------------------------------------------------


def _round(x, quant):
    if quant is None:
        return x
    if quant == "fp8":
        scale = F8_MAX / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
        q = (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale
    else:
        raise ValueError(f"unknown quant {quant!r}")
    return x + lax.stop_gradient(q - x)


def _mm(eq, x, y, quant):
    return jnp.einsum(eq, _round(x, quant), _round(y, quant),
                      precision=lax.Precision.HIGHEST)


def _layer_norm(x, p, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * (1.0 + p["scale"]) + p["bias"]


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def _block(a, quant, x, lp):
    b, s, d = x.shape
    H = a["H"]
    hd = d // H
    h = _layer_norm(x, lp["ln1"], a["eps"])
    q = _mm("bsd,de->bse", h, lp["attn"]["wq"], quant).reshape(b, s, H, hd)
    k = _mm("bsd,de->bse", h, lp["attn"]["wk"], quant).reshape(b, s, H, hd)
    v = _mm("bsd,de->bse", h, lp["attn"]["wv"], quant).reshape(b, s, H, hd)
    scores = _mm("bqhd,bkhd->bhqk", q, k, quant) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    o = _mm("bhqk,bkhd->bqhd", probs, v, quant).reshape(b, s, d)
    x = x + _mm("bsd,de->bse", o, lp["attn"]["wo"], quant)
    h = _layer_norm(x, lp["ln2"], a["eps"])
    f = _gelu(_mm("bsd,df->bsf", h, lp["mlp"]["w1"], quant))
    return x + _mm("bsf,fd->bsd", f, lp["mlp"]["w2"], quant)


def loss_sum(a, quant, params, tokens):
    """Summed next-token cross-entropy of `tokens` (b, S+1)."""
    inputs, labels = tokens[:, :-1], tokens[:, 1:]
    s = inputs.shape[1]
    emb = params["embed"]
    x = emb["tok"][inputs] + emb["pos"][:s]
    # Each layer's activations are recomputed in the backward pass (only
    # its input is kept), so that a block of rows fits next to the state.
    layer = jax.checkpoint(lambda c, lp: (_block(a, quant, c, lp), None))
    x, _ = lax.scan(layer, x, params["layers"])
    x = _layer_norm(x, params["final_norm"], a["eps"])
    logits = _mm("bsd,vd->bsv", x, emb["tok"], quant)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(lse - gold)


# ---------------------------------------------------------------------------
# The train step, in blocks of rows.
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnums=(0, 1))
def _block_grad(a_items, quant, params, tokens):
    a = dict(a_items)
    return jax.value_and_grad(partial(loss_sum, a, quant))(params, tokens)


@partial(jax.jit, donate_argnums=0)
def _accumulate(acc, loss, grads):
    return acc[0] + loss, jax.tree.map(jnp.add, acc[1], grads)


@partial(jax.jit, static_argnums=(0,))
def _adamw(a_items, state, loss_total, grads_total, n_tokens):
    a = dict(a_items)
    grads = jax.tree.map(lambda g: g / n_tokens, grads_total)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                         for g in jax.tree.leaves(grads)))
    clip = jnp.minimum(1.0, a["clip"] / jnp.maximum(gnorm, 1e-9))
    grads = jax.tree.map(lambda g: g * clip, grads)
    opt = state["opt"]
    t = opt["step"] + 1
    bc1 = 1.0 - a["b1"] ** t.astype(jnp.float32)
    bc2 = 1.0 - a["b2"] ** t.astype(jnp.float32)
    m = jax.tree.map(lambda m, g: a["b1"] * m + (1 - a["b1"]) * g,
                     opt["m"], grads)
    v = jax.tree.map(lambda v, g: a["b2"] * v + (1 - a["b2"]) * g * g,
                     opt["v"], grads)
    params = jax.tree.map(
        lambda p, m, v: p - a["lr"] * ((m / bc1) / (jnp.sqrt(v / bc2)
                                                    + a["adam_eps"])
                                       + a["wd"] * p),
        state["params"], m, v)
    new = {"params": params, "opt": {"m": m, "v": v, "step": t}}
    return new, loss_total / n_tokens, grads, gnorm


def train_step(a: dict, state, tokens, devices, *, rows_per_block: int = 1,
               quant=None, param_dtype=None):
    """One AdamW step on the global batch `tokens` (B, S+1), a host array.
    The state lives on `devices[0]`; the gradient is summed over blocks of
    `rows_per_block` rows, the blocks dealt out to `devices` in turn and
    the sums gathered on the first. Returns the new state, the mean loss,
    the clipped gradient the optimizer got and the norm before clipping. `param_dtype` rounds the
    parameters after the update (the control's bfloat16 parameters)."""
    a_items = tuple(sorted(a.items()))
    params = [state["params"]] + [jax.device_put(state["params"], d)
                                  for d in devices[1:]]
    accs = [None] * len(devices)
    for i, r in enumerate(range(0, tokens.shape[0], rows_per_block)):
        k = i % len(devices)
        loss, grads = _block_grad(
            a_items, quant, params[k],
            jax.device_put(tokens[r:r + rows_per_block], devices[k]))
        accs[k] = (loss, grads) if accs[k] is None else \
            _accumulate(accs[k], loss, grads)
    del params[1:]
    acc = accs[0]
    for other in accs[1:]:
        if other is not None:
            acc = _accumulate(acc, *jax.device_put(other, devices[0]))
    n_tokens = tokens.shape[0] * (tokens.shape[1] - 1)
    new, loss, grads, gnorm = _adamw(a_items, state, acc[0], acc[1],
                                     jnp.float32(n_tokens))
    if param_dtype is not None:
        new["params"] = jax.tree.map(
            lambda p: p.astype(param_dtype).astype(jnp.float32),
            new["params"])
    return new, float(loss), grads, float(gnorm)
