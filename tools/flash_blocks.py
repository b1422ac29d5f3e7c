"""Times the flash-attention kernels on a TPU for each legal block size.

    PYTHONPATH=src python tools/flash_blocks.py [--batch 8 --seq 1024
        --heads 16 --head-dim 64] > blocks.jsonl

Causal self-attention in bfloat16 (GPT-2 M's shape by default). Prints one
JSON line per measurement: the forward kernel at each (block_q, block_k);
the backward with the dQ kernel held at 128 x 128 while the dK/dV kernel's
blocks vary, and the reverse, so that each kernel's time is the backward's
less the held kernel's; then the forward and backward of
``ops.flash_attention`` at the blocks ``choose_blocks`` picks, and of the
XLA path (``blocked_attention``) for comparison. Times are the best of five
means of 20 calls, host clock around ``block_until_ready``. Exits 2 without
a TPU.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as fa
from repro.kernels import ops
from repro.models.layers import MaskSpec, blocked_attention

SIZES = (128, 256, 512)


def best_mean_s(f, *args, calls=20, repeats=5):
    jax.block_until_ready(f(*args))
    best = float("inf")
    for _ in range(repeats):
        t = time.perf_counter()
        for _ in range(calls):
            out = f(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t) / calls)
    return best


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--heads", type=int, default=16)
    ap.add_argument("--head-dim", type=int, default=64)
    a = ap.parse_args()
    if jax.default_backend() != "tpu":
        print("no TPU", file=sys.stderr)
        return 2
    B, S, H, hd = a.batch, a.seq, a.heads, a.head_dim
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k, v, do = (jax.random.normal(kk, (B, S, H, hd), jnp.bfloat16)
                   for kk in keys)
    scale = hd ** -0.5
    kw = dict(scale=scale, interpret=False)
    # Useful causal FLOPs: the forward's two products over half the scores;
    # the dK/dV kernel makes four, the dQ kernel three.
    fwd_flop = 2 * 2 * B * H * S * S * hd / 2

    def emit(**row):
        print(json.dumps(row), flush=True)

    o, lse = jax.jit(lambda q, k, v: fa.flash_attention_fwd(q, k, v, **kw))(
        q, k, v)
    for bq in SIZES:
        for bk in SIZES:
            if S % bq or S % bk:
                continue
            f = jax.jit(lambda q, k, v, bq=bq, bk=bk: fa.flash_attention_fwd(
                q, k, v, block_q=bq, block_k=bk, **kw))
            t = best_mean_s(f, q, k, v)
            emit(kernel="fwd", block_q=bq, block_k=bk, ms=t * 1e3,
                 tflops=fwd_flop / t / 1e12)
    for vary in ("dkv", "dq"):
        for bq in SIZES:
            for bk in SIZES:
                if S % bq or S % bk:
                    continue
                blocks = {"blocks_dkv": (128, 128), "blocks_dq": (128, 128),
                          f"blocks_{vary}": (bq, bk)}
                f = jax.jit(lambda q, k, v, o, lse, do, blocks=blocks:
                            fa.flash_attention_bwd(q, k, v, o, lse, do,
                                                   **blocks, **kw))
                t = best_mean_s(f, q, k, v, o, lse, do)
                emit(kernel=f"bwd, {vary} varied", block_q=bq, block_k=bk,
                     ms=t * 1e3)

    spec = MaskSpec("causal")

    def grad_of(attn):
        def loss(q, k, v):
            return jnp.sum(attn(q, k, v).astype(jnp.float32)
                           * do.astype(jnp.float32))
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))

    for name, attn in (
            ("ops.flash_attention", lambda q, k, v: ops.flash_attention(
                q, k, v, spec, scale=scale)),
            ("xla blocked_attention", lambda q, k, v: blocked_attention(
                q, k, v, spec, scale=scale))):
        t = best_mean_s(grad_of(attn), q, k, v)
        emit(kernel=f"{name} forward+backward", ms=t * 1e3,
             blocks=fa.choose_blocks(S, S))
    return 0


if __name__ == "__main__":
    sys.exit(main())
