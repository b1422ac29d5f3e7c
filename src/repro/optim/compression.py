"""Gradient / payload compression for distributed training at scale.

* ``topk_compress_ef``: top-k sparsification with error feedback (memory) —
  the classic bandwidth reducer for DP gradient exchange over slow links
  (the paper's edge setting); convergence-safe via EF residual accumulation.
* ``int8_quantize``/``int8_dequantize``: per-block int8 quantization used both
  for compressed all-reduce payloads and for Chaos state-replication shards
  (see kernels/shard_codec.py for the TPU kernel; this is the jnp reference
  implementation used on hosts).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

Q_BLOCK = 256

#: Relative slack on the ``scale / 2`` round-trip bound of
#: :func:`int8_dequantize`, from fp32 arithmetic. Codes are the exactly
#: rounded quotient (:func:`round_quotient`), so the only error past
#: ``scale / 2`` is the reconstruction ``code * scale``, which rounds by at
#: most 2**-24 * 127 * scale, i.e. 254 * 2**-24 of the bound; the f32
#: subtraction and comparison that check it add 2**-24 each. That is under
#: 2**-16 of the bound; the slack is twice that.
ROUNDTRIP_REL_SLACK = 2.0 ** -15


def round_quotient(x, scale):
    """``round_half_even(x / scale)`` of the exact quotient, for f32 ``x``
    and positive f32 ``scale`` (broadcast against ``x``) with
    ``|x / scale| <= 127.5``. An f32 division need not be correctly rounded
    (a TPU's is off by up to a few ulps), and even a correctly rounded one
    can land on a half-integer the exact quotient misses; either changes
    codes near a half-code. So the code from the division is checked against
    the half-codes on both sides: ``scale`` is split into two 12-bit halves,
    which makes every product with a half-code exact and the sign of
    ``x - h * scale`` exact. Plain jnp, so the Pallas kernel and the XLA
    references run the same arithmetic and agree on every device."""
    c = jnp.round(x / scale)
    hi = jax.lax.bitcast_convert_type(
        jax.lax.bitcast_convert_type(scale, jnp.int32) & -4096, jnp.float32)
    lo = scale - hi

    def past(h):  # sign of x - h * scale
        return (x - h * hi) - h * lo

    up, down = past(c + 0.5), past(c - 0.5)
    code = jnp.where(up > 0, c + 1, jnp.where(down < 0, c - 1, c))
    # an exact tie goes to the even neighbour
    code = jnp.where(up == 0, 2 * jnp.round(0.5 * (c + 0.5)), code)
    return jnp.where(down == 0, 2 * jnp.round(0.5 * (c - 0.5)), code)


def topk_compress_ef(grads, residual, k_frac: float = 0.01):
    """Top-|k| sparsification with error feedback.

    Returns (sparse_grads, new_residual). ``sparse_grads`` has the same
    pytree/shape as ``grads`` but only the top k fraction (by magnitude) of
    entries of (grad + residual) are kept; the remainder accumulates into the
    residual for future steps (error feedback).
    """

    def one(g, r):
        g = g.astype(jnp.float32) + r
        flat = g.reshape(-1)
        k = max(1, int(flat.size * k_frac))
        thresh = jax.lax.top_k(jnp.abs(flat), k)[0][-1]
        mask = jnp.abs(g) >= thresh
        sparse = jnp.where(mask, g, 0.0)
        return sparse, g - sparse

    out = jax.tree.map(one, grads, residual)
    sparse = jax.tree.map(lambda o: o[0], out, is_leaf=lambda x: isinstance(x, tuple))
    new_r = jax.tree.map(lambda o: o[1], out, is_leaf=lambda x: isinstance(x, tuple))
    return sparse, new_r


def ef_init(params):
    return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)


def int8_quantize(x, block: int = Q_BLOCK):
    """x: any-shape float array → (codes int8 (nb, block), scales fp32 (nb,), meta).

    This is the jnp reference for ``kernels/shard_codec.shard_encode_kernel``:
    identical per-block scale formula (max-abs times the fp32 constant 1/127,
    with a 1e-12 floor) and identical rounding (:func:`round_quotient`, the
    exactly rounded quotient), so codes and scales are
    **bit-identical** between the two on every device (the pairing property test in
    tests/test_codec.py pins this down). The scale is written as an explicit
    reciprocal multiply — a single well-defined fp32 op — because ``/ 127.0``
    is at the compiler's mercy: one lowering keeps the true division, another
    rewrites it to the reciprocal, and the two differ by 1 ulp on some
    inputs, silently breaking the bit-identity contract.
    """
    n = x.size
    pad = (-n) % block
    xf = jnp.pad(x.astype(jnp.float32).reshape(-1), (0, pad)).reshape(-1, block)
    scale = jnp.maximum(jnp.max(jnp.abs(xf), axis=1), 1e-12) * (1.0 / 127.0)
    codes = jnp.clip(round_quotient(xf, scale[:, None]), -127, 127).astype(jnp.int8)
    return codes, scale, (x.shape, x.dtype)


def int8_dequantize(codes, scale, meta, block: int = Q_BLOCK):
    """Inverse of :func:`int8_quantize`, with a documented error guarantee.

    **Max-error bound**: codes are the exactly rounded quotient inside each
    block, so for fp32 inputs every element satisfies
    ``|dequantized - original| <= scale_of_its_block / 2`` up to fp32
    rounding of the ``code * scale`` reconstruction: at most
    ``ROUNDTRIP_REL_SLACK`` of the bound (checked in
    ``repro.core.replication.roundtrip_max_error_ok``).

    The bound is stated in fp32 — reconstruction happens in fp32 and only
    the **final** cast goes to the original dtype, so for a non-fp32 input
    (e.g. bf16/f16 state) the guarantee holds for the fp32 values *before*
    that cast; the cast adds at most half an ulp of the target dtype on top.
    Integer dtypes round on the cast, keeping the same scale/2 + 1/2 bound
    element-wise. Earlier revisions cast silently, losing the bound without
    a trace — the contract is now explicit and tested.
    """
    shape, dtype = meta
    n = 1
    for s in shape:
        n *= int(s)
    xf = codes.astype(jnp.float32) * scale[:, None]
    xf = xf.reshape(-1)[:n].reshape(shape)
    if jnp.issubdtype(dtype, jnp.integer):
        # Round-to-nearest before the integer cast (a raw cast truncates,
        # which would double the worst-case error).
        xf = jnp.round(xf)
    return xf.astype(dtype)


def compressed_bytes(codes, scale) -> int:
    return codes.size + scale.size * 4
