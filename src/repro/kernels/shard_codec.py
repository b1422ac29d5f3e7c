"""Shard codec Pallas-TPU kernel: per-block int8 quantization of replication
payloads (paper §III — state shards shipped to a joining node; quantizing the
optimizer-moment shards cuts replication bytes ~4× with negligible recovery
error, a beyond-paper optimization).

Encode: (nb, 256) fp32 → int8 codes + fp32 per-block scales.
Decode: inverse. Grid over block rows; everything VMEM-resident.

Callers go through ``repro.kernels.ops``, which picks ``interpret``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.optim.compression import round_quotient

Q_BLOCK = 256

#: Block height of a grid step. int8 arrays tile as (32, 128) on the TPU, so a
#: block shorter than the array must be a multiple of 32 rows (which also
#: covers fp32's (8, 128)).
ROWS_PER_BLOCK = 256


def _block_rows(nb: int) -> int:
    """Rows per grid step. An array of at most ``ROWS_PER_BLOCK`` rows is one
    block (a block equal to the whole array is always legal); a taller one
    gets ``ROWS_PER_BLOCK``-row blocks over a ``cdiv`` grid, whose ragged last
    block Pallas pads on input and masks on output. Quantization is row-local,
    so the padding rows never touch a real row's scale or codes."""
    return min(nb, ROWS_PER_BLOCK)


def _encode_kernel(x_ref, codes_ref, scale_ref):
    x = x_ref[...].astype(jnp.float32)  # (rows, 256)
    # Explicit reciprocal multiply: "/ 127.0" may or may not be rewritten to
    # this by a given lowering; spelling it out keeps scales bit-identical to
    # the jnp references (ref.shard_codec_ref, compression.int8_quantize).
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=1, keepdims=True), 1e-12) * (1.0 / 127.0)
    codes = jnp.clip(round_quotient(x, scale), -127, 127)
    codes_ref[...] = codes.astype(jnp.int8)
    scale_ref[...] = scale


def _decode_kernel(codes_ref, scale_ref, x_ref):
    x_ref[...] = codes_ref[...].astype(jnp.float32) * scale_ref[...]


def shard_encode_kernel(x_blocks, *, interpret: bool):
    """x_blocks: (nb, 256) fp32 → (codes int8 (nb,256), scales fp32 (nb,))."""
    nb, w = x_blocks.shape
    assert w == Q_BLOCK
    r = _block_rows(nb)
    codes, scales = pl.pallas_call(
        _encode_kernel,
        grid=(pl.cdiv(nb, r),),
        in_specs=[pl.BlockSpec((r, w), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((r, w), lambda i: (i, 0)),
            pl.BlockSpec((r, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nb, w), jnp.int8),
            jax.ShapeDtypeStruct((nb, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x_blocks)
    return codes, scales[:, 0]


def shard_decode_kernel(codes, scales, *, interpret: bool):
    nb, w = codes.shape
    r = _block_rows(nb)
    out = pl.pallas_call(
        _decode_kernel,
        grid=(pl.cdiv(nb, r),),
        in_specs=[
            pl.BlockSpec((r, w), lambda i: (i, 0)),
            pl.BlockSpec((r, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((r, w), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, w), jnp.float32),
        interpret=interpret,
    )(codes, scales[:, None])
    return out
