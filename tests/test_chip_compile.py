"""The main path's Pallas kernels compile for a TPU v5e at GPT-2 S widths.

Nothing runs: each test lowers a kernel with ``interpret=False`` for one chip
of a described ``v5e:2x2`` topology and compiles it with the TPU compiler,
which refuses block shapes the chip cannot tile. The topology is described
inside a fixture, so the TPU library is loaded only by the worker that runs
this file, and the tests skip where it cannot be described.
"""
import os

import pytest

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import flash_attention_kernel
from repro.kernels.shard_codec import (
    Q_BLOCK,
    shard_decode_kernel,
    shard_encode_kernel,
)

#: GPT-2 S shard-codec block counts: the tied embedding (50257×768 →
#: 150,771 blocks), an awkward count, a tiny leaf (768 → 3) and a
#: 768×3072 matrix (9,216).
CODEC_NB = [150_771, 300, 3, 9_216]


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler, or its library is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A TPU program written to the persistent cache cannot be read back
    # without a chip; keep these compiles out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *shapes):
    """Compile ``fn`` for the described chip; returns the program text."""
    return jax.jit(fn).lower(*shapes).compile().as_text()


@pytest.mark.parametrize("nb", CODEC_NB)
def test_shard_codec_compiles_for_v5e(one_chip, nb):
    x = jax.ShapeDtypeStruct((nb, Q_BLOCK), jnp.float32, sharding=one_chip)
    codes = jax.ShapeDtypeStruct((nb, Q_BLOCK), jnp.int8, sharding=one_chip)
    scales = jax.ShapeDtypeStruct((nb,), jnp.float32, sharding=one_chip)
    enc = _compile(lambda a: shard_encode_kernel(a, interpret=False), x)
    dec = _compile(lambda c, s: shard_decode_kernel(c, s, interpret=False),
                   codes, scales)
    assert "tpu_custom_call" in enc and "tpu_custom_call" in dec


def test_flash_attention_compiles_for_v5e(one_chip):
    """GPT-2 S attention: batch 8, sequence 1024, 12 heads of 64."""
    q = jax.ShapeDtypeStruct((8, 1024, 12, 64), jnp.bfloat16,
                             sharding=one_chip)
    hlo = _compile(lambda q, k, v: flash_attention_kernel(
        q, k, v, scale=0.125, interpret=False), q, q, q)
    assert "tpu_custom_call" in hlo
