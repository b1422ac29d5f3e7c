"""The main path's Pallas kernels compile for a TPU v5e at GPT-2 widths.

Nothing runs: each test lowers a kernel with ``interpret=False`` for one chip
(or all four) of a described ``v5e:2x2`` topology and compiles it with the
TPU compiler, which refuses block shapes the chip cannot tile. The topology
is described inside a fixture, so the TPU library is loaded only by the
worker that runs this file, and the tests skip where it cannot be described.
"""
import os
import re
from dataclasses import replace

import pytest

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.kernels.flash_attention import flash_attention_fwd
from repro.models.layers import MaskSpec
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
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

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
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


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
    hlo = _compile(lambda q, k, v: flash_attention_fwd(
        q, k, v, scale=0.125, interpret=False), q, q, q)
    assert "tpu_custom_call" in hlo


@pytest.fixture
def mosaic(monkeypatch):
    """The kernels compile to Mosaic although the default backend is the
    CPU."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)


def _custom_calls(hlo):
    """The ``op_name`` of each Pallas kernel in a compiled program."""
    return [re.search(r'op_name="([^"]*)"', line).group(1)
            for line in hlo.splitlines()
            if "custom_call_target=\"tpu_custom_call\"" in line]


def test_flash_attention_op_compiles_for_v5e(one_chip, mosaic):
    """GPT-2 M attention, forward and backward through ops.flash_attention:
    batch 8, sequence 1024, 16 heads of 64, bf16; three kernels (the
    forward with its residuals, dK/dV, dQ) and no S x S buffer."""
    x = jax.ShapeDtypeStruct((8, 1024, 16, 64), jnp.bfloat16,
                             sharding=one_chip)

    def loss(q, k, v):
        o = ops.flash_attention(q, k, v, MaskSpec("causal"), scale=0.125)
        return jnp.sum(o.astype(jnp.float32))

    hlo = _compile(jax.value_and_grad(loss, argnums=(0, 1, 2)), x, x, x)
    assert len(_custom_calls(hlo)) == 3
    assert "1024,1024]" not in hlo


def test_flash_attention_op_compiles_per_shard_for_v5e_2x2(topo, mosaic):
    """GPT-2 S attention on a (data 2, model 2) mesh of four chips, set as
    the trainer sets it: the kernels run per shard on local arrays (16 rows
    x 6 heads), and nothing is gathered."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"))
    x = jax.ShapeDtypeStruct(
        (32, 1024, 12, 64), jnp.bfloat16,
        sharding=NamedSharding(mesh, P("data", None, "model", None)))

    def loss(q, k, v):
        o = ops.flash_attention(q, k, v, MaskSpec("causal"), scale=0.125)
        return jnp.sum(o.astype(jnp.float32))

    with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
        hlo = _compile(jax.value_and_grad(loss, argnums=(0, 1, 2)), x, x, x)
    assert len(_custom_calls(hlo)) == 3
    assert re.search(r"bf16\[96,1024,64\]\S* custom-call", hlo)
    assert "all-gather" not in hlo


def test_train_step_runs_attention_in_the_kernel(one_chip, mosaic):
    """A 2-layer train step at GPT-2 M's width and batch with the kernel
    selected: every kernel, forward (and recomputed forward) and backward,
    carries the `attention_core` scope that `attn_core_share` reads, and no
    S x S score buffer is left in f32 or bf16."""
    from repro.configs import get_config
    from repro.models import build_model

    cfg = replace(get_config("gpt2-medium"), n_layers=2)
    model = build_model(cfg)
    state = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        model.train_state_specs())
    batch = {"tokens": jax.ShapeDtypeStruct((8, 1025), jnp.int32,
                                            sharding=one_chip)}
    hlo = _compile(model.make_train_step(use_pallas="attention"), state,
                   batch)
    calls = _custom_calls(hlo)
    assert len(calls) == 4, calls
    assert all("attention_core" in op for op in calls), calls
    assert any(op.startswith("jit(train_step)/transpose(") for op in calls)
    assert not re.search(r"(f32|bf16)\[8,16,1024,1024\]", hlo)
