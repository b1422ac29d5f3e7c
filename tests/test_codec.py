"""Codec-aware replication: wire-byte math, per-link negotiation, the
codec="none" byte-identity invariant, int8 wire reduction through the engine,
control-plane sync compression, the real-array encode/decode path, and the
kernel-vs-reference bit-identity pairing (tentpole + satellites 1/2, PR 6)."""
import math
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

from repro.core import SimCluster, random_edge_topology, run_trace_sim
from repro.core import codec as wire_codec
from repro.core.engine import ChurnEvent, SimBackend
from repro.core.plans import build_plan
from repro.scenarios import poisson_churn

MB = 1024 * 1024
ROOT = Path(__file__).resolve().parent.parent


# ---------------------------------------------------------------------------
# Wire-byte math + negotiation (the cost model).
# ---------------------------------------------------------------------------


def test_wire_bytes_none_is_identity():
    for p in (0, 1, 17, 4096, 128 * MB):
        assert wire_codec.wire_bytes(wire_codec.CODEC_NONE, p) == p


def test_wire_bytes_int8_formula_and_asymptote():
    p = 128 * MB
    elems = math.ceil(p / 4)
    blocks = math.ceil(elems / wire_codec.Q_BLOCK)
    expect = elems + blocks * wire_codec.SCALE_BYTES
    assert wire_codec.wire_bytes(wire_codec.CODEC_INT8, p) == expect
    # Per-shard framing floor: 4 payload bytes become 1 code byte + a
    # 4/256-amortized scale — ~3.94×, which is why the CI bar is ≥3×.
    assert 3.9 < p / expect < 4.0


def test_wire_bytes_int8_topk_keeps_fraction_plus_indices():
    p = 64 * MB
    elems = p // 4
    kept = max(1, int(elems * wire_codec.TOPK_KEEP_FRAC))
    blocks = math.ceil(elems / wire_codec.Q_BLOCK)
    expect = kept * (1 + wire_codec.TOPK_INDEX_BYTES) + blocks * wire_codec.SCALE_BYTES
    assert wire_codec.wire_bytes(wire_codec.CODEC_INT8_TOPK, p) == expect
    assert p / expect > 10  # much sparser than plain int8


def test_wire_bytes_tiny_payloads_never_zero_or_negative():
    for codec in wire_codec.CODECS:
        for p in (1, 2, 3, 4, 5, 255, 256, 257):
            w = wire_codec.wire_bytes(codec, p)
            assert w >= 1, (codec, p, w)


def test_codec_compute_charges_zero_only_for_none():
    p = 32 * MB
    assert wire_codec.encode_s(wire_codec.CODEC_NONE, p) == 0.0
    assert wire_codec.decode_s(wire_codec.CODEC_NONE, p) == 0.0
    for codec in (wire_codec.CODEC_INT8, wire_codec.CODEC_INT8_TOPK):
        assert wire_codec.encode_s(codec, p) > 0.0
        assert wire_codec.decode_s(codec, p) > 0.0
    # top-k pays an extra selection pass over plain int8.
    assert (wire_codec.encode_s(wire_codec.CODEC_INT8_TOPK, p)
            > wire_codec.encode_s(wire_codec.CODEC_INT8, p))


def test_effective_per_byte_derates_fast_links_less():
    """On a fast link the encode/decode compute dominates and compression
    stops paying; on a slow link the wire saving dominates."""
    fast = 1.0 / (2000 * wire_codec.MBPS)  # s/byte on a 2 Gbps link
    slow = 1.0 / (50 * wire_codec.MBPS)
    assert (wire_codec.effective_trans_s_per_byte(wire_codec.CODEC_INT8, slow)
            < slow)
    assert (wire_codec.effective_trans_s_per_byte(wire_codec.CODEC_NONE, fast)
            == fast)


def test_negotiate_auto_picks_by_bandwidth_class():
    assert wire_codec.negotiate("auto", 10_000.0) == wire_codec.CODEC_NONE
    assert wire_codec.negotiate("auto", 2000.0) == wire_codec.CODEC_NONE
    assert wire_codec.negotiate("auto", 500.0) == wire_codec.CODEC_INT8
    assert wire_codec.negotiate("auto", 150.0) == wire_codec.CODEC_INT8
    assert wire_codec.negotiate("auto", 20.0) == wire_codec.CODEC_INT8_TOPK


def test_negotiate_forced_policy_wins_over_bandwidth():
    for bw in (10.0, 500.0, 10_000.0):
        assert wire_codec.negotiate("int8", bw) == wire_codec.CODEC_INT8
        assert wire_codec.negotiate("none", bw) == wire_codec.CODEC_NONE


def test_validate_policy_rejects_unknown():
    with pytest.raises(ValueError):
        wire_codec.validate_policy("gzip")
    with pytest.raises(ValueError):
        SimCluster(random_edge_topology(4, seed=0), state_bytes=MB,
                   tensor_sizes=[MB], codec="zstd")


# ---------------------------------------------------------------------------
# Plans carry wire accounting; "none" plans are byte-for-byte legacy.
# ---------------------------------------------------------------------------


def _plan(codec):
    topo = random_edge_topology(8, seed=0)
    new = 100
    topo.add_node(new)
    for p, bw in ((1, 400.0), (2, 600.0), (3, 250.0)):
        from repro.core.topology import Link
        topo.add_link(p, new, Link(bw, 0.01))
    return build_plan("chaos", topo, new, 64 * MB, [2 * MB] * 32, {},
                      codec=codec)


def test_plan_none_has_no_wire_fields_and_legacy_summary():
    plan = _plan("none")
    assert not plan.codec_active()
    assert plan.wire_sources == {}
    assert plan.codecs == {}
    assert "codecs" not in plan.summary()
    assert "wire_bytes" not in plan.summary()
    for u in plan.sources:
        assert plan.wire_for(u) == plan.sources[u]  # wire == payload


def test_plan_int8_wire_undercuts_payload_shard_aligned():
    plan = _plan("int8")
    assert plan.codec_active()
    s = plan.summary()
    assert set(s["codecs"]) == {str(u) for u in plan.sources}
    for u, payload in plan.sources.items():
        wire = plan.wire_for(u)
        assert wire < payload
        # Per-shard framing: n whole shards + remainder, each encoded
        # independently so partial credit can decode delivered prefixes.
        shard = plan.shard_size
        n_whole, rem = divmod(payload, shard)
        expect = n_whole * wire_codec.wire_bytes("int8", shard)
        if rem:
            expect += wire_codec.wire_bytes("int8", rem)
        assert wire == expect
        assert plan.wire_shard_for(u) == wire_codec.wire_bytes("int8", shard)
    assert plan.total_wire_bytes() < sum(plan.sources.values())


# ---------------------------------------------------------------------------
# Engine: byte-identity under "none", reduction + determinism under int8.
# ---------------------------------------------------------------------------


def _churny_cluster(seed=0):
    return SimCluster(random_edge_topology(16, seed=seed),
                      state_bytes=32 * MB, tensor_sizes=[MB] * 32)


def _churny_trace(seed=0):
    topo = random_edge_topology(16, seed=seed)
    return poisson_churn(topo.active_nodes(), seed=seed + 3, horizon_s=600.0,
                         rate_join=0.05, rate_leave=0.04)


def _churny_replay(omniscient_digest, codec=None, seed=0):
    kw = {} if codec is None else {"codec": codec}
    return omniscient_digest(lambda: _churny_cluster(seed),
                             _churny_trace(seed), **kw)


def test_codec_none_ledger_byte_identical_to_codec_less_engine(omniscient_digest):
    """The tentpole invariant: codec="none" reproduces the pre-codec ledger
    bytes exactly — same trace, same seed, a run that never mentions a
    codec vs one that passes codec="none" explicitly."""
    l_default = _churny_replay(omniscient_digest, codec=None)
    l_none = _churny_replay(omniscient_digest, codec="none")
    assert l_default.canonical_bytes() == l_none.canonical_bytes()
    assert l_default.digest() == l_none.digest()
    assert l_default.actions().count("ready") >= 3  # real work happened


def test_codec_int8_same_seed_byte_identical(same_seed_pair):
    same_seed_pair(lambda: _churny_cluster(0), _churny_trace(0),
                   codec="int8")


def test_codec_int8_ledger_carries_wire_fields_none_does_not(omniscient_digest):
    l_none = _churny_replay(omniscient_digest, codec="none")
    l_int8 = _churny_replay(omniscient_digest, codec="int8")
    none_started = [r for r in l_none if r.action == "scale-out-started"]
    int8_started = [r for r in l_int8 if r.action == "scale-out-started"]
    assert all("codec" not in r.detail for r in none_started)
    assert all(r.detail["codec"] == "int8" for r in int8_started)
    for r in int8_started:
        payload = sum(r.detail["plan"]["sources"].values())
        assert 0 < r.detail["wire_bytes_total"] < payload
    ready = [r for r in l_int8 if r.action == "ready"]
    assert ready and all(r.detail["wire_delivered_bytes"] > 0 for r in ready)


def test_codec_int8_cuts_wire_bytes_3x_and_join_delay():
    def join(codec):
        topo = random_edge_topology(8, seed=0)
        cl = SimCluster(topo, state_bytes=128 * MB,
                        tensor_sizes=[2 * MB] * 64)
        cl.train(1)
        ev = ChurnEvent(t=cl.sim.now, kind="join", node=100,
                        links={1: (200.0, 0.01), 2: (200.0, 0.01),
                               3: (200.0, 0.02)})
        _, results = run_trace_sim(cl, [ev], codec=codec)
        return results[0].delay_s, cl.scheduler.replication_wire_bytes

    none_delay, none_wire = join("none")
    int8_delay, int8_wire = join("int8")
    assert none_wire == 128 * MB  # wire == payload without a codec
    assert none_wire / int8_wire >= 3.0
    assert int8_delay < none_delay  # the saved bytes show up on the clock


def test_churn_event_codec_json_roundtrip():
    ev = ChurnEvent(t=1.5, kind="join", node=7,
                    links={1: (100.0, 0.01)}, codec="int8")
    d = ev.to_json()
    assert d["codec"] == "int8"
    back = ChurnEvent.from_json(d)
    assert back.codec == "int8"
    # Absent codec stays absent (legacy traces parse unchanged).
    ev2 = ChurnEvent(t=1.5, kind="join", node=7, links={1: (100.0, 0.01)})
    assert "codec" not in ev2.to_json()
    assert ChurnEvent.from_json(ev2.to_json()).codec is None


def test_join_event_codec_overrides_scheduler_policy():
    topo = random_edge_topology(8, seed=0)
    cl = SimCluster(topo, state_bytes=32 * MB, tensor_sizes=[MB] * 32)
    cl.train(1)
    ev = ChurnEvent(t=cl.sim.now, kind="join", node=100,
                    links={1: (200.0, 0.01), 2: (300.0, 0.01)},
                    codec="int8")
    ledger, _ = run_trace_sim(cl, [ev])  # engine policy stays "none"
    started = [r for r in ledger if r.action == "scale-out-started"][0]
    assert started.detail["codec"] == "int8"


# ---------------------------------------------------------------------------
# Control plane: deputy sync snapshots compress under the codec too.
# ---------------------------------------------------------------------------


def test_sync_payload_compresses_with_scheduler_policy():
    from repro.core.control import SYNC_BYTES

    def backend(codec):
        topo = random_edge_topology(8, seed=0)
        cl = SimCluster(topo, state_bytes=MB, tensor_sizes=[MB])
        return SimBackend(cl, codec=codec)

    b_none = backend("none")
    b_int8 = backend("int8")
    assert b_none.control._sync_payload_bytes() == SYNC_BYTES
    compressed = b_int8.control._sync_payload_bytes()
    assert compressed == wire_codec.wire_bytes(wire_codec.CODEC_INT8,
                                               SYNC_BYTES)
    assert compressed < SYNC_BYTES / 3


# ---------------------------------------------------------------------------
# Shard-codec grid on awkward block counts.
# ---------------------------------------------------------------------------


def test_block_rows_largest_divisor_within_cap():
    """Block heights the TPU accepts: the whole array when it fits in one
    block, else the cap (a multiple of the int8 tile's 32 rows) over a
    ragged ``cdiv`` grid. Divisor search gave illegal heights (nb=300 → 150,
    the GPT-2 embedding's 150,771 blocks → 87)."""
    from repro.kernels.shard_codec import ROWS_PER_BLOCK, _block_rows

    assert ROWS_PER_BLOCK == 256 and ROWS_PER_BLOCK % 32 == 0
    assert _block_rows(300) == 256
    assert _block_rows(510) == 256
    assert _block_rows(150_771) == 256
    assert _block_rows(7) == 7
    assert _block_rows(64) == 64
    assert _block_rows(257) == 256
    for nb in (1, 2, 3, 5, 12, 30, 97, 300, 510, 777, 1000):
        r = _block_rows(nb)
        assert r == nb or (r == 256 and nb > 256)


@pytest.mark.parametrize("nb", [1, 7, 97, 300, 510, 1000])
def test_shard_codec_roundtrip_awkward_block_counts(nb):
    """Awkward nb (not a multiple of the block height) runs a ragged last
    block, and must stay bit-identical to the reference through
    encode/decode."""
    from repro.kernels.ref import shard_codec_ref, shard_decode_ref
    from repro.kernels.shard_codec import (
        shard_decode_kernel,
        shard_encode_kernel,
    )
    import jax.numpy as jnp

    rng = np.random.default_rng(nb)
    x = jnp.asarray(rng.normal(size=(nb, 256)).astype(np.float32))
    c, s = shard_encode_kernel(x, interpret=True)
    cr, sr = shard_codec_ref(x)
    assert np.array_equal(np.asarray(c), np.asarray(cr))
    assert np.array_equal(np.asarray(s), np.asarray(sr))
    d = shard_decode_kernel(c, s, interpret=True)
    dr = shard_decode_ref(cr, sr)
    assert np.array_equal(np.asarray(d), np.asarray(dr))
    # Round-trip error within the documented bound (fp32 slack included).
    err = np.abs(np.asarray(d) - np.asarray(x))
    bound = np.asarray(s)[:, None] / 2.0
    assert np.all(err <= bound * (1 + 1e-5))


# ---------------------------------------------------------------------------
# Satellite 2: int8_quantize ⇄ kernel pairing is bit-identical; dequantize
# honors its documented max-error guarantee.
# ---------------------------------------------------------------------------


def test_quantize_pairs_bit_identical_with_kernel_encode():
    """Property sweep: over shapes, magnitudes, and degenerate values, the
    jnp quantizer and the Pallas encode kernel produce bit-identical codes
    AND scales (the contract the real-array transfer path asserts)."""
    from repro.kernels.shard_codec import shard_encode_kernel
    from repro.optim.compression import Q_BLOCK, int8_quantize
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    cases = []
    for shape in [(256,), (300, 17), (1000,), (5, 7, 11), (4096,)]:
        for mag in (1e-6, 1.0, 1e4):
            cases.append((rng.normal(size=shape) * mag).astype(np.float32))
    cases.append(np.zeros(512, np.float32))  # scale floor path
    cases.append(np.full(300, 7.25, np.float32))
    for x in cases:
        codes, scales, _ = int8_quantize(jnp.asarray(x))
        pad = (-x.size) % Q_BLOCK
        xf = np.pad(x.reshape(-1), (0, pad)).reshape(-1, Q_BLOCK)
        kc, ks = shard_encode_kernel(jnp.asarray(xf), interpret=True)
        assert np.array_equal(np.asarray(kc), np.asarray(codes)), x.shape
        assert np.array_equal(np.asarray(ks), np.asarray(scales)), x.shape


def test_dequantize_max_error_within_documented_bound():
    from repro.optim.compression import int8_dequantize, int8_quantize
    import jax.numpy as jnp

    rng = np.random.default_rng(1)
    x = (rng.normal(size=(3000,)) * 5.0).astype(np.float32)
    codes, scales, meta = int8_quantize(jnp.asarray(x))
    back = np.asarray(int8_dequantize(codes, scales, meta))
    err = np.abs(back - x)
    per_elem_bound = np.repeat(np.asarray(scales), 256)[: x.size] / 2.0
    assert np.all(err <= per_elem_bound * (1 + 1e-5))


def test_dequantize_integer_dtype_rounds_not_truncates():
    from repro.optim.compression import int8_dequantize, int8_quantize
    import jax.numpy as jnp

    x = np.arange(512, dtype=np.int32) - 256
    codes, scales, meta = int8_quantize(jnp.asarray(x, jnp.float32))
    meta = (x.shape, np.dtype(np.int32))
    back = np.asarray(int8_dequantize(codes, scales, meta))
    assert back.dtype == np.int32
    # Round-to-nearest: error ≤ scale/2 + 1/2, not the doubled truncation
    # error a raw cast would produce.
    bound = np.repeat(np.asarray(scales), 256)[: x.size] / 2.0 + 0.5
    assert np.all(np.abs(back - x) <= bound + 1e-3)


# ---------------------------------------------------------------------------
# Real-array transfer path: encode/decode shard buffers.
# ---------------------------------------------------------------------------


def _mixed_tree():
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    return {
        "w": jnp.asarray(rng.normal(size=(300, 17)).astype(np.float32)),
        "b": jnp.asarray(rng.normal(size=(1000,)).astype(np.float32)),
        "step": jnp.asarray(7, np.int32),
        "half": jnp.asarray(rng.normal(size=(64,)).astype(np.float16)),
        "lr": jnp.asarray(1e-3, jnp.float32),
    }


def test_encode_state_int8_reduces_wire_and_bounds_error():
    import jax
    from repro.core.replication import (
        decode_state,
        encode_state,
        roundtrip_max_error_ok,
    )

    tree = _mixed_tree()
    leaves, manifest, wire = encode_state(tree, "int8", verify_kernel=True)
    payload = sum(l.payload_bytes for l in leaves)
    assert payload == manifest.total_bytes
    assert payload / wire > 3.0  # fp32-dominated tree
    # fp32 leaves quantize; everything else ships raw (exactness contract).
    kinds = {e.path: l.kind for e, l in zip(manifest.entries, leaves)}
    assert kinds["w"] == kinds["b"] == kinds["lr"] == "int8"
    assert kinds["step"] == kinds["half"] == "raw"
    decoded = decode_state(leaves, manifest, verify_kernel=True)
    assert roundtrip_max_error_ok(tree, decoded, leaves)
    for o, d in zip(jax.tree_util.tree_leaves(tree),
                    jax.tree_util.tree_leaves(decoded)):
        assert np.asarray(o).shape == np.asarray(d).shape
        assert np.asarray(o).dtype == np.asarray(d).dtype


def test_int8_codes_round_the_exact_quotient():
    """Codes are round-half-even of the exact ``x / scale``, whatever the f32
    division returns. With block max 1.0, 0.94094485 / scale rounds onto
    119.5 in f32 (plain rounding gives code 120) though the exact quotient
    lies below it. Swept over values a few ulps around every half-code, of
    both signs; the kernel and the references agree (the pairing tests),
    the round trip holds ``scale/2`` within ROUNDTRIP_REL_SLACK, and a code
    one off fails it."""
    import jax.numpy as jnp
    from repro.core.replication import (
        decode_state,
        encode_state,
        roundtrip_max_error_ok,
    )
    from repro.optim.compression import int8_quantize

    f32 = np.float32
    scale = f32(1.0) * f32(1.0 / 127.0)
    near = []
    for k in range(127):
        v = (f32(k) + f32(0.5)) * scale
        for _ in range(4):
            v = np.nextafter(v, f32(0))
        for _ in range(9):
            near += [v, -v]
            v = np.nextafter(v, f32(2))
    pad = (-len(near)) % 255
    blocks = np.asarray(near + [f32(0)] * pad, f32).reshape(-1, 255)
    x = np.concatenate([np.ones((len(blocks), 1), f32), blocks], axis=1)
    x[0, 1] = f32(0.94094485)

    codes, scales, _ = int8_quantize(jnp.asarray(x))
    exact = np.round(x.astype(np.float64)
                     / np.asarray(scales, np.float64)[:, None])
    assert np.array_equal(np.asarray(codes), exact)
    assert np.asarray(codes)[0, 1] == 119

    tree = {"w": jnp.asarray(x)}
    leaves, manifest, _ = encode_state(tree, "int8", verify_kernel=True)
    decoded = decode_state(leaves, manifest, verify_kernel=True)
    assert roundtrip_max_error_ok(tree, decoded, leaves)
    off = np.asarray(decoded["w"]).copy()
    off[0, 0] -= leaves[0].scales[0]
    assert not roundtrip_max_error_ok(tree, {"w": off}, leaves)


def test_encode_state_none_is_lossless_passthrough():
    import jax
    from repro.core.replication import decode_state, encode_state

    tree = _mixed_tree()
    leaves, manifest, wire = encode_state(tree, "none")
    assert all(l.kind == "raw" for l in leaves)
    assert wire == manifest.total_bytes
    decoded = decode_state(leaves, manifest)
    for o, d in zip(jax.tree_util.tree_leaves(tree),
                    jax.tree_util.tree_leaves(decoded)):
        assert np.array_equal(np.asarray(o), np.asarray(d))


# ---------------------------------------------------------------------------
# ElasticTrainer: the codec rides real scale-outs; installed state is exact.
# ---------------------------------------------------------------------------


def test_trainer_validates_codec_policy():
    from repro.core.sharding_alg import NeighborLink
    from repro.elastic.trainer import ElasticTrainer

    class _Dev:
        def __init__(self, i):
            self.id = i

    with pytest.raises(ValueError):
        ElasticTrainer(None, devices=[_Dev(0), _Dev(1)], initial=2,
                       link_model=lambda i: NeighborLink(0.001, 1e-8),
                       codec="brotli")


@pytest.mark.slow
def test_trainer_scale_out_int8_reports_wire_and_installs_exact_state():
    """Real-array acceptance: a codec="int8" scale-out encodes the shard
    buffers through the codec (kernel equivalence asserted inside), reports
    >3× wire reduction, and still installs bit-exact state (training
    continues unperturbed — synchronous DP replicas must not diverge)."""
    code = """
        import numpy as np
        import jax
        from repro.configs import get_config
        from repro.elastic import ElasticTrainer
        from repro.models import build_model

        cfg = get_config("gpt2").reduced()
        tr = ElasticTrainer(build_model(cfg), initial=2, codec="int8")
        tr.init()
        before = jax.tree_util.tree_map(lambda x: np.asarray(x), tr.state)
        ev = tr.scale_out()
        cs = ev.plan_summary["codec"]
        assert cs["codec"] == "int8", cs
        assert cs["wire_reduction"] > 3.0, cs
        assert cs["wire_bytes"] < cs["payload_bytes"]
        after = jax.tree_util.tree_map(lambda x: np.asarray(x), tr.state)
        for a, b in zip(jax.tree_util.tree_leaves(before),
                        jax.tree_util.tree_leaves(after)):
            assert np.array_equal(a, b)  # lossy install would diverge DP
        assert len(tr.active) == 3
        print("OK trainer-codec", cs["wire_reduction"])
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = str(ROOT / "src")
    res = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, timeout=420, env=env)
    assert res.returncode == 0, f"STDOUT:\n{res.stdout}\nSTDERR:\n{res.stderr}"
    assert "OK trainer-codec" in res.stdout
