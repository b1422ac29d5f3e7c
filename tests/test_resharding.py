"""Parallelism-plan resharding: churn reshapes the (dp, tp) plan, not just
shard placement. Pins the plan algebra (intervals, moved bytes, divisor
chain), the decision gate, the engine's credited fetch lifecycle
(started → ready / cancelled / replanned), byte-identity of
``reshard="never"`` with pre-reshard ledgers, cross-substrate decision
parity, and — in the slow subprocess cases — bit-identical dp → tp → dp
round trips on real arrays."""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from repro.core import SimCluster, random_edge_topology, run_trace_sim
from repro.core.engine import ChurnEngine, ChurnEvent, SimBackend
from repro.core.plans import (
    ParallelismPlan,
    ReshardPolicy,
    candidate_plans,
    decide_reshard,
    default_reshard_policy,
    reshard_moved_bytes,
    reshard_plan,
)
from repro.core.topology import Link, Topology
from repro.scenarios import reshard_churn

MB = 1024 * 1024
ROOT = Path(__file__).resolve().parent.parent

# Ledger digest of the seeded omniscient poisson trace before the reshard
# path existed (PR 8's acceptance bar: reshard="never" replays pre-reshard
# ledgers byte-identically).
PRE_RESHARD_DIGEST = \
    "42f38e8cb5bb947daed699b7ee21d07c4aba991dbfb783a8978debd726bab42b"


def _poisson_cluster_and_trace():
    from repro.scenarios import poisson_churn
    topo = random_edge_topology(16, seed=0)
    cl = SimCluster(topo, state_bytes=32 * MB, tensor_sizes=[MB] * 32)
    cl.train(1)
    trace = poisson_churn(sorted(topo.active_nodes()), seed=3,
                          horizon_s=600.0, rate_join=0.05, rate_leave=0.04)
    return cl, trace


def _full_mesh(n, bw=800.0, lat=0.01):
    topo = Topology()
    for i in range(n):
        topo.add_node(i)
    for i in range(n):
        for j in range(i + 1, n):
            topo.add_link(i, j, Link(bw, lat))
    return topo


# ---------------------------------------------------------------------------
# Plan algebra.
# ---------------------------------------------------------------------------


def test_candidate_plans_walk_the_divisor_chain():
    plans = candidate_plans([3, 1, 4, 1000, 7, 2])  # 6 devices, unsorted
    assert [p.shape for p in plans] == [(6, 1), (3, 2), (2, 3), (1, 6)]
    for p in plans:
        assert p.devices == (1, 2, 3, 4, 7, 1000)  # canonical order
        assert p.dp * p.tp == 6
    assert [p.shape for p in candidate_plans(list(range(7)))] == \
        [(7, 1), (1, 7)]
    assert [p.shape for p in candidate_plans(list(range(12)), max_tp=4)] == \
        [(12, 1), (6, 2), (4, 3), (3, 4)]


def test_shard_intervals_partition_the_state():
    plan = ParallelismPlan((2, 4), devices=tuple(range(8)))
    S = 100 * MB
    for dp_row in range(2):
        intervals = [plan.shard_interval(dp_row * 4 + i, S) for i in range(4)]
        assert intervals[0][0] == 0 and intervals[-1][1] == S
        for (a, b), (c, d) in zip(intervals, intervals[1:]):
            assert b == c  # contiguous, no gaps or overlaps
    # dp-only: everyone holds everything
    dp = ParallelismPlan((8, 1), devices=tuple(range(8)))
    assert dp.shard_interval(5, S) == (0, S)


def test_plan_json_roundtrip():
    plan = ParallelismPlan((3, 2), ("data", "model"),
                           devices=(5, 1, 9, 2, 7, 3), microbatch=4)
    back = ParallelismPlan.from_json(plan.to_json())
    assert back == plan
    assert back.signature() == [3, 2]
    # device-free template round-trips too (launch/mesh.py's constants)
    tmpl = ParallelismPlan((2, 16, 16), ("pod", "data", "model"))
    assert ParallelismPlan.from_json(tmpl.to_json()) == tmpl


def test_reshard_moved_bytes_cases():
    S = 96 * MB
    devs = tuple(range(6))
    dp = ParallelismPlan((6, 1), devices=devs)
    tp2 = ParallelismPlan((3, 2), devices=devs)
    # DP -> TP: every tp interval is a subset of the full replica each
    # node already holds — zero movement. Same for "from nothing".
    assert reshard_moved_bytes(dp, tp2, S) == 0
    assert reshard_moved_bytes(None, tp2, S) == 0
    # TP -> DP: each node holds half, needs the other half.
    assert reshard_moved_bytes(tp2, dp, S) == 6 * (S // 2)
    # Death under tp>1 can force movement even tp2 -> tp2: losing node 2
    # shifts nodes 3 and 4 to the opposite tp position.
    tp2_5 = ParallelismPlan((2, 2), devices=(0, 1, 3, 4))
    assert reshard_moved_bytes(tp2, tp2_5, S) == 2 * (S // 2)
    # ...but an ordering-preserving shrink moves nothing.
    assert reshard_moved_bytes(tp2, ParallelismPlan((2, 2),
                                                    devices=devs[:4]),
                               S) == 0


def test_reshard_plan_fetches_come_from_actual_holders():
    S = 32 * MB
    topo = _full_mesh(4)
    devs = (0, 1, 2, 3)
    tp4 = ParallelismPlan((1, 4), devices=devs)
    dp = ParallelismPlan((4, 1), devices=devs)
    rp = reshard_plan(tp4, dp, topo, S)
    assert rp.moved_bytes == 4 * (S - S // 4)
    assert set(rp.fetches) == set(devs)
    for node, plan in rp.fetches.items():
        a, b = tp4.shard_interval(node, S)
        assert sum(plan.sources.values()) == S - (b - a)
        for src in plan.sources:
            assert src != node and src in devs
    # DP -> TP needs nothing on the wire.
    assert reshard_plan(dp, tp4, topo, S).fetches == {}


def test_reshard_plan_codec_wire_fields():
    from repro.core.codec import CODEC_INT8
    S = 32 * MB
    topo = _full_mesh(4)
    tp4 = ParallelismPlan((1, 4), devices=(0, 1, 2, 3))
    dp = ParallelismPlan((4, 1), devices=(0, 1, 2, 3))
    rp = reshard_plan(tp4, dp, topo, S, codec=CODEC_INT8)
    assert rp.fetches
    for plan in rp.fetches.values():
        assert set(plan.codecs) == set(plan.sources)
        assert all(c == CODEC_INT8 for c in plan.codecs.values())
        assert 0 < sum(plan.wire_sources.values()) < \
            sum(plan.sources.values())


# ---------------------------------------------------------------------------
# The decision gate.
# ---------------------------------------------------------------------------


def _policy(**kw):
    base = dict(mode="auto", memory_bytes=36 * MB,
                act_bytes_per_sample=4 * MB, act_comm_bytes=MB,
                global_batch=64, compute_s_per_sample=0.01,
                pass_overhead_s=0.05, link_s_per_byte=1e-8)
    base.update(kw)
    return ReshardPolicy(**base)


def test_decide_reshard_modes_and_pinning():
    S, sizes = 32 * MB, [MB] * 32
    devs = list(range(8))
    pol = _policy()
    # auto: memory-tight dp-only micro-batches pay pass overhead; tp wins.
    decision, baseline = decide_reshard(pol, None, devs, S, sizes)
    assert decision is not None and decision["plan"].tp > 1
    assert decision["step_s"] < baseline.tp * 1e9  # finite
    assert decision["moved_bytes"] == 0  # from-nothing holdings are full
    # never: no decision, baseline is dp-only.
    none_d, base2 = decide_reshard(pol, None, devs, S, sizes, mode="never")
    assert none_d is None and base2.shape == (8, 1)
    # hysteresis gate: with roomy memory and near-free links dp-only is
    # already optimal — no candidate clears the margin, auto stays put.
    cur = ParallelismPlan((8, 1), devices=tuple(devs))
    roomy = _policy(memory_bytes=float("inf"), link_s_per_byte=1e-12)
    d3, _ = decide_reshard(roomy, cur, devs, S, sizes)
    assert d3 is None
    # pinned shape (ChurnEvent.new_shape) overrides the chain search.
    d4, _ = decide_reshard(pol, None, devs, S, sizes, mode="always",
                           pinned_shape=(2, 4))
    assert d4 is not None and d4["plan"].shape == (2, 4)
    # pinned shape that doesn't fit the device count is ignored.
    d5, _ = decide_reshard(pol, None, devs, S, sizes, mode="always",
                           pinned_shape=(3, 4))
    assert d5 is None or d5["plan"].dp * d5["plan"].tp == 8


def test_forced_fallback_when_membership_breaks_tp():
    """A death under tp>1 *must* move the layout even when the step-time
    gate says stay: surviving a membership change is not optional."""
    S, sizes = 32 * MB, [MB] * 32
    cur = ParallelismPlan((4, 2), devices=tuple(range(8)))
    # 7 survivors: tp=2 no longer divides; even with reshard disabled by
    # cost the decision must come back (forced).
    slow = _policy(amortize_steps=1, link_s_per_byte=1.0)
    d, baseline = decide_reshard(slow, cur, list(range(7)), S, sizes)
    assert d is not None
    assert d["plan"].dp * d["plan"].tp == 7


# ---------------------------------------------------------------------------
# Engine ledger path.
# ---------------------------------------------------------------------------


def test_reshard_never_is_byte_identical_to_pre_reshard_ledger():
    cl, trace = _poisson_cluster_and_trace()
    ledger, _ = run_trace_sim(cl, trace)  # default kwargs
    assert ledger.digest() == PRE_RESHARD_DIGEST
    cl2, trace2 = _poisson_cluster_and_trace()
    ledger2, _ = run_trace_sim(cl2, trace2, reshard="never")
    assert ledger2.digest() == PRE_RESHARD_DIGEST


def test_reshard_auto_deterministic_and_terminal_records():
    digests = []
    for _ in range(2):
        cl, trace = _poisson_cluster_and_trace()
        ledger, _ = run_trace_sim(cl, trace, reshard="auto")
        digests.append(ledger.digest())
        started = [r for r in ledger if r.action == "reshard-started"]
        terminal = [r for r in ledger
                    if r.action in ("reshard-ready", "reshard-cancelled")]
        assert started, "auto never resharded on the churn trace"
        # every started reaches exactly one terminal record
        assert len(terminal) == len(started)
    assert digests[0] == digests[1]


def test_event_annotation_overrides_standing_mode():
    # 9 nodes -> 8 survivors: the divisor chain has useful tp shapes
    # (7 survivors would leave only tp=7, which degrades 1 MiB tensors
    # to full replication and correctly loses even under "always").
    topo = random_edge_topology(9, seed=2)
    cl = SimCluster(topo, state_bytes=32 * MB, tensor_sizes=[MB] * 32)
    cl.train(1)
    victim = [n for n in topo.active_nodes() if n != cl.scheduler.node][0]
    events = [ChurnEvent(t=5.0, kind="leave", node=victim,
                         reshard="always")]
    ledger, _ = run_trace_sim(cl, events, reshard="never")
    acts = ledger.actions()
    assert "reshard-started" in acts and "reshard-ready" in acts
    # and a bare trace under standing "never" has no reshard records
    cl2 = SimCluster(random_edge_topology(9, seed=2),
                     state_bytes=32 * MB, tensor_sizes=[MB] * 32)
    cl2.train(1)
    l2, _ = run_trace_sim(cl2, [ChurnEvent(t=5.0, kind="leave",
                                           node=victim)], reshard="never")
    assert not any(r.kind == "reshard" for r in l2)


def test_dp_to_tp_swaps_without_moving_bytes():
    """The first DP→TP reshard fetches nothing: full replicas already
    contain every interval; ready follows started after the solver +
    policy-sync charge alone."""
    topo = random_edge_topology(9, seed=2)
    cl = SimCluster(topo, state_bytes=32 * MB, tensor_sizes=[MB] * 32)
    cl.train(1)
    victim = [n for n in topo.active_nodes() if n != cl.scheduler.node][0]
    ledger, _ = run_trace_sim(
        cl, [ChurnEvent(t=5.0, kind="leave", node=victim, reshard="auto")],
        reshard="auto")
    started = [r for r in ledger if r.action == "reshard-started"]
    ready = [r for r in ledger if r.action == "reshard-ready"]
    assert len(started) == 1 and len(ready) == 1
    assert started[0].detail["new_shape"][1] > 1  # chose tp > 1
    assert started[0].detail["moved_bytes"] == 0
    assert started[0].detail["n_fetches"] == 0
    assert ready[0].t - started[0].t < 1.0


def _two_phase_cluster():
    """4-node full mesh with the state sharded tp=4, then a join pinned
    back to dp-only — the second reshard moves real bytes over the wire,
    giving a window to interrupt."""
    topo = _full_mesh(4, bw=200.0)
    cl = SimCluster(topo, state_bytes=64 * MB, tensor_sizes=[2 * MB] * 32)
    cl.train(1)
    events = [
        ChurnEvent(t=5.0, kind="leave", node=3, reshard="always",
                   new_shape=(1, 3)),
        ChurnEvent(t=40.0, kind="join", node=100,
                   links={0: (400.0, 0.01), 1: (400.0, 0.01),
                          2: (300.0, 0.01)},
                   compute_s=1.0, reshard="always", new_shape=(4, 1)),
    ]
    return cl, events


def test_midflight_link_degrade_replans_reshard_fetches():
    cl, events = _two_phase_cluster()
    ledger, _ = run_trace_sim(cl, events, reshard="never")
    started = [r for r in ledger if r.action == "reshard-started"
               and r.detail["n_fetches"] > 0]
    assert started, "TP→DP reshard scheduled no fetches"
    ready = [r for r in ledger if r.action == "reshard-ready"
             and r.t > started[-1].t][0]
    t_mid = (started[-1].t + ready.t) / 2
    fetcher = 0  # tp member refilling its interval
    degrade = [ChurnEvent(t=t_mid, kind="link-degrade", u=1, v=fetcher,
                          bandwidth_mbps=2.0, latency_s=0.01),
               ChurnEvent(t=t_mid, kind="link-degrade", u=2, v=fetcher,
                          bandwidth_mbps=2.0, latency_s=0.01),
               ChurnEvent(t=t_mid, kind="link-degrade", u=100, v=fetcher,
                          bandwidth_mbps=2.0, latency_s=0.01)]
    digests = []
    for _ in range(2):
        cl2, events2 = _two_phase_cluster()
        l2, _ = run_trace_sim(cl2, sorted(events2 + degrade,
                                          key=lambda e: e.t),
                              reshard="never")
        acts = l2.actions()
        assert "reshard-replanned" in acts
        assert acts.count("reshard-started") == \
            acts.count("reshard-ready") + acts.count("reshard-cancelled")
        digests.append(l2.digest())
    assert digests[0] == digests[1]


def test_membership_churn_cancels_inflight_reshard():
    cl, events = _two_phase_cluster()
    ledger, _ = run_trace_sim(cl, events, reshard="never")
    started = [r for r in ledger if r.action == "reshard-started"
               and r.detail["n_fetches"] > 0]
    ready = [r for r in ledger if r.action == "reshard-ready"
             and r.t > started[-1].t][0]
    t_mid = (started[-1].t + ready.t) / 2
    cl2, events2 = _two_phase_cluster()
    strike = ChurnEvent(t=t_mid, kind="node-failure", node=2)
    l2, _ = run_trace_sim(cl2, sorted(events2 + [strike],
                                      key=lambda e: e.t), reshard="never")
    cancelled = [r for r in l2 if r.action == "reshard-cancelled"]
    assert cancelled and cancelled[0].detail["reason"] == \
        "membership-changed"
    # the forced re-evaluation after the death starts a fresh reshard
    acts = l2.actions()
    assert acts.count("reshard-started") == \
        acts.count("reshard-ready") + acts.count("reshard-cancelled")
    # membership stayed sane: reshard fetches never activate/deactivate
    failed = [r for r in l2 if r.action == "node-failed"]
    assert len(failed) == 1


# ---------------------------------------------------------------------------
# Cross-substrate decision parity.
# ---------------------------------------------------------------------------


class _Dev:
    def __init__(self, i):
        self.id = i


class _FakeTrainer:
    """Membership-only ElasticTrainer double (established test idiom):
    enough surface for TrainerBackend's reshard path without jax."""

    def __init__(self, n):
        self.pool = [_Dev(i) for i in range(n)]
        self.active = list(self.pool)
        self.step_count = 0
        self.resharded = []

    def scale_in(self, device, failure=False):
        self.active.remove(device)
        return type("E", (), {"step": self.step_count})()

    def apply_reshard(self, tp, microbatch=1):
        self.resharded.append((tp, microbatch))
        return type("E", (), {"step": self.step_count})()

    def apply_link_event(self, kind, device_ids, **kw):
        pass


def test_cross_substrate_reshard_decision_parity():
    """The same spaced failure trace yields the same (old_shape,
    new_shape, moved_bytes) decision sequence on the simulator and the
    trainer backend — the step-time model is a pure function of layout
    and byte counts, never of substrate timing."""
    from repro.elastic.trainer import TrainerBackend

    S, sizes = 64 * MB, [2 * MB] * 32
    topo = random_edge_topology(12, seed=1)
    trace = reshard_churn(sorted(topo.active_nodes()), seed=4,
                          n_failures=4, n_joins=0)
    cl = SimCluster(topo, state_bytes=S, tensor_sizes=sizes)
    cl.train(1)
    sim_ledger, _ = run_trace_sim(cl, trace, reshard="auto")

    tr = _FakeTrainer(12)
    backend = TrainerBackend(tr, min_active=2, reshard="auto",
                             state_bytes=S, tensor_sizes=sizes)
    tr_ledger = ChurnEngine(backend).run(list(trace))

    def decisions(ledger):
        return [(tuple(r.detail["old_shape"]), tuple(r.detail["new_shape"]),
                 r.detail["moved_bytes"])
                for r in ledger if r.action == "reshard-started"]

    sim_d, tr_d = decisions(sim_ledger), decisions(tr_ledger)
    assert sim_d, "trace produced no reshards"
    assert sim_d == tr_d
    # and the step-time predictions agree too
    def steps(ledger):
        return [(r.detail["step_s"], r.detail["baseline_step_s"])
                for r in ledger if r.action == "reshard-started"]
    assert steps(sim_ledger) == pytest.approx(steps(tr_ledger))
    assert tr.resharded  # real apply hook fired on the trainer side


# ---------------------------------------------------------------------------
# Real arrays (subprocess, slow).
# ---------------------------------------------------------------------------


def _run(code: str, devices: int = 8, timeout: int = 420):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = str(ROOT / "src")
    res = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, timeout=timeout,
                         env=env)
    assert res.returncode == 0, \
        f"STDOUT:\n{res.stdout}\nSTDERR:\n{res.stderr}"
    return res.stdout


_TINY_MODEL = """
        import jax, numpy as np
        import jax.numpy as jnp

        class TinyModel:
            def init_train_state(self, key):
                k1, k2 = jax.random.split(key)
                return {"w1": jax.random.normal(k1, (16, 64)),
                        "w2": jax.random.normal(k2, (64, 16)),
                        "b": jnp.zeros((17,))}  # 17: degrades to replication
            def make_train_step(self):
                def step(state, batch):
                    def loss_fn(s):
                        y = (batch["x"] @ s["w1"]) @ s["w2"]
                        return jnp.mean((y - batch["y"]) ** 2)
                    loss = loss_fn(state)
                    g = jax.grad(loss_fn)(state)
                    new = jax.tree.map(lambda p, gr: p - 0.01 * gr, state, g)
                    return new, {"loss": loss}
                return step
"""


@pytest.mark.slow
def test_reshard_roundtrip_bit_identical_on_real_arrays():
    out = _run(_TINY_MODEL + """
        from repro.elastic.trainer import ElasticTrainer
        tr = ElasticTrainer(TinyModel(), initial=4, per_device_batch=2)
        tr.init()

        def batch():
            return {"x": np.ones((tr.global_batch, 16), np.float32),
                    "y": np.zeros((tr.global_batch, 16), np.float32)}

        tr.step(batch())
        snap = jax.tree.map(np.asarray, tr.state)
        for tp in (2, 4, 1):  # dp -> tp=2 -> tp=4 -> dp
            ev = tr.apply_reshard(tp)
            assert ev.plan_summary["shape"] == [len(tr.active) // tp, tp]
        after = jax.tree.map(np.asarray, tr.state)
        for a, b in zip(jax.tree.leaves(snap), jax.tree.leaves(after)):
            np.testing.assert_array_equal(a, b)
        # training still steps under tp=2, and scale_in gathers back to dp
        tr.apply_reshard(2)
        m = tr.step(batch())
        assert np.isfinite(m["loss"])
        tr.scale_in()
        assert tr.tp == 1 and len(tr.active) == 3
        m2 = tr.step(batch())
        assert np.isfinite(m2["loss"])
        print("OK roundtrip")
    """)
    assert "OK roundtrip" in out


@pytest.mark.slow
def test_trainer_backend_applies_reshard_on_real_arrays():
    out = _run(_TINY_MODEL + """
        from repro.core.engine import ChurnEvent
        from repro.elastic.trainer import ElasticTrainer
        MB = 1 << 20
        events = [
            ChurnEvent(5.0, "leave", node=5, reshard="auto"),
            ChurnEvent(20.0, "leave", node=4, reshard="auto"),
        ]
        def replay():
            tr = ElasticTrainer(TinyModel(), initial=6, per_device_batch=2)
            tr.init()
            ledger = tr.replay_scenario(events, reshard="auto",
                                        state_bytes=32 * MB,
                                        tensor_sizes=[MB] * 32)
            return tr, ledger
        tr, ledger = replay()
        started = [r for r in ledger
                   if r.action == "reshard-started"]
        assert started, "no reshard on the trainer substrate"
        assert tr.tp == started[-1].detail["new_shape"][1]
        assert tr.tp > 1  # memory-tight policy chose tensor parallelism
        # same-seed determinism on the real-array substrate
        _, l2 = replay()
        assert ledger.canonical_bytes() == l2.canonical_bytes()
        print("OK trainer-backend", tr.tp)
    """)
    assert "OK trainer-backend" in out


@pytest.mark.slow
def test_mesh_from_plan_matches_launch_meshes():
    out = _run("""
        from repro.launch.mesh import (DEBUG_PLAN, DEBUG_MULTI_POD_PLAN,
                                       make_debug_mesh, mesh_from_plan)
        m = make_debug_mesh()
        assert dict(m.shape) == {"data": 2, "model": 2}
        assert m.axis_names == DEBUG_PLAN.axes
        mp = make_debug_mesh(multi_pod=True)
        assert dict(mp.shape) == {"pod": 2, "data": 2, "model": 2}
        # explicit device binding (the elastic trainer's survivor list)
        import jax
        m2 = mesh_from_plan(DEBUG_PLAN, devices=jax.devices()[:4])
        assert dict(m2.shape) == dict(m.shape)
        print("OK meshes")
    """)
    assert "OK meshes" in out


# ---------------------------------------------------------------------------
# shard_report (measurement layer; abstract mesh, no devices needed).
# ---------------------------------------------------------------------------


def test_shard_report_counts_degraded_params():
    jax = pytest.importorskip("jax")
    from jax.sharding import AbstractMesh
    from repro.models.sharding import shard_report
    import numpy as np

    S = jax.ShapeDtypeStruct
    params = {
        "embed": {"tok": S((50257, 768), np.float32)},  # 50257 is prime
        "layers": {"l0": {
            "mlp": {"w1": S((768, 3072), np.float32),
                    "w2": S((3072, 768), np.float32)},
            "ln": S((768,), np.float32)}},
    }
    mesh = AbstractMesh((4, 4), ("data", "model"))
    rep = shard_report(mesh, params)
    assert rep["mesh_shape"] == {"data": 4, "model": 4}
    deg = rep["degraded"]
    assert set(deg) == {"embed/model"}
    assert deg["embed/model"]["tensors"] == 1
    assert deg["embed/model"]["bytes"] == 50257 * 768 * 4
    assert rep["replication_blowup"] > 1.0
    # tp=1 never degrades and never blows up
    rep1 = shard_report(AbstractMesh((16, 1), ("data", "model")), params)
    assert rep1["degraded"] == {}
    assert rep1["replication_blowup"] == pytest.approx(1.0)
