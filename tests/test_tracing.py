"""The trainer's real-clock recorder (`repro.core.telemetry.Recorder`).

Its rows nest, stay in a bounded ring, land in a profiler trace under their
own names, and carry every timing the trainer reports: `chaos.step` trees,
one `chaos.compile` per new layout, `chaos.move` trees whose byte counts
follow the shardings, and `chaos.handle` around each churn event. The
multi-device cases run in a subprocess on four virtual CPU devices.
"""
import os
import re
import subprocess
import sys
import textwrap
from pathlib import Path
from types import SimpleNamespace

import jax
import pytest

from repro.core.engine import ChurnEvent, EventLedger
from repro.core.telemetry import MetricsRegistry, Recorder, collect_trainer

ROOT = Path(__file__).resolve().parent.parent


def _run(code: str, devices: int):
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=str(ROOT / "src"))
    res = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, timeout=420, env=env)
    assert res.returncode == 0, f"STDOUT:\n{res.stdout}\nSTDERR:\n{res.stderr}"
    return res.stdout


def test_recorder_nests_rows():
    rec = Recorder()
    with rec.span("chaos.outer", n=2) as outer:
        with rec.span("chaos.inner") as inner:
            pass
        outer.attrs["late"] = 1
    assert [r.name for r in rec.rows] == ["chaos.inner", "chaos.outer"]
    assert outer.parent is None and inner.parent == outer.seq
    assert outer.t0 <= inner.t0 <= inner.t1 <= outer.t1
    assert outer.attrs == {"n": 2, "late": 1}
    assert outer.cat == "host"
    assert rec.named("chaos.inner") == [inner]


def test_recorder_ring_is_bounded():
    rec = Recorder(maxlen=4)
    for i in range(10):
        with rec.span("chaos.x", i=i):
            pass
    assert [r.attrs["i"] for r in rec.rows] == [6, 7, 8, 9]


def test_recorder_row_closes_on_error():
    rec = Recorder()
    with pytest.raises(RuntimeError):
        with rec.span("chaos.fails"):
            raise RuntimeError("boom")
    with rec.span("chaos.after") as after:
        pass
    assert [r.name for r in rec.rows] == ["chaos.fails", "chaos.after"]
    assert after.parent is None


def test_recorder_counters():
    rec = Recorder()
    rec.count("chaos_things_total", 2, help_text="Things", kind="a")
    rec.count("chaos_things_total", 3, kind="a")
    rec.count("chaos_things_total", kind="b")
    assert rec.counter("chaos_things_total") == {("a",): 5.0, ("b",): 1.0}
    assert rec.counter("chaos_none_total") == {}
    text = rec.registry.exposition()
    assert 'chaos_things_total{kind="a"} 5' in text


def test_recorder_spans_are_trace_annotations(tmp_path):
    from jax.profiler import ProfileData

    rec = Recorder()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with rec.span("chaos.outer"):
            with rec.span("chaos.inner"):
                jax.block_until_ready(jax.numpy.ones(4) + 1)
    finally:
        jax.profiler.stop_trace()
    path = next(tmp_path.rglob("*.xplane.pb"))
    names = [e.name for p in ProfileData.from_file(str(path)).planes
             for line in p.lines for e in line.events]
    assert names.count("chaos.outer") == 1
    assert names.count("chaos.inner") == 1


@pytest.fixture(scope="module")
def one_device_trainer():
    from repro.configs import get_config
    from repro.data.synthetic import TokenStream
    from repro.elastic import ElasticTrainer
    from repro.models import build_model

    cfg = get_config("gpt2").reduced()
    tr = ElasticTrainer(build_model(cfg), devices=jax.devices()[:1],
                        initial=1, per_device_batch=2)
    tr.init()
    batch = {"tokens": TokenStream(vocab=cfg.vocab, seq_len=16,
                                   seed=0).batch(range(2))}
    for _ in range(3):
        tr.step(batch)
    return tr


def test_one_step_tree_per_step(one_device_trainer):
    tr = one_device_trainer
    rec = tr.tracer
    steps = rec.named("chaos.step")
    assert len(steps) == 3
    assert all(s.attrs == {"n": 1, "tp": 1, "rows": 2} for s in steps)
    for i, s in enumerate(steps):
        kids = [r.name for r in rec.rows if r.parent == s.seq]
        want = ["chaos.step.put_batch", "chaos.step.run",
                "chaos.step.read_metrics"]
        if i == 0:
            want.insert(1, "chaos.compile")
        assert kids == want
    runs = [r.duration_s for r in rec.named("chaos.step.run")]
    assert tr.metrics_snapshot()["step_times"] == {1: runs}


def test_one_compile_per_layout(one_device_trainer):
    tr = one_device_trainer
    (comp,) = tr.tracer.named("chaos.compile")
    assert comp.attrs == {"n": 1, "tp": 1, "attention": "xla"}
    assert tr.tracer.counter("chaos_compiles_total") == {("1", "1"): 1.0}
    assert tr.tracer.counter("chaos_attention_sites_total") == {
        ("xla", "1", "1"): 1.0}
    assert tr.compile_seconds == {(1, 1): comp.duration_s}
    assert list(tr._compiled) == [(1, 1)]


def test_collect_trainer_reads_the_recorder(one_device_trainer):
    reg = MetricsRegistry()
    collect_trainer(reg, one_device_trainer)
    text = reg.exposition()
    assert 'chaos_trainer_step_seconds_count{n_active="1"} 3' in text
    assert 'chaos_compiles_total{n="1",tp="1"} 1' in text
    assert re.search(r'chaos_compile_seconds_total\{n="1",tp="1"\} [0-9.]+',
                     text)


def test_named_scopes_reach_the_compiled_step(one_device_trainer):
    """Scores, softmax and the products with V carry `attention_core`; the
    clip and AdamW's update carry `optimizer`, and nothing else does."""
    from chipbench.program_trace import op_scopes

    text = one_device_trainer._compiled[(1, 1)].as_text()
    scopes = op_scopes([text])
    opcode = {}
    for line in text.splitlines():
        m = re.match(r"^\s*(?:ROOT\s+)?%?(\S+) = (?:\([^)]*\)|\S+) "
                     r"([a-z-]+)\(", line)
        if m:
            opcode[m.group(1)] = m.group(2)

    def ops(scope):
        return {opcode.get(n) for n, op in scopes.items() if scope in op}

    assert {"exponential", "dot", "reduce", "fusion"} <= ops("attention_core")
    assert {"sqrt", "divide", "fusion"} <= ops("optimizer")
    sqrt = [op for n, op in scopes.items() if opcode.get(n) == "sqrt"]
    assert sqrt and all("optimizer" in op for op in sqrt)
    scores = [op for op in scopes.values()
              if "attention_core" in op and "dot_general" in op]
    assert scores and all("optimizer" not in op for op in scores)


def test_handle_span_with_a_trainer_double():
    from repro.elastic.trainer import TrainerBackend

    dev = SimpleNamespace(id=0)
    backend = TrainerBackend(SimpleNamespace(active=[dev], pool=[dev]))
    ledger = EventLedger()
    backend.handle(7, ChurnEvent(t=0.0, kind="checkpoint"), ledger)
    (h,) = backend.tracer.named("chaos.handle")
    assert h.attrs == {"kind": "checkpoint", "seq": 7}
    assert ledger.records[-1].action == "ckpt-skipped-no-checkpointer"


@pytest.mark.slow
def test_moves_bytes_and_wall_on_four_devices():
    """A join under replicate-only DP moves the whole state to the new chip;
    a dp -> tp reshard and a failure move nothing; (2, 2) gathering back to
    (3, 1) moves the halves each survivor lacks. `wall_s` is the move's
    plan + codec + transfer spans."""
    out = _run("""
        import jax, numpy as np
        from repro.configs import get_config
        from repro.data.synthetic import TokenStream
        from repro.elastic import ElasticTrainer
        from repro.models import build_model

        cfg = get_config("gpt2").reduced()
        tr = ElasticTrainer(build_model(cfg), initial=2, per_device_batch=2)
        tr.init()
        stream = TokenStream(vocab=cfg.vocab, seq_len=16, seed=0)
        step = lambda: tr.step({"tokens": stream.batch(range(tr.global_batch))})
        leaves = jax.tree.leaves(tr.state)
        full = sum(x.nbytes for x in leaves)
        halves = sum(x.nbytes // 2 for x in leaves
                     if x.ndim and x.shape[-1] % 2 == 0)

        step()
        ev = [tr.scale_out(codec="int8")]
        step()
        ev.append(tr.scale_out())
        ev.append(tr.apply_reshard(2))
        step()
        ev.append(tr.scale_in(tr.active[3]))
        step()
        ev.append(tr.scale_in(tr.active[2], failure=True))
        step()

        rec = tr.tracer
        moves = rec.named("chaos.move")
        got = [(m.attrs["kind"], m.attrs["from"], m.attrs["to"],
                m.attrs["bytes"]) for m in moves]
        want = [("scale-out", (2, 1), (3, 1), full),
                ("scale-out", (3, 1), (4, 1), full),
                ("reshard", (4, 1), (4, 2), 0),
                ("scale-in", (4, 2), (3, 1), 3 * halves),
                ("node-failure", (3, 1), (2, 1), 0)]
        assert got == want, (got, want)
        assert [e.plan_summary["bytes_moved"] for e in ev] == [w[3] for w in want]
        parts = ("chaos.move.plan", "chaos.move.codec", "chaos.move.transfer")
        for m, e in zip(moves, ev):
            kids = {r.name: r for r in rec.rows if r.parent == m.seq}
            assert set(kids) <= set(parts)
            assert ("chaos.move.codec" in kids) == (m is moves[0])
            assert e.wall_s == sum(k.duration_s for k in kids.values())
        assert rec.counter("chaos_move_bytes_total") == {
            ("scale-out",): 2.0 * full, ("reshard",): 0.0,
            ("scale-in",): 3.0 * halves, ("node-failure",): 0.0}
        compiles = [(c.attrs["n"], c.attrs["tp"])
                    for c in rec.named("chaos.compile")]
        assert compiles == [(2, 1), (3, 1), (4, 2)], compiles
        assert sorted(tr.compile_seconds) == sorted(compiles)
        steps = [(s.attrs["n"], s.attrs["tp"]) for s in rec.named("chaos.step")]
        assert steps == [(2, 1), (3, 1), (4, 2), (3, 1), (2, 1)], steps
        print("OK moves", full, halves)
    """, devices=4)
    assert "OK moves" in out
