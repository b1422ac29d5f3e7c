"""The readers of the program's own spans and scopes
(`chipbench/program_trace.py`, the metrics `attn_core_share`,
`step_host_ms`, `state_move_s`, `move_gbps`, `handle_host_ms`, and
`breakdown.py`): on a small trace built by hand, on a program without a
recorder, and on rehearsals of their cells."""
import json
from types import SimpleNamespace

import pytest

from chipbench import program_trace
from chipbench.metrics import (attn_core_share, handle_host_ms, move_gbps,
                               state_move_s, step_host_ms)
from chipbench.tests.conftest import last_json, make_root, merged_bench

SPAN_READERS = (step_host_ms, state_move_s, move_gbps, handle_host_ms)


def _plane(name, lines):
    return SimpleNamespace(name=name, lines=[
        SimpleNamespace(name=ln, events=[
            SimpleNamespace(name=n, start_ns=s, duration_ns=e - s)
            for n, s, e in evs]) for ln, evs in lines.items()])


@pytest.fixture
def pd():
    ops = [("%while.1 = f32[] while()", 0, 60),
           ("%fusion.2 = f32[] fusion()", 10, 40),
           ("%fusion.3 = f32[] fusion()", 70, 90),
           ("%fusion.9 = f32[] fusion()", 95, 110)]
    host = [("bench.window", 0, 100),
            ("chaos.step", 0, 64), ("chaos.step.run", 0, 62),
            ("chaos.step.read_metrics", 62, 64),
            ("chaos.step", 64, 93), ("chaos.step.put_batch", 65, 68),
            ("chaos.step.run", 68, 92), ("chaos.step.read_metrics", 92, 93)]
    return SimpleNamespace(planes=[
        _plane("/device:TPU:0", {"XLA Ops": ops}),
        _plane("/host:CPU", {"python": host})])


SCOPES = {"while.1": "jit(train_step)/while",
          "fusion.2": "jit(train_step)/transpose(jvp())/while/body/"
                      "attention_core/exp",
          "fusion.3": "jit(train_step)/optimizer/sqrt"}


def test_op_scopes_from_hlo_text():
    text = """HloModule jit_train_step
  %fusion.2 = f32[8]{0} fusion(%p), kind=kLoop, calls=%fc, metadata={op_name="a/attention_core/exp" source_file="x.py"}
  ROOT %tuple.5 = (f32[8]{0}) tuple(%fusion.2), metadata={op_name="a/b"}
  %param.1 = f32[8]{0} parameter(0)
"""
    other = '  %fusion.2 = f32[4]{0} fusion(%q), metadata={op_name="a/optimizer/mul"}\n'
    assert program_trace.op_scopes([text]) == {
        "fusion.2": "a/attention_core/exp", "tuple.5": "a/b"}
    assert program_trace.op_scopes([text, other]) == {"tuple.5": "a/b"}


def test_scope_share_and_spans(pd):
    ops = program_trace.trace_reduce.device_ops(pd)[0]
    window = program_trace.window_ns(pd)
    assert window == (0, 100)
    busy = 60 + 20 + 5
    assert program_trace.scope_share(ops, window, SCOPES, "attention_core") \
        == pytest.approx(100 * 30 / busy)
    assert program_trace.scope_share(ops, window, SCOPES, "optimizer") \
        == pytest.approx(100 * 20 / busy)
    names = [s[0] for s in program_trace.chaos_spans(pd)]
    assert names.count("chaos.step") == 2 and "bench.window" not in names


def test_device_breakdown(pd):
    out = program_trace.device_breakdown(pd, 0, SCOPES)
    busy = 85
    assert out["scopes"] == pytest.approx({"attention_core": 100 * 30 / busy,
                                           "optimizer": 100 * 20 / busy,
                                           "rest": 100 * 35 / busy})
    top = {n: (sec, op) for n, sec, op in out["top_ops"]}
    assert top == {"while.1": (pytest.approx(30e-9), SCOPES["while.1"]),
                   "fusion.2": (pytest.approx(30e-9), SCOPES["fusion.2"]),
                   "fusion.3": (pytest.approx(20e-9), SCOPES["fusion.3"]),
                   "fusion.9": (pytest.approx(5e-9), "")}
    assert out["profiled_steps"] == 2
    # Gap [60, 70]: 60-62 in run, 62-64 in read_metrics, 64-65 in the
    # second step, 65-68 in put_batch, 68-70 in run; gap [90, 95]: 90-92
    # in run, 92-93 in read_metrics, 93-95 outside every span.
    assert out["idle_ms_per_step"] == pytest.approx(
        {"chaos.step.run": 6e-6 / 2, "chaos.step.read_metrics": 3e-6 / 2,
         "chaos.step": 1e-6 / 2, "chaos.step.put_batch": 3e-6 / 2,
         "no span": 2e-6 / 2})
    assert out["run_cover"] == pytest.approx(80 / busy)


def test_readers_read_nothing_without_a_recorder():
    run = SimpleNamespace(trainer=SimpleNamespace(), w=(0.0, 1.0), trace=True)
    assert all(r.read(run) is None for r in SPAN_READERS)
    assert attn_core_share.read(run) is None


def _metric(name, unit, better, layer, cell):
    return {"name": name, "unit": unit, "better": better,
            "source": "program_span", "layer": layer, "moves": "tokens_per_s",
            "workloads": [cell]}


def test_steady_rehearsal_reads_step_host(run_py, bench_root):
    res = run_py(bench_root / "chipbench" / "run.py", "--workload",
                 "gpt2m-steady-1c", "--seed", 2**31 + 23, "--seconds", 1,
                 "--trace", 1, "--rehearse")
    assert res.returncode == 0, res.stderr[-3000:]
    out = last_json(res.stdout)
    assert out["correct"] is True
    # The CPU trace holds no TPU plane: no device metric from a rehearsal.
    assert set(out["metrics"]) == {"step_host_ms"}
    assert out["metrics"]["step_host_ms"]["value"] > 0


def test_churn_rehearsal_reads_moves_and_handles(run_py, tmp_path):
    cell = "gpt2s-churn-4c"
    bench = merged_bench()
    bench["per_layer"] += [
        _metric("state_move_s", "s", "lower", "state movement", cell),
        _metric("move_gbps", "GB/s", "higher", "state movement", cell),
        _metric("handle_host_ms", "ms", "lower",
                "churn engine and recovery policy", cell),
        _metric("step_host_ms", "ms", "lower", "train step host path", cell)]
    root = make_root(tmp_path, bench)
    res = run_py(root / "chipbench" / "run.py", "--workload", cell, "--seed",
                 2**31 + 5, "--seconds", 1, "--trace", 1, "--rehearse")
    assert res.returncode == 0, res.stderr[-3000:]
    out = last_json(res.stdout)
    assert out["correct"] is True
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert set(m) == {"move_s", "control_ms", "state_move_s", "move_gbps",
                      "handle_host_ms", "step_host_ms"}
    assert all(v > 0 for v in m.values()), m
    # The benchmark's own spans lie around the program's: its moves hold
    # the `wall_s` parts, its handles the program's.
    assert m["state_move_s"] >= m["move_s"] - 1e-6
    assert m["handle_host_ms"] <= m["control_ms"] + 1e-3


def test_breakdown_rehearsal(run_py, bench_root):
    res = run_py(bench_root / "chipbench" / "breakdown.py", "--workload",
                 "gpt2m-steady-1c", "--seed", 77, "--seconds", 1,
                 "--rehearse")
    assert res.returncode == 0, res.stderr[-3000:]
    out = json.loads(res.stdout[res.stdout.index("{"):])
    assert out["device"]["platform"] == "cpu"
    assert 0 < out["span_us"] < 1000
    assert 0 <= out["step_vs_bench"] < 0.05
    assert out["tokens_per_s"]["profiled"] > 0
    assert out["metrics"]["step_host_ms"]["value"] > 0
    assert "scopes" not in out  # no TPU plane on the CPU
