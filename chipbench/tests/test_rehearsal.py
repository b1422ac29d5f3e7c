"""Every cell runs end to end at reduced size on the CPU and prints one
well-formed result line; the chip path refuses a CPU; a directory without
the program gives no result; a new cell, traffic mix and per-layer metric
are found by name without editing a file."""
import json
import shutil

import pytest

from chipbench.tests.conftest import (IGNORE, ROOT, last_json, make_root,
                                      merged_bench)

BENCH = merged_bench()
CELLS = {w["name"]: w for w in BENCH["workloads"]}
KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _end_to_end(cell):
    return {m["name"] for m in BENCH["end_to_end"]
            if cell in m.get("workloads", [cell])}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_cell_rehearsal(run_py, bench_root, cell):
    res = run_py(bench_root / "chipbench" / "run.py", "--workload", cell, "--seed", 2**31 + 11,
                 "--seconds", 1, "--trace", 0, "--rehearse")
    assert res.returncode == 0, res.stderr[-3000:]
    out = last_json(res.stdout)
    assert KEYS <= set(out), out
    assert out["correct"] is True, res.stderr[-3000:]
    assert set(out["metrics"]) == _end_to_end(cell)
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert out["device"]["count"] == CELLS[cell]["chips"]
    assert out["window"]["compiles"] == 0
    assert list(out)[-1] == "checks"
    assert res.stderr.strip().splitlines()[-1].startswith("check ")


def test_churn_trace_rehearsal(run_py, bench_root):
    res = run_py(bench_root / "chipbench" / "run.py", "--workload", "gpt2s-churn-4c", "--seed", 909,
                 "--seconds", 1, "--trace", 1, "--rehearse")
    assert res.returncode == 0, res.stderr[-3000:]
    out = last_json(res.stdout)
    assert out["correct"] is True
    # No device metric from a CPU run: only the host-side layers report.
    assert set(out["metrics"]) == {"move_s", "control_ms"}
    assert "breakdown" not in out


def test_chip_path_refuses_cpu(run_py):
    res = run_py(ROOT / "chipbench" / "run.py", "--workload",
                 "gpt2m-steady-1c", "--seed", 1,
                 "--seconds", 1, "--trace", 0)
    assert res.returncode == 2
    assert last_json(res.stdout) is None
    assert "no TPU" in res.stderr


def test_no_result_without_the_program(run_py, tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench", ignore=IGNORE)
    res = run_py(tmp_path / "chipbench" / "run.py", "--workload",
                 "gpt2m-steady-1c", "--seed", 1, "--seconds", 1,
                 "--trace", 0, "--rehearse")
    assert res.returncode != 0
    assert last_json(res.stdout) is None


def test_new_cell_traffic_and_metric_found(run_py, tmp_path):
    """Adds a cell, its traffic mix, its limits and a per-layer metric as
    new files and new BENCHMARK.json entries only."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cb = make_root(tmp_path, bench) / "chipbench"
    before = {p: p.read_bytes() for p in cb.rglob("*") if p.is_file()}
    traffic = json.loads((cb / "traffic" / "steady.json").read_text())
    traffic["steps_between"] = 2
    (cb / "traffic" / "steady_pairs.json").write_text(json.dumps(traffic))
    shutil.copy(cb / "limits" / "gpt2m-steady-1c.json",
                cb / "limits" / "gpt2m-pairs-1c.json")
    (cb / "metrics" / "window_steps.py").write_text(
        "def read(run):\n    return float(len(run.steps()))\n")
    bench["workloads"].append({"name": "gpt2m-pairs-1c", "config": "gpt2-m",
                               "traffic": "steady_pairs", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "window_steps", "unit": "steps",
                               "better": "higher", "source": "host_clock",
                               "layer": "train step", "moves": "tokens_per_s",
                               "workloads": ["gpt2m-pairs-1c"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    res = run_py(cb / "run.py", "--workload", "gpt2m-pairs-1c", "--seed", 5,
                 "--seconds", 1, "--trace", 1, "--rehearse")
    assert res.returncode == 0, res.stderr[-3000:]
    out = last_json(res.stdout)
    assert out["correct"] is True
    assert out["metrics"]["window_steps"]["value"] == out["window"]["steps"]
    assert out["window"]["steps"] == 2 * out["window"]["units"]
    assert all(p.read_bytes() == b for p, b in before.items())
