"""Every cell runs end to end at reduced size on the CPU and prints one
well-formed result line; the chip path refuses a CPU; a directory without
the program gives no result; a new cell, traffic mix and per-layer metric
are found by name without editing a file."""
import json
import shutil

import pytest

from chipbench.tests.conftest import (IGNORE, ROOT, last_json, load_bench,
                                      make_root)

BENCH = load_bench()
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
    # No device metric from a CPU run: only the program's spans report.
    assert set(out["metrics"]) == {"state_move_s", "move_gbps",
                                   "handle_host_ms"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
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


def test_new_family_found(run_py, tmp_path):
    """Adds a model family as new files and new BENCHMARK.json entries
    only: a reference module named by the config's `model_type`, the
    config, a cell and its limits."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cb = make_root(tmp_path, bench) / "chipbench"
    before = {p: p.read_bytes() for p in cb.rglob("*") if p.is_file()}
    (cb / "reference" / "gpt2twin.py").write_text(
        "from chipbench.reference.gpt2 import *  # noqa: F401,F403\n")
    cfg = json.loads((cb / "configs" / "gpt2-m.json").read_text())
    cfg.update(name="gpt2twin-m", model_type="gpt2twin")
    (cb / "configs" / "gpt2twin-m.json").write_text(json.dumps(cfg))
    shutil.copy(cb / "limits" / "gpt2m-steady-1c.json",
                cb / "limits" / "gpt2twin-steady-1c.json")
    entry = next(c for c in bench["configs"] if c["name"] == "gpt2-m")
    bench["configs"].append(dict(entry, name="gpt2twin-m",
                                 file="chipbench/configs/gpt2twin-m.json"))
    bench["workloads"].append({"name": "gpt2twin-steady-1c",
                               "config": "gpt2twin-m", "traffic": "steady",
                               "chips": 1, "why": "test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    res = run_py(cb / "run.py", "--workload", "gpt2twin-steady-1c", "--seed",
                 2**33 + 7, "--seconds", 1, "--trace", 0, "--rehearse")
    assert res.returncode == 0, res.stderr[-3000:]
    out = last_json(res.stdout)
    assert out["correct"] is True, res.stderr[-3000:]
    assert "set-up (chipbench.reference.gpt2twin)" in res.stderr
    assert all(p.read_bytes() == b for p, b in before.items())


@pytest.mark.parametrize("key,value", [("n_head", 8), ("n_inner", 2048),
                                       ("layer_norm_epsilon", 1e-5)])
def test_config_differing_from_the_program_is_refused(monkeypatch, key,
                                                       value):
    """A config file whose model fields (`program_fields` of its family)
    differ from the registry's config it names is refused before a run."""
    monkeypatch.syspath_prepend(str(ROOT / "src"))
    from chipbench.harness import family, program_model

    cfg = json.loads((ROOT / "chipbench" / "configs" / "gpt2-s.json")
                     .read_text())
    wrong = dict(cfg, **{key: value})
    assert family(cfg).program_fields(wrong) != \
        family(cfg).program_fields(cfg)
    with pytest.raises(ValueError, match="differs from the config file"):
        program_model(wrong, rehearse=False)
