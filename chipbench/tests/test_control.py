"""The comparison at reduced size on the CPU: the program's first steps agree
with the plain reference within the limits, and the control (the reference
in the program's place one precision step down) and the faults that leave
part of the batch out do not."""
import json

import pytest

from chipbench.check import load_limits


@pytest.mark.parametrize("cell", ["gpt2m-steady-1c", "gpt2s-churn-4c"])
def test_program_agrees_and_control_fails(run_py, bench_root, cell, tmp_path):
    out = tmp_path / "cal.jsonl"
    res = run_py(bench_root / "chipbench" / "calibrate.py", "--workload", cell, "--program-seeds", 31,
                 "--control-seeds", 32, "--rehearse", "--out", out)
    assert res.returncode == 0, res.stderr[-3000:]
    rows = [json.loads(l) for l in out.read_text().splitlines()][1:]
    limits = load_limits(cell, "rehearse")

    def fails(r):
        return any(r[k] > limits[k] for k in limits)

    by = {r["variant"]: r for r in rows}
    assert not fails(by["program"]), by["program"]
    for variant in ("control_fp8", "control_bf16_params", "fault_half_batch"):
        assert fails(by[variant]), (variant, by[variant], limits)
    if cell == "gpt2s-churn-4c":
        assert fails(by["fault_no_exchange"])
