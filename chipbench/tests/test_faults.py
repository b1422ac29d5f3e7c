"""A run with its timed path broken underneath comes out not correct: once
for each fault the cell can have. The sound run comes out correct."""
import pytest

from chipbench.tests.conftest import last_json
CASES = [("gpt2m-steady-1c", f) for f in
         ("none", "unchanged", "half_batch", "dropped_update")] + \
        [("gpt2s-churn-4c", f) for f in
         ("none", "unchanged", "half_batch", "no_exchange", "dropped_update")]


@pytest.mark.parametrize("cell,fault", CASES)
def test_fault_is_caught(run_py, bench_root, cell, fault):
    res = run_py(bench_root / "chipbench" / "tests" / "fault_run.py",
                 "--workload", cell, "--fault", fault)
    assert res.returncode == 0, res.stderr[-3000:]
    out = last_json(res.stdout)
    assert out["correct"] is (fault == "none"), res.stderr[-2000:]
