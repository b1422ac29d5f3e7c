"""`device_idle_share`: the share of the profiled window in which no
operation ran on the first chip of the cell (a member of every layout),
from the profiler trace (`chipbench/trace_reduce.py`)."""


def read(run):
    tr = run.trace_summary()
    if not tr or "idle_share" not in tr:
        return None
    return 100.0 * tr["idle_share"]
