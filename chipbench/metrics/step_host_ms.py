"""`step_host_ms`: host milliseconds per train step outside the step's
execution: the program's `chaos.step` span less its `chaos.step.run` (the
batch's `device_put`, the compiled step's lookup and the metrics' reads),
averaged over the window's steps."""
from chipbench.program_trace import rows


def read(run):
    rs = rows(run)
    steps = rs.named("chaos.step") if rs is not None else []
    if not steps:
        return None
    host = [s.duration_s - rs.seconds(s, "chaos.step.run") for s in steps]
    return 1e3 * sum(host) / len(host)
