"""`handle_host_ms`: host milliseconds per churn event in the engine's
backend and the recovery policy, outside state movement: the program's
`chaos.handle` span less the `chaos.move` spans inside it, averaged over the
window's events."""
from chipbench.program_trace import moves_of, rows


def read(run):
    rs = rows(run)
    hs = rs.named("chaos.handle") if rs is not None else []
    if not hs:
        return None
    host = [h.duration_s - sum(m.duration_s for m in moves_of(rs, h))
            for h in hs]
    return 1e3 * sum(host) / len(host)
