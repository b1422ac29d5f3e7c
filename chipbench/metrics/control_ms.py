"""`control_ms`: milliseconds per event in the churn engine's backend and
the recovery policy, outside state movement: the host clock around
`TrainerBackend.handle` less the event's `ScaleEvent.wall_s`, averaged over
the window's events."""


def read(run):
    hs = run.handles()
    if not hs:
        return None
    return 1e3 * sum(h["t1"] - h["t0"] - h["move_s"] for h in hs) / len(hs)
