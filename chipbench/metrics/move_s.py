"""`move_s`: seconds the trainer spent moving state per layout-changing
event in the window: the sum of the `ScaleEvent.wall_s` that the event's
`scale_out` / `scale_in` / `apply_reshard` calls recorded, averaged over
the events."""


def read(run):
    moves = [h["move_s"] for h in run.handles()
             if h["layout"][0] != h["layout"][1]]
    return sum(moves) / len(moves) if moves else None
