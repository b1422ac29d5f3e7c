"""`state_move_s`: seconds of state movement per layout-changing event in
the window: the program's `chaos.move` spans inside the event's
`chaos.handle` (plan, codec, transfer and the data pipeline's callback),
summed per event and averaged over the events that change the layout."""
from chipbench.program_trace import changes_layout, moves_of, rows


def read(run):
    rs = rows(run)
    if rs is None:
        return None
    per_event = [sum(m.duration_s for m in moves)
                 for moves in (moves_of(rs, h) for h in rs.named("chaos.handle"))
                 if any(changes_layout(m) for m in moves)]
    return sum(per_event) / len(per_event) if per_event else None
