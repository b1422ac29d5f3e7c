"""`move_gbps`: the rate at which the window's state moves delivered bytes:
the bytes the devices of each new layout had to receive (the `bytes` of the
program's `chaos.move` spans, from the shardings' index maps) over the
seconds of their `chaos.move.transfer` spans (`device_put` until ready),
both summed over the moves that deliver any bytes; GB is 1e9 bytes."""
from chipbench.program_trace import rows


def read(run):
    rs = rows(run)
    if rs is None:
        return None
    moves = [m for m in rs.named("chaos.move") if m.attrs["bytes"] > 0]
    seconds = sum(rs.seconds(m, "chaos.move.transfer") for m in moves)
    if not seconds:
        return None
    return sum(m.attrs["bytes"] for m in moves) / seconds / 1e9
