"""From a profiler trace (`.xplane.pb`) to busy and idle time, top device
operations, and idle gaps named by the host span open during them.

Read with `jax.profiler.ProfileData`. On a TPU each chip is a plane named
`/device:TPU:<i>`; its `XLA Ops` line holds every operation with its start
and duration on the same clock as the host planes. Operations nest there (a
`while` holds its body), so busy time is the union of the intervals and an
operation's own time is its duration less that of the operations inside it.
Host spans are the benchmark's `jax.profiler.TraceAnnotation`s, whose names
start with `bench.`; the profiled window is the span `bench.window`.
"""
from __future__ import annotations

import gzip
import tempfile
from pathlib import Path

OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


def load(path):
    from jax.profiler import ProfileData

    path = Path(path)
    if path.suffix == ".gz":
        with tempfile.NamedTemporaryFile(suffix=".xplane.pb") as tmp:
            tmp.write(gzip.decompress(path.read_bytes()))
            tmp.flush()
            return ProfileData.from_file(tmp.name)
    return ProfileData.from_file(str(path))


def _events(plane, line_name):
    for line in plane.lines:
        if line.name == line_name:
            return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events]
    return []


def device_ops(pd) -> dict:
    """chip index -> [(op name, start ns, end ns)] for every TPU plane."""
    out = {}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            out[int(plane.name.rsplit(":", 1)[1])] = _events(plane, OPS_LINE)
    return out


def host_spans(pd) -> list:
    """[(name, start ns, end ns)] of the benchmark's own host spans."""
    spans = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return spans


def merge(intervals, lo, hi) -> list:
    """Union of (start, end) intervals clipped to [lo, hi], in order."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def self_times(ops) -> dict:
    """Op name (before ' = ' in the HLO text) -> own seconds, nested
    operations subtracted from the one that holds them."""
    totals = {}
    stack = []  # [name, end, own ns]

    def close(entry):
        totals[entry[0]] = totals.get(entry[0], 0.0) + entry[2] * 1e-9

    for name, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][1] <= s:
            close(stack.pop())
        if stack:
            stack[-1][2] -= min(e, stack[-1][1]) - s
        stack.append([name.split(" = ", 1)[0].lstrip("%"), e, e - s])
    while stack:
        close(stack.pop())
    return totals


def _label(spans, s, e) -> str:
    """The innermost benchmark span open at the middle of [s, e]."""
    mid = (s + e) / 2
    open_ = [sp for sp in spans if sp[1] <= mid < sp[2]
             and sp[0] != WINDOW_SPAN]
    if not open_:
        return "no span"
    return min(open_, key=lambda sp: sp[2] - sp[1])[0][len(SPAN_PREFIX):]


def reduce(pd, chips=None, top: int = 10, window=None) -> dict:
    """Busy seconds per chip within the `bench.window` span (or `window`,
    a (start ns, end ns) pair), the window's length, the first chip's idle
    share, its top operations by own time and its longest idle gaps by host
    span. `chips` limits the chips averaged."""
    spans = host_spans(pd)
    if window is None:
        windows = [sp for sp in spans if sp[0] == WINDOW_SPAN]
        if not windows:
            raise ValueError("trace holds no bench.window span")
        window = windows[0][1:]
    lo, hi = window
    ops = device_ops(pd)
    if chips is not None:
        ops = {c: v for c, v in ops.items() if c in chips}
    if not ops:
        return {"window_s": (hi - lo) * 1e-9, "busy_s": {}}
    busy = {c: merge([(s, e) for _, s, e in v], lo, hi)
            for c, v in ops.items()}
    busy_s = {c: sum(e - s for s, e in iv) * 1e-9 for c, iv in busy.items()}
    chip0 = min(busy)
    gaps, prev = [], lo
    for s, e in busy[chip0] + [[hi, hi]]:
        if s > prev:
            gaps.append((_label(spans, prev, s), (s - prev) * 1e-9))
        prev = max(prev, e)
    own = self_times([(n, max(s, lo), min(e, hi)) for n, s, e in ops[chip0]
                      if e > lo and s < hi])
    window_s = (hi - lo) * 1e-9
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "chip0": chip0,
        "idle_share": 1.0 - busy_s[chip0] / window_s,
        "device_ops": sorted(own.items(), key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(gaps, key=lambda g: -g[1])[:top],
        "idle_by_span": _sum_by(gaps),
    }


def _sum_by(gaps) -> list:
    out = {}
    for name, sec in gaps:
        out[name] = out.get(name, 0.0) + sec
    return sorted(out.items(), key=lambda kv: -kv[1])
