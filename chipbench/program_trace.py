"""The program's own spans and scopes, read for the per-layer metrics.

The trainer records its timings itself (`ElasticTrainer.tracer`, a
`repro.core.telemetry.Recorder`): rows named `chaos.*` with their start and
end in seconds of `time.perf_counter`, the clock of the benchmark's window
`run.w`, and the `seq` of the span open around them as `parent`. Each such
span is also a `jax.profiler.TraceAnnotation` of the same name, so a
profiler trace holds it on the host plane beside the device's operations.
Its compiled train steps (`ElasticTrainer._compiled`) name, in each
instruction's `op_name` metadata, the `jax.named_scope`s the instruction
came from (`attention_core`, `optimizer`); the trace's `XLA Ops` events
carry the same instruction names, so an operation's scope is a join on the
name.

A program that keeps none of these (one older than its recorder) gives
nothing to read: every function here then returns None or nothing.
"""
from __future__ import annotations

import re

from chipbench import trace_reduce

SPAN_PREFIX = "chaos."
_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%?([^\s=]+) = .*?metadata=\{[^}]*?'
                    r'op_name="([^"]*)"')


# ---------------------------------------------------------------------------
# The recorder's rows.
# ---------------------------------------------------------------------------


class Rows:
    """The recorder's rows of a run, with the parent of each."""

    def __init__(self, tracer, window):
        self.all = list(tracer.rows)
        self.lo, self.hi = window

    def named(self, name: str) -> list:
        """Rows of `name` that lie inside the window, oldest first."""
        return sorted((r for r in self.all if r.name == name
                       and r.t0 >= self.lo and r.t1 <= self.hi),
                      key=lambda r: r.t0)

    def children(self, row, name: str) -> list:
        return [r for r in self.all if r.parent == row.seq and r.name == name]

    def seconds(self, row, name: str) -> float:
        """Seconds of `row`'s children named `name`."""
        return sum(r.duration_s for r in self.children(row, name))


def rows(run):
    """The trainer's recorder rows inside the run's window, or None."""
    tracer = getattr(run.trainer, "tracer", None)
    return Rows(tracer, run.w) if tracer is not None else None


def moves_of(rs: Rows, handle) -> list:
    return rs.children(handle, "chaos.move")


def changes_layout(move) -> bool:
    return tuple(move.attrs["from"]) != tuple(move.attrs["to"])


# ---------------------------------------------------------------------------
# Scopes of the compiled steps and the device trace.
# ---------------------------------------------------------------------------


def op_scopes(hlo_texts) -> dict:
    """Instruction name -> its `op_name` metadata, over the HLO texts of
    compiled programs. A name that two programs give different scopes is
    left out (it cannot be told apart in a trace)."""
    out, clash = {}, set()
    for text in hlo_texts:
        for line in text.splitlines():
            m = _INSTR.match(line)
            if m is None:
                continue
            name, op = m.groups()
            if out.get(name, op) != op:
                clash.add(name)
            out[name] = op
    return {k: v for k, v in out.items() if k not in clash}


def program_scopes(run):
    """`op_scopes` of the trainer's compiled steps, or None."""
    compiled = getattr(run.trainer, "_compiled", None)
    if not compiled:
        return None
    if not hasattr(run, "_op_scopes"):
        run._op_scopes = op_scopes(c.as_text() for c in compiled.values())
    return run._op_scopes


def profile(run):
    """The run's profiler trace (loaded once), or None."""
    if not hasattr(run, "_profile"):
        files = sorted((run.out_dir / "trace").rglob("*.xplane.pb"))
        run._profile = (trace_reduce.load(files[-1])
                        if run.trace and files else None)
    return run._profile


def chaos_spans(pd) -> list:
    """[(name, start ns, end ns)] of the program's spans in a trace."""
    spans = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return spans


def window_ns(pd):
    """(start ns, end ns) of the benchmark's `bench.window` span, or None."""
    return next((s[1:] for s in trace_reduce.host_spans(pd)
                 if s[0] == trace_reduce.WINDOW_SPAN), None)


def scope_seconds(ops, window, scopes: dict) -> dict:
    """Own seconds of one chip's operations inside `window`, summed by the
    `op_name` that `scopes` gives each ('' for an operation it does not
    name)."""
    lo, hi = window
    own = trace_reduce.self_times([(n, max(s, lo), min(e, hi))
                                   for n, s, e in ops if e > lo and s < hi])
    out = {}
    for name, sec in own.items():
        op = scopes.get(name, "")
        out[op] = out.get(op, 0.0) + sec
    return out


def scope_share(ops, window, scopes: dict, scope: str) -> float:
    """Percent of the chip's busy time in `window` spent in operations whose
    `op_name` holds the scope `scope`, nested operations counted once."""
    lo, hi = window
    busy = sum(e - s for s, e in trace_reduce.merge(
        [(s, e) for _, s, e in ops], lo, hi)) * 1e-9
    by_op = scope_seconds(ops, window, scopes)
    part = sum(sec for op, sec in by_op.items() if scope in op)
    return 100.0 * part / busy if busy else None


def run_scope_share(run, scope: str):
    """`scope_share` on the run's first chip in its profiled window, or
    None without a device trace or compiled steps to read."""
    scopes = program_scopes(run)
    pd = profile(run) if scopes else None
    if pd is None:
        return None
    ops = trace_reduce.device_ops(pd)
    chips = sorted(c for c in ops if c in {d.id for d in run.pool})
    window = window_ns(pd)
    if not chips or window is None:
        return None
    return scope_share(ops[chips[0]], window, scopes, scope)


def _overlap(a, b) -> float:
    """Length of the intersection of two sorted lists of disjoint
    intervals."""
    total, i, j = 0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        total += max(0, hi - lo)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def _split_gap(lo, hi, spans, out: dict):
    """Adds the milliseconds of the idle gap [lo, hi] (ns) to `out`, each
    part under the innermost span open during it ("no span" where none
    is)."""
    cuts = sorted({lo, hi} | {t for sp in spans for t in sp[1:]
                             if lo < t < hi})
    for a, b in zip(cuts, cuts[1:]):
        mid = (a + b) / 2
        open_ = [sp for sp in spans if sp[1] <= mid < sp[2]]
        name = (min(open_, key=lambda sp: sp[2] - sp[1])[0] if open_
                else "no span")
        out[name] = out.get(name, 0.0) + (b - a) * 1e-6


def device_breakdown(pd, chip: int, scopes: dict) -> dict:
    """One chip's profiled window, read from the program's spans and
    scopes (the fields `breakdown.py` prints)."""
    tr = trace_reduce
    lo, hi = window = window_ns(pd)
    ops = tr.device_ops(pd)[chip]
    busy = tr.merge([(s, e) for _, s, e in ops], lo, hi)
    busy_ns = sum(e - s for s, e in busy)
    spans = [s for s in chaos_spans(pd) if s[1] < hi and s[2] > lo]
    by_op = scope_seconds(ops, window, scopes)
    shares = {}
    for op, sec in by_op.items():
        key = next((k for k in ("attention_core", "optimizer") if k in op),
                   "rest")
        shares[key] = shares.get(key, 0.0) + 100.0 * sec / (busy_ns * 1e-9)
    own = tr.self_times([(n, max(s, lo), min(e, hi)) for n, s, e in ops
                         if e > lo and s < hi])
    top = [(n, sec, scopes.get(n, "")[-120:])
           for n, sec in sorted(own.items(), key=lambda kv: -kv[1])[:10]]
    steps = sum(1 for s in spans if s[0] == "chaos.step")
    idle, prev = {}, lo
    for s, e in busy + [[hi, hi]]:
        if s > prev:
            _split_gap(prev, s, spans, idle)
        prev = max(prev, e)
    runs = sorted(s for s in spans if s[0] == "chaos.step.run")
    reads = sorted(s for s in spans if s[0] == "chaos.step.read_metrics")
    cover = []
    for _, s, _ in runs:
        nxt = next((r for r in reads if r[1] >= s), None)
        if nxt is not None:
            cover.append((s, nxt[2]))
    cover = tr.merge(cover, lo, hi)
    return {
        "scopes": shares,
        "top_ops": top,
        "idle_ms_per_step": {k: v / max(steps, 1) for k, v in idle.items()},
        "profiled_steps": steps,
        "run_cover": _overlap(busy, cover) / busy_ns if busy_ns else None,
    }
