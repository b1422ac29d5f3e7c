"""One run of one cell: set-up, the measured window, then the comparison.

Set-up builds one `ElasticTrainer` on the cell's chips, puts the seeded
weights on them in one jitted call, makes the rows of tokens, drives the
trainer through its first steps (the correctness pass: the traffic's event
cycle once with `check_steps[i]` steps before event i, or CHECK_STEPS steps)
and one more unit of the traffic to warm every layout. The window then runs
whole units (an event cycle through `ChurnEngine(TrainerBackend)`, or
`steps_between` steps) until `--seconds` have passed. After the window the
trainer's state is freed and the plain reference retraces the first
CHECK_STEPS steps for the comparison in `check.py`. The reference is the
module of the configuration's family, `chipbench.reference.<model_type>`
(its interface: `chipbench/reference/__init__.py`).

Spans are recorded by this file's own code around its calls into the
program: every `ElasticTrainer.step`, every `TrainerBackend.handle`, and the
benchmark's own pauses (the move fingerprints of `moves.py`, taken out of
the window). In a `--trace 1` run they are also written as
`jax.profiler.TraceAnnotation`s named `bench.<span>`.
"""
from __future__ import annotations

import gc
import importlib
import json
import math
import shutil
import sys
import time
from contextlib import contextmanager, nullcontext
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.configs import get_config
from repro.core.engine import ChurnEngine, ChurnEvent
from repro.elastic.trainer import ElasticTrainer, TrainerBackend
from repro.models import build_model

from chipbench import check, feed, trace_reduce
from chipbench.moves import MoveChecker

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
#: host-clock seconds of profiled window in a --trace 1 run (whole units)
TRACE_SECONDS = 3.0
REHEARSE_SIZES = {"seq_len": 64, "per_device_batch": 2}
REF_BLOCK_BYTES = 4e9


# ---------------------------------------------------------------------------
# Cell description.
# ---------------------------------------------------------------------------


def load_cell(name: str, root: Path = ROOT) -> dict:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    cell = dict(cells[name])
    cfg_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    cell["cfg"] = json.loads((root / cfg_entry["file"]).read_text())
    cell["traffic_spec"] = feed.load_traffic(cell["traffic"],
                                             root / "chipbench")
    cell["per_layer"] = [m for m in bench["per_layer"]
                         if name in m.get("workloads", [name])]
    cell["end_to_end"] = [m["name"] for m in bench["end_to_end"]
                          if name in m.get("workloads", [name])]
    return cell


def family(cfg: dict):
    """The reference module of the config file's model family."""
    return importlib.import_module(f"chipbench.reference.{cfg['model_type']}")


def program_model(cfg: dict, rehearse: bool):
    """The program's model for a config file, checked against the file:
    (model, config file as run, reference sizes)."""
    ref = family(cfg)
    arch = get_config(cfg["registry_name"])
    if rehearse:
        cfg, arch = ref.rehearse_cfg(cfg, arch.reduced())
        cfg = dict(cfg, train=dict(cfg["train"], **REHEARSE_SIZES))
    t = cfg["train"]
    want = dict(ref.program_fields(cfg),
                learning_rate=t["learning_rate"],
                weight_decay=t["weight_decay"], grad_clip=t["grad_clip"],
                dtype=t["compute_dtype"], param_dtype=t["param_dtype"],
                optimizer=t["optimizer"])
    got = {k: getattr(arch, k) for k in want}
    if got != want:
        raise ValueError(f"program config {arch.name} differs from the "
                         f"config file: {got} != {want}")
    model = build_model(arch)
    a = ref.arch(cfg)
    prog_shapes = jax.tree.map(lambda s: tuple(s.shape),
                               model.train_state_specs()["params"])
    ref_shapes = ref.param_shapes(a)
    if prog_shapes != ref_shapes:
        raise ValueError(f"program state {prog_shapes} != {ref_shapes}")
    return model, cfg, a


# ---------------------------------------------------------------------------
# Spans, the timed trainer and the timed backend.
# ---------------------------------------------------------------------------


class Spans:
    def __init__(self, annotate: bool):
        self.rows = []
        self.annotate = annotate

    @contextmanager
    def span(self, name: str, **attrs):
        rec = dict(name=name, **attrs)
        ann = (jax.profiler.TraceAnnotation("bench." + name)
               if self.annotate else nullcontext())
        with ann:
            rec["t0"] = time.perf_counter()
            try:
                yield rec
            finally:
                rec["t1"] = time.perf_counter()
                self.rows.append(rec)

    def between(self, name: str, t0: float, t1: float) -> list:
        return sorted((r for r in self.rows if r["name"] == name
                       and r["t0"] >= t0 and r["t1"] <= t1),
                      key=lambda r: r["t0"])


class TimedTrainer(ElasticTrainer):
    """`ElasticTrainer` with a span around every step and an optional hook
    after it (the correctness pass's reads of the state)."""

    spans: Spans = None
    after_step = None

    def step(self, batch):
        rows = int(batch["tokens"].shape[0])
        with self.spans.span("step", n=len(self.active), tp=self.tp,
                             rows=rows) as rec:
            metrics = super().step(batch)
        rec["loss"] = metrics["loss"]
        if self.after_step is not None:
            self.after_step(self, metrics)
        return metrics


class TimedBackend:
    """Proxy of a `TrainerBackend` for the engine: a span around every
    `handle` with the layouts before and after it, and the move check."""

    def __init__(self, backend: TrainerBackend, spans: Spans,
                 mover: MoveChecker):
        self.backend = backend
        self.spans = spans
        self.mover = mover

    def advance_to(self, t, ledger):
        self.backend.advance_to(t, ledger)

    def drain(self, ledger):
        self.backend.drain(ledger)

    def handle(self, seq, ev, ledger):
        tr = self.backend.trainer
        with self.spans.span("pause"):
            before = self.mover.fingerprint(tr.state)
        layout = (len(tr.active), tr.tp)
        with self.spans.span("handle", kind=ev.kind) as rec:
            self.backend.handle(seq, ev, ledger)
        rec["layout"] = [layout, (len(tr.active), tr.tp)]
        with self.spans.span("pause"):
            after = self.mover.fingerprint(tr.state)
            self.mover.check(ev.kind, before, after, tr.device_ids())


class CompileCounter:
    """Counts executables built or loaded from the persistent cache."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self)

    def __call__(self, event, duration, **_):
        if event == self.EVENT:
            self.count += 1


# ---------------------------------------------------------------------------
# The run.
# ---------------------------------------------------------------------------


def _events(traffic, pool):
    specs = traffic["events"]
    return [ChurnEvent(t=float(i), kind=s["kind"], node=pool[s["device"]].id,
                       reshard=s.get("reshard"),
                       new_shape=(tuple(s["new_shape"]) if "new_shape" in s
                                  else None))
            for i, s in enumerate(specs)]


class Run:
    def __init__(self, cell: dict, seed: int, *, trace: bool, rehearse: bool,
                 t_start: float, out_dir: Path):
        self.cell = cell
        self.ref = family(cell["cfg"])
        self.seed = seed
        self.trace = trace
        self.rehearse = rehearse
        self.t_start = t_start
        self.out_dir = out_dir
        self.spans = Spans(annotate=trace)
        self.mover = MoveChecker()
        self.compiles = CompileCounter()
        self.traffic = cell["traffic_spec"]

    # -- set-up ----------------------------------------------------------------

    def setup(self, trainer_cls=TimedTrainer):
        marks = [("start-up", time.perf_counter())]
        self.build(trainer_cls)
        marks.append(("build", time.perf_counter()))
        self.seed_state(self.seed)
        jax.block_until_ready(self.trainer.state)
        marks.append(("weights and rows", time.perf_counter()))
        self.check_pass()
        marks.append(("correctness pass", time.perf_counter()))
        self.unit()  # warm-up: the window's own unit
        jax.block_until_ready(self.trainer.state)
        marks.append(("warm-up unit", time.perf_counter()))
        self.setup_s = marks[-1][1] - self.t_start
        t = self.t_start
        _log(f"set-up ({self.ref.__name__}): " + ", ".join(
            f"{name} {m - t0:.2f} s" for (name, m), t0
            in zip(marks, [t] + [m for _, m in marks[:-1]])))

    def build(self, trainer_cls=TimedTrainer):
        """The program's model, trainer, backend and engine."""
        cell, traffic = self.cell, self.traffic
        self.model, self.cfg, self.a = program_model(cell["cfg"], self.rehearse)
        t = self.cfg["train"]
        self.seq_len, self.pdb = t["seq_len"], t["per_device_batch"]
        self.pool = jax.devices()[:cell["chips"]]
        tr = trainer_cls(self.model, devices=self.pool,
                         initial=traffic["start_devices"],
                         per_device_batch=self.pdb)
        tr.spans = self.spans
        self.trainer = tr
        self.backend = TrainerBackend(
            tr, batch_fn=lambda: self.feed.next(tr.global_batch),
            steps_between=traffic["steps_between"])
        self.engine = ChurnEngine(TimedBackend(self.backend, self.spans,
                                               self.mover))

    def seed_state(self, seed: int):
        """Seeded weights on the trainer's chips (one jitted call) and the
        pool of rows."""
        self.seed = seed
        tr = self.trainer
        init = jax.jit(partial(self.ref.init_state, self.a),
                       out_shardings=NamedSharding(tr.mesh(), P()))
        tr.state = init(self.ref.seed_key(seed))
        self.feed = feed.Feed(self.rows(seed))

    def rows(self, seed: int):
        spec = self.traffic["rows"]
        n_rows = spec["pool_batches"] * self.pdb * self.cell["chips"]
        return feed.token_rows(seed, n_rows, self.seq_len,
                               self.cfg["vocab_size"],
                               tokens_used=spec["tokens_used"],
                               successors=spec["successors"],
                               follow=spec["follow"])

    def unit(self):
        """One unit of the window: an event cycle, or steps_between steps."""
        if self.traffic["events"]:
            self.engine.run(_events(self.traffic, self.pool))
        else:
            self.backend.advance_to(0.0, self.engine.ledger)

    def check_pass(self):
        """The first steps, through the window's own calls and feed, with the
        reads of the state the comparison needs."""
        tr, b1 = self.trainer, self.cfg["train"]["adam_b1"]
        p0 = jax.jit(lambda t: jax.tree.map(jnp.copy, t))(tr.state["params"])
        got = {"losses": [], "grad_norms": []}

        def after_step(trainer, metrics):
            got["losses"].append(metrics["loss"])
            got["grad_norms"].append(metrics["grad_norm"])
            k = len(got["losses"])
            with self.spans.span("pause"):
                if k == 1:
                    got["grad"] = check.leaf_norms(trainer.state["opt"]["m"],
                                                   self.ref.STACKED,
                                                   1.0 / (1.0 - b1))
                if k == check.CHECK_STEPS:
                    got["change"] = check.change_norms(
                        trainer.state["params"], p0, self.ref.STACKED)

        tr.after_step = after_step
        try:
            keep = self.backend.steps_between
            for ev, steps in zip(_events(self.traffic, self.pool),
                                 self.traffic.get("check_steps", [])):
                self.backend.steps_between = steps
                self.engine.run([ev])
            self.backend.steps_between = keep
            while len(got["losses"]) < check.CHECK_STEPS:
                tr.step(self.feed.next(tr.global_batch))
        finally:
            tr.after_step = None
        n = check.CHECK_STEPS
        got["losses"], got["grad_norms"] = got["losses"][:n], got["grad_norms"][:n]
        self.program_readout = got
        self.check_batches = [self.feed.rows_of(k) for k in range(n)]

    # -- window ------------------------------------------------------------------

    def window(self, seconds: float):
        """Whole units until `seconds` have passed, pauses taken out. A
        traced run profiles the first units, at least TRACE_SECONDS of
        them, inside the span `window`."""
        c0 = self.compiles.count
        if self.trace:
            shutil.rmtree(self.out_dir / "trace", ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(self.out_dir / "trace"),
                                     profiler_options=opts)
        t0 = time.perf_counter()
        units = 0
        if self.trace:
            with self.spans.span("window"):
                while units == 0 or time.perf_counter() - t0 < min(
                        TRACE_SECONDS, seconds):
                    self.unit()
                    units += 1
            with self.spans.span("pause"):
                jax.profiler.stop_trace()
        while units == 0 or (time.perf_counter() - t0
                             - self._paused(t0, time.perf_counter())
                             < seconds):
            self.unit()
            units += 1
        t1 = time.perf_counter()
        self.w = (t0, t1)
        self.window_s = t1 - t0 - self._paused(t0, t1)
        self.units = units
        self.window_compiles = self.compiles.count - c0

    def _paused(self, t0, t1):
        return sum(r["t1"] - r["t0"] for r in self.spans.between("pause", t0, t1))

    # -- end-to-end metrics ----------------------------------------------------------

    def steps(self):
        return self.spans.between("step", *self.w)

    def handles(self):
        return self.spans.between("handle", *self.w)

    def tokens_per_s(self):
        return sum(s["rows"] for s in self.steps()) * self.seq_len / self.window_s

    def recoveries(self) -> list:
        """Per layout-changing event: handle start to the end of the first
        step after it, pauses taken out."""
        steps = self.steps()
        out = []
        for h in self.handles():
            if h["layout"][0] == h["layout"][1]:
                continue
            nxt = next((s for s in steps if s["t0"] >= h["t1"]), None)
            if nxt is None:
                continue
            out.append(nxt["t1"] - h["t0"] - self._paused(h["t0"], nxt["t1"]))
        return out

    def end_to_end(self) -> dict:
        vals = {"tokens_per_s": (self.tokens_per_s(), "tokens/s"),
                "setup_s": (self.setup_s, "s")}
        rec = self.recoveries()
        if rec:
            vals["recover_s"] = (sum(rec) / len(rec), "s")
        return {k: {"value": v, "unit": u} for k, (v, u) in vals.items()
                if k in self.cell["end_to_end"]}

    # -- per-layer metrics --------------------------------------------------------------

    def per_layer(self) -> dict:
        out = {}
        for m in self.cell["per_layer"]:
            reader = importlib.import_module(f"chipbench.metrics.{m['name']}")
            value = reader.read(self)
            if value is not None:
                out[m["name"]] = {"value": value, "unit": m["unit"]}
        return out

    def trace_summary(self):
        """The reduced trace of the profiled window (None without one)."""
        if not hasattr(self, "_trace"):
            files = sorted((self.out_dir / "trace").rglob("*.xplane.pb"))
            self._trace = None
            if self.trace and files:
                ids = {d.id for d in self.pool}
                self._trace = trace_reduce.reduce(trace_reduce.load(files[-1]),
                                                  chips=ids)
        return self._trace

    # -- device ---------------------------------------------------------------------------

    def device(self) -> dict:
        d0 = self.pool[0]
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in self.pool]
        out = {"platform": d0.platform, "kind": d0.device_kind,
               "count": len(self.pool), "memory_peak_bytes": int(max(peaks))}
        tr = self.trace_summary()
        if tr and tr["busy_s"]:
            out["busy_s"] = sum(tr["busy_s"].values()) / len(tr["busy_s"])
            out["window_s"] = tr["window_s"]
        return out

    # -- correctness -----------------------------------------------------------------------

    def free_program(self):
        self.trainer.state = None
        self.trainer._step_fns.clear()
        gc.collect()

    def reference_readout(self, *, quant=None, param_dtype=None,
                          rows_kept=None, drop_update=False) -> dict:
        """The reference's first steps over the same rows, its state on the
        first chip, in blocks of rows that fit dealt out to the cell's
        chips. `quant` / `param_dtype` give the control; `rows_kept[k]`
        keeps that share of step k's first rows (the faults that leave part
        of the batch out); `drop_update` keeps the first layer of the
        family's `UPDATE_LEAF` at its old value (an answer altered where it
        is made)."""
        dev = self.pool[0]
        a, ref = self.a, self.ref
        init = jax.jit(partial(ref.init_state, a),
                       out_shardings=SingleDeviceSharding(dev))

        def start():
            state = init(ref.seed_key(self.seed))
            if param_dtype is not None:
                state["params"] = jax.tree.map(
                    lambda p: p.astype(param_dtype).astype(jnp.float32),
                    state["params"])
            return state

        state = start()
        got = {"losses": [], "grad_norms": []}
        for k, rows in enumerate(self.check_batches):
            if rows_kept is not None:
                rows = rows[:max(1, int(len(rows) * rows_kept[k]))]
            old = state["params"] if drop_update else None
            state, loss, grads, gnorm = ref.train_step(
                a, state, rows, self.pool, quant=quant,
                param_dtype=param_dtype, rows_per_block=self.ref_rows())
            if drop_update:
                state["params"] = keep_first_layer(state["params"], old,
                                                   ref.UPDATE_LEAF)
            del old
            got["losses"].append(loss)
            got["grad_norms"].append(gnorm)
            if k == 0:
                got["grad"] = check.leaf_norms(grads, ref.STACKED)
            del grads
        params = state["params"]
        del state
        got["change"] = check.change_norms(params, start()["params"],
                                           ref.STACKED)
        return got

    def ref_rows(self) -> int:
        """Rows per block of the reference, a power of two: activations of
        at most REF_BLOCK_BYTES."""
        per_row = self.ref.activation_bytes_per_row(self.a, self.seq_len)
        rows = 1
        while rows < 8 and 2 * rows * per_row <= REF_BLOCK_BYTES:
            rows *= 2
        return rows

    def compare(self) -> dict:
        reference = self.reference_readout()
        detail = check.readings(self.program_readout, reference, detail=True)
        _log(f"losses: program {self.program_readout['losses']}, reference "
             f"{reference['losses']}; worst leaves: grad {detail['grad_leaf']}"
             f", change {detail['change_leaf']}")
        self.readings = {k: detail[k] for k in check.NUMBERS}
        key = "rehearse" if self.rehearse else "chip"
        limits = check.load_limits(self.cell["name"], key)
        judged = check.judge(self.readings, limits)
        if self.traffic["events"]:
            bad = sum(not r["ok"] for r in self.mover.results)
            judged["moves_not_identical"] = {"value": bad, "limit": 0,
                                             "ok": bad == 0}
        return judged


def execute(cell_name: str, seed: int, seconds: float, trace: bool, *,
            rehearse: bool, t_start: float, root: Path = ROOT,
            trainer_cls=TimedTrainer) -> dict:
    """Runs one cell once; returns the result line's object. Prints the
    numbers compared, each with its limit, to standard error last."""
    cell = load_cell(cell_name, root)
    out_dir = HERE / "out" / cell_name
    out_dir.mkdir(parents=True, exist_ok=True)
    run = Run(cell, seed, trace=trace, rehearse=rehearse, t_start=t_start,
              out_dir=out_dir)
    run.setup(trainer_cls)
    run.window(seconds)
    metrics = run.per_layer() if trace else run.end_to_end()
    device = run.device()
    steps = run.steps()
    losses = [s["loss"] for s in steps]
    nonfinite = sum(not math.isfinite(x) for x in losses)
    _log(f"window: {run.window_s:.3f} s, {run.units} units, {len(steps)} "
         f"steps, {len(run.handles())} events, {run.window_compiles} "
         f"compiles; set-up {run.setup_s:.2f} s")
    if run.window_compiles:
        _log(f"WARNING: {run.window_compiles} executables compiled or loaded "
             "inside the window")
    tsum = run.trace_summary() if trace else None
    run.free_program()
    t0 = time.perf_counter()
    judged = run.compare()
    _log(f"reference and comparison: {time.perf_counter() - t0:.2f} s")
    correct = all(j["ok"] for j in judged.values()) and nonfinite == 0
    result = {
        "correct": correct,
        "attempted": len(steps) + len(run.handles()),
        "failed": nonfinite + sum(not r["ok"] for r in run.mover.results),
        "metrics": metrics,
        "device": device,
        "window": {"seconds": run.window_s, "units": run.units,
                   "steps": len(steps), "events": len(run.handles()),
                   "compiles": run.window_compiles},
    }
    if tsum and "device_ops" in tsum:
        result["breakdown"] = {"device_ops": [list(x) for x in tsum["device_ops"]],
                               "idle_gaps": [list(x) for x in tsum["idle_gaps"]]}
        _log(f"idle by host span: {tsum['idle_by_span']}")
    result["checks"] = {k: {"value": j["value"], "limit": j["limit"]}
                        for k, j in judged.items()}
    for k, j in judged.items():
        _log(f"check {k}: {j['value']!r} limit {j['limit']!r} "
             f"{'ok' if j['ok'] else 'FAIL'}")
    return result


def keep_first_layer(new: dict, old: dict, path: tuple) -> dict:
    """`new` with the first layer of the stacked leaf at `path` taken from
    `old`: an update dropped where it is made."""
    if len(path) == 1:
        return dict(new, **{path[0]: new[path[0]].at[0].set(old[path[0]][0])})
    return dict(new, **{path[0]: keep_first_layer(new[path[0]], old[path[0]],
                                                  path[1:])})


def _log(msg: str):
    print(msg, file=sys.stderr, flush=True)
