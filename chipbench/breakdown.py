"""Where a cell's time goes, read from the program's own spans and scopes.

    python chipbench/breakdown.py --workload <name> --seed <n> \\
        [--seconds <s>] [--rehearse]

Runs the cell's set-up and window once with a profiler trace of the
window's first units (as `run.py --trace 1` does, without the comparison)
and prints one JSON object, also written to `chipbench/out/<cell>/
breakdown.json`:

- `span_us`: the cost of one empty recorder span with no profiler session,
  mean over 20,000, and `span_share`: four such spans per step (a step's
  `chaos.step` and its three children) over the window's mean step;
- `tokens_per_s`: of the profiled part of the window and of the rest
  (the profiler's stop taken out);
- `scopes`: the first chip's busy time in the profiled window by
  `jax.named_scope` (`attention_core`, `optimizer`, the rest), in percent;
- `top_ops`: its ten longest operations by own time, with their `op_name`;
- `idle_ms_per_step`: its idle time per step, each part of a gap under
  the innermost `chaos.` span open during it;
- `run_cover`: the share of its busy time that lies between the start of
  a `chaos.step.run` and the end of the next `chaos.step.read_metrics`;
- `step_vs_bench`: the largest relative gap between a window step's
  `chaos.step` and the benchmark's `bench.step` around it;
- `metrics`: the cell's per-layer metrics.
"""
import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path[:0] = [str(ROOT), str(HERE)]

from run import setup_env  # noqa: E402

SPANS_PER_STEP = 4


def span_cost_us(n: int = 20_000) -> float:
    from repro.core.telemetry import Recorder

    rec = Recorder()
    t0 = time.perf_counter()
    for _ in range(n):
        with rec.span("chaos.cost"):
            pass
    return (time.perf_counter() - t0) / n * 1e6


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    chips = next(w["chips"] for w in bench["workloads"]
                 if w["name"] == args.workload)
    setup_env(chips, args.rehearse)

    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from chipbench import program_trace as pt
    from chipbench.harness import Run, load_cell

    cell = load_cell(args.workload)
    out_dir = HERE / "out" / args.workload
    out_dir.mkdir(parents=True, exist_ok=True)
    span_us = span_cost_us()
    run = Run(cell, args.seed % 2**63, trace=True, rehearse=args.rehearse,
              t_start=time.perf_counter(), out_dir=out_dir)
    run.setup()
    run.window(args.seconds)
    bench_steps = run.steps()
    rows = pt.rows(run)
    steps = rows.named("chaos.step")
    window = run.spans.between("window", *run.w)[0]

    def tokens_per_s(lo, hi):
        ss = [s for s in bench_steps if s["t0"] >= lo and s["t1"] <= hi]
        return (sum(s["rows"] for s in ss) * run.seq_len
                / (hi - lo - run._paused(lo, hi)))

    gaps = []
    for b in bench_steps:
        inner = [s for s in steps if s.t0 >= b["t0"] and s.t1 <= b["t1"]]
        if inner:
            gaps.append(abs(b["t1"] - b["t0"] - inner[0].duration_s)
                        / (b["t1"] - b["t0"]))
    step_s = sum(s.duration_s for s in steps) / len(steps)
    out = {
        "device": run.device(),
        "span_us": span_us,
        "span_share": SPANS_PER_STEP * span_us * 1e-6 / step_s,
        "mean_step_s": step_s,
        "tokens_per_s": {"profiled": tokens_per_s(window["t0"], window["t1"]),
                         "rest": tokens_per_s(window["t1"], run.w[1])},
        "step_vs_bench": max(gaps) if gaps else None,
        "metrics": run.per_layer(),
    }
    pd = pt.profile(run)
    if pd is not None and any(p.name.startswith("/device:TPU:")
                              for p in pd.planes):
        out.update(pt.device_breakdown(pd, min(d.id for d in run.pool),
                                       pt.program_scopes(run)))
    text = json.dumps(out, indent=1)
    (out_dir / "breakdown.json").write_text(text)
    print(text, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
