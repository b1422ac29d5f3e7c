"""Readings that the limits of `chipbench/limits/<cell>.json` are set from.

    python chipbench/calibrate.py --workload <name> --program-seeds 1,2,... \\
        --control-seeds 7,8,9 [--rehearse] [--out FILE]

For each program seed: the cell's set-up and correctness pass (the program's
first steps through the window's own calls), then the plain reference over
the same rows, and the three numbers of `check.py` (the sound runs' readings,
whose largest is a limit's lower reading). For each control seed, with the
reference alone in the program's place: the control (matmul operands in
float8 e4m3, one step below the configuration's bfloat16 compute), the
reference with bfloat16 parameters (one step below their float32), and the
faults that leave part of the batch out (half of each batch; on several
chips, every step on one data-parallel replica's rows: the exchange between
chips left out), and an update dropped (the first layer of the family's
`UPDATE_LEAF` left at its old value). A step that returns its state
unchanged reads 1 on `grad_gap` and `change_gap` without a run. Control
seeds need one chip.
Writes one JSON object per reading to `--out` and prints them.
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


def check_layouts(traffic: dict, steps: int) -> list:
    """(n, tp) of the correctness pass's first `steps` steps: a join adds a
    chip, a leave or failure drops one, a membership change resets tp to 1
    and a pinned `new_shape` that fits the new count sets it."""
    n, tp, out = traffic["start_devices"], 1, []
    for ev, before in zip(traffic["events"], traffic.get("check_steps", [])):
        out += [(n, tp)] * before
        n += {"join": 1, "leave": -1, "node-failure": -1}[ev["kind"]]
        shape = ev.get("new_shape")
        tp = shape[1] if shape and shape[0] * shape[1] == n else 1
    out += [(n, tp)] * steps
    return out[:steps]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--program-seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    seeds = lambda s: [int(x) for x in s.split(",") if x]  # noqa: E731
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    chips = next(w["chips"] for w in bench["workloads"]
                 if w["name"] == args.workload)
    if not seeds(args.program_seeds):
        chips = 1
    setup_env(chips, args.rehearse)

    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from chipbench import check, feed
    from chipbench.harness import Run, load_cell, program_model

    cell = load_cell(args.workload)
    out = open(args.out, "w") if args.out else None

    def emit(rec):
        line = json.dumps(rec)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    dev = jax.devices()[0]
    emit({"device": dev.device_kind, "platform": dev.platform,
          "count": len(jax.devices())})
    run = Run(cell, 0, trace=False, rehearse=args.rehearse,
              t_start=time.perf_counter(), out_dir=HERE / "out")
    layouts = check_layouts(cell["traffic_spec"], check.CHECK_STEPS)
    if seeds(args.program_seeds):
        run.build()
        for seed in seeds(args.program_seeds):
            t0 = time.perf_counter()
            run.spans.rows.clear()
            run.seed_state(seed)
            run.check_pass()
            got = [(s["n"], s["tp"]) for s in
                   sorted(run.spans.rows, key=lambda r: r["t0"])
                   if s["name"] == "step"][:check.CHECK_STEPS]
            if got != layouts:
                raise RuntimeError(f"check pass ran {got}, expected {layouts}")
            prog = run.program_readout
            run.trainer.state = None
            reference = run.reference_readout()
            emit({"seed": seed, "variant": "program",
                  "seconds": time.perf_counter() - t0,
                  **check.readings(prog, reference, detail=True)})
        run.free_program()
    else:
        run.model, run.cfg, run.a = program_model(cell["cfg"], args.rehearse)
        t = run.cfg["train"]
        run.seq_len, run.pdb = t["seq_len"], t["per_device_batch"]
        run.pool = jax.devices()[:1]
    variants = {
        "control_fp8": {"quant": "fp8"},
        "control_bf16_params": {"param_dtype": jax.numpy.bfloat16},
        "fault_half_batch": {"rows_kept": [0.5] * check.CHECK_STEPS},
        "fault_dropped_update": {"drop_update": True},
    }
    if cell["chips"] > 1:
        variants["fault_no_exchange"] = {
            "rows_kept": [tp / n for n, tp in layouts]}
    for seed in seeds(args.control_seeds):
        run.seed = seed
        f = feed.Feed(run.rows(seed))
        run.check_batches = [f.next(run.pdb * n)["tokens"]
                             for n, _ in layouts]
        t0 = time.perf_counter()
        reference = run.reference_readout()
        emit({"seed": seed, "variant": "reference",
              "seconds": time.perf_counter() - t0,
              "losses": reference["losses"]})
        for name, kw in variants.items():
            t0 = time.perf_counter()
            got = run.reference_readout(**kw)
            emit({"seed": seed, "variant": name,
                  "seconds": time.perf_counter() - t0,
                  **check.readings(got, reference, detail=True)})
    if out:
        out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
