"""Runs one benchmark cell once and prints its result as the last line.

    python chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (its configuration, traffic and chips) is looked up by name in
`BENCHMARK.json`. With `--trace 0` the result holds the cell's end-to-end
metrics, with `--trace 1` its per-layer metrics from spans and a profiler
trace of the first units of the window. The run exits 2 without printing a
result when JAX finds no TPU or fewer chips than the cell asks for.
`--rehearse` (for tests) runs on JAX's CPU backend at the program's
`reduced()` size instead, with the cell's chips as virtual CPU devices.

JAX's persistent compilation cache is kept in `chipbench/.jax_cache/` of this
checkout whatever the environment says, so that only a cell's first run in a
checkout compiles.
"""
import argparse
import json
import os
import sys
import time
from pathlib import Path

T_START = time.perf_counter()
HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU backend at reduced() size (tests only)")
    return ap.parse_args(argv)


def setup_env(chips: int, rehearse: bool):
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(HERE / ".jax_cache")
    (HERE / ".jax_cache").mkdir(exist_ok=True)
    os.environ["TPU_LOG_DIR"] = str(HERE / "out" / "tpu_logs")
    (HERE / "out" / "tpu_logs").mkdir(parents=True, exist_ok=True)
    if rehearse:
        os.environ.pop("JAX_COMPILATION_CACHE_DIR")  # CPU programs: no cache
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                                   f" --xla_force_host_platform_device_count"
                                   f"={chips}")
    for p in (str(ROOT), str(ROOT / "src")):
        if p not in sys.path:
            sys.path.insert(0, p)


def main(argv=None) -> int:
    args = parse_args(argv)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    chips = next((w["chips"] for w in bench["workloads"]
                  if w["name"] == args.workload), None)
    if chips is None:
        print(f"run.py: no workload {args.workload!r} in BENCHMARK.json",
              file=sys.stderr)
        return 2
    setup_env(chips, args.rehearse)

    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    devices = jax.devices()
    if devices[0].platform != "tpu" and not args.rehearse:
        print(f"run.py: JAX found no TPU (platform "
              f"{devices[0].platform!r}); no result", file=sys.stderr)
        return 2
    if len(devices) < chips:
        print(f"run.py: {args.workload} needs {chips} chips, JAX found "
              f"{len(devices)}; no result", file=sys.stderr)
        return 2

    from chipbench.harness import execute

    result = execute(args.workload, args.seed % 2**63, args.seconds,
                     bool(args.trace), rehearse=args.rehearse,
                     t_start=T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
