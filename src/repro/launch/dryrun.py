"""Multi-pod dry-run.

For every (architecture × shape cell × mesh) combination this lowers and
compiles the real train_step / prefill / decode_step under production
shardings, prints memory_analysis() and cost_analysis(), parses the
post-SPMD HLO for collective wire bytes, and derives the three roofline
terms for the chip the production meshes are sized for
(``TARGET_DEVICE_KIND``, a key of ``DEVICE_PEAKS``). The roofline is a model
of that chip from the compiled program's counts, whatever devices compiled
it; it is never a measurement. Results accumulate in
benchmarks/results/dryrun*.json for the roofline report.

The production meshes need 256/512 devices; on a host without them the CLI
asks XLA's CPU backend for 512 virtual devices unless ``XLA_FLAGS`` is set.

    PYTHONPATH=src python -m repro.launch.dryrun --arch tinyllama-1.1b \
        --shape train_4k --mesh single
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
"""
import argparse
import json
import math
import os
import re
import time
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.configs import ASSIGNED, SHAPES, get_config
from repro.launch.hlo_analysis import analyze as hlo_analyze
from repro.launch.mesh import make_debug_mesh, make_production_mesh
from repro.models import build_model
from repro.models import sharding as SH
from repro.models.shardctx import activation_sharding

#: Per-chip peaks keyed by ``jax.Device.device_kind``: bf16 FLOP/s, HBM
#: bytes/s, and bytes/s of one ICI link. Source: Google Cloud documentation,
#: "TPU v5e" (197 TFLOP/s bf16, 819 GB/s HBM, 1,600 Gbit/s of chip-to-chip
#: interconnect over 4 links = 50 GB/s per link).
DEVICE_PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9, "link_bw": 50e9},
}
#: the chip the production meshes (16×16 per pod) are sized for
TARGET_DEVICE_KIND = "TPU v5 lite"


def device_peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; a kind not in the table is an error."""
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(DEVICE_PEAKS)}") from None

RESULTS_DIR = Path(__file__).resolve().parents[3] / "benchmarks" / "results"

_COLL_RE = re.compile(
    r"=\s*(?:\()?([a-z0-9]+)\[([0-9,]*)\][^ ]*\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)\b")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
                "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
                "f64": 8, "c64": 8, "u4": 1, "s4": 1}


def collective_wire_bytes(hlo_text: str) -> dict:
    """Per-device wire bytes by collective type (ring-algorithm estimates):
    all-gather/all-to-all: R·(n−1)/n; all-reduce: 2R·(n−1)/n;
    reduce-scatter: R·(n−1); collective-permute: R — R = result bytes."""
    per_type: dict = {}
    count = 0
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        dtype, shape_s, op = m.groups()
        if dtype not in _DTYPE_BYTES:
            continue
        elems = 1
        for d in shape_s.split(","):
            if d:
                elems *= int(d)
        rbytes = elems * _DTYPE_BYTES[dtype]
        n = _group_size(line)
        if op == "all-reduce":
            wire = 2.0 * rbytes * (n - 1) / max(n, 1)
        elif op == "reduce-scatter":
            wire = float(rbytes) * (n - 1)
        elif op == "collective-permute":
            wire = float(rbytes)
        else:  # all-gather / all-to-all
            wire = float(rbytes) * (n - 1) / max(n, 1)
        per_type[op] = per_type.get(op, 0.0) + wire
        count += 1
    per_type["n_ops"] = count
    return per_type


def _group_size(line: str) -> int:
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_LIST_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return 2


def _mem_analysis(compiled) -> dict:
    try:
        ma = compiled.memory_analysis()
        return {
            "argument_bytes": getattr(ma, "argument_size_in_bytes", None),
            "output_bytes": getattr(ma, "output_size_in_bytes", None),
            "temp_bytes": getattr(ma, "temp_size_in_bytes", None),
            "alias_bytes": getattr(ma, "alias_size_in_bytes", None),
            "peak_bytes": (getattr(ma, "argument_size_in_bytes", 0) or 0)
            + (getattr(ma, "output_size_in_bytes", 0) or 0)
            + (getattr(ma, "temp_size_in_bytes", 0) or 0)
            - (getattr(ma, "alias_size_in_bytes", 0) or 0),
        }
    except Exception as e:  # backend without memory analysis
        return {"error": str(e)}


def _cost_analysis(compiled) -> dict:
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        return {k: float(v) for k, v in ca.items()
                if isinstance(v, (int, float)) and (
                    "flops" in k or "bytes" in k or "utilization" not in k)}
    except Exception as e:
        return {"error": str(e)}


def build_cell_fn(model, cfg, cell, mesh, n_groups):
    """Returns (fn, in_specs_tree, in_shardings, out_shardings, donate, tp)."""
    ba = SH.batch_axes(mesh)
    tp = not SH.dp_only_mapping(cfg, cell, mesh)
    if cell.kind == "train":
        if not tp:
            n_groups = math.prod(mesh.devices.shape)
        state_shapes = model.train_state_specs()
        state_spec = SH.state_specs_tree(state_shapes, cfg, mesh, tp=tp)
        batch_shapes = model.input_specs(cell)
        batch_spec = SH.batch_spec_tree(batch_shapes, cfg, mesh, cell=cell, tp=tp)
        fn = model.make_train_step(n_groups=n_groups)
        in_shard = (SH.named(mesh, state_spec), SH.named(mesh, batch_spec))
        out_shard = (SH.named(mesh, state_spec), None)
        return fn, (state_shapes, batch_shapes), in_shard, out_shard, (0,), tp

    params_shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    param_spec = SH.param_specs_tree(params_shapes, cfg, mesh)
    batch_shapes = model.input_specs(cell)
    batch_spec = SH.batch_spec_tree(batch_shapes, cfg, mesh, cell=cell)

    if cell.kind == "prefill":
        def fn(params, batch):
            return model.prefill(params, batch)

        cache_shapes = jax.eval_shape(
            lambda p, b: model.prefill(p, b), params_shapes, batch_shapes)[1]
        cache_spec = SH.batch_spec_tree({"cache": cache_shapes}, cfg, mesh,
                                        cell=cell)["cache"]
        lspec = SH.logits_spec(cfg, mesh, cell.global_batch)
        in_shard = (SH.named(mesh, param_spec), SH.named(mesh, batch_spec))
        out_shard = (SH.named(mesh, lspec), SH.named(mesh, cache_spec))
        return fn, (params_shapes, batch_shapes), in_shard, out_shard, (), True

    # decode
    def fn(params, batch):
        return model.decode_step(params, batch)

    cache_shapes = batch_shapes["cache"]
    cache_spec = SH.batch_spec_tree({"cache": cache_shapes}, cfg, mesh,
                                    cell=cell)["cache"]
    lspec = SH.logits_spec(cfg, mesh, cell.global_batch)
    in_shard = (SH.named(mesh, param_spec), SH.named(mesh, batch_spec))
    out_shard = (SH.named(mesh, lspec), SH.named(mesh, cache_spec))
    return fn, (params_shapes, batch_shapes), in_shard, out_shard, (1,), True


def run_cell(arch: str, shape: str, mesh_kind: str, *, debug=False,
             skip_hlo=False) -> dict:
    cfg = get_config(arch)
    cell = SHAPES[shape]
    rec = {"arch": arch, "shape": shape, "mesh": mesh_kind}
    ok, reason = cfg.supports_cell(cell)
    if not ok:
        rec.update(skipped=True, reason=reason)
        return rec

    multi = mesh_kind == "multi"
    mesh = (make_debug_mesh(multi_pod=multi) if debug
            else make_production_mesh(multi_pod=multi))
    n_dev = math.prod(mesh.devices.shape)
    rec["n_devices"] = n_dev
    data_shards = mesh.shape.get("data", 1) * mesh.shape.get("pod", 1)

    model = build_model(cfg)
    fn, shapes, in_shard, out_shard, donate, tp = build_cell_fn(
        model, cfg, cell, mesh, n_groups=data_shards)
    rec["mapping"] = "tp" if tp else "dp-only"

    t0 = time.time()
    with mesh, activation_sharding(mesh, tp=tp):
        jitted = jax.jit(fn, in_shardings=in_shard, out_shardings=out_shard,
                         donate_argnums=donate)
        lowered = jitted.lower(*shapes)
        rec["lower_s"] = round(time.time() - t0, 2)
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 2)

    mem = _mem_analysis(compiled)
    cost = _cost_analysis(compiled)
    rec["memory_analysis"] = mem
    rec["cost_analysis"] = {k: v for k, v in cost.items()
                            if k in ("flops", "bytes accessed", "transcendentals",
                                     "error")}
    print(f"  memory_analysis: {mem}")
    print(f"  cost_analysis: flops={cost.get('flops')} "
          f"bytes={cost.get('bytes accessed')}")

    if not skip_hlo:
        hlo = compiled.as_text()
        t = hlo_analyze(hlo)
        rec["hlo_analysis"] = {
            "flops_per_device": t.flops,
            "bytes_per_device": t.bytes_accessed,
            "collective_wire_per_device": t.collective_wire,
            "collective_msgs": t.collective_msgs,
            "n_while": t.n_while,
            "unknown_trip_counts": t.unknown_trip,
        }
        rec["hlo_bytes"] = len(hlo)
    rec.update(_roofline(rec, cfg, cell, n_dev, TARGET_DEVICE_KIND))
    return rec


def _roofline(rec, cfg, cell, n_dev, device_kind) -> dict:
    peaks = device_peaks(device_kind)
    # Loop-aware HLO analysis (preferred); raw cost_analysis kept for
    # reference (it counts scan bodies once — see hlo_analysis.py).
    ha = rec.get("hlo_analysis")
    if ha:
        flops_dev = ha["flops_per_device"]
        bytes_dev = ha["bytes_per_device"]
        wire_dev = sum(ha["collective_wire_per_device"].values())
    else:
        cost = rec.get("cost_analysis", {})
        flops_dev = cost.get("flops") or 0.0
        bytes_dev = cost.get("bytes accessed") or 0.0
        wire_dev = 0.0
    compute_s = flops_dev / peaks["flops"]
    memory_s = bytes_dev / peaks["hbm_bw"]
    collective_s = wire_dev / peaks["link_bw"]
    terms = {"compute": compute_s, "memory": memory_s, "collective": collective_s}
    dominant = max(terms, key=terms.get)
    train = cell.kind == "train"
    tokens = cell.global_batch * (cell.seq_len if cell.kind != "decode" else 1)
    model_flops = cfg.model_flops_per_token(train=train) * tokens
    hlo_global = flops_dev * n_dev
    return {"roofline": {
        "device_kind": device_kind,
        "compute_s": compute_s,
        "memory_s": memory_s,
        "collective_s": collective_s,
        "dominant": dominant,
        "model_flops": model_flops,
        "hlo_flops_global": hlo_global,
        "useful_flops_ratio": (model_flops / hlo_global) if hlo_global else None,
        "step_time_lower_bound_s": max(terms.values()),
    }}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--debug-mesh", action="store_true",
                    help="tiny mesh (needs only 8 devices) for smoke tests")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=512")

    archs = list(ASSIGNED) if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    RESULTS_DIR.mkdir(parents=True, exist_ok=True)
    out_path = Path(args.out) if args.out else RESULTS_DIR / (
        "dryrun_debug.json" if args.debug_mesh else "dryrun.json")
    results = {}
    if out_path.exists():
        results = json.loads(out_path.read_text())

    failures = 0
    for arch in archs:
        for shape in shapes:
            for mk in meshes:
                key = f"{arch}|{shape}|{mk}"
                print(f"[dryrun] {key}")
                try:
                    rec = run_cell(arch, shape, mk, debug=args.debug_mesh)
                except Exception as e:
                    rec = {"arch": arch, "shape": shape, "mesh": mk,
                           "error": f"{type(e).__name__}: {e}"}
                    failures += 1
                    print(f"  FAILED: {rec['error']}")
                if rec.get("skipped"):
                    print(f"  skipped: {rec['reason']}")
                elif "roofline" in rec:
                    r = rec["roofline"]
                    print(f"  roofline: compute {r['compute_s']:.4f}s | "
                          f"memory {r['memory_s']:.4f}s | collective "
                          f"{r['collective_s']:.4f}s -> {r['dominant']}-bound")
                results[key] = rec
                out_path.write_text(json.dumps(results, indent=1))
    print(f"[dryrun] wrote {out_path} ({len(results)} cells, {failures} failures)")
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
