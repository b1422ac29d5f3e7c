"""Production training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b \
        --shape train_4k [--steps 20] [--devices 8] [--elastic] [--ckpt DIR]

Modes:
  * default: run real training steps on the available host devices with the
    production sharding rules scaled to a debug mesh (the same code path the
    dry-run lowers at 256/512 chips), synthetic data, async checkpointing.
  * --elastic: wrap the loop in the ElasticTrainer and exercise one Poisson
    join + one leave mid-run (the paper's §VI-B/E scenario).
  * --lower-only: lower+compile for the full production mesh and print the
    memory/cost analysis (alias of the dryrun path for one cell).

Scale knobs live in the config (`repro/configs/<arch>.py`); per-run reduction
uses the same `reduced()` family transform the smoke tests use, so the
launcher runs anywhere while staying architecturally faithful. On a CPU
host, ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` gives it eight
devices to scale over.
"""
import argparse
import dataclasses
import sys
import time

import jax
import numpy as np

from repro.configs import SHAPES, get_config
from repro.configs.base import ShapeCell
from repro.data.synthetic import TokenStream, make_train_batch
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="train_4k", choices=list(SHAPES))
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--reduced", action="store_true", default=True,
                    help="reduced config (full configs are dry-run only on CPU)")
    ap.add_argument("--full-config", dest="reduced", action="store_false")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--elastic", action="store_true")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--lower-only", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()

    if args.lower_only:
        from repro.launch import dryrun

        rec = dryrun.run_cell(args.arch, args.shape, "single")
        print({k: v for k, v in rec.items() if k != "hlo_analysis"})
        return 0

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = dataclasses.replace(cfg.reduced(), learning_rate=args.lr)
        cell = ShapeCell("launch", args.seq, args.batch, "train")
    else:
        cell = SHAPES[args.shape]
    model = build_model(cfg)

    ckpt = None
    if args.ckpt:
        from repro.checkpoint import AsyncCheckpointer

        ckpt = AsyncCheckpointer(args.ckpt, keep=3)

    if args.elastic:
        from repro.elastic import ElasticTrainer

        n_dev = len(jax.devices())
        trainer = ElasticTrainer(model, initial=min(n_dev, max(2, n_dev // 2)),
                                 per_device_batch=max(1, cell.global_batch // 8))
        trainer.init()
        stream = TokenStream(vocab=cfg.vocab, seq_len=cell.seq_len, seed=0)
        join_at, leave_at = args.steps // 3, 2 * args.steps // 3
        for i in range(args.steps):
            if i == join_at and len(trainer.active) < len(trainer.pool):
                ev = trainer.scale_out()
                print(f"[elastic] scale-out -> {len(trainer.active)} devices "
                      f"({ev.wall_s*1e3:.0f} ms)")
            if i == leave_at and len(trainer.active) > 1:
                ev = trainer.scale_in()
                print(f"[elastic] scale-in -> {len(trainer.active)} devices")
            toks = stream.batch(range(i * trainer.global_batch,
                                      (i + 1) * trainer.global_batch))
            m = trainer.step({"tokens": toks})
            if i % 5 == 0 or i == args.steps - 1:
                print(f"step {i:4d} loss {m['loss']:.4f}")
            if ckpt and i % args.ckpt_every == 0:
                ckpt.save(i, trainer.state)
        if ckpt:
            ckpt.close()
        return 0

    state = model.init_train_state(jax.random.PRNGKey(0))
    step = jax.jit(model.make_train_step())
    losses = []
    for i in range(args.steps):
        batch = make_train_batch(cfg, cell, seed=i)
        t0 = time.perf_counter()
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
        if i % 5 == 0 or i == args.steps - 1:
            print(f"step {i:4d} loss {losses[-1]:.4f} "
                  f"({(time.perf_counter()-t0)*1e3:.0f} ms)")
        if ckpt and i % args.ckpt_every == 0:
            ckpt.save(i, state)
    if ckpt:
        ckpt.close()
    ok = np.isfinite(losses).all()
    print("TRAIN_OK" if ok else "TRAIN_FAILED")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
