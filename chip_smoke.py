"""Smoke test of the elastic trainer on a TPU: GPT-2 S at full width.

    python chip_smoke.py                  # one chip
    python chip_smoke.py --chips 4        # four chips: scale-out, reshard, scale-in
    python chip_smoke.py --rehearse [--chips 4]   # same phases, CPU, reduced()

One chip (default): ``ElasticTrainer`` on GPT-2 S (12 layers, d_model 768,
vocab 50257, sequence 1024, batch 8) replays a seeded churn trace through
``TrainerBackend``: about 20 steps, three ``checkpoint`` events into a
``MemoryReplicaStore`` and an ``AsyncCheckpointer``, and one ``join`` that the
one-chip pool cannot hold. It checks the first loss against the same step's
loss on the host CPU, that the loss falls, that both recovery tiers restore
the last pushed state bit for bit, and that the compiled Pallas shard codec
matches the jnp reference bit for bit on the whole training state.

Four chips (``--chips 4``): the trainer starts on 2 chips and replays a join
under the int8 codec, a join to 4 chips that reshards to (dp, tp) = (2, 2),
a node failure and a checkpoint. Every scale-out, scale-in and reshard must
leave the state bit-identical, every layout's step must run attention's
core on the path the backend selects (the Pallas kernel, per shard, on a
TPU), and the first step on the 4-chip mesh must give the loss of the same
global batch stepped on one chip (on XLA's attention).

Every check prints one ``CHECK`` line. The last line of standard output is
``{"ok": true, "device": {...}}`` when every check passed; a failed check or
an exception exits non-zero without it. Without a TPU the script exits 2
before any phase, unless ``--rehearse`` asks for the CPU rehearsal.
Checkpoints go to ``.chip_smoke/`` next to this file; the compile cache to
``$JAX_COMPILATION_CACHE_DIR`` or ``.jax_cache/``.
"""
import argparse
import json
import os
import shutil
import statistics
import sys
import time
from collections import Counter
from pathlib import Path

ROOT = Path(__file__).resolve().parent
OUT = ROOT / ".chip_smoke"

#: first-step loss on the chip vs the host CPU, and 4 chips vs 1 chip.
LOSS_RTOL = 1e-2


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: train/checkpoint/restore/codec; "
                         "4: scale-out, reshard and scale-in across chips")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="run the phases on the CPU at reduced() size")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        os.environ.setdefault(
            "XLA_FLAGS", f"--xla_force_host_platform_device_count={args.chips}")
    OUT.mkdir(exist_ok=True)
    os.environ.setdefault("TPU_LOG_DIR", str(OUT / "tpu_logs"))
    sys.path.insert(0, str(ROOT / "src"))

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not args.rehearse:
        print(f"chip_smoke: JAX found no TPU (platform {platform!r}); "
              "use --rehearse for the CPU rehearsal", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 2

    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    cache = _CacheEvents()
    jax.monitoring.register_event_listener(cache)
    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    print(f"device_kind: {device['kind']}  platform: {platform}  "
          f"device count: {device['count']}")
    print(f"compile cache: {cache_dir}")

    checks = _Checks()
    t0 = time.perf_counter()
    if args.chips == 1:
        phase_one_chip(args, checks)
    else:
        phase_four_chips(args, checks)
    print(f"phase wall seconds (host clock, {device['kind']}): "
          f"{time.perf_counter() - t0:.1f}")
    print(f"compile cache events: {cache.hits} hits, {cache.misses} misses")
    if checks.failed:
        print(f"chip_smoke: {len(checks.failed)} check(s) failed: "
              f"{', '.join(checks.failed)}", file=sys.stderr)
        return 1
    result = {"ok": True, "device": device}
    if args.rehearse:
        result["rehearse"] = True
    print(json.dumps(result))
    return 0


# ---------------------------------------------------------------------------
# Shared pieces.
# ---------------------------------------------------------------------------


class _Checks:
    def __init__(self):
        self.failed = []

    def __call__(self, name: str, ok: bool, detail: str = ""):
        print(f"CHECK {name}: {'PASS' if ok else 'FAIL'}  {detail}".rstrip())
        if not ok:
            self.failed.append(name)
        return ok


class _CacheEvents:
    """Counts persistent compilation cache hits and misses."""

    def __init__(self):
        self.hits = 0
        self.misses = 0

    def __call__(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def _model(args):
    from repro.configs import get_config
    from repro.models import build_model

    cfg = get_config("gpt2")
    seq = 1024
    if args.rehearse:
        cfg, seq = cfg.reduced(), 64
    return cfg, build_model(cfg), seq


def _host(tree):
    import jax
    import numpy as np

    return jax.tree.map(np.asarray, tree)


def _bit_equal(a, b) -> bool:
    """Same structure, shapes, dtypes and bytes (NaN-safe, -0.0-aware)."""
    import jax
    import numpy as np

    la, ta = jax.tree.flatten(a)
    lb, tb = jax.tree.flatten(b)
    if ta != tb:
        return False
    for x, y in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        if x.dtype != y.dtype or x.shape != y.shape:
            return False
        if not np.array_equal(x.reshape(-1).view(np.uint8),
                              y.reshape(-1).view(np.uint8)):
            return False
    return True


def _rel(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-12)


def device_label(d) -> str:
    return f"{d.platform}:{d.device_kind}"


def _placement(state, active):
    """Per-leaf placement: (path, shape, device_set size, shard shape)."""
    import jax

    rows = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(state)[0]:
        sh = leaf.sharding
        rows.append((jax.tree_util.keystr(path), tuple(leaf.shape),
                     len(sh.device_set), tuple(sh.shard_shape(leaf.shape)),
                     sh.device_set == set(active)))
    return rows


def _smoke_trainer_class():
    from repro.elastic import ElasticTrainer

    class SmokeTrainer(ElasticTrainer):
        """ElasticTrainer that records what the smoke test checks: each
        step's loss, the state each ``checkpoint()`` pushed, and whether
        each layout change kept the state bit for bit."""

        def __init__(self, *a, capture_n=None, **kw):
            super().__init__(*a, **kw)
            self.losses = []  # (n, tp, loss)
            self.moves = []  # one dict per scale-out / scale-in / reshard
            self.pushed = None  # (step, host tree) of the last checkpoint()
            self.capture_n = capture_n
            self.captured = None  # (host state, batch, loss) at capture_n

        def step(self, batch):
            n = len(self.active)
            pre = None
            if n == self.capture_n and self.captured is None:
                pre = _host(self.state)
            m = super().step(batch)
            self.losses.append((n, self.tp, m["loss"]))
            if pre is not None:
                self.captured = (pre, batch, m["loss"])
            return m

        def _checked(self, op, fn, *a, **kw):
            before = _host(self.state)
            n0 = len(self.active)
            ev = fn(*a, **kw)
            self.moves.append({
                "op": op, "n": (n0, len(self.active)), "tp": self.tp,
                "identical": _bit_equal(before, _host(self.state)),
                "wall_s": ev.wall_s,
                "placement": _placement(self.state, self.active),
                "codec": (ev.plan_summary or {}).get("codec"),
            })
            return ev

        def scale_out(self, device=None, codec=None):
            return self._checked("scale-out", super().scale_out, device,
                                 codec=codec)

        def scale_in(self, device=None, failure=False):
            return self._checked("node-failure" if failure else "scale-in",
                                 super().scale_in, device, failure=failure)

        def apply_reshard(self, tp, microbatch=1):
            return self._checked("reshard", super().apply_reshard, tp,
                                 microbatch=microbatch)

        def checkpoint(self, step=None):
            host = _host(self.state)
            info = super().checkpoint(step)
            self.pushed = (info["step"], host)
            return info

    return SmokeTrainer


def _trace(seed: int, specs):
    """Seeded scenario times for a fixed sequence of event specs."""
    import numpy as np

    from repro.core.engine import ChurnEvent

    ts = np.sort(np.random.default_rng(seed).uniform(0.0, 60.0, len(specs)))
    return [ChurnEvent(t=round(float(t), 3), **spec)
            for t, spec in zip(ts, specs)]


def _print_model(cfg, params, seq, batch):
    import jax

    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"model: {cfg.name}  n_layers={cfg.n_layers}  d_model={cfg.d_model}"
          f"  n_heads={cfg.n_heads}  vocab={cfg.vocab}  seq={seq}  "
          f"global batch={batch}  params={n_params}")


def _print_steps(tr, seq, kind):
    for sp in tr.tracer.named("chaos.compile"):
        print(f"compile seconds (n, tp)=({sp.attrs['n']}, {sp.attrs['tp']}): "
              f"{sp.duration_s:.2f}; attention {sp.attrs['attention']}")
    for n, times in sorted(tr.metrics_snapshot()["step_times"].items()):
        steady = times[1:] or times
        med = statistics.median(steady)
        tokens = tr.per_device_batch * n * seq
        print(f"step seconds n={n}: median {med:.4f} over {len(steady)} "
              f"steady steps (first {times[0]:.4f}); tokens/s "
              f"{tokens / med:.0f}  [{kind}, host clock around "
              "block_until_ready]")


def _print_memory(devices):
    for d in devices:
        stats = d.memory_stats() or {}
        print(f"peak_bytes_in_use {d}: {stats.get('peak_bytes_in_use')}")


# ---------------------------------------------------------------------------
# Phase 1: one chip.
# ---------------------------------------------------------------------------


def phase_one_chip(args, checks):
    import jax
    import numpy as np

    from repro.checkpoint import AsyncCheckpointer, MemoryReplicaStore
    from repro.core.replication import (
        decode_state,
        encode_state,
        roundtrip_max_error_ok,
    )
    from repro.data.synthetic import TokenStream
    from repro.kernels import ops as kernel_ops

    cfg, model, seq = _model(args)
    per_device_batch = 2 if args.rehearse else 8
    chip = jax.devices()[0]
    tr = _smoke_trainer_class()(model, devices=[chip], initial=1,
                                per_device_batch=per_device_batch,
                                codec="int8", seed=args.seed)
    tr.init()
    params0 = _host(tr.state["params"])
    _print_model(cfg, params0, seq, tr.global_batch)
    batch = {"tokens": TokenStream(vocab=cfg.vocab, seq_len=seq,
                                   seed=args.seed).batch(
        range(tr.global_batch))}

    ckpt_dir = OUT / "ckpt"
    shutil.rmtree(ckpt_dir, ignore_errors=True)
    ckpt = AsyncCheckpointer(ckpt_dir, keep=2)
    tr.attach_recovery(replica_store=MemoryReplicaStore(), checkpointer=ckpt)
    try:
        events = _trace(args.seed, [
            {"kind": "checkpoint"},
            {"kind": "join", "node": chip.id + 1},
            {"kind": "checkpoint"},
            {"kind": "checkpoint"},
        ])
        ledger = tr.replay_scenario(events, batch_fn=lambda: batch,
                                    steps_between=5)
        actions = ledger.actions()
        print(f"ledger: {actions}")
        checks("trace", actions == ["ckpt-saved", "skipped-pool-exhausted",
                                    "ckpt-saved", "ckpt-saved"],
               "3 checkpoints saved, the join skipped-pool-exhausted")

        _print_steps(tr, seq, device_label(chip))
        _print_memory([chip])
        losses = [l for _, _, l in tr.losses]
        print(f"losses: {[round(l, 4) for l in losses]}")

        # The first step's loss, recomputed on the host CPU.
        cpu = jax.devices("cpu")[0]
        loss_fn = jax.jit(lambda p, b: model.loss_fn(p, b)[1]["loss"])
        t0 = time.perf_counter()
        cpu_loss = float(loss_fn(jax.device_put(params0, cpu),
                                 jax.device_put(batch, cpu)))
        print(f"cpu reference: loss {cpu_loss:.6f} "
              f"({time.perf_counter() - t0:.1f} s on the host CPU)")
        checks("first-loss-vs-cpu", _rel(losses[0], cpu_loss) <= LOSS_RTOL,
               f"{device_label(chip)} {losses[0]:.6f} vs cpu {cpu_loss:.6f}, "
               f"rel {_rel(losses[0], cpu_loss):.2e} <= {LOSS_RTOL}")
        checks("loss-falls", bool(np.isfinite(losses).all())
               and losses[-1] < losses[0],
               f"{losses[0]:.4f} -> {losses[-1]:.4f} over {len(losses)} "
               "steps on one fixed batch")

        pushed_step, pushed = tr.pushed
        for tier in ("replica", "checkpoint"):
            t0 = time.perf_counter()
            step = tr.restore_from(tier)
            got = _host(tr.state)
            print(f"restore {tier}: step {step}, "
                  f"{time.perf_counter() - t0:.2f} s")
            checks(f"restore-{tier}", step == pushed_step
                   and _bit_equal(got, pushed),
                   f"bit-identical to the step-{pushed_step} push")
    finally:
        ckpt.close()
    shutil.rmtree(ckpt_dir, ignore_errors=True)

    # The codec calls scale_out makes, on the whole training state.
    t0 = time.perf_counter()
    enc, manifest, wire = encode_state(tr.state, "int8", verify_kernel=True)
    decoded = decode_state(enc, manifest, verify_kernel=True)
    bound_ok = roundtrip_max_error_ok(tr.state, decoded, enc)
    n_int8 = sum(e.kind == "int8" for e in enc)
    print(f"codec: {n_int8} int8 leaves, {len(enc) - n_int8} raw; payload "
          f"{manifest.total_bytes} B, wire {wire} B; "
          f"{time.perf_counter() - t0:.1f} s")
    checks("codec-kernel-matches-reference", True,
           "encode_state/decode_state(verify_kernel=True) raise on any "
           "kernel/reference difference in codes, scales or decoded values")
    checks("codec-scale/2-bound", bound_ok)
    big = max(e.codes.shape[0] for e in enc if e.kind == "int8")
    hlo = kernel_ops.shard_encode.lower(
        jax.ShapeDtypeStruct((big, 256), np.float32)).compile().as_text()
    compiled = "tpu_custom_call" in hlo
    if args.rehearse:
        print(f"codec kernel compiled to Mosaic: {compiled} (interpret mode "
              "on the CPU)")
    else:
        checks("codec-kernel-compiled", compiled,
               f"Mosaic custom call in the encode program for nb={big}")


# ---------------------------------------------------------------------------
# Phase 2: four chips.
# ---------------------------------------------------------------------------


def phase_four_chips(args, checks):
    import jax
    import numpy as np

    from repro.checkpoint import MemoryReplicaStore
    from repro.data.synthetic import TokenStream
    from repro.elastic.trainer import kernels_selected

    cfg, model, seq = _model(args)
    pool = jax.devices()[:4]
    per_device_batch = 2
    tr = _smoke_trainer_class()(model, devices=pool, initial=2,
                                per_device_batch=per_device_batch,
                                seed=args.seed, capture_n=4)
    tr.init()
    _print_model(cfg, tr.state["params"], seq, "per device "
                 f"{per_device_batch}")
    tokens = TokenStream(vocab=cfg.vocab, seq_len=seq, seed=args.seed).batch(
        range(len(pool) * per_device_batch))
    tr.attach_recovery(replica_store=MemoryReplicaStore())

    events = _trace(args.seed, [
        {"kind": "join", "node": pool[2].id, "codec": "int8"},
        {"kind": "join", "node": pool[3].id, "reshard": "always",
         "new_shape": (2, 2)},
        {"kind": "node-failure", "node": pool[1].id},
        {"kind": "checkpoint"},
    ])
    ledger = tr.replay_scenario(
        events, batch_fn=lambda: {"tokens": tokens[:tr.global_batch]},
        steps_between=2, min_active=2)
    actions = ledger.actions()
    print(f"ledger: {actions}")
    checks("trace", actions.count("scale-out") == 2
           and "node-failed" in actions and "ckpt-saved" in actions
           and any(r.action == "reshard-ready"
                   and list(r.detail["new_shape"]) == [2, 2] for r in ledger),
           "two scale-outs, a reshard to (2, 2), a node failure, a "
           "checkpoint")

    ops = [m["op"] for m in tr.moves]
    print(f"layout changes: {ops}")
    checks("moves-present", "scale-out" in ops and "node-failure" in ops
           and any(m["op"] == "reshard" and m["tp"] == 2 for m in tr.moves))
    for i, m in enumerate(tr.moves):
        sizes = Counter(p[2] for p in m["placement"])
        sharded = sum(p[1] != p[3] for p in m["placement"])
        print(f"{m['op']} n {m['n'][0]}->{m['n'][1]} tp={m['tp']}: "
              f"{m['wall_s']:.2f} s; device_set sizes {dict(sizes)}; "
              f"{sharded}/{len(m['placement'])} leaves split")
        if m["codec"]:
            print(f"  codec: {m['codec']}")
        checks(f"move{i}-{m['op']}-bit-identical", m["identical"])
        checks(f"move{i}-{m['op']}-placed-on-active",
               all(p[4] for p in m["placement"]),
               f"every leaf on all {m['n'][1]} active devices")
        if m["op"] == "reshard" and m["tp"] == 2:
            for path, shape, size, shard, _ in m["placement"]:
                print(f"  leaf {path} {shape}: device_set {size}, "
                      f"shard {shard}")
            checks("reshard-splits-leaves", sharded > 0)
    codec_moves = [m for m in tr.moves if m["codec"]]
    checks("int8-scale-out", len(codec_moves) == 1
           and codec_moves[0]["codec"]["codec"] == "int8")

    _print_steps(tr, seq, device_label(pool[0]))
    want = "pallas" if kernels_selected() else "xla"
    checks("attention-path", all(sp.attrs["attention"] == want for sp in
                                 tr.tracer.named("chaos.compile")),
           f"every layout's step runs attention on {want}")
    print(f"losses (n, tp, loss): "
          f"{[(n, tp, round(l, 4)) for n, tp, l in tr.losses]}")
    checks("losses-finite", bool(np.isfinite([l for *_, l in tr.losses])
                                 .all()))

    # The first step on the 4-chip mesh, replayed on one chip.
    pre, batch, loss4 = tr.captured
    one = pool[0]
    step1 = jax.jit(model.make_train_step())
    state1, batch1 = jax.device_put(pre, one), jax.device_put(batch, one)
    t0 = time.perf_counter()
    step1.lower(state1, batch1).compile()
    print(f"compile seconds one-chip reference: "
          f"{time.perf_counter() - t0:.2f}")
    loss1 = float(step1(state1, batch1)[1]["loss"])
    checks("4-chip-loss-vs-1-chip", _rel(loss4, loss1) <= LOSS_RTOL,
           f"4 chips (2, 2) {loss4:.6f} vs 1 chip {loss1:.6f}, "
           f"rel {_rel(loss4, loss1):.2e} <= {LOSS_RTOL}")

    pushed_step, pushed = tr.pushed
    step = tr.restore_from("replica")
    checks("restore-replica", step == pushed_step
           and _bit_equal(_host(tr.state), pushed),
           f"onto {len(tr.active)} chips, bit-identical to the "
           f"step-{pushed_step} push")
    _print_memory(pool)


if __name__ == "__main__":
    sys.exit(main())
