"""Elastic training demo: stop-free scale-out / scale-in on live JAX arrays.

Mirrors the paper's experiment (§VI-B/E): start training on 4 devices, nodes
join one by one (Poisson-style, as at the edge), then one leaves — all
without restarts or checkpoints. Each membership change reshards the data
pipeline (nodes bring/take their data split) and reports the Chaos
replication plan used to ship the training state. On a CPU host it asks for
8 virtual devices; with fewer devices (one chip) it starts on what there is
and skips the joins and the leave that the pool cannot hold.

    PYTHONPATH=src python examples/elastic_training.py
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import sys

import jax
import numpy as np

from repro.configs import get_config
from repro.core.sharding_alg import NeighborLink
from repro.data.synthetic import ShardedLoader, TokenStream
from repro.elastic import ElasticTrainer
from repro.models import build_model

SEQ = 64
PER_DEV_BATCH = 2


def main():
    cfg = get_config("gpt2").reduced()
    model = build_model(cfg)
    stream = TokenStream(vocab=cfg.vocab, seq_len=SEQ, seed=0)
    loader = ShardedLoader(stream, n_examples=512, node_ids=[0],
                           batch_per_node=PER_DEV_BATCH)

    # Heterogeneous synthetic links: even devices fast, odd devices slower —
    # the shard scheduler derates the slow ones.
    def link_model(device_id: int) -> NeighborLink:
        fast = device_id % 2 == 0
        return NeighborLink(prop_s=0.002 if fast else 0.01,
                            trans_s_per_byte=1 / (500e6 / 8) if fast else 1 / (120e6 / 8),
                            sync_s=0.0)

    initial = min(4, len(jax.devices()))
    trainer = ElasticTrainer(model, initial=initial,
                             per_device_batch=PER_DEV_BATCH,
                             link_model=link_model,
                             on_reshard=lambda ids: loader.reshard(ids))
    trainer.init()
    print(f"devices: {len(jax.devices())}; starting on {initial}")

    def run_steps(n):
        for _ in range(n):
            toks = np.concatenate([loader.next_batch(i)
                                   for i in trainer.device_ids()])
            m = trainer.step({"tokens": toks})
        return m

    m = run_steps(10)
    print(f"[4 devices] step {trainer.step_count}: loss {m['loss']:.4f}")

    for join in range(2):  # two nodes join, one by one (paper: Poisson joins)
        if len(trainer.active) == len(trainer.pool):
            break
        ev = trainer.scale_out()
        ps = ev.plan_summary
        print(f"scale-out -> {len(trainer.active)} devices in {ev.wall_s*1e3:.1f} ms "
              f"(plan: {ev.plan_summary['n_shards']} shards of "
              f"{ps['shard_size']} B from {len(ps['bytes_per_source'])} neighbors, "
              f"predicted completion {ps['predicted_completion_s']*1e3:.1f} ms)")
        m = run_steps(8)
        print(f"[{len(trainer.active)} devices] step {trainer.step_count}: "
              f"loss {m['loss']:.4f}")

    if len(trainer.active) > 1:
        ev = trainer.scale_in()
        print(f"scale-in -> {len(trainer.active)} devices in "
              f"{ev.wall_s*1e3:.1f} ms")
    m = run_steps(8)
    print(f"[{len(trainer.active)} devices] step {trainer.step_count}: "
          f"loss {m['loss']:.4f}")

    print("straggler report:", trainer.straggler_report())
    losses_ok = m["loss"] < 8.0
    print("ELASTIC_DEMO_OK" if losses_ok else "ELASTIC_DEMO_FAILED")
    return 0 if losses_ok else 1


if __name__ == "__main__":
    sys.exit(main())
