"""Bit-identity of the training state across layout changes, on every chip.

Before and after each churn event the harness takes a fingerprint of the
trainer's state: for every leaf and every chip, a hash of the chip's shard,
`sum(bits[i] * (2 * i + 1)) mod 2**32` over the shard's elements, `i` being
the element's flat index in the whole array. Sums of shards over distinct
shard indices give the hash of the whole array whatever the layout, so a
move keeps the state bit for bit when every leaf's hash is unchanged, every
replica of a shard agrees, and every leaf lives on every active chip. The
hash weights each position differently, so shards swapped or shifted show.
One program per chip reads all of that chip's shards; it runs outside the
timed spans and its time is taken out of the window.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def _shard_hash(x, gshape, starts):
    u = (lax.bitcast_convert_type(x, jnp.uint32) if x.dtype.itemsize == 4
         else x.astype(jnp.uint32))
    idx = jnp.zeros(x.shape, jnp.uint32)
    stride = 1
    for ax in reversed(range(x.ndim)):
        io = lax.broadcasted_iota(jnp.uint32, x.shape, ax) + np.uint32(starts[ax])
        idx = idx + io * np.uint32(stride)
        stride *= gshape[ax]
    return jnp.sum(u * (2 * idx + 1), dtype=jnp.uint32)


def _starts(index, shape):
    return tuple(0 if sl.start is None else int(sl.start)
                 for sl, _ in zip(index, shape))


class MoveChecker:
    """Fingerprints the state around each event and records what moved."""

    def __init__(self):
        self._fns = {}
        self.results = []  # one dict per event

    def _fn(self, meta):
        if meta not in self._fns:
            self._fns[meta] = jax.jit(lambda xs: jnp.stack(
                [_shard_hash(x, g, s) for x, (g, s) in zip(xs, meta)]))
        return self._fns[meta]

    def fingerprint(self, state) -> dict:
        """leaf index -> {"devices": set of chip ids, "parts": {starts:
        {chip id: hash}}}."""
        per_dev = {}
        for i, leaf in enumerate(jax.tree.leaves(state)):
            for sh in leaf.addressable_shards:
                per_dev.setdefault(sh.device, []).append(
                    (i, tuple(leaf.shape), _starts(sh.index, leaf.shape),
                     sh.data))
        pending = {}
        for dev, items in per_dev.items():
            meta = tuple((g, s) for _, g, s, _ in items)
            pending[dev] = (items, self._fn(meta)([d for *_, d in items]))
        out = {}
        for dev, (items, hashes) in pending.items():
            for (i, _, s, _), h in zip(items, np.asarray(hashes)):
                leaf = out.setdefault(i, {"devices": set(), "parts": {}})
                leaf["devices"].add(dev.id)
                leaf["parts"].setdefault(s, {})[dev.id] = int(h)
        return out

    @staticmethod
    def whole(fp) -> dict:
        """leaf -> hash of the whole array; None where replicas disagree."""
        out = {}
        for i, leaf in fp.items():
            total = 0
            for copies in leaf["parts"].values():
                if len(set(copies.values())) != 1:
                    total = None
                    break
                total = (total + next(iter(copies.values()))) % 2**32
            out[i] = total
        return out

    def check(self, kind: str, before: dict, after: dict, active_ids) -> dict:
        hb, ha = self.whole(before), self.whole(after)
        active = set(active_ids)
        res = {
            "kind": kind,
            "changed": sorted(i for i in hb if hb[i] is None
                              or ha.get(i) != hb[i]),
            "misplaced": sorted(i for i, leaf in after.items()
                                if leaf["devices"] != active),
        }
        res["ok"] = not res["changed"] and not res["misplaced"]
        self.results.append(res)
        return res
