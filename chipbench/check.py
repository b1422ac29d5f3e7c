"""The comparison that decides `correct` for a training cell.

Three numbers, each against a limit of its own (`chipbench/limits/<cell>.json`):

- `loss_gap`: the largest relative gap between the program's loss and the
  reference's over the first CHECK_STEPS steps;
- `grad_gap`: the first step's gradient as the optimizer got it, read back
  from the program's AdamW state after one step (m / (1 - b1)), against the
  reference's clipped gradient: per leaf, the gap between the two norms over
  the larger of the reference's norm of that leaf and its median leaf norm;
  the worst leaf;
- `change_gap`: the same for the parameters' change over those steps,
  leaving out leaves whose reference gradient is under a thousandth of the
  median leaf's (round-off alone moves them under AdamW).

A leaf is one parameter of one layer: the leaves under the family's
`STACKED` top-level keys hold one layer per row of their leading axis and
are split along it.

Two steps, not three: at GPT-2 M's lr 3e-4 without warm-up the third step
of some seeds lands in a loss spike (loss back up from 10.3 to 12.2), where
the program and the reference part by 0.4 % in loss and 4 % in the change
of the token embedding while their first two steps agree to 1e-4 (PERF.md,
section 6). The later step's noise says nothing about the program.
"""
from __future__ import annotations

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

HERE = Path(__file__).resolve().parent
NUMBERS = ("loss_gap", "grad_gap", "change_gap")
QUIET_GRAD = 1e-3  # of the median leaf's gradient norm
CHECK_STEPS = 2


def _norms(leaves, stacked):
    return [jnp.sqrt(jnp.sum(jnp.square(
        x.reshape(x.shape[0], -1) if s else x.reshape(1, -1)), axis=1))
        for x, s in zip(leaves, stacked)]


def _layout(tree, stacked_keys):
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    names = [jax.tree_util.keystr(p) for p, _ in flat]
    stacked = tuple(p[0].key in stacked_keys for p, _ in flat)
    return names, [x for _, x in flat], stacked


_norms_jit = jax.jit(_norms, static_argnums=1)
_diff_norms_jit = jax.jit(
    lambda a, b, s: _norms([x - y for x, y in zip(a, b)], s),
    static_argnums=2)


def _named(names, stacked, values) -> dict:
    out = {}
    for n, s, v in zip(names, stacked, values):
        v = np.asarray(v, np.float64)
        if s:
            out.update({f"{n}[{i}]": float(x) for i, x in enumerate(v)})
        else:
            out[n] = float(v[0])
    return out


def leaf_norms(tree, stacked_keys, scale: float = 1.0) -> dict:
    names, leaves, stacked = _layout(tree, stacked_keys)
    return {k: v * scale for k, v in
            _named(names, stacked, _norms_jit(leaves, stacked)).items()}


def change_norms(new, old, stacked_keys) -> dict:
    """Per-leaf norms of new - old, computed where `new` lives."""
    names, a, stacked = _layout(new, stacked_keys)
    b = [jax.device_put(y, x.sharding)
         for x, y in zip(a, jax.tree.leaves(old))]
    return _named(names, stacked, _diff_norms_jit(a, b, stacked))


def _worst(prog: dict, ref: dict, keys):
    """(gap, leaf) of the worst leaf."""
    med = float(np.median([ref[k] for k in keys]))
    return max((abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30), k)
               for k in keys)


def readings(prog: dict, ref: dict, detail: bool = False) -> dict:
    """Both arguments: {"losses": [3 floats], "grad": {leaf: norm},
    "change": {leaf: norm}}. `detail` adds the worst leaves' names."""
    loss_gap = max(abs(p - r) / abs(r)
                   for p, r in zip(prog["losses"], ref["losses"]))
    grads = ref["grad"]
    med = float(np.median(list(grads.values())))
    moving = [k for k, g in grads.items() if g >= QUIET_GRAD * med]
    grad_gap, grad_leaf = _worst(prog["grad"], grads, list(grads))
    change_gap, change_leaf = _worst(prog["change"], ref["change"], moving)
    out = {"loss_gap": float(loss_gap), "grad_gap": float(grad_gap),
           "change_gap": float(change_gap)}
    if detail:
        out.update(grad_leaf=grad_leaf, change_leaf=change_leaf,
                   quiet_leaves=sorted(set(grads) - set(moving)))
    return out


def load_limits(cell: str, key: str, root: Path = HERE) -> dict:
    return json.loads((root / "limits" / f"{cell}.json").read_text())[key]


def judge(values: dict, limits: dict) -> dict:
    """name -> {"value", "limit"}, with `ok` where value <= limit."""
    return {k: {"value": values[k], "limit": limits[k],
                "ok": bool(values[k] <= limits[k])} for k in values}
