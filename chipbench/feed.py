"""The one traffic generator: rows of tokens from the seed, fed in turn.

A traffic mix is a data file, `chipbench/traffic/<name>.json`; this module
reads it. The rows follow `repro.data.synthetic.TokenStream` (a Markov chain
over the first `tokens_used` token ids, each id with `successors` likely next
ids, followed with probability `follow`), generated for all rows at once
from a NumPy generator seeded by the run's seed, so any seed up to 2**63
works and every seed gets the same sizes.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent


def load_traffic(name: str, root: Path = HERE) -> dict:
    return json.loads((root / "traffic" / f"{name}.json").read_text())


def token_rows(seed: int, n_rows: int, seq_len: int, vocab: int, *,
               tokens_used: int, successors: int, follow: float) -> np.ndarray:
    """(n_rows, seq_len + 1) int32 token rows; the same seed, the same rows."""
    rng = np.random.default_rng([seed, 0x7EED])
    v = min(vocab, tokens_used)
    nxt = rng.integers(0, v, size=(v, successors))
    out = np.empty((n_rows, seq_len + 1), np.int32)
    t = rng.integers(0, v, n_rows)
    for i in range(seq_len + 1):
        out[:, i] = t
        keep = rng.random(n_rows) < follow
        t = np.where(keep, nxt[t, rng.integers(0, successors, n_rows)],
                     rng.integers(0, v, n_rows))
    return out


class Feed:
    """Hands out the next `rows` rows of a fixed pool, wrapping around; the
    first pass over the pool never repeats a row."""

    def __init__(self, pool: np.ndarray):
        self.pool = pool
        self.cursor = 0
        self.taken = []  # (start, rows) of every batch handed out

    def next(self, rows: int) -> dict:
        idx = (self.cursor + np.arange(rows)) % len(self.pool)
        self.taken.append((self.cursor, rows))
        self.cursor = (self.cursor + rows) % len(self.pool)
        return {"tokens": self.pool[idx]}

    def rows_of(self, k: int) -> np.ndarray:
        """The rows of the k-th batch handed out."""
        start, rows = self.taken[k]
        return self.pool[(start + np.arange(rows)) % len(self.pool)]
