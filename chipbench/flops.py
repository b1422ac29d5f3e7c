"""Operations a GPT-2 train step requires, from the configuration's shapes.

The PaLM count (Chowdhery et al. 2022, App. B): per token, 6 N for the
forward and backward matmuls over the N matmul parameters, plus 12 L S d for
the attention scores and their weighted sums (causal masking not
subtracted). N counts the four attention projections and the two MLP
matrices of every layer and the head, which is tied to the token embedding
but still a matmul; the learned position table and the embedding lookup do
no matmul and are left out, as are layer norms. Recomputed work (remat) is
not counted: it is not required.
"""
from __future__ import annotations


def matmul_params(cfg: dict) -> int:
    d = cfg["n_embd"]
    ff = cfg["n_inner"] or 4 * d
    return cfg["n_layer"] * (4 * d * d + 2 * d * ff) + cfg["vocab_size"] * d


def train_flops_per_token(cfg: dict) -> int:
    seq = cfg["train"]["seq_len"]
    return (6 * matmul_params(cfg)
            + 12 * cfg["n_layer"] * seq * cfg["n_embd"])
