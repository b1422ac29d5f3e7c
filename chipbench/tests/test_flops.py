"""The FLOP count of the GPT-2 reference family and the peaks table."""
import json
from pathlib import Path

import pytest

from chipbench import peaks
from chipbench.reference import gpt2

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def test_gpt2_s_by_hand():
    cfg = json.loads((CONFIGS / "gpt2-s.json").read_text())
    # 12 layers of q, k, v, o (4 x 768 x 768) and fc, proj (2 x 768 x 3072),
    # plus the tied head 50257 x 768; the 32768 x 768 position table is no
    # matmul and is left out.
    n = 12 * (4 * 768 * 768 + 2 * 768 * 3072) + 50257 * 768
    assert n == 123_532_032
    assert gpt2.matmul_params(cfg) == n
    # Attention: 12 L S d = 12 x 12 x 1024 x 768 per token.
    assert gpt2.train_flops_per_token(cfg) == 6 * n + 113_246_208
    assert gpt2.train_flops_per_token(cfg) == 854_438_400


def test_gpt2_m():
    cfg = json.loads((CONFIGS / "gpt2-m.json").read_text())
    assert gpt2.train_flops_per_token(cfg) == (
        6 * (24 * 12 * 1024 ** 2 + 50257 * 1024) + 12 * 24 * 1024 * 1024)


def test_peaks():
    assert peaks.peak("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peak("cpu")
