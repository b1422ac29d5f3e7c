"""`step_mfu`: the whole train step's share of the chip's bf16 peak.

FLOPs the window's steps require (`train_flops_per_token` of the cell's
reference family, `chipbench/reference/<model_type>.py`; recomputation not
counted) over the sum, over the window's steps, of step seconds times the
chips the step ran on, times the peak of one chip (`chipbench/peaks.py`).
Step seconds are the host clock around each `ElasticTrainer.step`. Nothing
to read off a TPU.
"""
from chipbench.peaks import peak


def read(run):
    d0 = run.pool[0]
    steps = run.steps()
    if d0.platform != "tpu" or not steps:
        return None
    flops = sum(s["rows"] for s in steps) * run.seq_len \
        * run.ref.train_flops_per_token(run.cfg)
    chip_s = sum((s["t1"] - s["t0"]) * s["n"] for s in steps)
    return 100.0 * flops / (chip_s * peak(d0.device_kind)["bf16_flops"])
