"""`attn_core_share`: the share of the first chip's busy time in the
profiled window spent in attention's core: the device operations whose
`op_name` holds the program's `jax.named_scope("attention_core")` (scores,
softmax and the products with V, forward, recomputed and backward; XLA's
operations or a Pallas kernel alike), own time with nested operations
counted once (`chipbench/program_trace.py`)."""
from chipbench.program_trace import run_scope_share


def read(run):
    return run_scope_share(run, "attention_core")
