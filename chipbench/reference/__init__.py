"""Plain float32 references, one module per model family.

The harness imports `chipbench.reference.<model_type>`, `model_type` being
the key every configuration file carries (as in the family's public
`config.json`). A new family is a new module here, a configuration file, a
limits file and entries in `BENCHMARK.json`: nothing else changes. The
module imports nothing of the program under test and provides:

- `arch(cfg) -> dict`: the sizes and training settings it reads from a
  configuration file (a plain dict, hashable as sorted items);
- `seed_key(seed)`: a PRNG key for any non-negative seed, 64 bits included;
- `param_shapes(a) -> dict`: the parameter tree's shapes, with the names
  and stacking of the trainer's state, so the same seeded weights go to
  both and are compared leaf by leaf;
- `init_state(a, key)`: parameters and zeroed AdamW moments and step, made
  in one jitted call;
- `train_step(a, state, tokens, devices, *, rows_per_block, quant,
  param_dtype) -> (state, loss, clipped grads, grad norm)`: one AdamW step
  on the global batch, in blocks of rows dealt out to `devices`;
  `quant="fp8"` is the control (operands one precision step down) and
  `param_dtype` rounds the parameters after the update;
- `program_fields(cfg) -> dict`: the model fields of the registry's
  `ArchConfig` that must equal the file's (the harness adds the training
  fields of the `train` block, common to every family);
- `rehearse_cfg(cfg, small) -> dict`: the configuration file at the size of
  the registry's `reduced()` config `small`, for CPU rehearsals;
- `train_flops_per_token(cfg) -> int`: the operations one training token
  requires, recomputation not counted (read by `step_mfu`);
- `activation_bytes_per_row(a, seq_len) -> int`: the reference's live
  activation bytes for one row, which size its blocks of rows;
- `UPDATE_LEAF`: the path (tuple of keys) of a stacked parameter whose
  first layer the dropped-update fault keeps at its old value;
- `STACKED`: the top-level keys of the parameter tree whose leaves are
  stacked per layer on their leading axis (`check.py` splits them).
"""
