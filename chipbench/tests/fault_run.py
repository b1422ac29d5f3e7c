"""One rehearsal run (CPU, reduced size) with the timed path broken
underneath, for the tests:

    python chipbench/tests/fault_run.py --workload <name> --fault <fault>

Faults: `none`; `unchanged` (the step returns its state unchanged);
`half_batch` (the step sees the first half of its batch, the mean taken over
it); `no_exchange` (on several chips, each chip steps on its own rows with no
gradient exchange); `dropped_update` (an answer altered where it is made:
the first layer of the family's `UPDATE_LEAF` keeps its old value). Prints
the run's result line last.
"""
import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parents[1]), str(HERE.parent)]

from run import setup_env  # noqa: E402

FAULTS = ("none", "unchanged", "half_batch", "no_exchange", "dropped_update")


def broken_step(step, fault, leaf):
    from chipbench.harness import keep_first_layer

    def unchanged(state, batch):
        return state, step(state, batch)[1]

    def half_batch(state, batch):
        rows = batch["tokens"].shape[0] // 2
        return step(state, {"tokens": batch["tokens"][:rows]})

    def dropped_update(state, batch):
        new, metrics = step(state, batch)
        params = keep_first_layer(new["params"], state["params"], leaf)
        return dict(new, params=params), metrics

    return {"unchanged": unchanged, "half_batch": half_batch,
            "dropped_update": dropped_update}[fault]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--fault", choices=FAULTS, required=True)
    ap.add_argument("--seed", type=int, default=4242)
    ap.add_argument("--seconds", type=float, default=0.5)
    args = ap.parse_args(argv)
    t_start = time.perf_counter()
    bench = json.loads((HERE.parents[1] / "BENCHMARK.json").read_text())
    chips = next(w["chips"] for w in bench["workloads"]
                 if w["name"] == args.workload)
    setup_env(chips, rehearse=True)

    import jax
    from jax.sharding import PartitionSpec as P

    from chipbench.harness import TimedTrainer, execute, family, load_cell
    from repro.models.model_api import Model

    trainer_cls = TimedTrainer
    if args.fault in ("unchanged", "half_batch", "dropped_update"):
        leaf = family(load_cell(args.workload)["cfg"]).UPDATE_LEAF
        make = Model.make_train_step
        Model.make_train_step = (
            lambda self, **kw: broken_step(make(self, **kw), args.fault,
                                           leaf))
    elif args.fault == "no_exchange":
        class NoExchange(TimedTrainer):
            def _get_step_fn(self, n, batch):
                if n == 1 or self.tp != 1:
                    return super()._get_step_fn(n, batch)
                key = (n, 1, "no_exchange")
                if key not in self._step_fns:
                    self._step_fns[key] = jax.jit(jax.shard_map(
                        self.model.make_train_step(), mesh=self.mesh(),
                        in_specs=(P(), P("data")), out_specs=(P(), P()),
                        check_vma=False))
                return self._step_fns[key]

        trainer_cls = NoExchange
    result = execute(args.workload, args.seed, args.seconds, False,
                     rehearse=True, t_start=t_start, trainer_cls=trainer_cls)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
