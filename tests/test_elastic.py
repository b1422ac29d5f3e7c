"""Elastic runtime tests.

The multi-device cases run in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8 so the main test process
keeps its single-device view (per the task spec: never set this globally).
"""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent


def _run(code: str, devices: int = 8, timeout: int = 420):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = str(ROOT / "src")
    res = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, timeout=timeout, env=env)
    assert res.returncode == 0, f"STDOUT:\n{res.stdout}\nSTDERR:\n{res.stderr}"
    return res.stdout


@pytest.mark.slow
def test_scale_out_preserves_state_and_loss():
    out = _run("""
        import jax, numpy as np
        from repro.configs import get_config
        from repro.data.synthetic import TokenStream
        from repro.elastic import ElasticTrainer
        from repro.models import build_model

        cfg = get_config("gpt2").reduced()
        model = build_model(cfg)
        stream = TokenStream(vocab=cfg.vocab, seq_len=32, seed=0)
        tr = ElasticTrainer(model, initial=2, per_device_batch=2)
        tr.init()

        def batch():
            return {"tokens": stream.batch(range(tr.global_batch))}

        for _ in range(3):
            m = tr.step(batch())
        before = jax.tree.map(np.asarray, tr.state["params"])
        ev = tr.scale_out()
        after = jax.tree.map(np.asarray, tr.state["params"])
        for a, b in zip(jax.tree.leaves(before), jax.tree.leaves(after)):
            np.testing.assert_array_equal(a, b)   # stop-free: state unchanged
        assert len(tr.active) == 3
        assert ev.plan_summary["n_shards"] > 0
        m2 = tr.step(batch())
        assert np.isfinite(m2["loss"]) and abs(m2["loss"] - m["loss"]) < 1.0
        print("OK scale_out", m["loss"], m2["loss"])
    """)
    assert "OK scale_out" in out


@pytest.mark.slow
def test_scale_in_and_failure_recovery():
    out = _run("""
        import jax, numpy as np
        from repro.checkpoint import MemoryReplicaStore
        from repro.configs import get_config
        from repro.core.sharding_alg import NeighborLink
        from repro.data.synthetic import TokenStream
        from repro.elastic import ElasticTrainer
        from repro.models import build_model

        cfg = get_config("gpt2").reduced()
        model = build_model(cfg)
        stream = TokenStream(vocab=cfg.vocab, seq_len=32, seed=0)
        tr = ElasticTrainer(model, initial=4, per_device_batch=2)
        tr.init()
        store = MemoryReplicaStore(redundancy=2)
        nbrs = {i: NeighborLink(0.001, 1e-9) for i in (1, 2, 3)}

        def batch():
            return {"tokens": stream.batch(range(tr.global_batch))}

        for _ in range(3):
            tr.step(batch())
        store.push(owner=0, step=tr.step_count, tree=tr.state, neighbors=nbrs)
        snap = jax.tree.map(np.asarray, tr.state)

        tr.scale_in(failure=True)          # node dies
        store.drop_holder(1)               # including one replica holder
        restored, step = store.restore(0, available=[2, 3])
        for a, b in zip(jax.tree.leaves(snap), jax.tree.leaves(restored)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        tr.state = jax.device_put(restored, tr._state_sharding())
        m = tr.step(batch())
        assert np.isfinite(m["loss"])
        assert len(tr.active) == 3
        print("OK failure_recovery", step, m["loss"])
    """)
    assert "OK failure_recovery" in out


@pytest.mark.slow
def test_elastic_loss_continuity_across_churn():
    """Loss stays smooth across join/leave churn (paper Figs 11-14)."""
    out = _run("""
        import numpy as np
        from repro.configs import get_config
        from repro.data.synthetic import ShardedLoader, TokenStream
        from repro.elastic import ElasticTrainer
        from repro.models import build_model
        import dataclasses

        cfg = dataclasses.replace(get_config("gpt2").reduced(), learning_rate=2e-3)
        model = build_model(cfg)
        stream = TokenStream(vocab=cfg.vocab, seq_len=32, seed=0)
        loader = ShardedLoader(stream, 256, [0], batch_per_node=2)
        tr = ElasticTrainer(model, initial=3, per_device_batch=2,
                            on_reshard=lambda ids: loader.reshard(ids))
        tr.init()

        losses = []
        def run(n):
            for _ in range(n):
                toks = np.concatenate([loader.next_batch(i) for i in tr.device_ids()])
                losses.append(tr.step({"tokens": toks})["loss"])

        run(6); tr.scale_out(); run(6); tr.scale_in(); run(6)
        arr = np.asarray(losses)
        assert np.isfinite(arr).all()
        # No catastrophic spike at the churn boundaries.
        jumps = np.abs(np.diff(arr))
        assert jumps.max() < 1.5, jumps
        print("OK continuity", arr[0], arr[-1], jumps.max())
    """)
    assert "OK continuity" in out


def test_step_compile_timed_apart_from_step_time():
    """The first step at a new (n, tp) compiles ahead of its call: the
    compile lands in ``compile_seconds`` and the step times exclude it."""
    import jax
    from repro.configs import get_config
    from repro.data.synthetic import TokenStream
    from repro.elastic import ElasticTrainer
    from repro.models import build_model

    cfg = get_config("gpt2").reduced()
    tr = ElasticTrainer(build_model(cfg), devices=jax.devices()[:1],
                        initial=1, per_device_batch=2)
    tr.init()
    batch = {"tokens": TokenStream(vocab=cfg.vocab, seq_len=16,
                                   seed=0).batch(range(2))}
    tr.step(batch)
    tr.step(batch)
    assert list(tr.compile_seconds) == [(1, 1)]
    assert tr.compile_seconds[(1, 1)] > 0
    times = tr.metrics_snapshot()["step_times"][1]
    assert len(times) == 2 and all(t > 0 for t in times)


@pytest.mark.parametrize("layout", [(1, 1), (2, 1), (4, 2)],
                         ids=["1x1", "2x1", "4x2"])
def test_kernel_step_matches_xla_step(layout):
    """With the kernel selected (the backend predicate patched, so that the
    kernel runs in the interpreter), one step of a tiny GPT-2 matches the
    XLA path's step on the same layout: the loss, the gradient (AdamW's
    first moment after one step) and each parameter's change. On (2, 1) and
    (4, 2) the kernel runs per shard, over data and over model. The compile
    span and the site counter name the path each step took."""
    n, tp = layout
    out = _run(f"""
        import jax, numpy as np
        import repro.elastic.trainer as T
        from repro.configs import get_config
        from repro.data.synthetic import TokenStream
        from repro.elastic import ElasticTrainer
        from repro.models import build_model

        cfg = get_config("gpt2").reduced()
        res = {{}}
        for impl in ("xla", "pallas"):
            T.kernels_selected = lambda: impl == "pallas"
            tr = ElasticTrainer(build_model(cfg), initial={n},
                                per_device_batch=2)
            tr.init(jax.random.PRNGKey(0))
            if {tp} > 1:
                tr.apply_reshard({tp})
            stream = TokenStream(vocab=cfg.vocab, seq_len=128, seed=0)
            p0 = jax.tree.map(np.asarray, tr.state["params"])
            m = tr.step({{"tokens": stream.batch(range(tr.global_batch))}})
            (comp,) = tr.tracer.named("chaos.compile")
            assert comp.attrs["attention"] == impl, comp.attrs
            sites = tr.tracer.counter("chaos_attention_sites_total")
            assert sites == {{(impl, "{n}", "{tp}"): 1.0}}, sites
            res[impl] = m, jax.tree.map(np.asarray, tr.state), p0
        (mx, sx, p0), (mk, sk, _) = res["xla"], res["pallas"]
        assert abs(mx["loss"] - mk["loss"]) < 1e-4, (mx, mk)
        assert abs(mx["grad_norm"] - mk["grad_norm"]) < 1e-2 * mx["grad_norm"]
        gaps = [np.linalg.norm(a - b) / np.linalg.norm(a) for a, b in zip(
            jax.tree.leaves(sx["opt"]["m"]), jax.tree.leaves(sk["opt"]["m"]))]
        assert max(gaps) < 2e-2, gaps
        # Each leaf's change, the kernel's against XLA's, as a share of
        # XLA's. AdamW's first step is about lr times the gradient's sign,
        # so leaves with gradients near zero read up to about 0.25 here; an
        # update of the wrong sign would read 2, one left out 1.
        moves = [np.linalg.norm(b - a) / np.linalg.norm(a - o)
                 for a, b, o in zip(jax.tree.leaves(sx["params"]),
                                    jax.tree.leaves(sk["params"]),
                                    jax.tree.leaves(p0))]
        assert max(moves) < 0.5, moves
        print("OK kernel step", max(gaps), max(moves))
    """, devices=4)
    assert "OK kernel step" in out


def test_kernel_step_reused_on_other_devices():
    """A layout's step, compiled with the kernel selected on chips {0, 1},
    runs again after chip 1 fails and the trainer is back to two chips,
    {0, 2}: the step is traced and called under the layout's abstract
    mesh, which names no devices, so it is reused without a recompile."""
    out = _run("""
        import jax, numpy as np
        import repro.elastic.trainer as T
        from repro.configs import get_config
        from repro.data.synthetic import TokenStream
        from repro.elastic import ElasticTrainer
        from repro.models import build_model

        T.kernels_selected = lambda: True
        cfg = get_config("gpt2").reduced()
        tr = ElasticTrainer(build_model(cfg), initial=2, per_device_batch=2)
        tr.init(jax.random.PRNGKey(0))
        stream = TokenStream(vocab=cfg.vocab, seq_len=128, seed=0)
        losses = []
        for move in (None, lambda: tr.scale_out(),
                     lambda: tr.scale_in(tr.active[1], failure=True)):
            if move:
                move()
            batch = {"tokens": stream.batch(range(tr.global_batch))}
            losses.append(tr.step(batch)["loss"])
        assert [d.id for d in tr.active] == [0, 2], tr.active
        assert np.all(np.isfinite(losses)), losses
        assert tr.tracer.counter("chaos_compiles_total") == {
            ("2", "1"): 1.0, ("3", "1"): 1.0}
        assert tr.tracer.counter("chaos_attention_sites_total") == {
            ("pallas", "2", "1"): 1.0, ("pallas", "3", "1"): 1.0}
        print("OK reused")
    """, devices=4)
    assert "OK reused" in out


def test_traced_window_flag_stays_on_xla(monkeypatch):
    """Gemma2's alternating local and global layers carry a traced window
    flag, which the kernel's contract excludes: with the kernel selected the
    step records the XLA path and trains."""
    import jax
    import numpy as np

    import repro.elastic.trainer as T
    from repro.configs import get_config
    from repro.data.synthetic import TokenStream
    from repro.elastic import ElasticTrainer
    from repro.models import build_model

    monkeypatch.setattr(T, "kernels_selected", lambda: True)
    cfg = get_config("gemma2-27b").reduced()
    assert cfg.alt_local_global
    tr = ElasticTrainer(build_model(cfg), devices=jax.devices()[:1],
                        initial=1, per_device_batch=2)
    tr.init()
    stream = TokenStream(vocab=cfg.vocab, seq_len=128, seed=0)
    losses = [tr.step({"tokens": stream.batch(range(2))})["loss"]
              for _ in range(2)]
    (comp,) = tr.tracer.named("chaos.compile")
    assert comp.attrs["attention"] == "xla"
    assert tr.tracer.counter("chaos_attention_sites_total") == {
        ("xla", "1", "1"): 1.0}
    assert np.all(np.isfinite(losses))


@pytest.mark.parametrize("arch", ["rwkv6-1.6b", "zamba2-1.2b"])
def test_selection_keeps_recurrences_on_xla(arch, monkeypatch):
    """The trainer's selection asks for attention's kernel alone: under it
    the WKV6 and SSD recurrences trace no Pallas call (their kernels have
    no per-shard path), where ``use_pallas=True`` traces one. At sequence
    64 attention is below the kernel's block, so a Pallas call here could
    only be the recurrence's. The step trains."""
    import jax
    import numpy as np

    import repro.elastic.trainer as T
    from repro.configs import get_config
    from repro.data.synthetic import TokenStream
    from repro.elastic import ElasticTrainer
    from repro.models import build_model

    monkeypatch.setattr(T, "kernels_selected", lambda: True)
    model = build_model(get_config(arch).reduced())
    asked = []
    make = model.make_train_step
    monkeypatch.setattr(model, "make_train_step",
                        lambda **kw: asked.append(kw) or make(**kw))
    tr = ElasticTrainer(model, devices=jax.devices()[:1], initial=1,
                        per_device_batch=2)
    tr.init()
    batch = {"tokens": TokenStream(vocab=model.cfg.vocab, seq_len=64,
                                   seed=0).batch(range(2))}
    params = jax.tree.map(np.asarray, tr.state["params"])
    assert np.isfinite(tr.step(batch)["loss"])
    (kw,) = asked

    def pallas_calls(use_pallas):
        jaxpr = jax.make_jaxpr(lambda p: model.loss_fn(
            p, batch, use_pallas=use_pallas))(params)
        return str(jaxpr).count("pallas_call")

    assert pallas_calls(kw["use_pallas"]) == 0
    assert pallas_calls(True) > 0
