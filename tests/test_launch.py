"""Launcher + roofline-infrastructure tests.

hlo_analysis is what turns the dry-run into the roofline report — its scan
trip-count handling and collective accounting get direct regression tests
here (XLA's own cost_analysis counts scan bodies once; we must not)."""
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent


def _run(args, devices=8, timeout=420, env_extra=None):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = str(ROOT / "src")
    if env_extra:
        env.update(env_extra)
    res = subprocess.run([sys.executable] + args, capture_output=True,
                         text=True, timeout=timeout, env=env, cwd=ROOT)
    assert res.returncode == 0, f"STDOUT:\n{res.stdout}\nSTDERR:\n{res.stderr}"
    return res.stdout


@pytest.mark.slow
def test_train_launcher():
    out = _run(["-m", "repro.launch.train", "--arch", "tinyllama-1.1b",
                "--steps", "6"])
    assert "TRAIN_OK" in out


@pytest.mark.slow
def test_train_launcher_elastic():
    out = _run(["-m", "repro.launch.train", "--arch", "gpt2", "--steps", "9",
                "--elastic"])
    assert "scale-out" in out and "scale-in" in out


@pytest.mark.slow
def test_serve_launcher():
    out = _run(["-m", "repro.launch.serve", "--arch", "zamba2-1.2b",
                "--requests", "1", "--tokens", "4"])
    assert "SERVE_OK" in out


@pytest.mark.slow
def test_dryrun_debug_mesh_cell():
    """The dry-run machinery end-to-end on the tiny mesh (fast CI check)."""
    out = _run(["-m", "repro.launch.dryrun", "--arch", "whisper-small",
                "--shape", "train_4k", "--mesh", "multi", "--debug-mesh",
                "--out", "/tmp/dryrun_ci.json"])
    assert "0 failures" in out and "roofline" in out


# ---------------------------------------------------------------------------
# hlo_analysis unit tests (in-process, 1 device is fine).
# ---------------------------------------------------------------------------


def test_hlo_analysis_counts_scan_trips():
    import jax
    import jax.numpy as jnp
    from jax import lax

    from repro.launch.hlo_analysis import analyze

    def make(n_layers):
        w = jnp.zeros((n_layers, 32, 32))
        x0 = jnp.zeros((4, 32))

        def f(ws):
            def body(x, wl):
                return jnp.tanh(x @ wl), None

            x, _ = lax.scan(body, x0, ws)
            return x.sum()

        c = jax.jit(f).lower(jax.ShapeDtypeStruct(w.shape, w.dtype)).compile()
        return analyze(c.as_text())

    t4, t16 = make(4), make(16)
    per_layer = 2 * 4 * 32 * 32
    assert t4.flops == pytest.approx(4 * per_layer)
    assert t16.flops == pytest.approx(16 * per_layer)
    assert t16.unknown_trip == 0


def test_hlo_analysis_replica_groups():
    from repro.launch.hlo_analysis import _group_size

    assert _group_size("replica_groups=[16,32]<=[512]") == 32
    assert _group_size("replica_groups={{0,1,2,3},{4,5,6,7}}") == 4
    assert _group_size(
        "replica_groups={{0,16,32,48},{1,17,33,49}}, other=1") == 4


def test_hlo_analysis_dot_flops_parsing():
    from repro.launch.hlo_analysis import Computation, Instr, _dot_flops

    comp = Computation("c")
    comp.types["%a"] = "f32[8,64]"
    ins = Instr("%d", "f32[8,32]", "dot",
                "%a, %b), lhs_contracting_dims={1}, rhs_contracting_dims={0}")
    assert _dot_flops(ins, comp) == 2 * 8 * 32 * 64


def test_device_peaks_keyed_by_device_kind():
    from repro.launch.dryrun import (
        DEVICE_PEAKS,
        TARGET_DEVICE_KIND,
        device_peaks,
    )

    assert device_peaks(TARGET_DEVICE_KIND)["flops"] == 197e12
    assert TARGET_DEVICE_KIND in DEVICE_PEAKS
    with pytest.raises(KeyError, match="no peaks for device kind"):
        device_peaks("cpu")


def test_compile_cache_dir_placed_from_outside(monkeypatch, tmp_path):
    import jax
    from repro.launch import compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(compile_cache.CACHE_ENV, str(tmp_path / "outside"))
        assert compile_cache.enable_compile_cache(tmp_path / "default") == \
            str(tmp_path / "outside")
        assert jax.config.jax_compilation_cache_dir == before

        monkeypatch.delenv(compile_cache.CACHE_ENV)
        assert compile_cache.enable_compile_cache(tmp_path / "default") == \
            str(tmp_path / "default")
        assert jax.config.jax_compilation_cache_dir == str(tmp_path /
                                                           "default")
        assert compile_cache.DEFAULT_CACHE_DIR == ROOT / ".jax_cache"
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
