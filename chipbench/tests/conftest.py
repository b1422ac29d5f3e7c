"""The benchmark's tests run on JAX's CPU backend: every chip-facing path is
driven in a child process with `--rehearse` (reduced sizes, virtual CPU
devices), so the test process itself never needs a chip.

They run against a copy of the benchmark (`bench_root`)."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
IGNORE = shutil.ignore_patterns(".jax_cache", "out", "scratch", "__pycache__")


def load_bench() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def make_root(path: Path, bench: dict) -> Path:
    """A checkout-like directory: the benchmark, the program, `bench`."""
    shutil.copytree(ROOT / "chipbench", path / "chipbench", ignore=IGNORE)
    (path / "src").symlink_to(ROOT / "src")
    (path / "BENCHMARK.json").write_text(json.dumps(bench))
    return path


def last_json(stdout: str):
    lines = [l for l in stdout.strip().splitlines() if l.startswith("{")]
    return json.loads(lines[-1]) if lines else None


@pytest.fixture(scope="session")
def bench_root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("bench"), load_bench())


@pytest.fixture
def run_py():
    """Runs a script of the benchmark in a child process on the CPU, from
    the root that holds the script."""

    def run(script, *args, timeout=300):
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env.pop("XLA_FLAGS", None)
        root = next(p.parent for p in Path(script).parents
                    if p.name == "chipbench")
        return subprocess.run([sys.executable, str(script), *map(str, args)],
                              cwd=root, env=env, capture_output=True,
                              text=True, timeout=timeout)

    return run
