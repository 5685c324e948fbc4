"""Without an accelerator the benchmark prints no result and fails; so it
does in a directory that holds only the benchmark."""
import os
import shutil
import subprocess
import sys

from perfbench import catalog

CHECKOUT = os.path.dirname(catalog.ROOT)
ARGS = ["--workload", "mnist-k1024.ingest", "--seed", str(2 ** 31 + 3),
        "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "perfbench/run.py", *ARGS], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300)


def test_no_accelerator_no_result():
    p = _run(CHECKOUT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no accelerator" in p.stderr


def test_the_benchmark_alone_gives_no_result(tmp_path):
    shutil.copy(os.path.join(CHECKOUT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(catalog.ROOT, tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(str(tmp_path))
    assert p.returncode != 0
    assert p.stdout.strip() == ""
