"""The command refuses to measure without a chip, and without the program."""
import _paths  # noqa: F401
import os
import shutil
import subprocess
import sys

from _paths import HERE, ROOT

ARGS = ["--workload", "qwen1.5-4b.decode-16k", "--seed", str(2**32 + 5),
        "--seconds", "1", "--trace", "0"]


def _run(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, script, *ARGS], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_cpu_run_exits_nonzero_and_prints_no_result():
    p = _run(ROOT, str(HERE / "run.py"))
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_checkout_without_program_exits_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path, str(tmp_path / "benchmarks" / "chip" / "run.py"))
    assert p.returncode != 0
    assert p.stdout.strip() == ""
