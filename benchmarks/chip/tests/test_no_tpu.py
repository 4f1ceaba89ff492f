"""Without a TPU the benchmark exits non-zero and prints no result."""
import os
import shutil
import subprocess
import sys

from yardstick import harness


ARGS = ["--workload", "cell_day.heavy_packed", "--seed", "3000000019", "--seconds", "1",
        "--trace", "0"]


def _run(cwd, cmd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(cmd, cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_fails_on_the_cpu():
    p = _run(harness.CHECKOUT, [sys.executable, "benchmarks/chip/run.py", *ARGS])
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not p.stdout.strip()


def test_run_fails_without_the_program(tmp_path):
    shutil.copy(harness.CHECKOUT / "BENCHMARK.json", tmp_path)
    shutil.copytree(harness.BENCH_DIR, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    # past the chip check, as on a machine with one: the program's import fails
    code = ("import sys; sys.path.insert(0, 'benchmarks/chip'); from yardstick import harness; "
            f"harness.run({ARGS!r}, require_tpu=False)")
    p = _run(tmp_path, [sys.executable, "-c", code])
    assert p.returncode != 0
    assert "repro" in p.stderr
    assert not p.stdout.strip()
