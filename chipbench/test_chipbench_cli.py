"""The command refuses to run off a TPU, and prints no result there."""

import os
import shutil
import subprocess
import sys

import pytest

from chipbench import cells


def run(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "chipbench/run.py", *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_nonzero_and_prints_nothing():
    p = run(cells.ROOT, "--workload", "vga32.live", "--seed",
            str(2**31 + 5), "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


@pytest.mark.parametrize("workload", ["no.such", "vga32.live"])
def test_benchmark_files_alone_exit_nonzero(tmp_path, workload):
    """In a directory that holds only BENCHMARK.json and chipbench/ (no
    program under test), no cell runs and nothing is printed."""
    shutil.copytree(os.path.join(cells.ROOT, "chipbench"),
                    tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(cells.ROOT, "BENCHMARK.json"), tmp_path)
    p = run(tmp_path, "--workload", workload, "--seed", "1",
            "--seconds", "1")
    assert p.returncode != 0 and p.stdout.strip() == ""
