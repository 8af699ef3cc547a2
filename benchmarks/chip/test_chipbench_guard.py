"""The benchmark refuses to run where it cannot measure."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from lagsbench import peaks
from lagsbench.spec import BENCH_DIR, REPO_DIR

CELLS = [w["name"] for w in json.load(open(REPO_DIR / "BENCHMARK.json"))
         ["workloads"]]


def _run(cwd, workload):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", workload,
         "--seed", "4294967311", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _printed_result(stdout: str) -> bool:
    return any(line.strip().startswith("{") for line in stdout.splitlines())


@pytest.mark.parametrize("workload", CELLS)
def test_no_tpu_exits_nonzero_without_result(workload):
    p = _run(REPO_DIR, workload)
    assert p.returncode != 0
    assert not _printed_result(p.stdout)
    assert "no TPU" in p.stderr


def test_checkout_without_the_program_exits_nonzero(tmp_path):
    shutil.copy(REPO_DIR / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH_DIR, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path, CELLS[0])
    assert p.returncode != 0
    assert not _printed_result(p.stdout)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        peaks.lookup("TPU v99 imaginary")
    assert peaks.lookup("TPU v5 lite")["bf16_flops"] == 197e12
