"""The command refuses to measure without a TPU: it exits non-zero and
prints no result line."""

import os
import shutil
import subprocess
import sys

import pytest

from bench import spec

ARGS = ["--workload", "stablelm-3b.decode-heavy", "--seed", "4294967301",
        "--seconds", "1", "--trace", "0"]


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def _no_result(out: str) -> bool:
    return not any(line.startswith("{") for line in out.splitlines())


def test_no_tpu_exits_nonzero_without_a_result():
    p = _run(spec.ROOT)
    assert p.returncode != 0
    assert _no_result(p.stdout)
    assert "TPU" in p.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(spec.BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert _no_result(p.stdout)


@pytest.mark.parametrize("bad", [["--seed", "-1"], ["--seconds", "0"]])
def test_bad_arguments_are_refused(bad):
    args = ARGS[:2] + bad + ([] if bad[0] == "--seconds" else ARGS[4:6])
    p = subprocess.run([sys.executable, "bench/run.py", *args],
                       cwd=spec.ROOT, capture_output=True, text=True,
                       timeout=120)
    assert p.returncode != 0 and _no_result(p.stdout)
