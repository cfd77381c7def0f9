"""A run that finds no TPU exits with another code than 0 and prints no
result line; so does one in a directory holding only the benchmark."""

import os
import shutil
import subprocess
import sys

import pytest

from chipbench import spec

ARGS = ["--workload", "granite-3-2b.decode", "--seed", "3000000019",
        "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(cwd / "src"))
    return subprocess.run([sys.executable, "chipbench/run.py", *ARGS],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=120)


def _assert_no_result(p):
    assert p.returncode != 0
    assert not [ln for ln in p.stdout.splitlines()
                if ln.strip().startswith("{")]


def test_no_tpu_exits_nonzero_without_a_result():
    p = _run(spec.ROOT)
    _assert_no_result(p)
    assert "no TPU" in p.stderr


@pytest.mark.parametrize("keep_src", [False, True])
def test_bare_checkout_exits_nonzero_without_a_result(tmp_path, keep_src):
    """Without ``src`` the program cannot be imported; with it, still no
    chip here. Neither prints a result."""
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(spec.ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    if keep_src:
        shutil.copytree(spec.ROOT / "src", tmp_path / "src",
                        ignore=shutil.ignore_patterns("__pycache__"))
    _assert_no_result(_run(tmp_path))
