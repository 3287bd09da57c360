"""No TPU, too few chips, or only the benchmark's own files: exit
non-zero and print no result."""
import os
import shutil
import subprocess
import sys

import pytest


def _run(cwd, env=None):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "paper-htl.closed",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu", **(env or {})))


def test_no_tpu_no_result(root):
    proc = _run(root)
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    assert proc.stdout.strip() == ""


def test_only_the_benchmark_no_result(root, tmp_path):
    shutil.copytree(os.path.join(root, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(root, "BENCHMARK.json"), tmp_path)
    proc = _run(str(tmp_path), {"PYTHONPATH": ""})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_chip_count_must_match(monkeypatch):
    import jax
    from bench import run

    class Dev:
        platform, device_kind = "tpu", "TPU v5 lite"
    monkeypatch.setattr(jax, "devices", lambda *a: [Dev()] * 4)
    with pytest.raises(SystemExit):
        run.require_chips(1)
    assert len(run.require_chips(4)) == 4
