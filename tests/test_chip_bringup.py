"""What keeps the main path honest on a TPU, checked on the CPU: the
compile-cache placement, the one-process-per-chip guard, the autotuner's
refusal to hide a failing kernel, and chip_smoke.py's phases at tiny
sizes (the script itself refuses to run without a TPU)."""
import json
import os
import subprocess
import sys

import jax
import pytest

import chip_smoke
from repro.core import compile_cache
from repro.core.experiment import get_preset
from repro.core.launcher import HostsExecutor
from repro.core.parallel import ChipHeldError, get_executor
from repro.data.synthetic_covtype import make_covtype_like
from repro.kernels import ops as kernel_ops

REPO = os.path.join(os.path.dirname(__file__), "..")
DATA = make_covtype_like(seed=0)


# ---------------------------------------------------------------------------
# compile cache: placed from outside, else a fixed in-checkout path
# ---------------------------------------------------------------------------

@pytest.fixture
def cache_dir_config():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_respects_the_environment(monkeypatch, tmp_path,
                                                cache_dir_config):
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before   # set nothing


def test_compile_cache_defaults_to_the_repo(monkeypatch, cache_dir_config):
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    path = compile_cache.use_compile_cache()
    assert path == os.path.join(os.path.realpath(REPO), ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert compile_cache.use_compile_cache() == path        # fixed, not fresh


# ---------------------------------------------------------------------------
# one process per chip: backends that start interpreters fail fast on a TPU
# ---------------------------------------------------------------------------

def _smoke_runs():
    runs = get_preset("smoke", windows=2, n_seeds=1).configs()
    return [lbl for lbl, _ in runs], [cfg for _, cfg in runs]


@pytest.mark.parametrize("executor", [
    lambda: get_executor("processes:n=2"),
    lambda: HostsExecutor(channel="local", n=2),
], ids=["processes", "hosts_local"])
def test_child_interpreter_backends_refuse_a_held_chip(monkeypatch,
                                                       executor):
    labels, cfgs = _smoke_runs()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ChipHeldError, match="one process"):
        executor().execute(labels, cfgs, DATA, stack=True)


# ---------------------------------------------------------------------------
# the autotuner never hides a kernel that fails to compile
# ---------------------------------------------------------------------------

def test_autotune_raises_on_a_failing_candidate(monkeypatch):
    kernel_ops.reset_autotune_cache()

    def refuse(fn, args, reps):
        raise RuntimeError("Mosaic refused the kernel")

    monkeypatch.setattr(kernel_ops, "_time_call", refuse)
    with pytest.raises(RuntimeError, match="Mosaic refused"):
        kernel_ops.autotune_loo_trials(300, 23, 16, backend="tpu",
                                       candidates=[("pallas", 64)])
    assert "R512_D23_M16" not in kernel_ops.autotune_table("tpu")
    with pytest.raises(ValueError, match="at least one candidate"):
        kernel_ops.autotune_loo_trials(300, 23, 16, backend="tpu",
                                       candidates=[])
    kernel_ops.reset_autotune_cache()


# ---------------------------------------------------------------------------
# chip_smoke.py: each phase at a tiny size on the CPU
# ---------------------------------------------------------------------------

def test_chip_smoke_city_phase_tiny():
    out = chip_smoke.phase_city(DATA, fleet_size=64, windows=3)
    assert len(out["f1_curve"]) == 3
    assert out["collection_mj"] > 0 and out["learning_mj"] > 0
    assert out["peak_bytes_in_use"] == [None] * len(jax.devices())


def test_chip_smoke_city_energy_check_catches_a_wrong_charge():
    result = get_preset("city", fleet_size=64, windows=2).run(DATA)
    events = result.records[0].events
    want_c, want_l = chip_smoke.city_energy_mj(events, 64, 4, 2)
    got_l = sum(e["mj"] for e in events if e["purpose"] == "learning")
    assert got_l == pytest.approx(want_l, rel=1e-12)
    wrong_c, _ = chip_smoke.city_energy_mj(events, 64, 5, 2)
    assert wrong_c != pytest.approx(want_c, rel=1e-6)


@pytest.fixture(scope="module")
def scan_phase():
    with open(chip_smoke.GOLDEN) as f:
        golden = json.load(f)
    return golden, chip_smoke.phase_scan(golden)


def test_chip_smoke_scan_phase_matches_the_golden(scan_phase):
    golden, (spec, data, result, report) = scan_phase
    assert report["max_f1_diff"] <= chip_smoke.F1_ATOL
    assert spec.configs()[0][1].engine == "scan"
    assert len(result.records) == golden["n_runs"]


def test_chip_smoke_golden_check_flags_f1_drift(scan_phase):
    golden, (_, _, result, report) = scan_phase
    drifted = json.loads(json.dumps(golden))
    drifted["per_run_final_f1"][0]["final_f1"] += 0.05
    assert chip_smoke.golden_f1_diff(result, drifted) >= 0.05
    drifted = json.loads(json.dumps(golden))
    drifted["per_label"]["a2a_wifi"]["energy_mj"] *= 1.001
    with pytest.raises(chip_smoke.SmokeFailure, match="energy_mj"):
        chip_smoke.golden_f1_diff(result, drifted)


def test_chip_smoke_service_phase(scan_phase):
    _, (spec, data, result, _) = scan_phase
    out = chip_smoke.phase_service(spec, data, result.to_json())
    assert out["first_s"] > 0 and out["resubmit_s"] > 0


def test_chip_smoke_shard_invariance_phase_tiny():
    inv = chip_smoke.phase_shard_invariance(DATA, fleet_size=96, windows=2,
                                            shards=1)
    assert len(inv["f1_curve"]) == 2 and len(inv["centres"]) == 2


def test_chip_smoke_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, os.path.join(REPO,
                                                        "chip_smoke.py")],
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "needs a TPU" in proc.stderr
