"""Golden-value regression gate: silent numeric drift anywhere in the
collection / learning / energy pipeline turns into a red test.

tests/golden/smoke_golden.json pins known-good smoke-preset values
(per-label converged F1, mean F1 curves, energy totals by purpose — the
quantities behind the paper tables).
A failure here means the published numbers changed: either fix the
regression, or — for an *intentional* numeric change — regenerate the
fixture and say so in the PR:

    PYTHONPATH=src python tests/golden/regen_smoke_golden.py
"""
import json
import os

import numpy as np
import pytest

from repro.core.experiment import get_preset
from repro.data.synthetic_covtype import make_covtype_like

GOLDEN = os.path.join(os.path.dirname(__file__), "golden",
                      "smoke_golden.json")
ATOL = 1e-6


@pytest.fixture(scope="module")
def golden():
    with open(GOLDEN) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def result(golden):
    data = make_covtype_like(seed=golden["data_seed"])
    spec = get_preset("smoke", windows=golden["windows"],
                      n_seeds=golden["n_seeds"])
    return spec.run(data)


def test_labels_and_run_count_pinned(golden, result):
    assert result.labels() == list(golden["per_label"])
    assert len(result.records) == golden["n_runs"]


def test_f1_matches_golden(golden, result):
    for lbl, want in golden["per_label"].items():
        s = result.summary(lbl)
        np.testing.assert_allclose(
            s["f1"], want["f1"], rtol=0, atol=ATOL,
            err_msg=f"converged F1 drifted for {lbl!r}")
        np.testing.assert_allclose(
            s["f1_curve"], want["f1_curve"], rtol=0, atol=ATOL,
            err_msg=f"F1 curve drifted for {lbl!r}")


def test_energy_matches_golden(golden, result):
    """Energies are host-side float64 sums over the event ledger —
    deterministic, so they must match to full precision (gated at the
    same 1e-6, relative, since totals are ~1e4 mJ)."""
    for lbl, want in golden["per_label"].items():
        s = result.summary(lbl)
        for k in ("energy_mj", "collection_mj", "learning_mj"):
            np.testing.assert_allclose(
                s[k], want[k], rtol=1e-6, atol=0,
                err_msg=f"{k} drifted for {lbl!r}")


def test_per_run_final_f1_matches_golden(golden, result):
    """Per-(label, seed) resolution — a mean can hide two cancelling
    regressions."""
    finals = [(r.label, r.cfg.seed, r.f1_curve[-1])
              for r in result.records]
    for (lbl, seed, f1), want in zip(finals, golden["per_run_final_f1"]):
        assert lbl == want["label"] and seed == want["seed"]
        np.testing.assert_allclose(
            f1, want["final_f1"], rtol=0, atol=ATOL,
            err_msg=f"final F1 drifted for {lbl!r} seed={seed}")
