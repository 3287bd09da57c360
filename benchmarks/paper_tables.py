"""Reproduction of the paper's tables/figures (one function per table).

All energies in mJ, F-measures on the held-out test set, losses relative to
our own Edge-Only run (exactly how the paper computes its losses). Results
are cached under results/benchmarks/ as JSON; ``--quick`` runs fewer windows
and seeds for CI-speed smoke validation.

The grid is the ``"paper_tables"`` :mod:`repro.core.experiment` preset —
one declarative ``SweepSpec`` whose expansion matches the legacy
hand-rolled row list config for config — evaluated by ONE
``SweepSpec.run(stack="auto")`` call: every stack-compatible row x seed
replica (same algorithm, any mix of seeds, technologies, p_edge,
allocation and aggregation settings — derived from ``host_side`` field
metadata) runs in lockstep on a shared fleet axis, so the sweep pays
O(sample buckets) jitted dispatches per window for a whole table column
group instead of O(rows x seeds).

    PYTHONPATH=src python -m benchmarks.paper_tables [--quick]
"""
from __future__ import annotations

import argparse
import json
import os
import time

from repro.core.experiment import get_preset
from repro.data.synthetic_covtype import make_covtype_like

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "results",
                           "benchmarks")


def run_all(windows: int = 100, n_seeds: int = 3, quick: bool = False,
            engine: str = "fleet"):
    if quick:
        windows, n_seeds = 30, 1
    data = make_covtype_like(seed=0)
    spec = get_preset("paper_tables", windows=windows, n_seeds=n_seeds,
                      engine=engine)
    out = {"windows": windows, "n_seeds": n_seeds, "engine": engine}

    t0 = time.time()
    print(f"sweeping {len(spec.rows())} rows x {n_seeds} seed(s), {windows} "
          f"windows, replica-stacked (rows print when the sweep returns)",
          flush=True)
    result = spec.run(data, stack="auto")
    out["sweep_seconds"] = round(time.time() - t0, 1)
    print(f"sweep done in {out['sweep_seconds']}s", flush=True)

    ref = None
    for label in result.labels():
        r = result.summary(label)
        if label == "fig2_edge_only":
            ref = r
        else:
            r["gain_pct"] = 100.0 * (1 - r["energy_mj"] / ref["energy_mj"])
            r["acc_loss_pct"] = (100.0 * (ref["f1"] - r["f1"])
                                 / max(ref["f1"], 1e-9))
            print(f"{label:34s} E={r['energy_mj']:8.0f} mJ "
                  f"gain={r['gain_pct']:5.1f}% "
                  f"F1={r['f1']:.3f} loss={r['acc_loss_pct']:4.1f}%",
                  flush=True)
        out[label] = r

    os.makedirs(RESULTS_DIR, exist_ok=True)
    with open(os.path.join(RESULTS_DIR, "paper_tables.json"), "w") as f:
        json.dump(out, f, indent=1)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="30 windows and one seed instead of 100 and three")
    args = ap.parse_args()
    from repro.core.compile_cache import use_compile_cache
    use_compile_cache()
    run_all(quick=args.quick)


if __name__ == "__main__":
    main()
