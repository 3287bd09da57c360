#!/usr/bin/env python
"""Nightly bench-trend gate: the quick paper-tables wall time may not
regress past the committed trajectory.

Re-measures the ``paper_tables --quick`` cold (fresh jit cache) and warm
(persistent jit cache) subprocess wall times — the CPU measurement behind
the table timings in BENCH_greedytl.json — and fails when either exceeds
the latest trajectory entry by more than ``--threshold`` (default 1.25x,
i.e. a >25% regression). Writes the measurement next to the other bench
artifacts as results/benchmarks/bench_trend.json so the nightly workflow
uploads a comparable trend point per run.

    python scripts/bench_trend.py --threshold 1.25

Wired into .github/workflows/nightly-bench.yml (kernel selection
unpinned there: REPRO_KERNEL_FORCE is deliberately NOT set, so the
autotuner path the benchmarks exercise is the one users get).
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# fixed path (a cache directory that moves never hits); emptied before the
# cold run, reused by the warm one
CACHE_DIR = os.path.join(ROOT, ".jax_cache", "bench_trend")
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)

TABLES_CODE = ("import time; t0 = time.time(); "
               "from benchmarks.paper_tables import run_all; "
               "run_all(quick=True); print('WALL_S', time.time() - t0)")


def run_tables_once(cache_dir: str) -> float:
    env = dict(os.environ,
               PYTHONPATH="src" + os.pathsep
               + os.environ.get("PYTHONPATH", ""),
               JAX_COMPILATION_CACHE_DIR=cache_dir)
    out = subprocess.run([sys.executable, "-c", TABLES_CODE], cwd=ROOT,
                         env=env, capture_output=True, text=True,
                         check=True)
    return float(out.stdout.strip().split()[-1])


def baseline_entry(trajectory):
    """Latest trajectory entry that carries table timings (older entries
    may only record refine latency)."""
    for row in reversed(trajectory):
        if "paper_tables_quick_cold_s" in row:
            return row
    return None


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--threshold", type=float, default=1.25,
                    help="fail when measured/baseline exceeds this "
                         "ratio on either axis")
    ap.add_argument("--baseline", default=os.path.join(
        ROOT, "BENCH_greedytl.json"))
    args = ap.parse_args()

    from benchmarks.paper_tables import RESULTS_DIR

    with open(args.baseline) as f:
        base = baseline_entry(json.load(f)["trajectory"])
    if base is None:
        print("bench trend: no trajectory entry carries table timings — "
              "nothing to gate against")
        return 1

    # The quick subprocess writes a reduced paper_tables.json; keep the
    # committed artifact intact (same guard as bench_greedytl_incremental).
    tables_json = os.path.join(RESULTS_DIR, "paper_tables.json")
    keep = open(tables_json).read() if os.path.exists(tables_json) \
        else None
    try:
        shutil.rmtree(CACHE_DIR, ignore_errors=True)
        cold = run_tables_once(CACHE_DIR)
        warm = run_tables_once(CACHE_DIR)
    finally:
        if keep is not None:
            with open(tables_json, "w") as f:
                f.write(keep)

    rc = 0
    report = {"baseline_label": base["label"],
              "threshold": args.threshold,
              "kernel_force": os.environ.get("REPRO_KERNEL_FORCE", ""),
              "measured_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                            time.gmtime()),
              "axes": {}}
    for axis, measured in (("cold", cold), ("warm", warm)):
        ref = base[f"paper_tables_quick_{axis}_s"]
        ratio = measured / ref
        ok = ratio <= args.threshold
        report["axes"][axis] = {"measured_s": round(measured, 1),
                                "baseline_s": ref,
                                "ratio": round(ratio, 3), "ok": ok}
        state = "OK" if ok else "REGRESSION"
        print(f"bench trend [{axis}]: {state} — {measured:.1f}s vs "
              f"{base['label']} baseline {ref}s "
              f"(ratio {ratio:.2f}, threshold {args.threshold})")
        if not ok:
            rc = 1

    os.makedirs(RESULTS_DIR, exist_ok=True)
    out_path = os.path.join(RESULTS_DIR, "bench_trend.json")
    with open(out_path, "w") as f:
        json.dump(report, f, indent=1)
        f.write("\n")
    print(f"bench trend: wrote {os.path.relpath(out_path, ROOT)}")
    if rc == 0:
        print("bench trend: quick paper-tables wall time within "
              f"{args.threshold}x of the committed trajectory")
    return rc


if __name__ == "__main__":
    sys.exit(main())
