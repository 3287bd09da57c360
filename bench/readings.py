#!/usr/bin/env python
"""The readings a cell's correctness limits are set from, on the chip.

    python bench/readings.py --workload <cell> --seeds 11,12,... \
        --control 11,12,13 --faults 11,12,13 --seconds 5 [--out FILE]

One process. For every seed it sets the cell up as a run would, runs a
short window of its timed path, and compares the scenarios a run would
sample with the plain reference: the program's numbers (the lower
readings). For the ``--control`` seeds it also puts the reference
computed one precision below the configuration's in the program's place
on the same scenarios: the control's numbers (the upper readings). For
the ``--faults`` seeds it runs the same scenarios again through the
program with each fault of the configuration's ``bench/faults/<name>.py``
planted. One JSON line per scenario and variant, with every number the
configuration's comparison holds to a limit or prints, and under
``gaps`` what they were reduced from. The benchmark's own runs never do this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != BENCH]
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench.data import dataset  # noqa: E402
from bench.run import Run, require_chips  # noqa: E402
from bench.spec import load_cell  # noqa: E402


def readings(cell, seeds, control, fault_seeds, seconds, chip=True,
             out=None):
    import jax
    from repro.core.compile_cache import use_compile_cache

    if chip:
        require_chips(cell.chips)
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    ref, comparison, faults = cell.reference(), cell.comparison(), \
        cell.faults()
    rows = []

    def emit(base, variant, answer, want):
        gaps = comparison.gaps(answer, want)
        row = dict(base, variant=variant, **comparison.numbers([gaps]),
                   gaps=gaps)
        rows.append(row)
        line = json.dumps(row)
        print(line, flush=True)
        if out:
            with open(out, "a") as f:
                f.write(line + "\n")

    for seed in seeds:
        run = Run(cell, seed, seconds, False)
        run.data = dataset(cell.config, seed)
        driver = cell.driver().Driver(run)
        driver.setup()
        driver.window(seconds)
        pairs = driver.outputs()
        for scenario, answer in pairs:
            want = ref.answer(scenario.plain(), run.data,
                              cell.config["reference_precision"])
            base = {"seed": seed, "scenario": scenario.key}
            emit(base, "program", answer, want)
            if seed in control:
                emit(base, "control",
                     ref.answer(scenario.plain(), run.data, "control"), want)
            if seed in fault_seeds:
                for name in faults.FAULTS:
                    with faults.plant(name):
                        got = driver.rerun(scenario)
                    emit(base, name, got, want)
        driver.close()
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", default="")
    ap.add_argument("--faults", default="")
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    def ints(text):
        return [int(s) for s in text.split(",") if s]
    readings(load_cell(args.workload, ROOT), ints(args.seeds),
             set(ints(args.control)), set(ints(args.faults)), args.seconds,
             out=args.out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
