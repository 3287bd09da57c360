#!/usr/bin/env python
"""One run of one benchmark cell, on the chip.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Reads the cell from ``BENCHMARK.json`` and the files it names under
``bench/`` (see ``bench/spec.py``), checks that JAX sees a TPU and as many
chips as the cell asks for (otherwise it exits non-zero and prints no
result), builds the data from ``--seed``, warms every shape the window
uses (set-up), measures for ``--seconds`` (with ``--trace 1`` a traced
window, as long as the cell's ``trace_seconds`` at most), then compares
what the window produced with the plain reference. The last line of
standard output is the result:

    {"correct": ..., "attempted": ..., "failed": ..., "metrics": {...},
     "device": {...}, "breakdown": {...}, "checks": {...}}

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics. Each number compared is printed beside its limit as
the last lines of standard error and under ``checks``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()   # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
# the script's own directory would shadow standard modules by its files'
# names (``trace``); the harness imports itself as the ``bench`` package
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != BENCH]
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import check, trace  # noqa: E402
from bench.compiles import CompileCounter  # noqa: E402
from bench.data import dataset  # noqa: E402
from bench.spec import load_cell  # noqa: E402


class NoChip(SystemExit):
    """JAX found no TPU, or not as many chips as the cell asks for."""


def require_chips(chips: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"bench: needs a TPU, JAX found "
                     f"{devices[0].platform!r}")
    if len(devices) != chips:
        raise NoChip(f"bench: the cell asks for {chips} chip(s), JAX found "
                     f"{len(devices)}")
    return devices


class Run:
    """What a driver and a metric reader see of one run."""

    def __init__(self, cell, seed: int, seconds: float, traced: bool):
        self.cell = cell
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.traced = traced
        self.params = dict(cell.traffic.get("params", {}))
        self.params.update(cell.params)
        self.data = None
        self.windows_done = 0          # scenario-windows in the window
        self.summary = None            # trace reduction (traced runs)


def device_info(devices, chips: int) -> dict:
    """As JAX reports the devices. A chip's peak is the allocator's peak
    in use plus its peak reservation: program temporaries live in the
    reservation, which ``peak_bytes_in_use`` leaves out."""
    peak = None
    for d in devices[:chips]:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            v = int(stats["peak_bytes_in_use"]) + int(
                stats.get("peak_bytes_reserved", 0))
            peak = max(peak or 0, v)
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def run_cell(cell, seed: int, seconds: float, traced: bool, *,
             chip: bool = True, keep_trace: str = None) -> dict:
    """Set up, measure, check. ``chip=False`` skips the look for a TPU
    (the harness's own tests drive the rest of a run on the CPU)."""
    import jax

    devices = require_chips(cell.chips) if chip else jax.devices()
    from repro.core.compile_cache import use_compile_cache

    use_compile_cache()
    # small programs too go to the persistent cache, so a second run of
    # a cell loads every program it needs and compiles none
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    counter = CompileCounter().install()

    run = Run(cell, seed, seconds, traced)
    run.data = dataset(cell.config, run.seed)
    driver = cell.driver().Driver(run)
    driver.setup()

    length = min(seconds, float(run.params.get("trace_seconds", seconds))) \
        if traced else seconds
    recorder = trace.Recorder() if traced else None
    before = counter.snapshot()
    setup_s = time.perf_counter() - T_START
    if recorder:
        recorder.start()
    try:
        with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
            window = driver.window(length)
    finally:
        xplane = recorder.stop() if recorder else None
    after = counter.snapshot()
    device = device_info(devices, cell.chips)
    pairs = driver.outputs()
    driver.close()

    print(f"bench: compilations in the window: "
          f"{after[1] - before[1]} (jaxpr traces: {after[0] - before[0]})",
          file=sys.stderr)
    for line in window.get("notes", []):
        print(f"bench: {line}", file=sys.stderr)

    metrics, breakdown = {}, None
    if traced:
        t_reduce = time.perf_counter()
        flat = trace.flatten(xplane)
        recorder.close()
        if keep_trace:
            os.makedirs(os.path.dirname(os.path.abspath(keep_trace)),
                        exist_ok=True)
            with open(keep_trace, "w") as f:
                json.dump(trace.trimmed(flat, 20000), f)
        run.summary = trace.reduce(flat, span=driver.span)
        device["busy_s"] = run.summary["busy_s"]
        device["window_s"] = run.summary["window_s"]
        breakdown = {"device_ops": run.summary["device_ops"],
                     "idle_gaps": run.summary["idle_gaps"]}
        print(f"bench: trace of {sum(map(len, flat['devices'].values()))} "
              f"device operations reduced in "
              f"{time.perf_counter() - t_reduce!r} s", file=sys.stderr)
        for m in cell.per_layer:
            value = cell.metric_reader(m["name"]).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = dict(window["metrics"], setup_s=setup_s)
        for m in cell.end_to_end:
            if m["name"] in values:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}

    reference, comparison = cell.reference(), cell.comparison()
    t_check = time.perf_counter()
    values = check.compare(pairs, reference, run.data,
                           cell.config["reference_precision"], comparison)
    checks = check.judge(values, cell.limits, comparison.COMPARED)
    printed = "".join(f"; {k} (held to no limit): {values[k]!r}"
                      for k in comparison.PRINTED)
    print(f"bench: {len(pairs)} scenarios compared with the reference in "
          f"{time.perf_counter() - t_check!r} s{printed}", file=sys.stderr)
    correct = (bool(pairs) and window["failed"] == 0
               and all(c["value"] <= c["limit"] for c in checks.values()))
    out = {"correct": correct, "attempted": window["attempted"],
           "failed": window["failed"], "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="write the traced run's flattened trace (its "
                         "first 20000 operations per chip) to this file")
    args = ap.parse_args(argv)
    cell = load_cell(args.workload, ROOT)
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                   keep_trace=args.keep_trace)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
