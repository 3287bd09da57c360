#!/usr/bin/env python
"""The program's own host spans (``htl.*``) in a traced window, and the
split of ``host_ms_per_window`` into the host steps they name.

    python3 bench/program.py --workload <cell> --seed <n> --seconds <s> \\
        [--pairs <k>] [--out <file>]

The program writes ``htl.*`` spans with ``jax.profiler.TraceAnnotation``
(``repro.core.dispatch.span``) on the profiler's one clock, beside the
harness's ``bench.*`` spans and the TPUs' ``XLA Ops`` line. This module
reads them in three steps, each on plain data:

* :func:`flatten`: the flat form of :func:`bench.trace.flatten`, plus

      "program": [[span name, start ns, duration ns, thread, {stat: value}],
                  ...]

  where the thread names the host plane and line the span sits on;
* :func:`reduce`: every number of :func:`bench.trace.reduce`, with the
  idle gaps named by the innermost span of either list, plus ``program``
  (per span name: count, self time, the device's idle time inside that
  self time, and the sums of its numeric stats) and
  ``unattributed_idle_s``. On a trace with no program spans every key of
  :func:`bench.trace.reduce` holds what it gives;
* :func:`parts`: the per-window numbers below, of which the seven ``_ms_``
  ones sum to ``host_ms_per_window``.

Run as a script it sets a cell up as ``bench/run.py`` does, then makes
``--pairs`` pairs of windows of ``--seconds`` each, one untraced and one
traced (in turns, the first of each pair alternating), every window
starting from the cell's first scenario. It prints each window's
``windows_per_s`` and, for the traced ones, :func:`parts` and the idle
gaps; the last line of standard output holds them all. The traced
against untraced rates are what tracing costs when it is on.
"""
from __future__ import annotations

import heapq
import os
import sys
from typing import Dict, Optional

if __name__ == "__main__":
    # as bench/run.py: the script's directory would shadow standard
    # modules by its files' names; the harness imports itself as ``bench``
    _BENCH = os.path.dirname(os.path.abspath(__file__))
    sys.path[:] = [p for p in sys.path
                   if os.path.abspath(p or ".") != _BENCH]
    for _p in (os.path.join(os.path.dirname(_BENCH), "src"),
               os.path.dirname(_BENCH)):
        if _p not in sys.path:
            sys.path.insert(0, _p)

from bench import trace  # noqa: E402

PROGRAM_PREFIX = "htl."

# per-window numbers: (the program spans whose idle time they add up)
IDLE_PARTS = {
    "plan_ms_per_window": ("htl.plan",),
    "pack_ms_per_window": ("htl.pack",),
    "upload_ms_per_window": ("htl.upload",),
    "launch_ms_per_window": ("htl.dispatch", "htl.fetch"),
    "result_ms_per_window": ("htl.result",),
    "sweep_ms_per_window": ("htl.sweep", "htl.scenario"),
}


# ---------------------------------------------------------------------------
# flat form
# ---------------------------------------------------------------------------

def program_spans(xplane: str) -> list:
    """The ``htl.*`` events of every host plane, in the flat form."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(xplane).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            thread = f"{plane.name}/{i}:{line.name}"
            for e in line.events:
                if e.name.startswith(PROGRAM_PREFIX):
                    out.append([e.name, int(e.start_ns), int(e.duration_ns),
                                thread, dict(e.stats)])
    return out


def flatten(xplane: str) -> dict:
    return dict(trace.flatten(xplane), program=program_spans(xplane))


# ---------------------------------------------------------------------------
# reduction
# ---------------------------------------------------------------------------

def _clip(s: int, e: int, lo: int, hi: int):
    return max(s, lo), min(e, hi)


def _self_parts(program: list):
    """Yield (span, [(start, end) of its direct children]) for every
    program span, nesting by time on each thread (a thread's spans come
    from context managers, so they nest properly)."""
    by_thread: Dict[str, list] = {}
    for sp in program:
        by_thread.setdefault(sp[3], []).append(sp)
    for spans in by_thread.values():
        spans = sorted(spans, key=lambda sp: (sp[1], -sp[2]))
        children = {id(sp): [] for sp in spans}
        stack: list = []
        for sp in spans:
            while stack and stack[-1][1] + stack[-1][2] <= sp[1]:
                stack.pop()
            if stack:
                children[id(stack[-1])].append((sp[1], sp[1] + sp[2]))
            stack.append(sp)
        for sp in spans:
            yield sp, children[id(sp)]


def reduce(flat: dict, *, span: Optional[str] = None, top: int = 10
           ) -> dict:
    """:func:`bench.trace.reduce`'s numbers of one traced window, and:

    * ``idle_gaps`` named by the innermost span, harness's or program's;
    * ``program``: for each program span name, ``self_s`` (the spans'
      time in the window less that of their child program spans on the
      same thread), ``idle_s`` (device 0's idle time inside that self
      time), ``count`` (the spans that start in the window) and ``stats``
      (the sum of each numeric stat over those);
    * ``unattributed_idle_s``: device 0's idle time inside the spans named
      ``span`` that no program span covers (None without ``span``).

    Where every program span lies inside a ``span`` item, and spans of
    different threads do not overlap in time, the ``idle_s`` of all
    program spans and ``unattributed_idle_s`` add up to ``span_idle_s``.
    Without program spans, the keys of :func:`bench.trace.reduce` hold
    what it gives."""
    out = trace.reduce(flat, span=span, top=top)
    program = flat.get("program", [])
    lo, hi = trace.window_of(flat)
    d0 = sorted(flat["devices"], key=int)[0]
    merged = trace.union([(s, s + du) for _, s, du, _ in
                          flat["devices"][d0]], lo, hi)
    cover = trace.Coverage(merged)

    def idle(a: int, b: int) -> int:
        a, b = _clip(a, b, lo, hi)
        return (b - a) - cover(a, b) if b > a else 0

    def length(a: int, b: int) -> int:
        a, b = _clip(a, b, lo, hi)
        return max(0, b - a)

    per: Dict[str, dict] = {}
    for sp, kids in _self_parts(program):
        name, s, du, _, stats = sp
        if s >= hi or s + du <= lo:
            continue
        e = s + du
        row = per.setdefault(name, {"count": 0, "self_ns": 0, "idle_ns": 0,
                                    "stats": {}})
        row["self_ns"] += length(s, e) - sum(length(*_clip(a, b, s, e))
                                             for a, b in kids)
        row["idle_ns"] += idle(s, e) - sum(idle(*_clip(a, b, s, e))
                                           for a, b in kids)
        if lo <= s < hi:
            row["count"] += 1
            for k, v in stats.items():
                if isinstance(v, (int, float)) and not isinstance(v, bool):
                    row["stats"][k] = row["stats"].get(k, 0) + v

    if program:
        named = flat["spans"] + [[n, s, du] for n, s, du, *_ in program]
        longest = heapq.nlargest(top, trace.gaps(merged, lo, hi),
                                 key=lambda g: g[1] - g[0])
        out["idle_gaps"] = [[trace.innermost(named, (a + b) // 2),
                             (b - a) / 1e9] for a, b in longest]
    out["program"] = {
        name: {"count": r["count"], "self_s": r["self_ns"] / 1e9,
               "idle_s": r["idle_ns"] / 1e9, "stats": r["stats"]}
        for name, r in sorted(per.items())}
    out["unattributed_idle_s"] = None
    if span is not None:
        covered = trace.union([(s, s + du) for _, s, du, *_ in program],
                              lo, hi)
        items = trace.union([(s, s + du) for n, s, du in flat["spans"]
                             if n == span], lo, hi)
        out["unattributed_idle_s"] = sum(
            idle(a, b) - sum(idle(*_clip(c, d, a, b)) for c, d in covered)
            for a, b in items) / 1e9
    return out


def parts(summary: dict, windows: int) -> dict:
    """The split of ``host_ms_per_window`` (ms per scenario-window
    completed in the window), the padding share of the packed sample slots
    (``slot_fill``, %) and the upload per window (MB); a number whose
    spans the trace lacks is left out."""
    program = summary.get("program") or {}
    if not windows or not program:
        return {}
    out = {}
    for name, spans in IDLE_PARTS.items():
        if any(s in program for s in spans):
            out[name] = sum(program[s]["idle_s"] for s in spans
                            if s in program) * 1e3 / windows
    if summary.get("unattributed_idle_s") is not None:
        out["unattributed_ms_per_window"] = \
            summary["unattributed_idle_s"] * 1e3 / windows
    pack = program.get("htl.pack", {}).get("stats", {})
    if pack.get("slots"):
        out["slot_fill"] = 100.0 * pack.get("rows", 0) / pack["slots"]
    upload = program.get("htl.upload", {}).get("stats", {})
    if "bytes" in upload:
        out["upload_mb_per_window"] = upload["bytes"] / 1e6 / windows
    return out


# ---------------------------------------------------------------------------
# script: paired untraced and traced windows of one cell
# ---------------------------------------------------------------------------

def _window(cell, run, seconds: float, traced: bool) -> dict:
    import jax

    driver = cell.driver().Driver(run)        # from the first scenario
    recorder = trace.Recorder() if traced else None
    if recorder:
        recorder.start()
    try:
        with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
            window = driver.window(seconds)
    finally:
        xplane = recorder.stop() if recorder else None
    driver.close()
    out = {"traced": traced,
           "windows_per_s": window["metrics"]["windows_per_s"],
           "windows": run.windows_done, "scenarios": window["attempted"]}
    if traced:
        try:
            summary = reduce(flatten(xplane), span=driver.span)
        finally:
            recorder.close()
        out["host_ms_per_window"] = (summary["span_idle_s"] * 1e3
                                     / run.windows_done)
        out["device_ms_per_window"] = (summary["busy_s"] * 1e3
                                       / run.windows_done)
        out["parts"] = parts(summary, run.windows_done)
        out["program"] = summary["program"]
        out["idle_gaps"] = summary["idle_gaps"]
    return out


def main(argv=None) -> int:
    import argparse
    import json

    import jax

    from bench.data import dataset
    from bench.run import ROOT, Run, require_chips
    from bench.spec import load_cell

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--pairs", type=int, default=1)
    ap.add_argument("--out", default=None,
                    help="also write the result line to this file")
    args = ap.parse_args(argv)

    cell = load_cell(args.workload, ROOT)
    devices = require_chips(cell.chips)
    from repro.core.compile_cache import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    run = Run(cell, args.seed, args.seconds, traced=False)
    run.data = dataset(cell.config, run.seed)
    cell.driver().Driver(run).setup()

    windows = []
    for k in range(args.pairs):
        for traced in ((False, True) if k % 2 == 0 else (True, False)):
            w = _window(cell, run, args.seconds, traced)
            print(f"program: {json.dumps({**w, 'program': None})}",
                  file=sys.stderr, flush=True)
            windows.append(w)
    out = {"device": devices[0].device_kind, "seed": args.seed,
           "seconds": args.seconds, "windows": windows}
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
