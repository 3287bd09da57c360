"""Benchmark driver. One section per paper table/figure + framework benches.

Prints ``name,us_per_call,derived`` CSV rows; full numeric payloads are
written to results/benchmarks/*.json.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--skip-tables]
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


def bench_paper_tables(quick: bool, engine: str = "fleet"):
    from benchmarks.paper_tables import run_all
    t0 = time.time()
    out = run_all(quick=quick, engine=engine)
    dt = (time.time() - t0) * 1e6
    rows = []
    ref = out["fig2_edge_only"]
    rows.append(("fig2_edge_only", dt, f"E={ref['energy_mj']:.0f}mJ "
                 f"F1={ref['f1']:.3f}"))
    for k, v in out.items():
        if isinstance(v, dict) and "gain_pct" in v:
            rows.append((k, 0.0, f"E={v['energy_mj']:.0f}mJ "
                         f"gain={v['gain_pct']:.1f}% F1={v['f1']:.3f} "
                         f"loss={v['acc_loss_pct']:.1f}%"))
    return rows


def bench_kernels(quick: bool):
    """Per-kernel call latency (interpret mode on CPU; numbers are
    correctness-path timings, not TPU performance)."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops

    rows = []
    key = jax.random.PRNGKey(0)

    q = jax.random.normal(key, (1, 4, 512, 64), jnp.float32)
    k = jax.random.normal(key, (1, 2, 512, 64), jnp.float32)
    v = jax.random.normal(key, (1, 2, 512, 64), jnp.float32)
    f = lambda: ops.flash_attention(q, k, v, causal=True)
    f()
    t0 = time.time()
    n = 3
    for _ in range(n):
        jax.block_until_ready(f())
    rows.append(("kernel_flash_attention_512", (time.time() - t0) / n * 1e6,
                 "interpret"))

    x = jax.random.normal(key, (1, 512, 4, 64), jnp.float32)
    dt_ = jax.nn.softplus(jax.random.normal(key, (1, 512, 4)))
    A = -jnp.exp(jax.random.normal(key, (4,)) * 0.5)
    Bm = jax.random.normal(key, (1, 512, 64)) * 0.5
    Cm = jax.random.normal(key, (1, 512, 64)) * 0.5
    f = lambda: ops.ssd_scan(x, dt_, A, Bm, Cm, chunk=128)
    f()
    t0 = time.time()
    for _ in range(n):
        jax.block_until_ready(f())
    rows.append(("kernel_ssd_scan_512", (time.time() - t0) / n * 1e6,
                 "interpret"))

    a = jax.nn.sigmoid(jax.random.normal(key, (1, 512, 128)))
    b = jax.random.normal(key, (1, 512, 128)) * 0.5
    f = lambda: ops.rglru_scan(a, b)
    f()
    t0 = time.time()
    for _ in range(n):
        jax.block_until_ready(f())
    rows.append(("kernel_rglru_scan_512", (time.time() - t0) / n * 1e6,
                 "interpret"))
    return rows


def bench_greedytl(quick: bool):
    """GreedyTL source-selection microbenchmark: us/call vs candidate-pool
    size M (the factorized-LOO hot path; track this in results/)."""
    import jax
    import jax.numpy as jnp
    from repro.core.greedytl import greedytl

    rng = np.random.default_rng(0)
    F, C, cap = 54, 7, 160
    x = jnp.asarray(rng.normal(size=(cap, F)).astype(np.float32))
    y = jnp.asarray(rng.integers(0, C, cap).astype(np.int32))
    m = jnp.asarray(np.ones(cap, np.float32))
    rows = []
    n = 10 if quick else 30
    for M in (8, 16, 32):
        src = jnp.asarray(rng.normal(0, 0.5, (M, F + 1, C))
                          .astype(np.float32))
        sm = jnp.asarray(np.ones(M, np.float32))
        f = lambda: greedytl(x, y, m, src, sm, num_classes=C)[0]
        jax.block_until_ready(f())
        t0 = time.time()
        for _ in range(n):
            jax.block_until_ready(f())
        rows.append((f"greedytl_M{M}", (time.time() - t0) / n * 1e6,
                     f"cap={cap} factorized-LOO"))
    return rows


def _deep_greedy_problem(cap=160, n_src=12, seed=0):
    """Deep-accepting GreedyTL problem at the production shape: n_src
    sources each explain a disjoint feature block of the true boundary, so
    greedy selection keeps accepting (depth == n_src at k_max=16)."""
    import jax.numpy as jnp
    F, C, M = 54, 7, 16
    r = np.random.default_rng(seed)
    src = np.zeros((M, F + 1, C), np.float32)
    sm = np.zeros(M, np.float32)
    w_total = np.zeros((F + 1, C), np.float32)
    for i, blk in enumerate(np.array_split(np.arange(F), n_src)):
        w = np.zeros((F + 1, C), np.float32)
        w[blk] = r.normal(0, 1.0, (len(blk), C))
        src[i] = w
        sm[i] = 1.0
        w_total += w
    x = r.normal(size=(cap, F)).astype(np.float32)
    y = np.argmax(x @ w_total[:-1] + w_total[-1], axis=1).astype(np.int32)
    return tuple(jnp.asarray(v) for v in
                 (x, y, np.ones(cap, np.float32), src, sm))


def bench_greedytl_incremental(quick: bool):
    """Incremental Cholesky carry vs the refactorize-per-step PR-2 path
    (``incremental=False``): warm wall-clock at greedy depths 4/8/16 on a
    deep-accepting production-shape problem (cap=160 -> R=1120, D=23,
    M=16), per-refine jitted dispatch counts, and the ``loo_trials``
    autotuner table. Updates results/benchmarks/greedytl_incremental.json
    and the repo-level BENCH_greedytl.json trajectory (the refine/dispatch
    numbers are refreshed; the recorded paper_tables cold/warm CPU wall
    times are carried over, no longer measured)."""
    import jax
    import jax.numpy as jnp
    from benchmarks.paper_tables import RESULTS_DIR
    from repro.core.dispatch import dispatch_scope
    from repro.core.greedytl import (greedytl, greedytl_fleet,
                                     greedytl_fleet_stacked)
    from repro.kernels import ops as kernel_ops

    C, M, cap = 7, 16, 160
    x, y, m, src, sm = _deep_greedy_problem(cap=cap)
    n = 10 if quick else 30
    rows, refine = [], {}
    for k_max in (4, 8, 16):
        per, depth = {}, 0
        for label, inc in (("incremental", True), ("refactor", False)):
            f = lambda: greedytl(x, y, m, src, sm, num_classes=C,
                                 k_max=k_max, incremental=inc)
            w_, sel = f()
            jax.block_until_ready(w_)
            depth = int(np.asarray(sel).sum())
            t0 = time.time()
            for _ in range(n):
                jax.block_until_ready(f()[0])
            per[label] = (time.time() - t0) / n * 1e6
        speedup = per["refactor"] / per["incremental"]
        refine[f"k_max_{k_max}"] = {
            "incremental_us": round(per["incremental"]),
            "refactor_us": round(per["refactor"]),
            "depth": depth, "speedup": round(speedup, 2)}
        rows.append((f"greedytl_inc_k{k_max}", per["incremental"],
                     f"depth={depth} speedup={speedup:.2f}x vs refactor"))

    # accepting k candidates must still be ONE dispatch per entry point
    with dispatch_scope() as d1:
        jax.block_until_ready(greedytl(x, y, m, src, sm, num_classes=C)[0])
    L = 2
    xf, yf, mf = (jnp.stack([v] * L) for v in (x, y, m))
    with dispatch_scope() as d2:
        jax.block_until_ready(
            greedytl_fleet(xf, yf, mf, src, sm, num_classes=C)[0])
    srcs, sms = (jnp.stack([v] * L) for v in (src, sm))
    with dispatch_scope() as d3:
        jax.block_until_ready(greedytl_fleet_stacked(
            xf, yf, mf, srcs, sms, num_classes=C)[0])
    dispatches = {**d1, **d2, **d3}

    # persist the kernel-selection table for the production trial shape
    entry = kernel_ops.autotune_loo_trials(cap * C, M + C, M, persist=True)
    rows.append(("loo_trials_autotune",
                 min(entry["timings_us"].values()),
                 f"{kernel_ops.autotune_key(cap * C, M + C, M)} -> "
                 f"{entry['impl']}"))

    os.makedirs(RESULTS_DIR, exist_ok=True)
    path = os.path.join(RESULTS_DIR, "greedytl_incremental.json")
    payload = {}
    if os.path.exists(path):
        with open(path) as f:
            payload = json.load(f)
    payload["description"] = (
        "Before/after record for the incremental-factor GreedyTL PR: the "
        "greedy while_loop carries the active-set Cholesky factor across "
        "accepted steps (border update) instead of refactorizing; "
        "'refactor' is the in-tree incremental=False oracle (the PR-2 "
        "path). Deep-accepting problem, cap=160, M=16, warm jit, CI-class "
        "container.")
    payload["refine_us_per_call"] = refine
    payload["dispatches_per_deep_refine"] = dispatches
    payload["autotune"] = {"backend": jax.default_backend(),
                           "key": kernel_ops.autotune_key(cap * C, M + C,
                                                          M),
                           "entry": entry}
    with open(path, "w") as f:
        json.dump(payload, f, indent=1)
        f.write("\n")

    # repo-level trajectory (pr1/pr2 history seeded from
    # results/benchmarks/greedytl_factorized.json)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    bench_path = os.path.join(root, "BENCH_greedytl.json")
    traj = {"description": (
        "paper_tables --quick wall-clock and deep-refine latency across "
        "PRs; updated by benchmarks/run.py bench_greedytl_incremental "
        "(bench-smoke CI refreshes the refine numbers; table timings come "
        "from full local runs)."), "trajectory": []}
    if os.path.exists(bench_path):
        with open(bench_path) as f:
            traj = json.load(f)
    deep = refine["k_max_16"]
    entry_row = {"label": "pr7_incremental_carry",
                 "deep_refine_us": deep["incremental_us"],
                 "deep_refine_speedup_vs_refactor": deep["speedup"],
                 "deep_refine_depth": deep["depth"]}
    prev = {r["label"]: r for r in traj["trajectory"]}
    old = prev.get("pr7_incremental_carry", {})
    for k in ("paper_tables_quick_cold_s", "paper_tables_quick_warm_s"):
        if k in old:
            entry_row[k] = old[k]
    traj["trajectory"] = [r for r in traj["trajectory"]
                          if r["label"] != entry_row["label"]]
    traj["trajectory"].append(entry_row)
    with open(bench_path, "w") as f:
        json.dump(traj, f, indent=1)
        f.write("\n")
    return rows


def bench_fleet_engine(quick: bool):
    """Fleet vs loop engine: warm per-scenario wall-clock and per-window
    jitted dispatch counts (the fleet engine is O(1) per window)."""
    import dataclasses

    from repro.core import fleet, htl
    from repro.core.scenario import ScenarioConfig, run_sweep
    from repro.data.synthetic_covtype import make_covtype_like

    data = make_covtype_like(seed=0)
    windows = 6 if quick else 20
    rows = []
    for algo in ("star", "a2a"):
        base = ScenarioConfig(windows=windows, eval_every=windows, algo=algo,
                              tech="wifi")
        times = {}
        for engine in ("loop", "fleet"):
            cfgs = [dataclasses.replace(base, engine=engine, seed=s)
                    for s in (1, 2)]
            run_sweep(cfgs, data)       # warm the jit cache on these seeds
            t0 = time.time()
            run_sweep(cfgs, data)
            times[engine] = (time.time() - t0) / 2 * 1e6
        # dispatch count per window: loop pays one train + (a2a) one refine
        # per DC; fleet pays one of each per window regardless of fleet size
        counts = {"loop": 0, "fleet": 0}
        orig_train, orig_fleet = htl.train_svm, fleet.train_svm_fleet

        def count_loop(*a, **k):
            counts["loop"] += 1
            return orig_train(*a, **k)

        def count_fleet(*a, **k):
            counts["fleet"] += 1
            return orig_fleet(*a, **k)

        try:
            htl.train_svm, fleet.train_svm_fleet = count_loop, count_fleet
            run_sweep([dataclasses.replace(base, engine="loop", seed=3),
                       dataclasses.replace(base, engine="fleet", seed=3)],
                      data)
        finally:
            htl.train_svm, fleet.train_svm_fleet = orig_train, orig_fleet
        rows.append((f"scenario_{algo}_fleet", times["fleet"],
                     f"loop_us={times['loop']:.0f} "
                     f"speedup={times['loop'] / times['fleet']:.2f}x "
                     f"train_dispatches_loop={counts['loop']} "
                     f"fleet={counts['fleet']} ({windows} windows)"))
    return rows


def bench_stacked_sweep(quick: bool):
    """Replica-stacked sweep vs sequential per-seed runs (ROADMAP: batched
    multi-seed rounds) — same configs, same results, fewer dispatches."""
    import dataclasses

    from repro.core.dispatch import dispatch_counts, reset_dispatch_counts
    from repro.core.scenario import ScenarioConfig, run_sweep
    from repro.data.synthetic_covtype import make_covtype_like

    data = make_covtype_like(seed=0)
    windows = 6 if quick else 20
    base = ScenarioConfig(windows=windows, eval_every=windows, algo="a2a",
                          tech="wifi")
    cfgs = [dataclasses.replace(base, seed=s) for s in range(4)]
    rows = []
    run_sweep(cfgs, data, stack_seeds=True)        # warm the jit cache
    times, counts = {}, {}
    for label, stack in (("sequential", False), ("stacked", True)):
        reset_dispatch_counts()
        t0 = time.time()
        run_sweep(cfgs, data, stack_seeds=stack)
        times[label] = (time.time() - t0) * 1e6
        c = dispatch_counts()
        counts[label] = sum(v for k, v in c.items() if "fleet" in k)
    rows.append(("sweep_stacked_4seeds", times["stacked"],
                 f"sequential_us={times['sequential']:.0f} "
                 f"speedup={times['sequential'] / times['stacked']:.2f}x "
                 f"dispatches={counts['stacked']} "
                 f"vs {counts['sequential']} ({windows} windows)"))
    return rows


def bench_fleet_scaling(quick: bool):
    """Million-DC fleet engine (DESIGN.md §10): wall-clock and bytes/DC
    across fleet sizes, scan engine vs per-window execution. Two
    per-window comparators: the PR-1 fleet engine driven one window at a
    time (per-DC Python objects + O(L^2) pairwise ledger events — measured
    up to 10^3 DCs, quadratically extrapolated above, where a single
    window already costs minutes) and the host-driven city round
    (run_city_perwindow: host draw/pack/upload + one dispatch + one sync
    per window). Writes results/benchmarks/fleet_scaling.json."""
    import resource

    from benchmarks.paper_tables import RESULTS_DIR
    from repro.core.cityscan import (city_fleet_pad, run_city,
                                     run_city_perwindow)
    from repro.core.energy import Ledger
    from repro.core.fleet import run_window_star
    from repro.core.htl import DC
    from repro.core.scenario import ScenarioConfig
    from repro.data.synthetic_covtype import NUM_CLASSES, make_covtype_like

    data = make_covtype_like(seed=0)
    W = 3 if quick else 6
    sizes = (100, 1000, 10_000) if quick else (100, 1000, 10_000, 100_000)
    fleet_measure_max = 1000
    K, iters = 4, 6
    x = data.x_train.astype(np.float32)
    y = data.y_train.astype(np.int32)
    F = x.shape[1]

    def fleet_engine_window_s(L):
        rng = np.random.default_rng(0)
        idx = rng.integers(0, len(y), size=(L, K))
        dcs = [DC(f"SM{i + 1}", x[idx[i]], y[idx[i]]) for i in range(L)]

        def once(prev):
            return run_window_star(dcs, prev, Ledger(), "wifi", cap=160,
                                   num_classes=NUM_CLASSES,
                                   n_subsample=None,
                                   rng=np.random.default_rng(1))
        prev = once(None)                  # warm the jit at this shape
        t0 = time.time()
        once(prev)
        return time.time() - t0

    fleet_window_s = {}
    for L in sizes:
        if L <= fleet_measure_max:
            fleet_window_s[L] = (fleet_engine_window_s(L), True)
        else:
            # O(L^2) pairwise ledger events dominate: scale the largest
            # measured size quadratically (documented as extrapolated)
            base_L = max(k for k in fleet_window_s)
            base_s = fleet_window_s[base_L][0]
            fleet_window_s[L] = (base_s * (L / base_L) ** 2, False)

    rows = []
    per_size = {}
    for L in sizes:
        cfg = ScenarioConfig(windows=W, eval_every=1, algo="star",
                             engine="scan", tech="wifi", fleet_size=L,
                             obs_per_dc=K, train_iters=iters)
        run_city(cfg, data)                # warm (compile at this shape)
        t0 = time.time()
        r_scan = run_city(cfg, data)
        scan_s = time.time() - t0
        run_city_perwindow(cfg, data)
        t0 = time.time()
        run_city_perwindow(cfg, data)
        pw_s = time.time() - t0
        fw_s, measured = fleet_window_s[L]
        speedup_fleet = fw_s * W / scan_s
        per_size[str(L)] = {
            "padded_dcs": city_fleet_pad(L),
            "scan_wall_s": round(scan_s, 4),
            "scan_per_window_s": round(scan_s / W, 4),
            "perwindow_city_wall_s": round(pw_s, 4),
            "fleet_engine_window_s": round(fw_s, 4),
            "fleet_engine_measured": measured,
            "speedup_scan_vs_fleet_engine": round(speedup_fleet, 1),
            "speedup_scan_vs_perwindow_city": round(pw_s / scan_s, 2),
            "peak_rss_mb": round(
                resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0,
                1),
            "final_f1": round(r_scan.f1_curve[-1], 4),
        }
        tag = "" if measured else "(extrap)"
        rows.append((f"fleet_scaling_L{L}", scan_s * 1e6,
                     f"perwindow_s={pw_s:.2f} "
                     f"fleet_window_s={fw_s:.1f}{tag} "
                     f"speedup_vs_fleet={speedup_fleet:.0f}x "
                     f"({W} windows)"))

    payload = {
        "windows": W,
        "obs_per_dc": K,
        "train_iters": iters,
        "sizes": list(sizes),
        "per_size": per_size,
        # device-resident footprint per DC inside the scan (window block
        # x/y/m + base model) — constant across fleet sizes AND windows
        "scan_device_bytes_per_dc": 4 * (K * F + 2 * K
                                         + (F + 1) * NUM_CLASSES),
        # the per-window pattern re-uploads every DC's x/y/m each window
        "perwindow_upload_bytes_per_dc_per_window": 4 * (K * F + 2 * K),
        "note": "fleet_engine_window_s beyond 1000 DCs is extrapolated "
                "quadratically from the largest measured size (pairwise "
                "ledger events are O(L^2)); peak_rss_mb is the process "
                "high-water mark, sizes run in increasing order",
    }
    os.makedirs(RESULTS_DIR, exist_ok=True)
    with open(os.path.join(RESULTS_DIR, "fleet_scaling.json"), "w") as f:
        json.dump(payload, f, indent=1)
    return rows


def bench_sweep_api(quick: bool):
    """Experiment-API smoke + timing: a tiny ``SweepSpec`` preset end to
    end through ``SweepSpec.run``, asserting the ``SweepResult`` JSON
    round-trip and parity with the legacy ``run_sweep`` shim, then writing
    a timing row to results/benchmarks/sweep_api.json so the bench
    trajectory starts populating."""
    import numpy as np
    from benchmarks.paper_tables import RESULTS_DIR
    from repro.core.experiment import SweepResult, get_preset
    from repro.core.scenario import run_sweep
    from repro.data.synthetic_covtype import make_covtype_like

    data = make_covtype_like(seed=0)
    spec = get_preset("smoke", windows=4 if quick else 10)
    spec.run(data, stack="auto")                 # warm both jit paths
    spec.run(data, stack="off")
    t0 = time.time()
    result = spec.run(data, stack="auto")
    stacked_us = (time.time() - t0) * 1e6
    t0 = time.time()
    spec.run(data, stack="off")
    off_us = (time.time() - t0) * 1e6

    roundtrip = SweepResult.from_json(result.to_json())
    assert roundtrip == result, "SweepResult JSON round-trip drifted"

    # deprecation-shim parity: the same run list through legacy run_sweep
    legacy = run_sweep([c for _, c in spec.configs()], data,
                       stack_seeds=True)
    for rec, ref in zip(result.records, legacy):
        assert rec.f1_curve == list(ref.f1_curve)
        assert np.isclose(sum(e["mj"] for e in rec.events),
                          ref.energy_total)

    payload = {
        "preset": "smoke",
        "rows": len(spec.rows()),
        "runs": len(result.records),
        "windows": spec.configs()[0][1].windows,
        "stacked_us": round(stacked_us, 1),
        "sequential_us": round(off_us, 1),
        "labels": result.labels(),
        "converged_f1": {lbl: round(result.summary(lbl)["f1"], 4)
                         for lbl in result.labels()},
    }
    os.makedirs(RESULTS_DIR, exist_ok=True)
    with open(os.path.join(RESULTS_DIR, "sweep_api.json"), "w") as f:
        json.dump(payload, f, indent=1)
    return [("sweep_api_smoke", stacked_us,
             f"runs={payload['runs']} sequential_us={off_us:.0f} "
             f"json_roundtrip=ok shim_parity=ok")]


def bench_parallel_sweep(quick: bool):
    """Sharded sweep executor (DESIGN.md §7): partitioner balance on the
    full paper grid, bitwise parity of the devices backend, and the
    process backend's wall-clock speedup. n=1 vs n=2 worker pools share
    the same spawn/import/compile overhead structure, so their ratio is
    the genuine parallel speedup; the warm in-process sequential time is
    reported alongside for the overhead context."""
    from benchmarks.paper_tables import RESULTS_DIR
    from repro.core.experiment import get_preset
    from repro.core.parallel import partition_runs, run_cost
    from repro.data.synthetic_covtype import make_covtype_like

    data = make_covtype_like(seed=0)
    spec = get_preset("smoke", windows=4 if quick else 12)
    cfgs = [c for _, c in spec.configs()]

    ref = spec.run(data)                           # warm + parity reference
    t0 = time.time()
    seq_us = ((spec.run(data), time.time() - t0)[1]) * 1e6
    t0 = time.time()
    r_dev = spec.run(data, parallel="devices:n=8")
    dev_us = (time.time() - t0) * 1e6
    assert r_dev.to_json() == ref.to_json(), "devices backend parity drifted"

    t0 = time.time()
    r1 = spec.run(data, parallel="processes:n=1")
    p1_us = (time.time() - t0) * 1e6
    t0 = time.time()
    r2 = spec.run(data, parallel="processes:n=2")
    p2_us = (time.time() - t0) * 1e6
    assert r1.to_json() == ref.to_json(), "processes n=1 parity drifted"
    assert r2.to_json() == ref.to_json(), "processes n=2 parity drifted"
    speedup = p1_us / p2_us

    # partitioner balance on the full paper grid, 8 shards: max shard
    # cost over the achievable ideal max(total/n, largest atomic group) —
    # the same ideal the partitioner property test bounds against
    from repro.core.scenario import stack_groups
    grid = [c for _, c in get_preset("paper_tables").configs()]
    shards = partition_runs(grid, 8)
    costs = [sum(run_cost(grid[i]) for i in s) for s in shards]
    max_group = max(sum(run_cost(grid[i]) for i in g)
                    for g in stack_groups(grid))
    ideal = max(sum(costs) / len(shards), max_group)
    imbalance = max(costs) / ideal

    payload = {
        "preset": "smoke",
        "windows": cfgs[0].windows,
        "runs": len(cfgs),
        "sequential_warm_us": round(seq_us, 1),
        "devices_n8_us": round(dev_us, 1),
        "processes_n1_us": round(p1_us, 1),
        "processes_n2_us": round(p2_us, 1),
        "processes_speedup_n2_vs_n1": round(speedup, 3),
        "parity": "bitwise (JSON-identical across all backends)",
        "note": "speedup is compile/compute-bound by the host: tiny "
                "quick grids are dominated by per-worker jit compile, and "
                "XLA intra-op threading already spreads a sequential run "
                "over the cores, so small/low-core hosts sit near 1x; "
                "the backends target multi-device / many-core hosts",
        "paper_grid_shards8": {
            "groups": len(stack_groups(grid)),
            "nonempty_shards": len([s for s in shards if s]),
            "shard_costs": costs,
            "ideal_max_shard_cost": ideal,
            "balance_max_over_ideal": round(imbalance, 3),
        },
    }
    os.makedirs(RESULTS_DIR, exist_ok=True)
    with open(os.path.join(RESULTS_DIR, "parallel_sweep.json"), "w") as f:
        json.dump(payload, f, indent=1)
    return [
        ("parallel_sweep_processes2", p2_us,
         f"n1_us={p1_us:.0f} speedup={speedup:.2f}x "
         f"seq_warm_us={seq_us:.0f} parity=bitwise"),
        ("parallel_sweep_devices8", dev_us, "parity=bitwise (1 host dev "
         "unless XLA_FLAGS forces more)"),
        ("parallel_sweep_partition_paper8", 0.0,
         f"balance={imbalance:.3f}x_ideal "
         f"shard_costs={[int(c) for c in costs]}"),
    ]


def bench_hosts_launcher(quick: bool):
    """Multi-host launcher (DESIGN.md §8): local-channel dispatch timing
    (n=1 vs n=2 worker hosts share the same spawn/import/compile overhead
    structure, so their ratio is the genuine multi-host speedup), bitwise
    parity, and the wall-clock cost of surviving one SIGKILLed worker
    (retry overhead = fault run vs clean run at the same width)."""
    from benchmarks.paper_tables import RESULTS_DIR
    from repro.core.experiment import get_preset
    from repro.data.synthetic_covtype import make_covtype_like

    data = make_covtype_like(seed=0)
    spec = get_preset("smoke", windows=3 if quick else 8)
    ref = spec.run(data).to_json()                 # warm + parity reference

    timings = {}
    runs = {}
    grids = (("hosts_n1", "hosts:channel=local,n=1"),
             ("hosts_n2", "hosts:channel=local,n=2"),
             ("hosts_n2_fault",
              "hosts:channel=local,n=2,retries=1,backoff=0.01,"
              "inject_kill=0"))
    for label, backend in grids:
        t0 = time.time()
        runs[label] = spec.run(data, parallel=backend)
        timings[label] = (time.time() - t0) * 1e6
        assert runs[label].to_json() == ref, f"{label} parity drifted"
    fault_log = runs["hosts_n2_fault"].meta["launcher"]
    assert any(a["status"] == "crash"
               for s in fault_log["shards"] for a in s["attempts"]), \
        "fault run recorded no crash attempt"

    payload = {
        "preset": "smoke",
        "windows": spec.configs()[0][1].windows,
        "hosts_n1_us": round(timings["hosts_n1"], 1),
        "hosts_n2_us": round(timings["hosts_n2"], 1),
        "hosts_speedup_n2_vs_n1":
            round(timings["hosts_n1"] / timings["hosts_n2"], 3),
        "hosts_n2_fault_us": round(timings["hosts_n2_fault"], 1),
        "fault_overhead_vs_clean":
            round(timings["hosts_n2_fault"] / timings["hosts_n2"], 3),
        "fault_attempts": fault_log["attempts_total"],
        "parity": "bitwise (JSON-identical to sequential, clean and "
                  "under one injected worker SIGKILL)",
        "note": "local channel spawns a fresh interpreter per shard "
                "attempt, so quick grids are dominated by per-worker "
                "import+jit compile; the channel abstraction targets "
                "real multi-machine fleets (ssh/slurm)",
    }
    os.makedirs(RESULTS_DIR, exist_ok=True)
    with open(os.path.join(RESULTS_DIR, "hosts_launcher.json"), "w") as f:
        json.dump(payload, f, indent=1)
    return [
        ("hosts_launcher_n2", timings["hosts_n2"],
         f"n1_us={timings['hosts_n1']:.0f} "
         f"speedup={payload['hosts_speedup_n2_vs_n1']:.2f}x "
         f"parity=bitwise"),
        ("hosts_launcher_fault_retry", timings["hosts_n2_fault"],
         f"overhead={payload['fault_overhead_vs_clean']:.2f}x_clean "
         f"attempts={fault_log['attempts_total']} parity=bitwise"),
    ]


def bench_sweep_service(quick: bool):
    """Sweep service (DESIGN.md §12): what streaming, caching and the
    metrics plumbing actually buy/cost. Three headline numbers —
    time-to-first-shard over the stream vs the all-shards barrier of the
    launcher path (the latency the NDJSON stream removes), cold submit
    vs exact-cache-hit wall time, and the per-call overhead of the statsd
    counters the dispatch path now emits. Inline backend: shards run
    in-process, so the numbers measure the control plane, not worker
    spawn. Writes results/benchmarks/sweep_service.json."""
    import threading

    from benchmarks.paper_tables import RESULTS_DIR
    from repro.core.experiment import get_preset
    from repro.data.synthetic_covtype import make_covtype_like
    from repro.service.client import ServiceClient
    from repro.service.server import make_server
    from repro.service.statsd import Statsd

    data = make_covtype_like(seed=0)
    spec = get_preset("smoke", windows=3 if quick else 8)
    ref = spec.run(data).to_json()                 # warm + parity reference
    backend = "hosts:channel=inline,n=2"

    # barrier baseline (PR-5 path): nothing usable until every shard lands
    t0 = time.time()
    barrier = spec.run(data, parallel=backend)
    barrier_us = (time.time() - t0) * 1e6
    assert barrier.to_json() == ref, "barrier parity drifted"

    httpd, _service = make_server(backend=backend)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    client = ServiceClient(httpd.server_address[:2])

    # cold streamed pass: time-to-first-shard and total, over real HTTP
    t0 = time.time()
    sub = client.submit(spec, data)
    first_shard_us = None
    for event in client.stream_events(sub["job"]):
        if event["event"] == "shard" and first_shard_us is None:
            first_shard_us = (time.time() - t0) * 1e6
    cold_us = (time.time() - t0) * 1e6
    assert client.result_text(sub["job"]) == ref, "service parity drifted"

    # exact-cache hit: same spec again, served bytes — no recompute
    t0 = time.time()
    hit = client.run(spec, data)
    hit_us = (time.time() - t0) * 1e6
    assert hit.meta["service"]["cached"], "second submit missed the cache"
    assert hit.to_json() == ref, "cache-hit parity drifted"
    httpd.shutdown()

    # statsd counter overhead (the per-attempt cost added to dispatch)
    sink = Statsd()
    n = 20_000
    t0 = time.time()
    for _ in range(n):
        sink.increment("bench.counter", tags={"kind": "ok"})
    statsd_us = (time.time() - t0) * 1e6 / n

    payload = {
        "preset": "smoke",
        "windows": spec.configs()[0][1].windows,
        "backend": backend,
        "barrier_total_us": round(barrier_us, 1),
        "stream_first_shard_us": round(first_shard_us, 1),
        "stream_total_us": round(cold_us, 1),
        "first_result_speedup_vs_barrier":
            round(barrier_us / first_shard_us, 3),
        "cache_hit_us": round(hit_us, 1),
        "cache_hit_speedup_vs_cold": round(cold_us / hit_us, 3),
        "statsd_increment_us": round(statsd_us, 3),
        "parity": "bitwise (streamed merge, cache hit and barrier all "
                  "JSON-identical to sequential)",
        "note": "inline backend isolates control-plane cost; "
                "time-to-first-shard is measured client-side over real "
                "HTTP from submit to the first NDJSON shard event",
    }
    os.makedirs(RESULTS_DIR, exist_ok=True)
    with open(os.path.join(RESULTS_DIR, "sweep_service.json"), "w") as f:
        json.dump(payload, f, indent=1)
    return [
        ("sweep_service_first_shard", first_shard_us,
         f"barrier_us={barrier_us:.0f} "
         f"speedup={payload['first_result_speedup_vs_barrier']:.2f}x "
         f"parity=bitwise"),
        ("sweep_service_cache_hit", hit_us,
         f"cold_us={cold_us:.0f} "
         f"speedup={payload['cache_hit_speedup_vs_cold']:.2f}x "
         f"parity=bitwise"),
        ("statsd_increment", statsd_us, f"n={n} tagged_counter"),
    ]


def bench_pareto(quick: bool):
    """Cost-accuracy Pareto auto-tuner (DESIGN.md §14): what halving
    pruning buys over the exhaustive grid. Runs the ``pareto`` preset
    through the exhaustive search (every candidate at full budget) and
    successive halving, reporting wall-clock and window-evaluation cost,
    recovered-frontier completeness (halving's frontier vs the
    exhaustive one), and the frontier itself (energy mJ vs F1 — the
    paper's 94%-for-2% story as a searched curve). Writes
    results/benchmarks/pareto.json."""
    from benchmarks.paper_tables import RESULTS_DIR
    from repro.core.experiment import get_preset
    from repro.core.pareto import get_search
    from repro.data.synthetic_covtype import make_covtype_like

    data = make_covtype_like(seed=0)
    spec = get_preset("pareto", windows=8 if quick else 24,
                      n_seeds=1 if quick else 2)
    searches = {"exhaustive": "exhaustive",
                "halving": "halving:rungs=3,keep=0.5"}
    results, walls = {}, {}
    for label, s in searches.items():
        search = get_search(s)
        search.run(spec, data)             # warm the jit at rung shapes
        t0 = time.time()
        results[label] = search.run(spec, data)
        walls[label] = (time.time() - t0) * 1e6

    ex, hv = results["exhaustive"], results["halving"]
    ex_front = ex.frontier_labels()
    recovered = [lbl for lbl in hv.frontier_labels() if lbl in ex_front]
    completeness = len(recovered) / len(ex_front)
    payload = {
        "preset": "pareto",
        "rows": len(spec.rows()),
        "windows": spec.rows()[0][1].windows,
        "seeds": max(1, len(spec.seeds)),
        "searches": searches,
        "exhaustive_wall_us": round(walls["exhaustive"], 1),
        "halving_wall_us": round(walls["halving"], 1),
        "halving_speedup": round(walls["exhaustive"] / walls["halving"],
                                 3),
        "halving_cost": hv.cost,
        "exhaustive_cost": ex.cost,
        "frontier_completeness": completeness,
        "frontier": [p.as_dict() for p in ex.frontier],
        "halving_frontier": [p.as_dict() for p in hv.frontier],
        "halving_ledger_counts": hv.dominated_counts(),
        "schedule": hv.schedule,
        "note": "completeness = |halving frontier ∩ exhaustive frontier|"
                " / |exhaustive frontier| (pareto-smoke gates it at 1.0 "
                "on the smoke budget); costs are window-evaluations "
                "including the final bitwise frontier rerun",
    }
    os.makedirs(RESULTS_DIR, exist_ok=True)
    with open(os.path.join(RESULTS_DIR, "pareto.json"), "w") as f:
        json.dump(payload, f, indent=1)
    return [
        ("pareto_halving", walls["halving"],
         f"exhaustive_us={walls['exhaustive']:.0f} "
         f"speedup={payload['halving_speedup']:.2f}x "
         f"completeness={completeness:.2f} "
         f"frontier={len(ex_front)}/{len(spec.rows())}"),
        ("pareto_frontier_cost", float(hv.cost["evals_windows"]),
         f"exhaustive_windows={hv.cost['exhaustive_windows']} "
         f"savings={hv.cost['savings_pct']}%"),
    ]


def bench_realism(quick: bool):
    """Realism axis (DESIGN.md §13): what churn, drift and byzantine
    collectors cost. Runs the fleet engine once per knob against a shared
    clean baseline and reports the F1/energy deltas plus the wall-clock
    overhead of each realism path (drift rewrites the stream host-side;
    churn adds a ledger sweep per window; trim swaps the combine). Writes
    results/benchmarks/realism.json."""
    import dataclasses

    from benchmarks.paper_tables import RESULTS_DIR
    from repro.core.scenario import ScenarioConfig, run_scenario
    from repro.data.mobility import generate_trace
    from repro.data.synthetic_covtype import make_covtype_like

    data = make_covtype_like(seed=0)
    W = 4 if quick else 10
    base_cfg = ScenarioConfig(windows=W, eval_every=1, algo="a2a",
                              tech="wifi", engine="fleet", seed=0)
    trace = generate_trace(os.path.join("results", "traces"), windows=W,
                           mules=6, sensors=36, seed=0)
    knobs = [
        ("baseline", {}),
        ("churn_batt12", {"battery_mj": 12.0}),
        ("drift_rotate_prior", {"drift": "rotate_prior"}),
        ("byz30_mean", {"byz_frac": 0.3}),
        ("byz30_trim25", {"byz_frac": 0.3,
                          "robust_agg": "trim:frac=0.25"}),
        ("mobility_trace", {"collection": f"trace_file:path={trace}"}),
    ]
    rows, per_knob = [], {}
    results = {}
    for name, kw in knobs:
        cfg = dataclasses.replace(base_cfg, **kw)
        run_scenario(cfg, data)            # warm the jit at this shape
        t0 = time.time()
        results[name] = run_scenario(cfg, data)
        per_knob[name] = {"wall_us": round((time.time() - t0) * 1e6, 1)}
    base = results["baseline"]
    for name, kw in knobs:
        r = results[name]
        churned = sum(1 for e in r.ledger.events
                      if e["purpose"] == "churn")
        per_knob[name].update({
            "final_f1": round(r.f1_curve[-1], 4),
            "f1_delta_vs_baseline": round(r.f1_curve[-1]
                                          - base.f1_curve[-1], 4),
            "energy_mj": round(r.energy_total, 1),
            "energy_delta_vs_baseline": round(r.energy_total
                                              - base.energy_total, 1),
            "churn_events": churned,
        })
        overhead = (per_knob[name]["wall_us"]
                    / per_knob["baseline"]["wall_us"])
        rows.append((f"realism_{name}", per_knob[name]["wall_us"],
                     f"f1={r.f1_curve[-1]:.3f} "
                     f"dE={per_knob[name]['energy_delta_vs_baseline']:+.1f}mJ "
                     f"churn={churned} overhead={overhead:.2f}x"))

    payload = {
        "windows": W,
        "base": {"algo": base_cfg.algo, "tech": base_cfg.tech,
                 "engine": base_cfg.engine, "seed": base_cfg.seed},
        "trace_file": trace,
        "per_knob": per_knob,
        "note": "wall_us is one warm run_scenario call; deltas are "
                "against the clean baseline row at the same windows/seed "
                "(negative churn energy delta = depleted mules stopped "
                "spending; trim vs mean shows the robust-combine recovery "
                "under 30% mislabelled collection)",
    }
    os.makedirs(RESULTS_DIR, exist_ok=True)
    with open(os.path.join(RESULTS_DIR, "realism.json"), "w") as f:
        json.dump(payload, f, indent=1)
    return rows


def bench_htl_trainer(quick: bool):
    """Paper's technique at LM scale: DCN traffic vs sync baseline."""
    import dataclasses

    import jax
    from repro.configs import get_config
    from repro.configs.base import HTLConfig, OptimizerConfig
    from repro.core.htl_trainer import HTLTrainer
    from repro.models import build_model

    cfg = get_config("llama3.2-3b").reduced()
    cfg = dataclasses.replace(cfg, num_layers=2, d_model=64, num_heads=2,
                              num_kv_heads=2, head_dim=32, d_ff=128,
                              vocab_size=256)
    model = build_model(cfg)
    rows = []
    for mode in ("a2a", "star"):
        for H in (8, 32):
            htl = HTLConfig(mode=mode, num_collectors=4, local_steps=H)
            tr = HTLTrainer(model, OptimizerConfig(), htl)
            t = tr.round_traffic_bytes()
            rows.append((f"htl_traffic_{mode}_H{H}", 0.0,
                         f"ratio_vs_sync={t['traffic_ratio_vs_sync']:.3f}"))
    return rows


def bench_dryrun_summary(quick: bool):
    """Roofline headline numbers from the cached dry-run records."""
    from repro.roofline.report import analyze, load_records
    d = os.path.join(os.path.dirname(__file__), "..", "results", "dryrun")
    rows = []
    if not os.path.isdir(d):
        return [("dryrun_summary", 0.0, "no dry-run cache; run "
                 "python -m repro.launch.dryrun --all")]
    recs = [r for r in load_records(d) if r["status"] == "ok"]
    doms = {}
    for r in recs:
        a = analyze(r)
        doms[a["dominant"]] = doms.get(a["dominant"], 0) + 1
    rows.append(("dryrun_combos_ok", 0.0, f"n={len(recs)} dominant={doms}"))
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--skip-tables", action="store_true")
    ap.add_argument("--engine", default="fleet", choices=("fleet", "loop"),
                    help="scenario learning-round engine for the tables")
    args, _ = ap.parse_known_args()

    from repro.core.compile_cache import use_compile_cache
    use_compile_cache()
    print("name,us_per_call,derived")
    sections = [bench_sweep_api, bench_parallel_sweep,
                bench_hosts_launcher, bench_sweep_service, bench_greedytl,
                bench_greedytl_incremental,
                bench_fleet_engine, bench_stacked_sweep,
                bench_fleet_scaling, bench_realism, bench_pareto,
                bench_kernels,
                bench_htl_trainer, bench_dryrun_summary]
    if not args.skip_tables:
        sections.insert(
            0, functools.partial(bench_paper_tables, engine=args.engine))
    for fn in sections:
        try:
            for name, us, derived in fn(args.quick):
                print(f"{name},{us:.1f},{derived}")
        except Exception as e:              # noqa: BLE001
            print(f"{getattr(fn, '__name__', 'bench_paper_tables')},0,"
                  f"ERROR:{e}")
            raise


if __name__ == "__main__":
    main()
