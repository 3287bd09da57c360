#!/usr/bin/env python
"""Smoke run of the system's main path on a TPU, in one process.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # four chips: the sharded city engine

One chip runs three phases through the entry points a user calls:

1. the city fleet (``get_preset("city")`` + ``SweepSpec.run``) at 250,000
   DCs: one ``city_scan`` dispatch, a sane F1 curve, energy equal to the
   closed-form charge, peak device memory;
2. the paper-scale scan engine on the ``smoke`` preset, held to
   tests/golden/smoke_golden.json (energy to 1e-6 relative, F1 to 0.01
   absolute for every label and run);
3. the sweep service (``make_server`` with its default inline backend +
   ``ServiceClient``) answering the same ``smoke`` spec twice: the
   streamed result byte-identical to phase 2's, the resubmit a cache hit.

``--chips 4`` runs only the city engine sharded over ``fleet_mesh(4)``:
10^6 DCs over four shards (peak memory per device), then 250,000 DCs on
four shards against one, which must agree bitwise: confusion counts,
centres, global models and source pools (DESIGN.md §10).

Cold (with compile) and warm wall times are smoke timings, not benchmark
numbers. Any failed check raises and exits non-zero. Where JAX finds no
TPU the script exits non-zero before any phase; it never falls back to
the CPU. The last line of stdout is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

from repro.core.cityscan import city_fleet_pad, city_outputs  # noqa: E402
from repro.core.dispatch import (dispatch_counts,  # noqa: E402
                                 reset_dispatch_counts)
from repro.core.energy import (INDEX_BYTES, MODEL_BYTES,  # noqa: E402
                               OBS_BYTES, TECHS)
from repro.core.experiment import get_preset  # noqa: E402
from repro.core.metrics import f_measure_from_confusion  # noqa: E402
from repro.data.synthetic_covtype import make_covtype_like  # noqa: E402

GOLDEN = os.path.join(ROOT, "tests", "golden", "smoke_golden.json")
CITY_FLEET = 250_000
CITY_FLEET_4CHIPS = 1_000_000
CITY_WINDOWS = 3
CITY_F1_FLOOR = 0.15          # scripts/city_smoke.py's sanity floor
F1_ATOL = 0.01                # chip vs the CPU-pinned golden F1
ENERGY_RTOL = 1e-6            # the ledger is host-side arithmetic


class SmokeFailure(RuntimeError):
    """A phase's output is wrong."""


def require(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def timed(fn, *args, **kwargs):
    t0 = time.perf_counter()
    out = fn(*args, **kwargs)
    return out, time.perf_counter() - t0


def require_tpu(chips: int):
    """The devices, or exit non-zero: this script never runs on the CPU."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found "
                 f"{devices[0].platform!r}")
    if len(devices) < chips:
        sys.exit(f"chip_smoke: --chips {chips} needs {chips} TPU devices, "
                 f"JAX found {len(devices)}")
    return devices


def peak_bytes(device) -> "int | None":
    return (device.memory_stats() or {}).get("peak_bytes_in_use")


# ---------------------------------------------------------------------------
# phase 1: the city fleet
# ---------------------------------------------------------------------------

def city_energy_mj(events, fleet_size: int, obs_per_dc: int, windows: int):
    """(collection, learning) mJ of a churn-free StarHTL city run over WiFi,
    from closed-form hop counts: every DC sends ``obs_per_dc`` observations
    over 802.15.4; the entropy index goes over all L(L-1) ordered pairs,
    relayed (2 hops) unless the AP is an endpoint; the centre id broadcast
    and model gather take L-1 hops when the centre is the AP, else
    1 + 2(L-2). The centre's role per window is read from the ledger's
    ``center id`` event."""
    L, sensor, wifi = fleet_size, TECHS["802.15.4"], TECHS["wifi"]

    def pair(tech, nbytes):
        return tech.tx_mj(nbytes) + tech.rx_mj(nbytes)

    collection = windows * L * pair(sensor, obs_per_dc * OBS_BYTES)
    centre_is_ap = [e["n_tx"] == L - 1 for e in events
                    if e["what"] == "center id"]
    require(len(centre_is_ap) == windows,
            f"expected {windows} centre-id events, got {len(centre_is_ap)}")
    learning = 0.0
    for is_ap in centre_is_ap:
        hops = L - 1 if is_ap else 1 + 2 * (L - 2)
        learning += (2 * (L - 1) ** 2 * pair(wifi, INDEX_BYTES)
                     + hops * (pair(wifi, INDEX_BYTES)
                               + pair(wifi, MODEL_BYTES)))
    return collection, learning


def phase_city(data, *, fleet_size: int, windows: int) -> dict:
    """The city preset through ``SweepSpec.run``, cold then warm, sharded
    over every visible device; peak memory per device."""
    import jax

    spec = get_preset("city", fleet_size=fleet_size, windows=windows)
    cfg = spec.configs()[0][1]
    require(cfg.tech == "wifi" and cfg.battery_mj is None,
            "the closed-form energy check is for churn-free WiFi")
    times, results = [], []
    for _ in range(2):
        reset_dispatch_counts()
        result, dt = timed(spec.run, data)
        counts = dispatch_counts()
        require(counts.get("city_scan") == 1,
                f"expected exactly 1 city_scan dispatch, got {counts}")
        times.append(dt)
        results.append(result)
    rec = results[0].records[0]
    require(results[1].to_json() == results[0].to_json(),
            "two city runs of one spec differ")
    curve = rec.f1_curve
    require(len(curve) == windows and all(0.0 < v <= 1.0 for v in curve),
            f"malformed F1 curve {curve}")
    require(curve[-1] >= CITY_F1_FLOOR,
            f"final F1 {curve[-1]} below {CITY_F1_FLOOR}: the fleet did "
            f"not learn")
    want_c, want_l = city_energy_mj(rec.events, fleet_size, cfg.obs_per_dc,
                                    windows)
    got_c = sum(e["mj"] for e in rec.events if e["purpose"] == "collection")
    got_l = sum(e["mj"] for e in rec.events if e["purpose"] == "learning")
    np.testing.assert_allclose([got_c, got_l], [want_c, want_l], rtol=1e-9,
                               err_msg="city energy != closed-form charge")
    return {"f1_curve": curve, "collection_mj": got_c, "learning_mj": got_l,
            "cold_s": times[0], "warm_s": times[1],
            "peak_bytes_in_use": [peak_bytes(d) for d in jax.devices()]}


# ---------------------------------------------------------------------------
# phase 2: the paper-scale scan engine against the golden pins
# ---------------------------------------------------------------------------

def golden_f1_diff(result, golden: dict) -> float:
    """Largest |F1 - golden| over every label (converged F1 and curve) and
    every run (final F1); energies must match to ``ENERGY_RTOL``."""
    require(result.labels() == list(golden["per_label"]),
            f"labels {result.labels()} != golden")
    require(len(result.records) == golden["n_runs"], "run count != golden")
    diffs = []
    for lbl, want in golden["per_label"].items():
        s = result.summary(lbl)
        for k in ("energy_mj", "collection_mj", "learning_mj"):
            require(abs(s[k] - want[k]) <= ENERGY_RTOL * abs(want[k]),
                    f"{lbl} {k} {s[k]} != golden {want[k]}")
        require(len(s["f1_curve"]) == len(want["f1_curve"]),
                f"{lbl} F1 curve length != golden")
        diffs.append(abs(s["f1"] - want["f1"]))
        diffs += [abs(a - b) for a, b in zip(s["f1_curve"],
                                             want["f1_curve"])]
    for rec, want in zip(result.records, golden["per_run_final_f1"]):
        require((rec.label, rec.cfg.seed) == (want["label"], want["seed"]),
                f"run order != golden at {want}")
        diffs.append(abs(rec.f1_curve[-1] - want["final_f1"]))
    return max(diffs)


def phase_scan(golden: dict):
    """The ``smoke`` preset on ``engine="scan"`` at the golden's data seed,
    windows and seeds, cold then warm. Returns (spec, data, result,
    report)."""
    data = make_covtype_like(seed=golden["data_seed"])
    spec = get_preset("smoke", windows=golden["windows"],
                      n_seeds=golden["n_seeds"], engine="scan")
    result, cold = timed(spec.run, data)
    again, warm = timed(spec.run, data)
    require(again.to_json() == result.to_json(),
            "two scan-engine runs of one spec differ")
    diff = golden_f1_diff(result, golden)
    require(diff <= F1_ATOL, f"F1 differs from the golden by {diff} "
                             f"(> {F1_ATOL})")
    return spec, data, result, {"max_f1_diff": diff, "cold_s": cold,
                                "warm_s": warm}


# ---------------------------------------------------------------------------
# phase 3: the sweep service
# ---------------------------------------------------------------------------

def phase_service(spec, data, expected_json: str) -> dict:
    """Serve ``spec`` twice from an in-process server on its default
    (inline) backend; both answers must be ``expected_json`` byte for
    byte, the second from the result cache."""
    from repro.service.client import ServiceClient
    from repro.service.server import make_server

    httpd, _ = make_server()
    thread = threading.Thread(target=httpd.serve_forever, daemon=True)
    thread.start()
    try:
        client = ServiceClient(httpd.server_address[:2])
        first, t_first = timed(client.run, spec, data)
        second, t_second = timed(client.run, spec, data)
    finally:
        httpd.shutdown()
        httpd.server_close()
        thread.join(timeout=30)
    require(not first.meta["service"]["cached"],
            "first submit was served from the cache")
    require(first.to_json() == expected_json,
            "streamed result differs from the in-process run")
    require(second.meta["service"]["cached"], "resubmit missed the cache")
    require(second.to_json() == expected_json,
            "cached result differs from the in-process run")
    return {"first_s": t_first, "resubmit_s": t_second}


# ---------------------------------------------------------------------------
# four chips: the sharded city engine
# ---------------------------------------------------------------------------

def phase_shard_invariance(data, *, fleet_size: int, windows: int,
                           shards: int) -> dict:
    """``shards`` shards against one on the same host: confusion counts,
    elected centres, global models, source pools and F1 curves must be
    identical."""
    from repro.sharding.partitioning import dc_shards

    cfg = get_preset("city", fleet_size=fleet_size,
                     windows=windows).configs()[0][1]
    require(dc_shards(city_fleet_pad(fleet_size), shards) == shards,
            f"{shards} shards do not divide the padded fleet or exceed "
            f"the devices")
    one, t1 = timed(city_outputs, cfg, data, max_shards=1)
    many, tn = timed(city_outputs, cfg, data, max_shards=shards)
    f1 = [f_measure_from_confusion(c.astype(np.int64)) for c in one.cms]
    fn = [f_measure_from_confusion(c.astype(np.int64)) for c in many.cms]
    require(np.array_equal(one.cms, many.cms),
            "confusion counts differ by shards")
    require(np.array_equal(one.centres, many.centres),
            "elected centres differ by shards")
    require(np.array_equal(one.models, many.models),
            "global models differ by shards")
    require(np.array_equal(one.pools, many.pools),
            "source pools differ by shards")
    require(f1 == fn, "F1 curves differ by shards")
    return {"f1_curve": f1, "centres": [int(c) for c in one.centres],
            "one_shard_s": t1, f"{shards}_shards_s": tn}


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    devices = require_tpu(args.chips)
    from repro.core.compile_cache import use_compile_cache
    from repro.kernels.ops import autotune_table

    print(f"compile cache: {use_compile_cache()}")
    print(f"devices: {len(devices)} x {devices[0].device_kind}")
    print("timings below are smoke timings (cold includes compile), not "
          "benchmark numbers")
    data = make_covtype_like(seed=0)
    if args.chips == 4:
        size = phase_city(data, fleet_size=CITY_FLEET_4CHIPS,
                          windows=CITY_WINDOWS)
        print(f"city {CITY_FLEET_4CHIPS} DCs over {len(devices)} chips: "
              f"{json.dumps(size)}")
        inv = phase_shard_invariance(data, fleet_size=CITY_FLEET,
                                     windows=CITY_WINDOWS, shards=4)
        print(f"city {CITY_FLEET} DCs, 4 shards == 1 shard: "
              f"{json.dumps(inv)}")
    else:
        city = phase_city(data, fleet_size=CITY_FLEET, windows=CITY_WINDOWS)
        print(f"city {CITY_FLEET} DCs: {json.dumps(city)}")
        with open(GOLDEN) as f:
            golden = json.load(f)
        spec, sdata, result, scan = phase_scan(golden)
        print(f"scan engine, smoke preset vs golden: {json.dumps(scan)}")
        service = phase_service(spec, sdata, result.to_json())
        print(f"sweep service, smoke preset twice: {json.dumps(service)}")
    for d in devices[:args.chips]:
        print(f"memory_stats device {d.id}: {json.dumps(d.memory_stats())}")
    for key, e in autotune_table().items():
        print(f"loo_trials {key}: {e['impl']} block_r={e['block_r']} "
              f"timings_us={json.dumps(e['timings_us'])}")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
