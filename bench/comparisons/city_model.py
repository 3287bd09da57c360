"""The global model and its sources: what a user of a city fleet reads.

At city scale every Data Collector's base model competes for GreedyTL's
source pool (the program's fleet-wide screen), so these numbers read the
work of the whole fleet, not of a few DCs:

* ``model_gap``: the mean, over the compared windows, of
  ||w_program - w_reference||_F / ||w_reference||_F of the global model
  after the window;
* ``pool_miss``: the share of the program's filled source-pool slots
  whose DC is not in the reference's pool that window;
* ``energy_gap``: the widest relative gap between the energy totals
  (collection and learning) of the compared windows, as in ``f1_energy``.

Also read, and printed, but held to no limit: ``f1_mean_gap`` (the mean
F1 gap over the compared windows) and ``centre_miss`` (the share of
windows whose elected centre differs).

The screen and GreedyTL decide: where the reference finds a decision's
two sides within 1% of each other, the program's rounding may take the
other side. The reference then also answers with that side taken (its
``tied`` paths), and the model, pool and F1 are read against the path
whose chosen sources are the program's (``gaps``).

Only the first ``WINDOWS`` windows of a scenario are compared: the
program's output at window t depends on windows up to t alone, so that
prefix is what the timed path produced, and the plain reference
(``bench/references/city.py``, which reads ``WINDOWS`` from here) need
not train the whole scenario. Two windows cover the first (no global
model yet) and one update of the global model.
"""
from __future__ import annotations

import math
from typing import Dict, List

import numpy as np

from bench.check import FAR

WINDOWS = 2
EVENTS_PER_WINDOW = 4       # collection, entropy index, centre id, m0 gather

COMPARED = ("model_gap", "pool_miss", "energy_gap")
PRINTED = ("f1_mean_gap", "centre_miss")


def answer(record) -> dict:
    """What a user reads of one city ``RunRecord``, for the first
    ``WINDOWS`` windows: the global models, the source pools and centres,
    the F1 curve, and the ledger's totals by purpose. The city engine
    charges ``EVENTS_PER_WINDOW`` ledger events a window (no battery), in
    window order."""
    models = getattr(record, "models", None)
    sources = getattr(record, "sources", None)
    if models is None or sources is None:
        raise ValueError("the record carries no global models or sources: "
                         "the program does not report what this "
                         "comparison reads")
    events = record.events[:EVENTS_PER_WINDOW * WINDOWS]
    pools = np.asarray(sources["pool"])[:WINDOWS]
    chosen = np.asarray(sources["selected"])[:WINDOWS] > 0
    return {"models": np.asarray(models, np.float64)[:WINDOWS].tolist(),
            "pool": [list(map(int, p)) for p in pools],
            "selected": [sorted(map(int, p[c])) for p, c in zip(pools,
                                                                 chosen)],
            "centre": [int(c) for c in sources["centre"][:WINDOWS]],
            "f1_curve": [float(v) for v in record.f1_curve[:WINDOWS]],
            "collection_mj": sum(e["mj"] for e in events
                                 if e["purpose"] == "collection"),
            "learning_mj": sum(e["mj"] for e in events
                               if e["purpose"] == "learning")}


def _finite(values) -> bool:
    return all(math.isfinite(v) for v in values)


def _pool_miss(program: List[int], reference: List[int]) -> float:
    """1 - |P & R| / max(|P|, |R|) over the filled slots (gid >= 0): the
    share of the program's slots missing from the reference's pool when
    both fill as many, and a missing slot counts as a miss otherwise."""
    p = {g for g in program if g >= 0}
    r = {g for g in reference if g >= 0}
    if not p and not r:
        return 0.0
    return 1.0 - len(p & r) / max(len(p), len(r))


def gaps(program: dict, reference: dict) -> Dict[str, list]:
    """Per compared window: the model's relative gap, the pool's miss
    share, the F1 gap and whether the centre differs; and both relative
    energy gaps (``FAR`` in place of what cannot be compared). The
    model, pool and F1 are read against one of the reference's paths: its
    own, or one under ``tied`` (a near tie of the screen or of GreedyTL
    taken the other way), among those whose sources chosen each window
    are the program's, the closest to the program's models; against its
    own where none chose as the program did."""
    n = len(reference["models"])
    same = all(len(program[k]) == n == len(reference[k])
               for k in ("models", "pool", "selected", "centre", "f1_curve")
               ) and n > 0
    if not same:
        return {k: [FAR] for k in ("model", "pool", "energy", "f1",
                                   "centre")}
    a = np.asarray(program["models"], np.float64)
    paths = [p for p in [reference] + list(reference.get("tied", ()))
             if p["selected"] == program["selected"]] or [reference]
    model, ref = [FAR], reference
    for path in paths:
        b = np.asarray(path["models"], np.float64)
        if a.shape != b.shape:
            continue
        gap = [float(np.linalg.norm(x - y) / max(np.linalg.norm(y), 1e-30))
               for x, y in zip(a, b)]
        if _finite(gap) and (model == [FAR] or sum(gap) < sum(model)):
            model, ref = gap, path
    pool = [_pool_miss(p, r) for p, r in zip(program["pool"], ref["pool"])]
    f1 = [abs(x - y) for x, y in zip(program["f1_curve"], ref["f1_curve"])]
    if not _finite(f1):
        f1 = [FAR]
    energy = [abs(program[k] - reference[k]) / abs(reference[k])
              for k in ("collection_mj", "learning_mj")]
    if not _finite(energy):
        energy = [FAR]
    centre = [float(x != y) for x, y in zip(program["centre"],
                                            reference["centre"])]
    return {"model": model, "pool": pool, "energy": energy, "f1": f1,
            "centre": centre}


def _mean(values: List[float]) -> float:
    return FAR if not values or FAR in values else sum(values) / len(values)


def numbers(per_scenario: List[Dict[str, list]]) -> Dict[str, float]:
    if not per_scenario:
        return {k: FAR for k in COMPARED + PRINTED}

    def every(key):
        return [g for s in per_scenario for g in s[key]]
    return {"model_gap": _mean(every("model")),
            "pool_miss": _mean(every("pool")),
            "energy_gap": max(every("energy")),
            "f1_mean_gap": _mean(every("f1")),
            "centre_miss": _mean(every("centre"))}
