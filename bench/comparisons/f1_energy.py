"""F1 and energy: what a user of the paper's scenarios reads.

* ``f1_mean_gap``: the mean gap between the program's and the
  reference's F1, over every evaluation point of the sampled scenarios.
  The F1 comes from the confusion counts the device returns for the
  global model of each window, so it reads the base SVMs, GreedyTL, the
  update of the global model and the evaluation;
* ``energy_gap``: the widest relative gap between the energy totals
  (collection and learning), the host's ledger.

Also read, and printed, but held to no limit: ``f1_gap``, the widest of
the F1 gaps. One near tie moves it as far as the control does, so no
limit lies three times apart from both (PERF.md).
"""
from __future__ import annotations

import math
from typing import Dict, List

from bench.check import FAR

COMPARED = ("f1_mean_gap", "energy_gap")
PRINTED = ("f1_gap",)


def _finite(values) -> bool:
    return all(math.isfinite(v) for v in values)


def answer(record) -> dict:
    """What a user reads of one ``RunRecord``: the F1 curve and the
    energy ledger's totals by purpose."""
    return {"f1_curve": [float(v) for v in record.f1_curve],
            "collection_mj": sum(e["mj"] for e in record.events
                                 if e["purpose"] == "collection"),
            "learning_mj": sum(e["mj"] for e in record.events
                               if e["purpose"] == "learning")}


def gaps(program: dict, reference: dict) -> Dict[str, list]:
    """Every per-point F1 gap and both relative energy gaps of one
    scenario (``FAR`` in place of what cannot be compared)."""
    a, b = program["f1_curve"], reference["f1_curve"]
    f1 = [abs(x - y) for x, y in zip(a, b)]
    if len(a) != len(b) or not a or not _finite(f1):
        f1 = [FAR]
    energy = [abs(program[k] - reference[k]) / abs(reference[k])
              for k in ("collection_mj", "learning_mj")]
    if not _finite(energy):
        energy = [FAR]
    return {"f1": f1, "energy": energy}


def numbers(per_scenario: List[Dict[str, list]]) -> Dict[str, float]:
    if not per_scenario:
        return {"f1_gap": FAR, "f1_mean_gap": FAR, "energy_gap": FAR}
    f1 = [g for s in per_scenario for g in s["f1"]]
    return {"f1_gap": max(f1),
            "f1_mean_gap": FAR if FAR in f1 else sum(f1) / len(f1),
            "energy_gap": max(g for s in per_scenario for g in s["energy"])}
