"""The comparison that decides ``correct``.

Each sampled scenario the window finished is run again through the plain
reference (``bench/references/<reference>.py``), and the two answers are
compared. Held to the cell's limits (``bench/cells/<cell>.json``):

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

A missing, non-finite or mis-shaped answer reads as ``FAR`` (finite, so
the result line stays plain JSON).
"""
from __future__ import annotations

import math
from typing import Dict, List, Tuple

FAR = 1e9
COMPARED = ("f1_mean_gap", "energy_gap")


def _finite(values) -> bool:
    return all(math.isfinite(v) for v in values)


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


def compare(pairs: List[Tuple[object, dict]], reference, data,
            precision: str) -> Dict[str, float]:
    """The numbers over every (scenario, program answer) pair, against
    the reference computed in the configuration's ``precision``."""
    return numbers([gaps(answer, reference.answer(scenario.plain(), data,
                                                  precision))
                    for scenario, answer in pairs])


def judge(values: Dict[str, float], limits: Dict[str, float]) -> dict:
    """``{name: {"value", "limit"}}`` for every number compared; a number
    compared with no limit in the cell's file is an error, never a
    pass."""
    missing = sorted(set(COMPARED) - set(limits))
    if missing:
        raise KeyError(f"no limit for {missing} in the cell's file")
    return {k: {"value": values[k], "limit": limits[k]} for k in COMPARED}
