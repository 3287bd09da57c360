"""The comparison that decides ``correct``.

Each sampled scenario the window finished is run again through the plain
reference (``bench/references/<reference>.py``), and the two answers are
compared by the configuration's comparison
(``bench/comparisons/<comparison>.py``), which names the numbers it holds
to the cell's limits (``COMPARED``, limits in ``bench/cells/<cell>.json``)
and those it prints but holds to none (``PRINTED``).

A missing, non-finite or mis-shaped answer reads as ``FAR`` (finite, so
the result line stays plain JSON).
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

FAR = 1e9


def compare(pairs: List[Tuple[object, dict]], reference, data,
            precision: str, comparison) -> Dict[str, float]:
    """The comparison's numbers over every (scenario, program answer)
    pair, against the reference computed in the configuration's
    ``precision``."""
    return comparison.numbers([
        comparison.gaps(answer, reference.answer(scenario.plain(), data,
                                                 precision))
        for scenario, answer in pairs])


def judge(values: Dict[str, float], limits: Dict[str, float],
          compared: Sequence[str]) -> dict:
    """``{name: {"value", "limit"}}`` for every number ``compared``. A
    number compared with no limit in the cell's file is an error, never a
    pass; so is a limit that nothing compares, which guards nothing."""
    missing = sorted(set(compared) - set(limits))
    if missing:
        raise KeyError(f"no limit for {missing} in the cell's file")
    dead = sorted(set(limits) - set(compared))
    if dead:
        raise KeyError(f"the cell's file limits {dead}, which the "
                       f"comparison does not compare")
    return {k: {"value": values[k], "limit": limits[k]} for k in compared}
