"""The scenarios a configuration file names, as the program's
``ScenarioConfig`` rows: a ``preset`` with its arguments, its rows in the
preset's own order at the fixed ``scenario_seed``, leaving out the rows
whose ``algo`` is in ``skip_algos``. A closed loop cycles them.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Dict, List


@dataclass(frozen=True)
class Scenario:
    label: str
    cfg: Any                   # repro.core.scenario.ScenarioConfig
    key: str                   # the same key is the same scenario

    def plain(self) -> Dict[str, Any]:
        """The scenario as plain data, for the reference."""
        return dataclasses.asdict(self.cfg)


class Items:
    """An endless sequence of the ``cycle`` distinct scenarios, repeated."""

    def __init__(self, rows: List[Scenario]):
        self.rows = rows
        self.cycle = len(rows)
        self.i = 0

    def next(self) -> Scenario:
        i, self.i = self.i, self.i + 1
        return self.rows[i % len(self.rows)]


def items(config: Dict[str, Any]) -> Items:
    from repro.core.experiment import get_preset

    spec = get_preset(config["preset"], **config.get("preset_args", {}))
    skip = set(config.get("skip_algos", ()))
    rows = []
    for label, cfg in spec.configs():
        if cfg.algo in skip:
            continue
        cfg = dataclasses.replace(cfg, seed=int(config["scenario_seed"]))
        rows.append(Scenario(label, cfg, label))
    return Items(rows)
