"""Closed loop: one caller runs one scenario at a time through
``SweepSpec.run`` and starts the next as soon as it returns.

The configuration names the scenarios (``bench/scenarios.py``): a
preset's rows in the preset's order, cycled. Set-up runs one cycle of
them (every shape the window meets), then the window runs them back to
back from the first.
The rate is every scenario-window completed over the whole time from the
window's start to the last completion.

Parameters (the mix's file, then the cell's): ``compare`` (how many
finished scenarios the check compares), ``trace_seconds``.
"""
from __future__ import annotations

import time

import numpy as np

from bench.scenarios import items

SPAN = "bench.scenario"


class Driver:
    span = SPAN

    def __init__(self, run):
        self.run = run
        self.items = items(run.cell.config)
        self.comparison = run.cell.comparison()
        self.done = []            # (scenario, t0, t1)
        self.first = {}           # scenario key -> (scenario, result)

    def _one(self, scenario):
        import jax
        from repro.core.experiment import SweepSpec

        spec = SweepSpec(self.run.cell.name, base=scenario.cfg,
                         label=scenario.label)
        with jax.profiler.TraceAnnotation(SPAN):
            return spec.run(self.run.data)

    def setup(self) -> None:
        for _ in range(self.items.cycle):
            self._one(self.items.next())

    def window(self, seconds: float) -> dict:
        t_start = time.perf_counter()
        t_end = t_start
        while t_end - t_start < seconds:
            scenario = self.items.next()
            t0 = time.perf_counter()
            result = self._one(scenario)
            t_end = time.perf_counter()
            self.done.append((scenario, t0, t_end))
            # one result per distinct scenario: repeats are the same answer
            self.first.setdefault(scenario.key, (scenario, result))
        elapsed = t_end - t_start
        windows = sum(s.cfg.windows for s, *_ in self.done)
        self.run.windows_done = windows
        per = [t1 - t0 for _, t0, t1 in self.done]
        return {"attempted": len(self.done), "failed": 0,
                "metrics": {"windows_per_s": windows / elapsed},
                "notes": [f"{len(self.done)} scenarios, {windows} windows "
                          f"in {elapsed!r} s; per scenario median "
                          f"{float(np.median(per))!r} s, max "
                          f"{max(per)!r} s"]}

    def outputs(self) -> list:
        """A sample drawn from the seed of the distinct scenarios that
        finished in the window, each with what the comparison reads of
        the result ``SweepSpec.run`` gave."""
        keys = sorted(self.first)
        k = min(int(self.run.params.get("compare", 3)), len(keys))
        rng = np.random.default_rng([self.run.seed, 0xC0])
        chosen = [keys[i] for i in sorted(rng.choice(len(keys), k,
                                                     replace=False))]
        return [(self.first[key][0],
                 self.comparison.answer(self.first[key][1].records[0]))
                for key in chosen]

    def rerun(self, scenario) -> dict:
        """One scenario through the timed path again, outside the window,
        as the comparison reads it (the fault readings)."""
        return self.comparison.answer(self._one(scenario).records[0])

    def close(self) -> None:
        self.first.clear()
