"""Engines' host side: the device's idle time inside the harness's
per-scenario spans (``SweepSpec.run`` calls) of the traced window, in ms
per scenario-window completed there. Moves ``windows_per_s``."""


def read(run):
    idle = run.summary.get("span_idle_s")
    if idle is None or not run.windows_done:
        return None
    return idle * 1e3 / run.windows_done
