"""Device: busy time (the union of the TPUs' operation intervals,
averaged over the chips) in the traced window, in ms per scenario-window
completed there. Moves ``windows_per_s``."""


def read(run):
    if not run.windows_done:
        return None
    return run.summary["busy_s"] * 1e3 / run.windows_done
