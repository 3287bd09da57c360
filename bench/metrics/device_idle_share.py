"""Device: the share of the traced window in which no operation ran on
the TPUs (averaged over the chips), in %. Moves ``windows_per_s``."""


def read(run):
    s = run.summary
    return 100.0 * (1.0 - s["busy_s"] / s["window_s"])
