"""Share of the traced window in which no program ran on the device
(1 - union of program intervals / window), averaged over the cell's
chips, in percent."""


def read(run):
    red = run.trace
    if red is None or not red.busy_s or red.window_s <= 0:
        return None
    return 100.0 * (1.0 - red.mean_busy_s / red.window_s)
