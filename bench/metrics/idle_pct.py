"""Share of the traced window in which no op ran on the chip, in percent
(layer: device). Nothing to read where no op of the chip is in the
trace."""


def read(run):
    red = run.reduction
    if red is None or not red.chips or red.window_s <= 0:
        return None
    return 100.0 * (1.0 - red.busy_s / red.window_s)
