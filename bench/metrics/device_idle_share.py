"""Share of the window in which no operation ran on the device: one less
the union of the chip's op intervals over the window, averaged over
chips.  Moves ``epoch_s``.
"""

UNIT = "%"


def read(run):
    if run.trace is None:
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.window_s)
