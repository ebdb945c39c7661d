"""Share of the window in which no operation ran on the device, in %:
1 - (union of the device's op intervals) / (window). Source: the trace."""


def read(run):
    lo, hi = run.trace["window_ns"]
    return 100.0 * (1.0 - run.trace["busy_ns"] / (hi - lo))
