"""device_idle.stream: share of the traced window in which no op ran.

1 minus the union of the device's op intervals over the window, from the
profiler trace of one segment (one cadence period with its reads).
"""


def read(run):
    if run.trace is None:
        return None
    t = run.trace
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
