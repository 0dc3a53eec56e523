"""updates_per_s: applied insert and delete events over the window's time.

Dropped events (pool overflow, deletes that matched no live edge) do not
count. The window is whole cadence periods, so every batch it holds has
had its refresh.
"""


def read(run):
    if "applied" not in run.window:
        return None
    return run.window["applied"] / run.window["window_s"]
