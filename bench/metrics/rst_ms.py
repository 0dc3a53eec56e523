"""rst_ms: the window's time over the from-scratch builds it completed."""


def read(run):
    if not run.window.get("builds"):
        return None
    return run.window["window_s"] / run.window["builds"] * 1e3
