"""read_p95_ms: 95th percentile of read-batch latency from its due time.

Over every read batch due in the window; a read's latency runs from the
time the open-loop schedule made it due to its answer being ready, so the
wait behind the write path counts.
"""
import numpy as np


def read(run):
    if not run.window.get("reads"):
        return None
    lat = [r["ready"] - r["due"] for r in run.window["reads"]]
    return float(np.percentile(lat, 95)) * 1e3
