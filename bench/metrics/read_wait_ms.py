"""read_wait_ms: mean time from a read's due time to its dispatch.

The serving loop's share of read latency: the read waits while the host
and the device finish the write step in flight and the reads before it.
"""


def read(run):
    reads = run.window.get("reads") or []
    if not reads:
        return None
    return sum(r["dispatch"] - r["due"] for r in reads) / len(reads) * 1e3
