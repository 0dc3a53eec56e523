"""query_ms: mean time from a read batch's dispatch to its answer ready."""


def read(run):
    reads = run.window.get("reads") or []
    if not reads:
        return None
    return sum(r["ready"] - r["dispatch"] for r in reads) / len(reads) * 1e3
