"""query_host_ms: mean length of the program's ``query`` span per read batch.

``QuerySession`` opens the span around each read op's staleness gate and
dispatch; it does not wait for the device, so it is the session's host
cost of one read batch. Read from the ``repro.obs`` tracer the harness
installs in the traced run.
"""


def read(run):
    durs = [s["dur"] for s in run.spans
            if s["type"] == "span" and s["name"] == "query"]
    if not durs:
        return None
    return sum(durs) / len(durs) / 1e3
