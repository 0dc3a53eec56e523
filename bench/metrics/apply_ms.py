"""apply_ms: mean length of the serving loop's ``apply_batch`` span.

Read from the ``repro.obs`` tracer the harness installs in the traced
run; the span closes after the device has finished (``block_until_ready``).
"""


def read(run):
    durs = [s["dur"] for s in run.spans
            if s["type"] == "span" and s["name"] == "apply_batch"]
    if not durs:
        return None
    return sum(durs) / len(durs) / 1e3
