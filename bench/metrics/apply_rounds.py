"""apply_rounds: mean link rounds of ``apply_batch`` per write batch.

``replay_batch`` reports each batch's rounds plus the final convergence
check to the ``obs`` ledger as ``apply``; under the harness's tracer each
report is a ``count`` record, so this is their mean less that check.
"""


def read(run):
    values = [c["value"] for c in run.spans
              if c["type"] == "count" and c["name"] == "apply"]
    if not values:
        return None
    return sum(values) / len(values) - 1
