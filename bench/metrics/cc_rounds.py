"""cc_rounds: mean GConn rounds per from-scratch build.

``gconn_euler_rst`` reports the rounds ``connected_components`` returns
to the ``obs`` ledger as ``gconn``; under the harness's tracer each report
is a ``count`` record.
"""


def read(run):
    values = [c["value"] for c in run.spans
              if c["type"] == "count" and c["name"] == "gconn"]
    if not values:
        return None
    return sum(values) / len(values)
