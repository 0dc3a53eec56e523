"""adopt_session_ms: mean length of the view's ``adopt_session`` span.

The span wraps ``ForestView.adopt_session`` on each refresh: the query
tables rebuilt over the new tour, and the host-side checks of the caches
against the live state.
"""


def read(run):
    durs = [s["dur"] for s in run.spans
            if s["type"] == "span" and s["name"] == "adopt_session"]
    if not durs:
        return None
    return sum(durs) / len(durs) / 1e3
