"""tick_self_ms: mean self time of the serving loop's ``tick`` span.

A tick's duration less the durations of its child spans (``apply_batch``,
the refresh spans), matched by the ``parent`` id each span record carries:
the host work of ``ResilientStreamLoop.step`` outside every layer it calls.
"""


def read(run):
    spans = [s for s in run.spans if s["type"] == "span"]
    ticks = [s for s in spans if s["name"] == "tick"]
    if not ticks or "id" not in ticks[0]:
        return None
    children: dict[int, int] = {}
    for s in spans:
        if s.get("parent") is not None:
            children[s["parent"]] = children.get(s["parent"], 0) + s["dur"]
    self_us = [t["dur"] - children.get(t["id"], 0) for t in ticks]
    return sum(self_us) / len(self_us) / 1e3
