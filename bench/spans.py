"""Device idle charged to the program's own spans.

``repro.obs.span`` opens a ``jax.profiler.TraceAnnotation`` of its name,
whether or not a tracer is installed, so a profiler capture holds the
program's spans on its ``/host:`` plane, on the device trace's clock.
``span_idle`` charges each piece of each device idle gap in the traced
window to the innermost program span open on the host over that piece, or
to ``none``. It finds the window, the device planes and their busy
intervals as ``bench.tracing.reduce_profile`` does, so its pieces add up
to the idle time that function reports.

The result line does not carry it yet; ``bench/span_report.py`` prints it
for one traced segment.
"""
from __future__ import annotations

import bisect

from bench import tracing


def innermost_timeline(spans):
    """Where the innermost open span changes: ``(times, names)``, sorted,
    ``names[k]`` (or ``None``) holding from ``times[k]`` on.

    ``spans`` are ``(start, end, name)``. A stack sweep over their starts
    and ends: at each instant the innermost span is the open one that
    started last (the longer of two that start together is the outer).
    """
    events = []
    for i, (s, e, _) in enumerate(spans):
        if e > s:
            events.append((s, 1, -e, i))
            events.append((e, 0, 0, i))
    events.sort()
    stack: list[int] = []
    times, names = [], []
    for k, (t, opens, _, i) in enumerate(events):
        if opens:
            stack.append(i)
        else:
            del stack[len(stack) - 1 - stack[::-1].index(i)]
        if k + 1 == len(events) or events[k + 1][0] != t:
            times.append(t)
            names.append(spans[stack[-1]][2] if stack else None)
    return times, names


def charge(timeline, s, e, into: dict) -> None:
    """Add the seconds of [s, e) (ns) under each innermost span to ``into``."""
    times, names = timeline
    j = bisect.bisect_right(times, s) - 1
    t = s
    while t < e:
        name = names[j] if j >= 0 else None
        end = min(e, times[j + 1]) if j + 1 < len(times) else e
        key = "none" if name is None else name
        into[key] = into.get(key, 0.0) + (end - t) * 1e-9
        t = end
        j += 1


def span_idle(pd, spans) -> dict | None:
    """{innermost program span, or ``none``: idle seconds, mean over chips}.

    ``pd`` is a ``jax.profiler.ProfileData``; ``spans`` the program's span
    names. ``None`` where the trace holds none of them (a program whose
    spans do not reach the profiler).
    """
    window = None
    program = []
    devices = []
    for plane in pd.planes:
        if tracing.DEVICE_PLANE.match(plane.name):
            devices.append(plane)
            continue
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == tracing.ANNOTATION_WINDOW:
                    window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                elif ev.name in spans:
                    program.append((ev.start_ns,
                                    ev.start_ns + ev.duration_ns, ev.name))
    if window is None:
        raise RuntimeError(
            f"no {tracing.ANNOTATION_WINDOW!r} annotation in the trace")
    if not devices:
        raise RuntimeError("no /device:TPU:<k> plane in the trace")
    if not program:
        return None
    w0, w1 = window
    timeline = innermost_timeline(program)
    idle: dict[str, float] = {}
    for plane in devices:
        busy = []
        for line in plane.lines:
            if line.name != tracing.OPS_LINE:
                continue
            for ev in line.events:
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                if e > w0 and s < w1:
                    busy.append((max(s, w0), min(e, w1)))
        merged = tracing._union(busy)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                charge(timeline, s, e, idle)
    return {k: v / len(devices) for k, v in idle.items()}
