"""Profiler capture and the reduction from a trace to numbers.

``capture`` runs one segment of a workload under ``jax.profiler`` and
returns the ``.xplane.pb`` it wrote. ``reduce_xspace`` reads it with
``jax.profiler.ProfileData`` alone:

- the device planes are those named ``/device:TPU:<k>``; on each, the
  events of the ``XLA Ops`` line are the operations that ran, and those
  of the ``XLA Modules`` line the compiled programs (jitted functions)
  they belong to;
- busy time is the union of the op intervals, averaged over the chips;
- each idle gap (between two busy intervals) is labelled with the
  innermost harness annotation (``jax.profiler.TraceAnnotation``) open on
  the host at the gap's midpoint, or ``other``.

The window is the span of the outermost annotation the harness opens
around the traced segment (``ANNOTATION_WINDOW``).
"""
from __future__ import annotations

import bisect
import glob
import os
import re
import shutil
import sys
import tempfile

ANNOTATION_WINDOW = "bench_window"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+\b")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def capture(segment) -> tuple[object, str, str]:
    """Run ``segment()`` under the profiler.

    Returns ``(segment's result, xplane path, directory to delete)``.
    """
    import jax

    logdir = tempfile.mkdtemp(prefix="bench_trace_")
    jax.profiler.start_trace(logdir)
    try:
        with jax.profiler.TraceAnnotation(ANNOTATION_WINDOW):
            out = segment()
    finally:
        jax.profiler.stop_trace()
    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        shutil.rmtree(logdir, ignore_errors=True)
        raise RuntimeError("the profiler wrote no .xplane.pb")
    return out, paths[0], logdir


def _union(intervals):
    """Merge [start, end) intervals; returns the sorted disjoint list."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def reduce_xspace(path: str, labels=()) -> dict:
    """``reduce_profile`` of the ``.xplane.pb`` file at ``path``."""
    import jax

    return reduce_profile(jax.profiler.ProfileData.from_file(path), labels)


def reduce_profile(pd, labels=()) -> dict:
    """Numbers of one trace (times in seconds).

    ``labels`` are the harness annotation names an idle gap may be
    charged to. Returns ``busy_s`` (mean over chips), ``window_s``,
    ``chips``, ``modules`` (program name -> total device seconds, summed
    over chips), ``ops`` (op name -> total device seconds) and
    ``idle_gaps`` (label -> idle seconds, mean over chips).
    """
    annotations = []
    window = None
    devices = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            devices.append(plane)
            continue
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == ANNOTATION_WINDOW:
                    window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                elif ev.name in labels:
                    annotations.append((ev.start_ns,
                                        ev.start_ns + ev.duration_ns,
                                        ev.name))
    if window is None:
        raise RuntimeError(f"no {ANNOTATION_WINDOW!r} annotation in the trace")
    if not devices:
        raise RuntimeError("no /device:TPU:<k> plane in the trace; planes: "
                           f"{[p.name for p in pd.planes]}")
    w0, w1 = window
    annotations.sort()
    starts = [a[0] for a in annotations]

    def label_at(t):
        """The innermost annotation open at time t (nesting is shallow)."""
        i = bisect.bisect_right(starts, t)
        inner = [a for a in annotations[max(i - 8, 0):i] if t < a[1]]
        return min(inner, key=lambda a: a[1] - a[0])[2] if inner \
            else "other"

    modules: dict[str, float] = {}
    ops: dict[str, float] = {}
    gaps: dict[str, float] = {}
    busy_total = 0.0
    for plane in devices:
        spans = []
        for line in plane.lines:
            if line.name not in (OPS_LINE, MODULES_LINE):
                continue
            table = ops if line.name == OPS_LINE else modules
            for ev in line.events:
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                if e <= w0 or s >= w1:
                    continue
                s, e = max(s, w0), min(e, w1)
                table[ev.name] = table.get(ev.name, 0.0) + (e - s) * 1e-9
                if line.name == OPS_LINE:
                    spans.append((s, e))
        merged = _union(spans)
        busy_total += sum(e - s for s, e in merged) * 1e-9
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for s, e in zip(edges[::2], edges[1::2]):
            if e <= s:
                continue
            label = label_at((s + e) / 2)
            gaps[label] = gaps.get(label, 0.0) + (e - s) * 1e-9
    chips = max(len(devices), 1)
    return {"busy_s": busy_total / chips, "window_s": (w1 - w0) * 1e-9,
            "chips": len(devices), "modules": modules, "ops": ops,
            "idle_gaps": {k: v / chips for k, v in gaps.items()}}


def module_seconds(trace: dict, program: str) -> float:
    """Device seconds of every module whose name holds ``jit_<program>``."""
    pat = re.compile(rf"\bjit_{re.escape(program)}\b")
    return sum(t for name, t in trace["modules"].items() if pat.search(name))


def program_ms(run, program: str) -> float | None:
    """Device milliseconds of ``jit_<program>`` per traced unit of ``run``.

    ``None`` where the run has no trace, or the trace no such module; in
    the second case the modules it does have are named on standard error,
    so that a renamed program shows instead of a metric going silent.
    """
    if run.trace is None or not run.traced_units:
        return None
    t = module_seconds(run.trace, program)
    if t > 0:
        return t / run.traced_units * 1e3
    if run.trace["modules"]:
        seen = sorted(run.trace["modules"], key=lambda k:
                      -run.trace["modules"][k])[:8]
        print(f"bench: no jit_{program} module in the trace; the longest "
              f"are {seen}", file=sys.stderr)
    return None


def op_label(name: str) -> str:
    """A device op's HLO text cut to its name and opcode.

    ``%while.24 = (s32[262144]{...}, ...) while((...)), ...`` gives
    ``%while.24 while``; a name that is not HLO text is kept whole.
    """
    lhs, eq, rhs = name.partition(" = ")
    if not eq:
        return name
    m = re.search(r"\s([a-z][\w-]*)\(", " " + rhs)
    return f"{lhs} {m.group(1)}" if m else lhs


def breakdown(trace: dict, top: int = 10) -> dict:
    """The ``breakdown`` of a result line: top device ops and idle gaps."""
    ops = sorted(trace["ops"].items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(trace["idle_gaps"].items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[op_label(k), v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in gaps]}
