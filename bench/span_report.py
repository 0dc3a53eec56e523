"""One cell's window under the ``obs`` tracer, then one profiled segment,
charged to the program's spans; prints one JSON line.

    python3 bench/span_report.py --workload grid2d.stream --seed 7 --seconds 51

Sets the cell up as ``bench/run.py`` does, runs the window with a
``repro.obs.Tracer`` installed, then one more segment under the profiler.
Prints every metric of the cell (end-to-end ones from the traced window,
so their gap to an untraced run is the tracing's cost), the segment's
``idle_gaps`` by harness annotation and ``span_idle`` by innermost
program span (``bench.spans``), and, where the cell has reads, the
program span that was open when each of the slowest 5% of reads was due
(spans placed on the ``perf_counter`` clock by the tracer's ``origin_s``).
It does not judge correctness; ``bench/run.py`` does. Exits 2 without a
TPU.
"""
from __future__ import annotations

import argparse
import bisect
import json
import pathlib
import shutil
import sys
import time

T_START = time.perf_counter()

_ROOT = pathlib.Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path.pop(0)
for _p in (str(_ROOT / "src"), str(_ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import harness, spans, tracing  # noqa: E402
from bench import run as bench_run  # noqa: E402


def due_spans(records, origin_s: float, reads, share: float = 0.05):
    """{innermost span open at the due time, or ``none``: reads} over the
    slowest ``share`` of ``reads`` (latency from due to ready)."""
    slow = sorted(reads, key=lambda r: r["ready"] - r["due"], reverse=True)
    slow = slow[:max(1, int(len(slow) * share))] if slow else []
    timeline = spans.innermost_timeline(
        [(origin_s + r["ts"] * 1e-6, origin_s + (r["ts"] + r["dur"]) * 1e-6,
          r["name"]) for r in records if r["type"] == "span"])
    times, names = timeline
    out: dict[str, int] = {}
    for r in slow:
        j = bisect.bisect_right(times, r["due"]) - 1
        key = names[j] if j >= 0 and names[j] is not None else "none"
        out[key] = out.get(key, 0) + 1
    return out


def main(argv=None) -> int:
    import jax

    from repro import obs

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    spec = harness.load_spec()
    cell, config, traffic = harness.resolve(spec, args.workload)
    harness.use_compile_cache()
    device = harness.require_chips(cell["chips"])
    ctx = bench_run.Context(cell=cell, config=config, traffic=traffic,
                            seed=args.seed, seconds=args.seconds, trace=True)
    module = harness.load("drivers", traffic["kind"])
    driver = module.Driver(ctx)
    driver.setup()
    setup_s = time.perf_counter() - T_START
    with obs.Tracer() as tracer:
        win = driver.window(ctx.seconds)
    names = {r["name"] for r in tracer.records if r["type"] == "span"}
    seg, path, logdir = tracing.capture(driver.traced_segment)
    try:
        trace = tracing.reduce_xspace(path, module.LABELS)
        idle = spans.span_idle(jax.profiler.ProfileData.from_file(path),
                               names)
    finally:
        shutil.rmtree(logdir, ignore_errors=True)
    record = bench_run.RunRecord(
        traffic=traffic, setup_s=setup_s, window=win, spans=tracer.records,
        trace=trace, traced_units=driver.traced_units(seg))
    metrics = {}
    for m in (harness.metrics_for(spec, cell["name"], False)
              + harness.metrics_for(spec, cell["name"], True)):
        value = harness.read_metric(m["name"], record)
        if value is not None:
            metrics[m["name"]] = value
    out = {"workload": cell["name"], "seed": args.seed, "device": device,
           "metrics": metrics, "busy_s": trace["busy_s"],
           "window_s": trace["window_s"], "idle_gaps": trace["idle_gaps"],
           "span_idle": idle}
    if win.get("reads"):
        out["slow_reads_due_in"] = due_spans(
            tracer.records, tracer.summary()["origin_s"], win["reads"])
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
