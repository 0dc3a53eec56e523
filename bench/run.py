"""One benchmark run of one cell on the chip; prints one JSON result line.

    python3 bench/run.py --workload grid2d.stream --seed 7 --seconds 51 --trace 0

Set-up (data from ``--seed``, the system built, every shape the cell
uses warmed up) is timed as ``setup_s``. The window then measures for
``--seconds``; with ``--trace 1`` the system's spans are recorded in the
window and one more segment runs under the profiler. Once the window has
closed, the device's memory peak is read, the program's state is copied
to the host and dropped, and the plain reference (``bench/reference.py``)
judges what the timed path produced. Each compared count is printed with
its limit, on standard error and under ``checks`` in the result line.

Without a TPU, or with fewer chips than the cell asks for, the run exits
with code 2 and prints no result.

``--control`` puts the control in the program's place (a step that
breaks a guarantee the configuration states; see ``PERF.md``): its runs
must come out not correct. Benchmark runs never pass it.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

_ROOT = pathlib.Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path.pop(0)                     # this script's own directory
# The checkout root (for ``bench``) and ``src`` (the system under test).
for _p in (str(_ROOT / "src"), str(_ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import harness  # noqa: E402


@dataclasses.dataclass
class Context:
    """What a driver is given: the cell, its data files and the run's knobs."""

    cell: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    control: bool = False

    @staticmethod
    def annotation(name: str):
        import jax

        return jax.profiler.TraceAnnotation(name)

    def annotate(self, name: str, fn):
        def wrapped(*args, **kwargs):
            with self.annotation(name):
                return fn(*args, **kwargs)
        return wrapped


@dataclasses.dataclass
class RunRecord:
    """Everything a metric reader may read (``bench/metrics/<name>.py``).

    ``window`` is what the traffic driver's window returned; ``spans`` the
    ``repro.obs`` spans of the traced run; ``trace`` the reduced profiler
    trace (``bench.tracing.reduce_profile``) of ``traced_units`` units of
    work (builds, cadence periods), or ``None``.
    """

    traffic: dict
    setup_s: float
    window: dict
    spans: list
    trace: dict | None
    traced_units: int


def execute(ctx: Context, *, on_chip: bool = True):
    """Set up, measure and judge one run; returns (record, checks, info)."""
    from repro import obs

    module = harness.load("drivers", ctx.traffic["kind"])
    driver = module.Driver(ctx)
    counter = harness.CompileCounter()
    driver.setup()
    setup_s = time.perf_counter() - T_START
    compiles0 = counter.count
    tracer = obs.Tracer() if ctx.trace else contextlib.nullcontext()
    with tracer:
        win = driver.window(ctx.seconds)
    info = {"setup_compiles": compiles0,
            "window_compiles": counter.count - compiles0}
    trace = None
    units = 0
    if ctx.trace and on_chip:
        from bench import tracing

        seg, path, logdir = tracing.capture(driver.traced_segment)
        try:
            trace = tracing.reduce_xspace(path, module.LABELS)
        finally:
            shutil.rmtree(logdir, ignore_errors=True)
        units = driver.traced_units(seg)
    info["memory_peak_bytes"] = (harness.memory_peak_bytes(
        ctx.cell["chips"]) if on_chip else None)
    host = driver.collect(win)
    t_ref = time.perf_counter()
    checks = driver.check(host)
    info["reference_s"] = time.perf_counter() - t_ref
    info["checked"] = driver.checked
    info["attempted"], info["failed"] = driver.counts(win)
    spans = tracer.records if ctx.trace else []
    record = RunRecord(traffic=ctx.traffic, setup_s=setup_s, window=win,
                       spans=spans, trace=trace, traced_units=units)
    return record, checks, info


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    spec = harness.load_spec()
    cell, config, traffic = harness.resolve(spec, args.workload)
    harness.use_compile_cache()
    device = harness.require_chips(cell["chips"])
    ctx = Context(cell=cell, config=config, traffic=traffic, seed=args.seed,
                  seconds=args.seconds, trace=bool(args.trace),
                  control=args.control)
    record, checks, info = execute(ctx)
    print(f"info {info}", file=sys.stderr)

    metrics = {}
    for m in harness.metrics_for(spec, cell["name"], ctx.trace):
        value = harness.read_metric(m["name"], record)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device["memory_peak_bytes"] = info["memory_peak_bytes"]
    result = {"correct": all(v <= 0 for v in checks.values()),
              "attempted": info["attempted"], "failed": info["failed"],
              "metrics": metrics,
              "device": device, "window_compiles": info["window_compiles"]}
    if record.trace is not None:
        from bench import tracing

        device["busy_s"] = record.trace["busy_s"]
        device["window_s"] = record.trace["window_s"]
        result["breakdown"] = tracing.breakdown(record.trace)
    harness.emit(result, {k: (v, 0) for k, v in checks.items()})
    return 0


if __name__ == "__main__":
    sys.exit(main())
