"""CPU rehearsal of every cell at a tiny size: set-up, window, correctness.

    JAX_PLATFORMS=cpu python3 -m bench.rehearse [--control] [--workload NAME]

Runs each cell's driver end to end on the CPU with the configuration and
traffic cut to a toy size (the ``TINY`` keys of the configuration's
generator and of the traffic's driver), and prints, per cell, whether the
reference found the run correct and each compared count. It prints no
timing and no device metric: those come only from ``bench/run.py`` on
the chip, which still exits non-zero without a TPU.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

from bench import harness  # noqa: E402
from bench import run as bench_run  # noqa: E402
from bench.gen import graphs  # noqa: E402



def tiny_context(workload: str, *, seed: int = 0, seconds: float = 0.5,
                 trace: bool = False, control: bool = False):
    spec = harness.load_spec()
    cell, config, traffic = harness.resolve(spec, workload)
    config = graphs.tiny(config)
    traffic = dict(traffic, **harness.load("drivers", traffic["kind"]).TINY)
    return bench_run.Context(cell=cell, config=config, traffic=traffic,
                             seed=seed, seconds=seconds, trace=trace,
                             control=control)


def rehearse(workload: str, **kwargs) -> dict:
    """One tiny CPU run; returns ``correct`` and the compared counts."""
    record, checks, info = bench_run.execute(tiny_context(workload, **kwargs),
                                             on_chip=False)
    return {"workload": workload,
            "correct": all(v <= 0 for v in checks.values()),
            "checks": checks, "checked": info["checked"],
            "window_compiles": info["window_compiles"],
            "spans": len(record.spans)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", action="append")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)
    names = args.workload or [w["name"]
                              for w in harness.load_spec()["workloads"]]
    for name in names:
        out = rehearse(name, seed=args.seed, trace=bool(args.trace),
                       control=args.control)
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
