"""What every run shares: the spec, the chip, the cache, the result line.

The harness is driven by ``BENCHMARK.json`` and the files it names:

- a cell (``workloads`` entry) names a configuration, whose file holds
  the deployment's sizes and whose ``generator`` names its graph
  generator, ``bench/gen/graphs/<generator>.py``, and a traffic mix,
  ``bench/traffic/<mix>.json``, whose ``kind`` names its driver,
  ``bench/drivers/<kind>.py``, and whose other keys are that driver's
  parameters;
- every metric is read by ``bench/metrics/<name>.py``, whose ``read(run)``
  returns a number, or ``None`` where the run has nothing to read;
- ``bench/peaks.json`` holds the chip's published peaks by device kind.

Each of these files is found by its name (``load``), so adding a cell,
a traffic kind, a generator or a metric means adding files and entries
only.
"""
from __future__ import annotations

import importlib.util
import json
import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"


class RunError(SystemExit):
    """A run that cannot produce a result: exits non-zero, prints none."""

    def __init__(self, msg: str):
        print(f"bench: {msg}", file=sys.stderr, flush=True)
        super().__init__(2)


def load_spec() -> dict:
    with open(ROOT / "BENCHMARK.json") as f:
        return json.load(f)


def resolve(spec: dict, workload: str) -> tuple[dict, dict, dict]:
    """(cell, configuration, traffic) of the named cell."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise RunError(f"unknown workload {workload!r}; "
                       f"choose from {', '.join(cells)}")
    cell = cells[workload]
    conf = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    with open(ROOT / conf["file"]) as f:
        config = json.load(f)
    with open(BENCH / "traffic" / f"{cell['traffic']}.json") as f:
        traffic = json.load(f)
    return cell, config, traffic


def metrics_for(spec: dict, cell: str, trace: bool) -> list[dict]:
    """The metrics a cell reports: end-to-end ones, or per-layer ones."""
    e2e = [m for m in spec["end_to_end"]
           if "workloads" not in m or cell in m["workloads"]]
    if not trace:
        return e2e
    reported = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in reported)]


def load(kind: str, name: str):
    """The module ``bench/<kind>/<name>.py``, loaded once by its file name.

    ``kind`` is ``drivers``, ``gen/graphs`` or ``metrics``; a name may
    hold dots (``device_idle.stream``).
    """
    key = f"bench._by_name.{kind.replace('/', '.')}.{name}"
    if key in sys.modules:
        return sys.modules[key]
    path = BENCH / kind / f"{name}.py"
    if not path.is_file():
        raise RunError(f"no {path.relative_to(ROOT)} for {name!r}")
    spec = importlib.util.spec_from_file_location(key, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[key] = mod
    spec.loader.exec_module(mod)
    return mod


def read_metric(name: str, run) -> float | None:
    return load("metrics", name).read(run)


def use_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout.

    ``JAX_COMPILATION_CACHE_DIR`` wins where it is set; otherwise
    ``<checkout>/.jax_cache``. Every program is cached, however short its
    compile, so a second run of a cell compiles nothing.
    """
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def require_chips(n: int) -> dict:
    """The TPU devices JAX sees, or ``RunError`` where there are fewer."""
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise RunError(f"no accelerator: {e}") from None
    if devices[0].platform != "tpu":
        raise RunError(f"no TPU: JAX runs on {devices[0].platform!r}")
    if len(devices) < n:
        raise RunError(f"the cell needs {n} chips, JAX sees {len(devices)}")
    with open(BENCH / "peaks.json") as f:
        peaks = json.load(f)["devices"]
    if devices[0].device_kind not in peaks:
        raise RunError(f"device kind {devices[0].device_kind!r} is not in "
                       "bench/peaks.json")
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": n}


def memory_peak_bytes(n: int) -> int | None:
    import jax

    peaks = []
    for d in jax.devices()[:n]:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


class CompileCounter:
    """Counts XLA backend compiles (persistent-cache misses) from now on."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.count = 0

        def listener(event, duration, **kwargs):
            if event == self.EVENT:
                self.count += 1

        jax.monitoring.register_event_duration_secs_listener(listener)


def emit(result: dict, checks: dict) -> None:
    """Print the checks on stderr, then the result as stdout's last line.

    ``checks`` maps a short name to ``(value, limit)``; it goes last in
    the result line, under ``checks``.
    """
    for name, (value, limit) in checks.items():
        print(f"check {name} = {value} (limit {limit})", file=sys.stderr)
    sys.stderr.flush()
    line = dict(result)
    line["checks"] = {k: {"value": v, "limit": lim}
                      for k, (v, lim) in checks.items()}
    print(json.dumps(line), flush=True)
