"""Unified observability layer (DESIGN.md §14).

Three jit-safe pieces, all host-side, all free when nothing is
installed (a span then costs one profiler annotation, about a
microsecond):

  * ``SyncLedger`` (``ledger``) — the single sync-accounting path.
    Engine-loop host wrappers ``record(phase, syncs)`` the convergence
    counts the loops already return; benchmarks and reports read
    per-phase totals instead of re-deriving ad-hoc sums.
  * ``Tracer`` (``trace``) — span tracing of the serving loops with
    per-span wall-clock AND sync attribution, span ids and parents, and
    the ledger's records as ``count`` records in their span; exports
    JSONL and Chrome trace-event JSON (Perfetto-loadable). Every span is
    also a ``jax.profiler.TraceAnnotation``, traced or not.
  * ``MetricsRegistry`` (``metrics``) — counters/gauges/histograms with
    per-tenant labels; ``percentile_line`` is the shared latency-report
    formatter (including the zero-sample path).

The hard contract, regression-tested in tests/test_obs.py: recording
adds ZERO engine syncs and leaves forest/tour/BCC state bit-identical
with tracing on vs off — instrumented wrappers always request the
counters that already ride every convergence loop's carry, and only the
host-side bookkeeping is conditional.
"""
from repro.obs.ledger import (SyncLedger, current_ledger, record,
                              recording)
from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                               METRICS_SCHEMA_VERSION, percentile_line)
from repro.obs.trace import (SCHEMA_VERSION, Tracer, chrome_to_records,
                             current_tracer, event, read_jsonl,
                             records_to_chrome, span)

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "METRICS_SCHEMA_VERSION", "SCHEMA_VERSION", "SyncLedger", "Tracer",
    "chrome_to_records", "current_ledger", "current_tracer", "event",
    "percentile_line", "read_jsonl", "record", "recording",
    "records_to_chrome", "span",
]
