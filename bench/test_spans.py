"""Self-checks of the readers of the program's spans and counts, on the CPU.

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/test_spans.py

- ``bench.spans.span_idle`` on a synthetic XSpace whose answer is known,
  with program spans nested deeper than any bounded lookback would see;
- the committed v5e trace reduces to the numbers it gave before program
  spans reached the profiler (pinned), and holds no program span;
- each reader of a span or count on hand-built run records, and on the
  records of a program whose spans carry no id and which reports no
  counts (they read nothing and do not raise).
"""
from __future__ import annotations

import os
import pathlib

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

from bench import harness, spans, tracing  # noqa: E402
from bench.run import RunRecord  # noqa: E402

TRACE = pathlib.Path(__file__).parent / "testdata" / \
    "rst_grid64_one_build.xplane.pb"
DEPTH = 12


def _event(meta: int, start_us: float, end_us: float) -> str:
    return (f"events {{ metadata_id: {meta} offset_ps: {int(start_us * 1e6)} "
            f"duration_ps: {int((end_us - start_us) * 1e6)} }}")


def _synthetic() -> str:
    """Ops over [0, 2] and [30, 32] us of a [0, 40] us window; spans s0 >
    s1 > ... > s11 nested over [4 + k, 28 - k] us, and q over [34, 36]."""
    host = [_event(1, 0, 40), _event(2, 33, 37)]
    host += [_event(10 + k, 4 + k, 28 - k) for k in range(DEPTH)]
    host.append(_event(9, 34, 36))
    meta = [(1, "bench_window"), (2, "read"), (9, "q")] + [
        (10 + k, f"s{k}") for k in range(DEPTH)]
    host_meta = " ".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                         f'name: "{n}" }} }}' for i, n in meta)
    return f"""
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0
    {_event(1, 0, 2)} {_event(1, 30, 32)} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "fusion.1" }} }} }}
planes {{ id: 2 name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 0 {" ".join(host)} }}
  {host_meta} }}
"""


def test_span_idle_of_deeply_nested_spans():
    """Idle [2, 30] and [32, 40] us: each s_k holds the innermost place
    for 1 us on each side of s_(k+1) (s11 for its 2 us), q for 2 us, and
    10 us lie outside every span."""
    import jax

    pd = jax.profiler.ProfileData.from_text_proto(_synthetic())
    names = {f"s{k}" for k in range(DEPTH)} | {"q"}
    idle = spans.span_idle(pd, names)
    want = {f"s{k}": 2e-6 for k in range(DEPTH)}
    want.update(q=2e-6, none=10e-6)
    assert idle == pytest.approx(want, abs=1e-12)
    red = tracing.reduce_profile(pd, ("read",))
    assert sum(idle.values()) == pytest.approx(
        red["window_s"] - red["busy_s"], abs=1e-12)
    assert red["idle_gaps"] == pytest.approx(
        {"read": 8e-6, "other": 28e-6}, abs=1e-12)
    assert spans.span_idle(pd, {"absent"}) is None


def test_innermost_timeline_with_shared_starts_and_crossing_threads():
    times, names = spans.innermost_timeline(
        [(0, 10, "outer"), (0, 4, "inner"), (8, 12, "other_thread"),
         (5, 5, "empty")])
    assert list(zip(times, names)) == [
        (0, "inner"), (4, "outer"), (8, "other_thread"), (10, "other_thread"),
        (12, None)]
    into: dict = {}
    spans.charge((times, names), 2, 11, into)
    assert into == pytest.approx({"inner": 2e-9, "outer": 4e-9,
                                  "other_thread": 3e-9})


@pytest.mark.skipif(not TRACE.exists(), reason="no recorded trace")
def test_committed_trace_reduces_as_pinned():
    import jax

    red = tracing.reduce_xspace(str(TRACE), ("build",))
    assert red["busy_s"] == pytest.approx(0.00457585, rel=1e-12)
    assert red["window_s"] == pytest.approx(0.024525801, rel=1e-12)
    assert red["idle_gaps"] == pytest.approx(
        {"build": 0.017960964999999968, "other": 0.0019889860000000003},
        rel=1e-12)
    assert len(red["modules"]) == 33
    assert tracing.module_seconds(red, "connected_components") == \
        pytest.approx(0.0013166450000000002, rel=1e-12)
    assert tracing.module_seconds(red, "euler_tour_root") == \
        pytest.approx(0.0030190430000000003, rel=1e-12)
    assert len(red["ops"]) == 176
    assert sum(red["ops"].values()) == pytest.approx(0.008585675000000001,
                                                     rel=1e-12)
    assert tracing.breakdown(red)["device_ops"][0] == [
        "%while.14 while", pytest.approx(0.0017235520000000002, rel=1e-12)]
    pd = jax.profiler.ProfileData.from_file(str(TRACE))
    assert spans.span_idle(pd, {"tick", "query", "apply_batch"}) is None


# -- the readers ------------------------------------------------------------

def _span(sid, name, ts, dur, parent=None, **args):
    return {"v": 2, "type": "span", "name": name, "ts": ts, "dur": dur,
            "id": sid, "parent": parent, "syncs": 0, "step": None,
            "args": args}


def _count(name, value, parent=None):
    return {"v": 2, "type": "count", "name": name, "value": value, "ts": 0,
            "parent": parent}


def _run(records):
    return RunRecord(traffic={}, setup_s=0.0, window={}, spans=records,
                     trace=None, traced_units=0)


# Two ticks: the first (10 ms) holds apply_batch (6 ms), which holds a
# grandchild, and the three refresh spans (1 + 1 + 0.5 ms); the second
# (7 ms) holds apply_batch (6.5 ms). Reads outside both.
STREAM = [
    _span(2, "write_batch_inner", 1_000, 5_000, parent=1),
    _span(1, "apply_batch", 500, 6_000, parent=0),
    _count("apply", 4, parent=1),
    _span(3, "refresh_tour", 6_600, 1_000, parent=0),
    _span(4, "refresh_bcc", 7_700, 1_000, parent=0),
    _span(5, "adopt_session", 8_800, 500, parent=0),
    _span(0, "tick", 0, 10_000),
    _span(6, "query", 10_100, 1_500, op="lca"),
    _span(7, "query", 11_700, 2_500, op="depth"),
    _span(9, "apply_batch", 14_100, 6_500, parent=8),
    _count("apply", 2, parent=9),
    _span(8, "tick", 14_000, 7_000),
]


@pytest.mark.parametrize("name,want", [
    ("query_host_ms", 2.0), ("adopt_session_ms", 0.5),
    ("tick_self_ms", ((10_000 - 6_000 - 2_500) + (7_000 - 6_500)) / 2e3),
    ("apply_rounds", 2.0), ("cc_rounds", None)])
def test_stream_readers(name, want):
    got = harness.read_metric(name, _run(STREAM))
    assert got == (None if want is None else pytest.approx(want))


def test_cc_rounds_reader():
    records = [_span(0, "build_inner", 0, 5), _count("gconn", 3, parent=0),
               _count("gconn", 5), _count("apply", 9)]
    assert harness.read_metric("cc_rounds", _run(records)) == 4


@pytest.mark.parametrize("name", ["query_host_ms", "tick_self_ms",
                                  "apply_rounds", "cc_rounds"])
def test_readers_of_a_program_without_ids_or_counts(name):
    """Schema-1 records: spans with no id or parent, and no counts."""
    old = [{"v": 1, "type": "span", "name": n, "ts": 0, "dur": 5,
            "syncs": 0, "step": 0, "args": {}}
           for n in ("tick", "apply_batch", "adopt_session")]
    assert harness.read_metric(name, _run(old)) is None
