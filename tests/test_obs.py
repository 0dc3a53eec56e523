"""Observability layer (DESIGN.md §14): sync ledger, span tracing,
metrics registry, span identity and counts, the spans' profiler
annotations — and the two contracts the whole subsystem stands on:
the ledger's totals are bit-equal to the engine's own ``return_syncs``
counters (single sync-accounting path), and instrumentation is FREE —
tracing on vs off leaves forest/tour/BCC state bit-identical and adds
zero engine syncs, across all three stream generators, the fleet tick,
and the full recovery ladder."""
import glob
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from numpy.testing import assert_array_equal

from repro import obs
from repro.core import rooted_spanning_tree
from repro.data import graphs as G
from repro.data.streams import STREAMS
from repro.dynamic.bcc import refresh_bcc
from repro.dynamic.chaos import inject
from repro.dynamic.fleet import (apply_batches, fleet_empty,
                                 fleet_sync_cost, refresh_tours)
from repro.dynamic.queries import QuerySession
from repro.dynamic.recovery import recover
from repro.dynamic.replay import init_state, replay_batch
from repro.dynamic.tour import refresh_tour
from repro.launch.resilient import ResilientStreamLoop

_STREAMS = ("sliding_window", "insert_heavy", "churn")


def _stream(name, g, batch=16, n=4, seed=0):
    kw = {"batch": batch, "seed": seed}
    if name == "sliding_window":
        kw["window"] = 2
    if name == "churn":
        kw["n_batches"] = n
    return STREAMS[name](g, **kw)


# ---- SyncLedger --------------------------------------------------------------

class TestSyncLedger:
    def test_record_accumulates_per_phase(self):
        with obs.SyncLedger() as led:
            obs.record("apply", 3)
            obs.record("apply", 2)
            obs.record("audit", 7)
        assert led.totals() == {"apply": 5, "audit": 7}
        assert led.counts() == {"apply": 2, "audit": 1}
        assert led.total() == 12
        assert led.total("apply") == 5
        assert led.total("missing") == 0

    def test_no_ledger_is_a_noop(self):
        assert obs.current_ledger() is None
        obs.record("apply", 3)  # nothing installed: must not raise

    def test_lazy_callable_only_evaluated_when_recording(self):
        calls = []

        def cost():
            calls.append(1)
            return 5

        obs.record("apply", cost)          # no ledger: never evaluated
        assert calls == []
        with obs.SyncLedger() as led:
            obs.record("apply", cost)
        assert calls == [1]
        assert led.total("apply") == 5

    def test_nested_ledgers_both_receive(self):
        with obs.SyncLedger() as outer:
            obs.record("apply", 1)
            with obs.SyncLedger() as inner:
                obs.record("apply", 2)
            obs.record("audit", 4)
        assert inner.totals() == {"apply": 2}
        assert outer.totals() == {"apply": 3, "audit": 4}
        assert obs.current_ledger() is None

    def test_tenant_labels(self):
        with obs.SyncLedger() as led:
            obs.record("apply", 3, tenant=0)
            obs.record("apply", 4, tenant=1)
            obs.record("apply", 5, tenant=0)
        assert led.by_tenant("apply") == {0: 8, 1: 4}
        assert led.total("apply") == 12


# ---- percentile_line (the shared serve_stream/serve_fleet helper) ------------

class TestPercentileLine:
    def test_zero_samples_shared_path(self):
        # The PR-8 regression, now on the single shared path: an op
        # that never ran must render a reason, not crash or fake a p50.
        assert obs.percentile_line([]) == "no samples"
        assert (obs.percentile_line((), empty_reason="op never reached")
                == "no samples (op never reached)")

    def test_fleet_format(self):
        line = obs.percentile_line([0.010, 0.020, 0.030])
        assert line == "p50  20.00 ms  p95  29.00 ms"

    def test_stream_per_op_format(self):
        line = obs.percentile_line([0.010] * 4, width=7,
                                   count_suffix=True)
        assert line == "p50   10.00 ms  p95   10.00 ms  (4 batches)"


# ---- Tracer: JSONL <-> Chrome round trip -------------------------------------

class TestTracer:
    def _traced(self):
        tracer = obs.Tracer()
        with tracer:
            with obs.span("tick", step=0):
                obs.record("apply", 3)
                with obs.span("apply_batch", step=0, tenants=2):
                    obs.record("apply", 2)
            obs.event("recovery", mode="scoped", reason="scoped_repair",
                      n_violating=4)
        return tracer

    def test_span_sync_attribution_is_inclusive(self):
        tracer = self._traced()
        tick, = tracer.spans("tick")
        inner, = tracer.spans("apply_batch")
        assert tick["syncs"] == 5      # includes the child's 2
        assert inner["syncs"] == 2
        assert tracer.summary()["sync_by_phase"] == {"apply": 5}

    def test_jsonl_round_trip(self, tmp_path):
        tracer = self._traced()
        path = tmp_path / "trace.jsonl"
        tracer.write_jsonl(path)
        records = obs.read_jsonl(path)
        assert records == tracer.records + [tracer.summary()]

    def test_chrome_round_trip(self, tmp_path):
        tracer = self._traced()
        path = tmp_path / "trace.chrome.json"
        tracer.write_chrome(path)
        chrome = json.loads(path.read_text())
        assert {e["ph"] for e in chrome["traceEvents"]} == {"X", "C", "i"}
        assert chrome["otherData"]["sync_total"] == 5
        assert chrome["otherData"]["schema_version"] == obs.SCHEMA_VERSION
        assert obs.chrome_to_records(chrome) == tracer.records

    def test_no_tracer_span_is_noop(self):
        with obs.span("tick", step=0):
            obs.event("recovery", mode="full")  # must not raise

    def test_span_ids_and_parents(self):
        tracer = obs.Tracer()
        with tracer:
            with obs.span("tick", step=0):
                with obs.span("apply_batch", step=0):
                    with obs.span("inner"):
                        pass
                with obs.span("refresh_tour", step=0):
                    pass
            with obs.span("tick", step=1):
                pass
        by = {(s["name"], s["step"]): s for s in tracer.spans()}
        tick0, tick1 = by[("tick", 0)], by[("tick", 1)]
        apply_, refresh = by[("apply_batch", 0)], by[("refresh_tour", 0)]
        inner = by[("inner", None)]
        ids = [s["id"] for s in tracer.spans()]
        assert len(set(ids)) == len(ids) == 5
        assert tick0["parent"] is None and tick1["parent"] is None
        assert apply_["parent"] == tick0["id"]
        assert refresh["parent"] == tick0["id"]
        assert inner["parent"] == apply_["id"]
        # Spans are written as they close: a child before its parent.
        order = [s["id"] for s in tracer.spans()]
        assert order.index(inner["id"]) < order.index(apply_["id"]) \
            < order.index(tick0["id"])

    def test_counts_carry_their_parent_span(self):
        tracer = self._traced()
        tick, = tracer.spans("tick")
        inner, = tracer.spans("apply_batch")
        counts = tracer.counts("apply")
        assert [(c["value"], c["parent"]) for c in counts] == \
            [(3, tick["id"]), (2, inner["id"])]
        assert all(c["v"] == obs.SCHEMA_VERSION for c in counts)
        # The ledger's totals are the same as without counts.
        assert tracer.ledger.totals() == {"apply": 5}
        with tracer:
            obs.record("audit", 1)           # outside every span
        assert tracer.counts("audit")[0]["parent"] is None

    def test_counts_lazy_and_only_under_a_tracer(self):
        calls = []

        def cost():
            calls.append(1)
            return 4

        obs.record("apply", cost)            # nothing installed
        assert calls == []
        tracer = obs.Tracer()
        with obs.SyncLedger() as led:        # a ledger alone: no count
            obs.record("apply", cost)
        assert calls == [1] and led.total("apply") == 4
        assert tracer.counts() == []
        with tracer:
            obs.record("apply", cost)
        assert calls == [1, 1]
        count, = tracer.counts("apply")
        assert count["value"] == 4 and count["parent"] is None

    @pytest.mark.parametrize("fmt", ["jsonl", "chrome"])
    def test_schema_2_round_trip(self, fmt, tmp_path):
        tracer = self._traced()
        assert obs.SCHEMA_VERSION == 2
        kinds = {r["type"] for r in tracer.records}
        assert kinds == {"span", "count", "event"}
        summary = tracer.summary()
        assert summary["v"] == 2 and isinstance(summary["origin_s"], float)
        if fmt == "jsonl":
            tracer.write_jsonl(tmp_path / "t.jsonl")
            back = obs.read_jsonl(tmp_path / "t.jsonl")
            assert back[:-1] == tracer.records and back[-1] == summary
        else:
            tracer.write_chrome(tmp_path / "t.json")
            chrome = json.loads((tmp_path / "t.json").read_text())
            assert obs.chrome_to_records(chrome) == tracer.records
            assert chrome["otherData"]["origin_s"] == summary["origin_s"]
            span = next(e for e in chrome["traceEvents"] if e["ph"] == "X")
            assert {"id", "parent"} <= set(span["args"])


# ---- spans on the profiler's clock --------------------------------------------

def _host_events(logdir, names):
    """(start, end, name) of the host-plane events named in ``names``."""
    path, = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    return [(e.start_ns, e.start_ns + e.duration_ns, e.name)
            for p in pd.planes if p.name.startswith("/host:")
            for ln in p.lines for e in ln.events if e.name in names]


def test_untraced_step_spans_reach_the_profiler(tmp_path):
    """With no Tracer installed, one loop step's spans show on the
    profiler's host plane, nested in time."""
    assert obs.current_tracer() is None
    stream = _stream("churn", G.grid2d(8))
    loop = ResilientStreamLoop.from_stream(
        stream, tour_mode="incremental", bcc_mode="off", tour_every=1)
    loop.step(0, stream.batches[0])             # compile outside the capture
    jax.profiler.start_trace(str(tmp_path))
    try:
        loop.step(1, stream.batches[1])
    finally:
        jax.profiler.stop_trace()
    got = _host_events(tmp_path, {"tick", "apply_batch", "refresh_tour"})
    by = {}
    for s, e, name in got:
        by.setdefault(name, []).append((s, e))
    assert {k: len(v) for k, v in by.items()} == \
        {"tick": 1, "apply_batch": 1, "refresh_tour": 1}
    (t0, t1), = by["tick"]
    (a0, a1), = by["apply_batch"]
    (r0, r1), = by["refresh_tour"]
    assert t0 <= a0 < a1 <= r0 < r1 <= t1


def test_each_query_op_runs_in_a_query_span():
    stream = _stream("churn", G.grid2d(6), n=1)
    state = init_state(stream)
    tn, state = refresh_tour(state, None)
    bcc = refresh_bcc(state, None, tour=tn)
    sess = QuerySession.from_state(state, tn, bcc)
    u = jnp.arange(4, dtype=jnp.int32)
    v = u + 1
    payload = jnp.ones(state.n_nodes, jnp.int32)
    calls = [("connected", (u, v)), ("depth", (u,)), ("lca", (u, v)),
             ("is_ancestor", (u, v)), ("subtree_agg", (u, payload)),
             ("path_agg", (u, v, payload)), ("is_bridge", (u, v)),
             ("is_articulation", (u,))]
    tracer = obs.Tracer()
    with tracer:
        for op, args in calls:
            getattr(sess, op)(state, *args)
    spans = tracer.spans("query")
    assert [s["args"]["op"] for s in spans] == [op for op, _ in calls]
    assert all(s["parent"] is None and s["syncs"] == 0 for s in spans)


@pytest.mark.parametrize("graph", ["grid", "rmat"])
def test_gconn_rounds_counted_once_per_build(graph):
    g = G.grid2d(9) if graph == "grid" else G.rmat(7, edge_factor=4, seed=3)
    res = rooted_spanning_tree(g, 0, method="gconn_euler")  # compile
    tracer = obs.Tracer()
    with tracer:
        res = rooted_spanning_tree(g, 3, method="gconn_euler")
    count, = tracer.counts("gconn")
    assert count["value"] == int(res.steps) > 0
    assert tracer.ledger.totals() == {"gconn": int(res.steps)}


def test_gconn_inside_a_jitted_caller_records_nothing():
    """``bcc_batch`` traces ``gconn_euler_rst`` under jit and vmap: an
    installed tracer must neither pull a traced value nor count it."""
    from repro.core import bcc_batch

    g = G.grid2d(3)
    src, dst = jnp.stack([g.src, g.src]), jnp.stack([g.dst, g.dst])
    tracer = obs.Tracer()
    with tracer:
        out = bcc_batch(src, dst, jnp.zeros(2, jnp.int32),
                        n_nodes=g.n_nodes)
    assert out.articulation.shape == (2, g.n_nodes)
    assert tracer.counts("gconn") == []


# ---- MetricsRegistry ---------------------------------------------------------

class TestMetrics:
    def test_counter_gauge_histogram(self):
        m = obs.MetricsRegistry()
        m.counter("applied").inc(3)
        m.counter("applied").inc(2)
        m.gauge("tenants").set(4)
        h = m.histogram("lat_ms")
        for v in (1.0, 2.0, 3.0, 100.0):
            h.observe(v)
        assert m.counter("applied").value == 5
        snap = h.snapshot()
        assert snap["count"] == 4
        assert snap["max"] == 100.0
        assert snap["p50"] == pytest.approx(2.0, rel=0.5)

    def test_labels_key_series_and_kind_conflicts_raise(self):
        m = obs.MetricsRegistry()
        m.counter("applied", tenant=0).inc(1)
        m.counter("applied", tenant=1).inc(2)
        assert m.counter("applied", tenant=0).value == 1
        assert m.counter("applied", tenant=1).value == 2
        with pytest.raises(TypeError):
            m.gauge("applied", tenant=0)

    def test_to_dict_stable_sorted(self, tmp_path):
        m = obs.MetricsRegistry()
        m.counter("b").inc(1)
        m.counter("a", tenant=1).inc(1)
        m.counter("a", tenant=0).inc(1)
        d = m.to_dict()
        keys = [(r["name"], tuple(sorted(r["labels"].items())))
                for r in d["metrics"]]
        assert keys == sorted(keys)
        assert d["schema_version"] == obs.METRICS_SCHEMA_VERSION
        m.write(tmp_path / "m.json")
        assert json.loads((tmp_path / "m.json").read_text()) == d


# ---- ledger == return_syncs (single sync-accounting path) --------------------

class TestLedgerBitEquality:
    def test_apply_phase_equals_replay_stats(self):
        stream = _stream("churn", G.grid2d(8))
        state = init_state(stream)
        hand = 0
        with obs.SyncLedger() as led:
            for b in stream.batches:
                state, stats = replay_batch(state, b)
                hand += int(stats["rounds"]) + 1
        assert led.total("apply") == hand

    def test_fleet_apply_phase_equals_fleet_sync_cost(self):
        g = G.grid2d(8)
        streams = [_stream("churn", g, seed=t) for t in range(2)]
        capacity = max(s.init_u.shape[0] + 64 for s in streams)
        fleet = fleet_empty(2, g.n_nodes, capacity)
        for t, s in enumerate(streams):
            fleet = fleet.set_tenant(t, init_state(s, capacity=capacity))
        hand = 0
        with obs.SyncLedger() as led:
            for i in range(len(streams[0].batches)):
                blk = tuple(
                    np.stack([np.asarray(getattr(s.batches[i], f))
                              for s in streams])
                    for f in ("ins_u", "ins_v", "del_u", "del_v"))
                fleet, stats = apply_batches(fleet, *blk)
                hand += fleet_sync_cost(stats)
        assert led.total("fleet_apply") == hand


# ---- instrumentation is free -------------------------------------------------

def _run_loop(stream, batches, traced):
    loop = ResilientStreamLoop.from_stream(
        stream, tour_mode="incremental", bcc_mode="incremental",
        tour_every=2, audit_every=2, chaos=("parent_bitflip",),
        chaos_every=3, sanitize=True)
    if traced:
        tracer = obs.Tracer()
        with tracer:
            state = loop.run(batches)
        return loop, state, tracer
    return loop, loop.run(batches), None


class TestInstrumentationIsFree:
    @pytest.mark.parametrize("stream_name", _STREAMS)
    def test_traced_run_bit_identical(self, stream_name):
        g = G.grid2d(8)
        stream = _stream(stream_name, g)
        batches = stream.batches[:4]
        loop_a, state_a, _ = _run_loop(stream, batches, traced=False)
        loop_b, state_b, tracer = _run_loop(stream, batches, traced=True)

        for field in ("parent", "rep", "pool_valid", "tree_mask",
                      "version"):
            assert_array_equal(np.asarray(getattr(state_a, field)),
                               np.asarray(getattr(state_b, field)),
                               err_msg=f"{stream_name}: {field}")
        assert_array_equal(np.asarray(loop_a.tn.pre),
                           np.asarray(loop_b.tn.pre))
        if loop_a.bcc is not None:
            assert_array_equal(np.asarray(loop_a.bcc.edge_bcc),
                               np.asarray(loop_b.bcc.edge_bcc))
        # The traced run actually observed the loop.
        assert tracer.spans("tick")
        assert tracer.summary()["sync_total"] > 0

    def test_fleet_tick_bit_identical(self):
        g = G.grid2d(8)
        streams = [_stream("churn", g, seed=t) for t in range(2)]
        capacity = max(s.init_u.shape[0] + 64 for s in streams)

        def run(traced):
            fleet = fleet_empty(2, g.n_nodes, capacity)
            for t, s in enumerate(streams):
                fleet = fleet.set_tenant(
                    t, init_state(s, capacity=capacity))
            tn = None
            ctx = obs.Tracer() if traced else None
            with ctx if ctx is not None else obs.span("noop"):
                for i in range(len(streams[0].batches)):
                    blk = tuple(
                        np.stack([np.asarray(getattr(s.batches[i], f))
                                  for s in streams])
                        for f in ("ins_u", "ins_v", "del_u", "del_v"))
                    fleet, _ = apply_batches(fleet, *blk)
                    tn, fleet = refresh_tours(fleet, tn)
            return fleet, tn

        fleet_a, tn_a = run(traced=False)
        fleet_b, tn_b = run(traced=True)
        assert_array_equal(np.asarray(fleet_a.parent),
                           np.asarray(fleet_b.parent))
        assert_array_equal(np.asarray(fleet_a.rep),
                           np.asarray(fleet_b.rep))
        assert_array_equal(np.asarray(tn_a.pre), np.asarray(tn_b.pre))

    def test_recover_ladder_bit_identical_and_emits_events(self):
        stream = _stream("churn", G.grid2d(8))
        state = init_state(stream)
        for b in stream.batches:
            state, _ = replay_batch(state, b)
        tn, state = refresh_tour(state, None)
        bad, _, _ = inject("parent_bitflip", state, seed=7)

        state_a, tn_a, _, _, info_a = recover(bad, tn)
        tracer = obs.Tracer()
        with tracer:
            state_b, tn_b, _, _, info_b = recover(bad, tn)

        assert info_a == info_b
        assert_array_equal(np.asarray(state_a.parent),
                           np.asarray(state_b.parent))
        assert_array_equal(np.asarray(state_a.rep),
                           np.asarray(state_b.rep))
        assert_array_equal(np.asarray(tn_a.pre), np.asarray(tn_b.pre))
        violation, = tracer.events("audit_violation")
        assert violation["args"]["violations"]
        recovery, = tracer.events("recovery")
        assert recovery["args"]["mode"] == info_b["mode"]
        assert recovery["args"]["reason"] == info_b["reason"]

    def test_traced_ledger_matches_untraced_hand_count(self):
        # Zero-added-syncs: the ledger only *reads* counters the compiled
        # program already carries, so the traced run's apply total equals
        # the untraced run's hand-summed rounds+1.
        stream = _stream("sliding_window", G.grid2d(8))
        state = init_state(stream)
        hand = 0
        for b in stream.batches[:4]:
            state, stats = replay_batch(state, b)
            hand += int(stats["rounds"]) + 1

        state2 = init_state(stream)
        tracer = obs.Tracer()
        with tracer:
            for b in stream.batches[:4]:
                state2, _ = replay_batch(state2, b)
        assert tracer.ledger.total("apply") == hand
        assert_array_equal(np.asarray(state.parent),
                           np.asarray(state2.parent))
