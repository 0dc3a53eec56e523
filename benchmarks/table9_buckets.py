"""Table IX (extension): shape-bucketed sub-fleets vs one wide schema.

The §15 headline (DESIGN.md): a single ``ForestFleet`` forces every
tenant through ONE ``(n, capacity)`` schema, so a mixed population —
many tiny sessions plus a few large ones — pays the largest tenant's
padding on every lane. A ``BucketedFleet`` routes tenants by
``FleetSchema`` into independently-ticking sub-fleets, each with its own
``(T_b, B_b)`` block, refresh cadence, and ``max_t(rounds)+1`` sync
bill.

The comparison holds the DEVICE MEMORY BUDGET equal, not the slot
count: the single-schema side gets as many wide slots as the bucketed
side's total slot footprint buys (``Σ_b slots_b · slot_cost_b`` over
the wide ``slot_cost``, ≥ 1). At equal memory the wide fleet fits only
a couple of residents, so the mixed population rotates through
idle-LRU eviction and pays far more ticks — more convergence syncs AND
more padded slot-work — while the bucketed side runs the tiny tenants
wide-in-parallel in their own cheap bucket.

Rows (one mix per line, identical logical event streams on both sides):

  table9_buckets/{mix}/T{total}/bucketed
  table9_buckets/{mix}/T{total}/single_schema

derived: events_per_sec, sync_total, sync_per_event, padded_rows
(Σ blocks · T_b · slot_cost_b — int32-rows of slot state ticked), and
pad_ratio (padded slot-events per applied event).

Before any row is reported, EVERY tenant on BOTH sides is checked
bit-identical against an independent single-tenant ``replay_batch``
loop under the tenant's own schema (parents/reps on the tenant's
vertices, plus the live-edge set on the wide side, whose pool layout
may legitimately differ). A fleet row that drifted from its replay
twin is a bug, not a datapoint. ``scripts/bench_smoke.sh`` asserts the
bucketed side's sync_per_event AND padded_rows stay strictly below the
single-schema side's.
"""
from __future__ import annotations

import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import csv_row
from repro import obs
from repro.data.graphs import resolve_graph
from repro.data.streams import STREAMS, StreamBatch
from repro.dynamic.fleet import BucketedFleet, FleetSchema
from repro.dynamic.forest import apply_batch, forest_empty
from repro.dynamic.replay import init_state, replay_batch, stream_capacity
from repro.dynamic.view import CadencePolicy

# (graph, tenants, slots, batch, units) per shape group. The smoke mix
# is the same SHAPE of population as the full mix (many tiny + few
# large) at CI scale.
_SMOKE_MIX = (("chain_64", 8, 4, 8, 6), ("rmat_8", 2, 2, 32, 3))
_FULL_MIX = (("chain_64", 12, 6, 8, 8), ("rmat_14", 2, 2, 64, 4))
_STREAM = "churn"
_CADENCE = CadencePolicy(tour="full", bcc="off", every=2, queries=False)


def _build_groups(mix):
    """Materialize streams + schemas for each shape group in the mix."""
    groups = []
    seed = 0
    for graph, tenants, slots, batch, units in mix:
        g = resolve_graph(graph)
        streams = []
        for _ in range(tenants):
            streams.append(STREAMS[_STREAM](g, batch=batch,
                                            n_batches=units, seed=seed))
            seed += 1
        units = min(units, min(len(s.batches) for s in streams))
        capacity = max(stream_capacity(s) for s in streams)
        groups.append({
            "name": graph,
            "schema": FleetSchema(g.n_nodes, capacity, batch),
            "slots": min(slots, tenants),
            "streams": streams,
            "units": units,
        })
    return groups


def _pad_unit(unit: StreamBatch, n_small: int,
              schema: FleetSchema) -> StreamBatch:
    """Re-shape a narrow tenant's unit to the wide schema's block width.

    The §9 sentinel is the tenant's OWN ``n`` — under the wide schema
    that id is a real vertex, so sentinel entries are remapped to the
    wide ``n`` before padding (an unremapped pad would count as an
    applied event and hook a phantom vertex).
    """
    def pad(a):
        a = np.asarray(a)
        out = np.full(schema.batch, schema.n_nodes, np.int32)
        out[:a.shape[0]] = np.where(a == n_small, schema.n_nodes, a)
        return out
    return StreamBatch(ins_u=pad(unit.ins_u), ins_v=pad(unit.ins_v),
                       del_u=pad(unit.del_u), del_v=pad(unit.del_v))


def _wide_seed(stream, schema: FleetSchema):
    """The tenant's initial live edges as a wide-schema seed forest."""
    state = forest_empty(schema.n_nodes, schema.capacity)
    if stream.init_u.shape[0]:
        no_del = jnp.zeros((schema.capacity,), jnp.bool_)
        state, _ = apply_batch(state, jnp.asarray(stream.init_u),
                               jnp.asarray(stream.init_v), no_del)
    return state


def _oracle(stream, capacity: int, units: int):
    """Independent single-tenant replay under the tenant's own schema."""
    state = init_state(stream, capacity=capacity)
    for i in range(units):
        state, _ = replay_batch(state, stream.batches[i])
    return state


def _tenant_ids(groups):
    return [(f"{grp['name']}.{j}", gi, j)
            for gi, grp in enumerate(groups)
            for j in range(len(grp["streams"]))]


def _run_bucketed(groups):
    bf = BucketedFleet(tempfile.mkdtemp(prefix="t9_bucketed_"))
    for grp in groups:
        bf.add_bucket(grp["schema"], grp["slots"], cadence=_CADENCE,
                      name=grp["name"])
        for j, s in enumerate(grp["streams"]):
            tid = f"{grp['name']}.{j}"
            bf.route(tid, grp["schema"],
                     seed=init_state(s, capacity=grp["schema"].capacity))
            for unit in s.batches[:grp["units"]]:
                bf.offer(tid, unit)
    with obs.Tracer() as tracer:
        bf.run()
        bf.finalize()
    for b in bf.buckets.values():
        jax.block_until_ready(b.manager.fleet.parent)
    # The ledger is the reporting path; the per-bucket counters are the
    # regression oracle — both count the same while_loop carries, and
    # each ``fleet_apply`` count must sit in its bucket's tick span.
    led = tracer.ledger
    apply_sum = sum(b.sync_apply for b in bf.buckets.values())
    assert led.total("fleet_apply") == apply_sum, \
        (led.total("fleet_apply"), apply_sum)
    bucket_of = {s["id"]: s["args"]["bucket"]
                 for s in tracer.spans("bucket_tick")}
    per_bucket: dict[str, int] = {}
    for c in tracer.counts("fleet_apply"):
        name = bucket_of[c["parent"]]
        per_bucket[name] = per_bucket.get(name, 0) + c["value"]
    want = {b.name: b.sync_apply for b in bf.buckets.values()
            if b.sync_apply}
    assert per_bucket == want, (per_bucket, want)
    return bf


def _run_single(groups, wide: FleetSchema, n_slots: int):
    bf = BucketedFleet(tempfile.mkdtemp(prefix="t9_single_"))
    bf.add_bucket(wide, n_slots, cadence=_CADENCE, name="single")
    for grp in groups:
        n_small = grp["schema"].n_nodes
        for j, s in enumerate(grp["streams"]):
            tid = f"{grp['name']}.{j}"
            bf.route(tid, wide, seed=_wide_seed(s, wide))
            for unit in s.batches[:grp["units"]]:
                bf.offer(tid, _pad_unit(unit, n_small, wide))
    bf.run()
    bf.finalize()
    jax.block_until_ready(bf.buckets["single"].manager.fleet.parent)
    return bf


def _live_edges(forest) -> set:
    valid = np.asarray(forest.pool_valid)
    src = np.asarray(forest.pool_src)[valid]
    dst = np.asarray(forest.pool_dst)[valid]
    return {(min(int(u), int(v)), max(int(u), int(v)))
            for u, v in zip(src, dst)}


def _assert_equal(groups, bucketed: BucketedFleet, single: BucketedFleet):
    for tid, gi, j in _tenant_ids(groups):
        grp = groups[gi]
        n = grp["schema"].n_nodes
        oracle = _oracle(grp["streams"][j], grp["schema"].capacity,
                         grp["units"])
        own = bucketed.tenant_forest(tid)
        for field in ("parent", "rep", "pool_valid", "tree_mask"):
            assert np.array_equal(np.asarray(getattr(own, field)),
                                  np.asarray(getattr(oracle, field))), \
                f"bucketed/replay divergence: {tid} field {field}"
        wide = single.tenant_forest(tid)
        for field in ("parent", "rep"):
            assert np.array_equal(np.asarray(getattr(wide, field))[:n],
                                  np.asarray(getattr(oracle, field))), \
                f"single-schema/replay divergence: {tid} field {field}"
        assert _live_edges(wide) == _live_edges(oracle), \
            f"single-schema/replay divergence: {tid} live-edge set"


def _measure(run_fn):
    bf = run_fn()             # warm (compile); discarded
    bf.close()
    t0 = time.perf_counter()
    bf = run_fn()
    dt = time.perf_counter() - t0
    return bf, dt


def run(smoke: bool = True) -> list[str]:
    mix = _SMOKE_MIX if smoke else _FULL_MIX
    groups = _build_groups(mix)
    total_tenants = sum(len(g["streams"]) for g in groups)
    mix_tag = "+".join(f"{len(g['streams'])}x{g['name']}" for g in groups)

    # Equal-memory-budget sizing: the wide fleet gets the number of
    # slots the bucketed side's total footprint pays for.
    wide = FleetSchema(
        n_nodes=max(g["schema"].n_nodes for g in groups),
        capacity=max(g["schema"].capacity for g in groups),
        batch=max(g["schema"].batch for g in groups))
    budget = sum(g["slots"] * g["schema"].slot_cost for g in groups)
    n_wide_slots = min(total_tenants, max(1, budget // wide.slot_cost))

    bucketed, t_bucketed = _measure(lambda: _run_bucketed(groups))
    single, t_single = _measure(
        lambda: _run_single(groups, wide, n_wide_slots))

    _assert_equal(groups, bucketed, single)
    events = bucketed.applied_events()
    assert events == single.applied_events(), \
        (events, single.applied_events())

    rows = []
    base = f"table9_buckets/{mix_tag}/T{total_tenants}"
    for label, bf, dt in (("bucketed", bucketed, t_bucketed),
                          ("single_schema", single, t_single)):
        sync = bf.sync_total()
        rows.append(csv_row(
            f"{base}/{label}", dt * 1e6,
            f"events_per_sec={events / max(dt, 1e-9):.0f};"
            f"sync_total={sync};"
            f"sync_per_event={sync / max(events, 1):.4f};"
            f"padded_rows={bf.padded_rows()};"
            f"pad_ratio={bf.padded_events() / max(events, 1):.2f}"))
        bf.close()
    return rows


if __name__ == "__main__":
    print("\n".join(run()))
