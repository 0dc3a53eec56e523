"""Multi-tenant forest fleet: T session graphs in one process (DESIGN.md §13).

``serve_stream`` drives ONE ``DynamicForest``; the north star is a
process serving many independent session graphs at once. The
``bcc_batch`` vmap pattern (§4) already showed the many-small-graphs
shape pays on this stack — and Hong et al. (PAPERS.md, arxiv 2008.11839)
show fixed-shape batched incremental updates are the right granularity
for GPU connectivity maintenance. This module lifts that pattern from a
single static call to the whole dynamic read/write loop:

  * ``ForestFleet`` stacks T tenant forests array-of-structs: one
    ``parent[T, n]`` (etc.) per field, one shared (n, capacity) schema,
    so every per-tenant array lives in one device buffer and one
    compiled program covers all tenants.
  * ``apply_batches`` applies one fixed-shape ``(T, B)`` event block —
    one vmapped ``edge_slots`` + ``apply_batch`` over the tenant axis.
    Inside, ``apply_batch``'s link ``while_loop`` runs until ALL lanes
    converge; a converged lane's body is a no-op (``link_components``
    with an all-False candidate mask changes nothing), so each tenant's
    result is bit-identical to running it alone (regression-tested in
    tests/test_fleet.py). The fleet's sync bill for a tick is therefore
    ``max_t(rounds_t) + 1`` convergence checks, against the sequential
    loop's ``Σ_t(rounds_t + 1)`` — the §13 amortization headline
    ``benchmarks/table8_fleet.py`` measures.
  * ``refresh_tours`` / ``refresh_bccs`` / ``build_fleet_tables`` vmap
    the §9/§10/§12 cache refreshes the same way; ``FleetQuerySession``
    serves per-tenant reads over the stacked tables with the per-tenant
    staleness policies of §12.
  * ``FleetDispatcher`` (host-side) coalesces each tick's incoming
    events by tenant into the ``(T, B)`` block, sentinel-padding slots
    with no traffic; batch units are atomic (never split or merged), so
    a tenant's applied-batch sequence is exactly its offered sequence.
  * ``FleetManager`` (host-side) admits sessions to slots and evicts
    least-recently-used ones when over capacity, checkpointing the
    evicted forest through the §8 path; re-admission restores it (and
    its stream cursor) bit-identically.

``launch.serve_fleet`` wires all of it behind the ``ServeConfig`` +
``FleetConfig`` CLI.
"""
from __future__ import annotations

import collections
import concurrent.futures
import dataclasses
import pathlib
from functools import partial
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import queries as q
from repro.core.compress import DEFAULT_JUMPS
from repro.core.euler import TourNumbering, tour_numbering
from repro.core.queries import QueryTables, build_tables
from repro.data.streams import StreamBatch
from repro.dynamic.bcc import (DynamicBCC, _refresh_full,
                               _refresh_incremental)
from repro.dynamic.forest import (DynamicForest, apply_batch, edge_slots,
                                  forest_empty)
from repro.dynamic.queries import POLICIES, StaleQueryError
from repro.dynamic.view import CadencePolicy
from repro.train import checkpoint as ckpt


def tenant_slice(tree, t: int):
    """Slice tenant ``t`` out of any stacked-leading-axis pytree."""
    return jax.tree_util.tree_map(lambda x: x[t], tree)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class ForestFleet:
    """T tenant forests, array-of-structs, one shared capacity schema.

    Every leaf is the single-tenant ``DynamicForest`` leaf with a
    leading tenant axis (``parent[T, n]``, ``pool_src[T, C]``, ...),
    plus ``active[T]`` marking occupied slots. An inactive slot holds an
    edgeless forest; vmapped updates still run over it (sentinel events
    on an empty forest are no-ops), keeping every program fixed-shape.
    """

    n_nodes: int
    parent: jnp.ndarray
    rep: jnp.ndarray
    pool_src: jnp.ndarray
    pool_dst: jnp.ndarray
    pool_valid: jnp.ndarray
    tree_mask: jnp.ndarray
    dirty: jnp.ndarray
    version: jnp.ndarray
    active: jnp.ndarray

    def tree_flatten(self):
        return ((self.parent, self.rep, self.pool_src, self.pool_dst,
                 self.pool_valid, self.tree_mask, self.dirty, self.version,
                 self.active), self.n_nodes)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(aux, *children)

    @property
    def n_slots(self) -> int:
        return int(self.parent.shape[0])

    @property
    def capacity(self) -> int:
        return int(self.pool_src.shape[1])

    # -- single-tenant views -------------------------------------------------

    def as_forest(self) -> DynamicForest:
        """The stacked leaves as one ``DynamicForest`` pytree — the
        vmap carrier (aux ``n_nodes`` is shared by every lane)."""
        return DynamicForest(
            n_nodes=self.n_nodes, parent=self.parent, rep=self.rep,
            pool_src=self.pool_src, pool_dst=self.pool_dst,
            pool_valid=self.pool_valid, tree_mask=self.tree_mask,
            dirty=self.dirty, version=self.version)

    def with_forest(self, forest: DynamicForest) -> "ForestFleet":
        """Re-wrap vmapped-update output, keeping the activity mask."""
        return ForestFleet(
            n_nodes=self.n_nodes, parent=forest.parent, rep=forest.rep,
            pool_src=forest.pool_src, pool_dst=forest.pool_dst,
            pool_valid=forest.pool_valid, tree_mask=forest.tree_mask,
            dirty=forest.dirty, version=forest.version, active=self.active)

    def tenant(self, t: int) -> DynamicForest:
        """Tenant ``t``'s forest, as a standalone ``DynamicForest``."""
        return tenant_slice(self.as_forest(), t)

    def set_tenant(self, t: int, forest: DynamicForest) -> "ForestFleet":
        """Install ``forest`` in slot ``t`` (marks it active)."""
        if forest.n_nodes != self.n_nodes:
            raise ValueError(f"tenant n_nodes {forest.n_nodes} != fleet "
                             f"{self.n_nodes}")
        if forest.capacity != self.capacity:
            raise ValueError(f"tenant capacity {forest.capacity} != fleet "
                             f"schema {self.capacity} (one shared "
                             "capacity per fleet)")
        stacked = jax.tree_util.tree_map(
            lambda full, new: full.at[t].set(new),
            self.as_forest(),
            DynamicForest(n_nodes=self.n_nodes,
                          **{f: getattr(forest, f) for f in (
                              "parent", "rep", "pool_src", "pool_dst",
                              "pool_valid", "tree_mask", "dirty",
                              "version")}))
        out = self.with_forest(stacked)
        return dataclasses.replace(out, active=out.active.at[t].set(True))

    def clear_tenant(self, t: int) -> "ForestFleet":
        """Reset slot ``t`` to an edgeless forest (marks it inactive)."""
        out = self.set_tenant(t, forest_empty(self.n_nodes, self.capacity))
        return dataclasses.replace(out, active=out.active.at[t].set(False))


def fleet_empty(n_slots: int, n_nodes: int, capacity: int) -> ForestFleet:
    """A fleet of T inactive, edgeless slots."""
    one = forest_empty(n_nodes, capacity)
    stacked = jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x, (n_slots,) + x.shape), one)
    return ForestFleet(
        n_nodes=n_nodes, parent=stacked.parent, rep=stacked.rep,
        pool_src=stacked.pool_src, pool_dst=stacked.pool_dst,
        pool_valid=stacked.pool_valid, tree_mask=stacked.tree_mask,
        dirty=stacked.dirty, version=stacked.version,
        active=jnp.zeros((n_slots,), jnp.bool_))


# -- the vmapped write path ---------------------------------------------------

def _replay_one(forest: DynamicForest, ins_u, ins_v, del_u, del_v, *,
                n_jumps: int, use_kernel: bool):
    dmask, found = edge_slots(forest, del_u, del_v)
    forest, stats = apply_batch(forest, ins_u, ins_v, dmask,
                                n_jumps=n_jumps, use_kernel=use_kernel)
    stats["deletes_found"] = jnp.sum(found.astype(jnp.int32))
    return forest, stats


@partial(jax.jit, static_argnames=("n_jumps", "use_kernel"))
def _apply_batches(fleet: ForestFleet, ins_u: jnp.ndarray,
                   ins_v: jnp.ndarray, del_u: jnp.ndarray,
                   del_v: jnp.ndarray, *, n_jumps: int = DEFAULT_JUMPS,
                   use_kernel: bool = False):
    fn = partial(_replay_one, n_jumps=n_jumps, use_kernel=use_kernel)
    forest, stats = jax.vmap(fn)(fleet.as_forest(), ins_u, ins_v,
                                 del_u, del_v)
    return fleet.with_forest(forest), stats


def apply_batches(fleet: ForestFleet, ins_u: jnp.ndarray,
                  ins_v: jnp.ndarray, del_u: jnp.ndarray,
                  del_v: jnp.ndarray, *, n_jumps: int = DEFAULT_JUMPS,
                  use_kernel: bool = False):
    """Apply one ``(T, B)`` event block: one vmapped §9 batch per tenant.

    Args:
      ins_u, ins_v: int32[T, B] per-tenant insertions (``n_nodes``
        sentinel pads inactive slots — inert, like any padded event).
      del_u, del_v: int32[T, D] per-tenant deletion pairs (``edge_slots``
        resolves them to pool slots lane-wise).

    Returns:
      (fleet', stats) — stats maps the ``apply_batch`` counters (plus
      ``deletes_found``) to int32[T] arrays. The vmapped link loop runs
      ``max_t(rounds_t)`` productive rounds; each lane's result is
      bit-identical to applying its batch alone.

    Host wrapper over the jitted block apply: reports the tick's sync
    bill (``fleet_sync_cost``) to the ambient ``obs`` ledger under the
    ``fleet_apply`` phase.
    """
    fleet, stats = _apply_batches(fleet, ins_u, ins_v, del_u, del_v,
                                  n_jumps=n_jumps, use_kernel=use_kernel)
    obs.record("fleet_apply", lambda: fleet_sync_cost(stats))
    return fleet, stats


def fleet_sync_cost(stats) -> int:
    """Convergence checks one ``apply_batches`` tick paid: the vmapped
    link loop trips ``max_t(rounds_t)`` times plus the final all-lanes
    check — versus ``Σ_t(rounds_t + 1)`` for T sequential calls."""
    return int(jnp.max(stats["rounds"])) + 1


# -- vmapped cache refreshes (§9 tour, §10 BCC, §12 tables) -------------------

def refresh_tours(fleet: ForestFleet, cached: TourNumbering | None = None,
                  *, incremental: bool = True, use_kernel: bool = False):
    """Vmapped ``refresh_tour`` over the fleet.

    ``cached`` is the stacked numbering from the previous call (lane t
    of the result is bit-identical to single-tenant ``refresh_tour`` on
    tenant t). Returns ``(numbering[T], fleet')`` with all dirty masks
    cleared. Reports the vmapped refresh's sync bill (max over lanes —
    the loops run lockstep until every lane converges) to the ambient
    ``obs`` ledger under ``fleet_refresh_tour``.
    """
    from repro.dynamic.tour import _merge_dirty

    if cached is None or not incremental:
        tn, syncs = jax.vmap(lambda p: tour_numbering(
            p, use_kernel=use_kernel, return_syncs=True))(fleet.parent)
    else:
        tn, syncs = jax.vmap(lambda p, r, d, c: _merge_dirty(
            p, r, d, c, use_kernel=use_kernel, return_syncs=True))(
                fleet.parent, fleet.rep, fleet.dirty, cached)
    obs.record("fleet_refresh_tour", lambda: int(jnp.max(syncs)))
    return tn, dataclasses.replace(
        fleet, dirty=jnp.zeros_like(fleet.dirty))


def refresh_bccs(fleet: ForestFleet, cached: DynamicBCC | None = None, *,
                 tour: TourNumbering, incremental: bool = True,
                 use_kernel: bool = False) -> DynamicBCC:
    """Vmapped ``refresh_bcc`` over the fleet (stacked ``DynamicBCC``).

    Reports the refresh's sync bill (max over lanes of
    ``seg_syncs + aux_rounds``) to the ambient ``obs`` ledger under
    ``fleet_refresh_bcc``.
    """
    forest = fleet.as_forest()
    if cached is None or not incremental:
        bcc = jax.vmap(lambda f, t: _refresh_full(
            f, t, use_kernel=use_kernel))(forest, tour)
    else:
        bcc = jax.vmap(lambda f, t, c: _refresh_incremental(
            f, t, c, use_kernel=use_kernel))(forest, tour, cached)
    obs.record("fleet_refresh_bcc",
               lambda: int(jnp.max(bcc.seg_syncs + bcc.aux_rounds)))
    return bcc


def build_fleet_tables(tn: TourNumbering, *,
                       n_jumps: int = DEFAULT_JUMPS) -> QueryTables:
    """Vmapped §12 ``build_tables``: one stacked query index, built in
    one program (``build_syncs`` is per-tenant, int32[T]).

    Vmaps the jitted ``_build_tables`` (the host-recording wrapper
    cannot be vmapped) and reports the stacked build's sync bill (max
    over lanes) to the ambient ``obs`` ledger under ``fleet_tables``.
    """
    from repro.core.queries import _build_tables

    tables = jax.vmap(lambda t: _build_tables(t, n_jumps=n_jumps))(tn)
    obs.record("fleet_tables", lambda: int(jnp.max(tables.build_syncs)))
    return tables


# -- per-tenant read sessions over the stacked tables -------------------------

def _i32(x) -> jnp.ndarray:
    return jnp.atleast_1d(jnp.asarray(x, jnp.int32))


@dataclasses.dataclass
class FleetQuerySession:
    """Version-stamped read views over every fleet slot (§12, fleet-wide).

    One stacked ``QueryTables`` (built by ``build_fleet_tables`` — all
    tenants in one vmapped program), per-tenant version stamps, and a
    per-tenant staleness policy. Query methods take ``(fleet, t, ...)``;
    the staleness gate is per call and per tenant:

      * ``strict``  — raise ``StaleQueryError``;
      * ``refresh`` — rebuild ONLY tenant t's slice of the stacked
        tables (a single-lane tour + ``build_tables``), then answer;
      * ``stale``   — serve the frozen slice and count it.
    """

    tables: QueryTables                  # stacked [T, ...]
    bcc: DynamicBCC | None               # stacked, optional
    versions: np.ndarray                 # int64[T] stamped fleet versions
    policies: tuple[str, ...]
    use_kernel: bool = False
    n_jumps: int = DEFAULT_JUMPS
    # per-tenant telemetry (host-side), keyed by STABLE tenant label —
    # not slot index — so counters survive evict→re-admit rotation even
    # when the tenant lands in a different slot. ``labels[slot]`` maps
    # residency to label; the default identity labels reproduce PR 8's
    # slot-indexed behavior exactly.
    labels: list = None                  # slot → stable tenant id
    stats: dict = None                   # label → Counter of telemetry

    @classmethod
    def from_fleet(cls, fleet: ForestFleet,
                   tn: TourNumbering | None = None,
                   bcc: DynamicBCC | None = None, *,
                   policy: str | Sequence[str] = "strict",
                   use_kernel: bool = False,
                   n_jumps: int = DEFAULT_JUMPS,
                   labels: Sequence | None = None) -> "FleetQuerySession":
        t_slots = fleet.n_slots
        if labels is not None and len(labels) != t_slots:
            raise ValueError(f"{len(labels)} labels for {t_slots} slots")
        if isinstance(policy, str):
            policies = (policy,) * t_slots
        else:
            policies = tuple(policy)
        if len(policies) != t_slots:
            raise ValueError(f"{len(policies)} policies for {t_slots} slots")
        for p in policies:
            if p not in POLICIES:
                raise ValueError(f"policy {p!r} not in {POLICIES}")
        if tn is None:
            tn, _ = refresh_tours(fleet, incremental=False,
                                  use_kernel=use_kernel)
        tables = build_fleet_tables(tn, n_jumps=n_jumps)
        sess = cls(tables=tables, bcc=bcc,
                   versions=np.asarray(fleet.version, np.int64).copy(),
                   policies=policies, use_kernel=use_kernel,
                   n_jumps=n_jumps,
                   labels=(list(labels) if labels is not None
                           else list(range(t_slots))), stats={})
        build_syncs = np.asarray(tables.build_syncs, np.int64)
        for s in range(t_slots):
            sess._bump(sess.labels[s], builds=1,
                       build_syncs_total=int(build_syncs[s]))
        return sess

    # -- stable-label bookkeeping --------------------------------------------

    def _bump(self, label, **deltas) -> None:
        c = self.stats.setdefault(label, collections.Counter())
        for k, v in deltas.items():
            c[k] += int(v)

    def set_label(self, slot: int, label) -> None:
        """Bind ``slot`` to a stable tenant id. Telemetry for ``label``
        accumulates across rotations — a re-admitted tenant's counters
        continue from where eviction left them."""
        self.labels[slot] = label
        self.stats.setdefault(label, collections.Counter())

    # -- lifecycle -----------------------------------------------------------

    def rebuild_tenant(self, fleet: ForestFleet, t: int) -> None:
        """Re-index ONE tenant: single-lane tour + tables, scattered
        into the stacked index with ``.at[t].set`` (other lanes frozen)."""
        tn_t, tn_syncs = tour_numbering(fleet.parent[t],
                                        use_kernel=self.use_kernel,
                                        return_syncs=True)
        obs.record("refresh_tour", tn_syncs, tenant=self.labels[t])
        tab_t = build_tables(tn_t, n_jumps=self.n_jumps)
        self.tables = jax.tree_util.tree_map(
            lambda full, new: full.at[t].set(new), self.tables, tab_t)
        if self.bcc is not None:
            bcc_t = _refresh_full(fleet.tenant(t), tn_t,
                                  use_kernel=self.use_kernel)
            self.bcc = jax.tree_util.tree_map(
                lambda full, new: full.at[t].set(new), self.bcc, bcc_t)
        self.versions[t] = int(fleet.version[t])
        self._bump(self.labels[t], builds=1,
                   build_syncs_total=int(tab_t.build_syncs))

    def restamp(self, fleet: ForestFleet, tn: TourNumbering,
                bcc: DynamicBCC | None = None) -> None:
        """Adopt freshly vmapped caches for the whole fleet (the cadence
        path: the serving loop refreshed every lane in one program)."""
        self.tables = build_fleet_tables(tn, n_jumps=self.n_jumps)
        self.bcc = bcc
        self.versions = np.asarray(fleet.version, np.int64).copy()
        build_syncs = np.asarray(self.tables.build_syncs, np.int64)
        for s in range(len(self.labels)):
            self._bump(self.labels[s], builds=1,
                       build_syncs_total=int(build_syncs[s]))

    def is_fresh(self, fleet: ForestFleet, t: int) -> bool:
        return int(fleet.version[t]) == int(self.versions[t])

    def ensure(self, fleet: ForestFleet, t: int) -> None:
        if self.is_fresh(fleet, t):
            return
        policy = self.policies[t]
        if policy == "stale":
            self._bump(self.labels[t], stale_served=1)
            return
        if policy == "strict":
            raise StaleQueryError(
                f"tenant {t} at version {int(fleet.version[t])}, session "
                f"slice stamped {int(self.versions[t])}: refresh the "
                "fleet caches first (or use policy='refresh' / 'stale')")
        self._bump(self.labels[t], auto_refreshes=1)
        self.rebuild_tenant(fleet, t)

    # -- per-tenant query ops (gathers over one slice of the stack) ----------

    def _tab(self, t: int) -> QueryTables:
        return tenant_slice(self.tables, t)

    def connected(self, fleet, t: int, u, v) -> jnp.ndarray:
        self.ensure(fleet, t)
        return q.connected(self._tab(t), _i32(u), _i32(v))

    def depth(self, fleet, t: int, v) -> jnp.ndarray:
        self.ensure(fleet, t)
        return q.depth_of(self._tab(t), _i32(v))

    def lca(self, fleet, t: int, u, v) -> jnp.ndarray:
        self.ensure(fleet, t)
        return q.lca(self._tab(t), _i32(u), _i32(v))

    def is_ancestor(self, fleet, t: int, a, x) -> jnp.ndarray:
        self.ensure(fleet, t)
        return q.is_ancestor(self._tab(t), _i32(a), _i32(x))

    def is_bridge(self, fleet, t: int, u, v) -> jnp.ndarray:
        self.ensure(fleet, t)
        if self.bcc is None:
            raise ValueError("session built without biconnectivity labels "
                             "— pass bcc=refresh_bccs(...) to from_fleet")
        b = tenant_slice(self.bcc, t)
        cap = b.pool_src.shape[0]
        _hit, flagged = q.edge_membership(
            _i32(u), _i32(v), b.pool_src, b.pool_dst, b.pool_valid,
            b.bridge[:cap])
        return flagged

    def is_articulation(self, fleet, t: int, v) -> jnp.ndarray:
        self.ensure(fleet, t)
        if self.bcc is None:
            raise ValueError("session built without biconnectivity labels "
                             "— pass bcc=refresh_bccs(...) to from_fleet")
        b = tenant_slice(self.bcc, t)
        vq = _i32(v)
        n = b.articulation.shape[0]
        return ((vq >= 0) & (vq < n)
                & b.articulation[jnp.clip(vq, 0, n - 1)])

    # -- telemetry -----------------------------------------------------------

    def sync_stats(self, t=None) -> dict:
        """§12 amortization counters — one tenant's, or fleet totals.

        ``t`` is a stable tenant label; a slot index also resolves (via
        ``labels``) when no tenant carries that exact label, so PR-8
        slot-indexed callers read the same numbers as before.
        """
        keys = ("builds", "build_syncs_total", "stale_served",
                "auto_refreshes")
        if t is None:
            return {k: sum(c[k] for c in self.stats.values())
                    for k in keys}
        if t not in self.stats and isinstance(t, int) \
                and 0 <= t < len(self.labels):
            t = self.labels[t]
        c = self.stats.get(t, collections.Counter())
        return {k: int(c[k]) for k in keys}


# -- host-side dispatch + admission -------------------------------------------

class FleetDispatcher:
    """Coalesces incoming per-tenant batches into ``(T, B)`` tick blocks.

    Host-side. Tenants ``offer`` fixed-shape ``StreamBatch`` units (the
    §9 contract: sentinel-padded, one shape per stream); each ``tick``
    pops AT MOST ONE unit per resident tenant — units are atomic, never
    split across ticks or merged within one, so every tenant's applied
    sequence equals its offered sequence (the fleet-replay equivalence
    invariant). Slots with no resident or no queued unit get all-sentinel
    rows: inert under ``apply_batches`` except the unconditional version
    bump, which the admission checkpoint path hides (an evicted tenant's
    clock restarts from its restored stamp).
    """

    def __init__(self, n_nodes: int, batch: int):
        self.n_nodes = int(n_nodes)
        self.batch = int(batch)
        self.queues: dict[Any, collections.deque] = \
            collections.defaultdict(collections.deque)
        self.offered = collections.Counter()
        self.served = collections.Counter()

    def offer(self, tenant, b: StreamBatch) -> None:
        for arr in (b.ins_u, b.ins_v, b.del_u, b.del_v):
            if arr.shape != (self.batch,):
                raise ValueError(
                    f"batch unit shape {arr.shape} != ({self.batch},) — "
                    "the fleet block is fixed-shape; regenerate the "
                    "stream with the fleet's batch size")
        self.queues[tenant].append(b)
        self.offered[tenant] += 1

    def pending(self, tenant=None) -> int:
        if tenant is not None:
            return len(self.queues[tenant])
        return sum(len(d) for d in self.queues.values())

    def tick(self, tenant_at: Sequence[Any]):
        """Build one tick block for the current residency map.

        Args:
          tenant_at: per-slot resident tenant id (``None`` = empty slot).

        Returns:
          ((ins_u, ins_v, del_u, del_v) int32[T, B] device arrays,
           served: {tenant: event count} for the units dispatched).
        """
        t_slots, n, b = len(tenant_at), self.n_nodes, self.batch
        ins_u = np.full((t_slots, b), n, np.int32)
        ins_v = np.full((t_slots, b), n, np.int32)
        del_u = np.full((t_slots, b), n, np.int32)
        del_v = np.full((t_slots, b), n, np.int32)
        served: dict[Any, int] = {}
        for s, tenant in enumerate(tenant_at):
            if tenant is None or not self.queues[tenant]:
                continue
            unit = self.queues[tenant].popleft()
            ins_u[s], ins_v[s] = unit.ins_u, unit.ins_v
            del_u[s], del_v[s] = unit.del_u, unit.del_v
            served[tenant] = int((unit.ins_u < n).sum()
                                 + (unit.del_u < n).sum())
            self.served[tenant] += 1
        return ((jnp.asarray(ins_u), jnp.asarray(ins_v),
                 jnp.asarray(del_u), jnp.asarray(del_v)), served)

    def drain(self, tenant_at: Sequence[Any], max_blocks: int = 1):
        """Cross-tick carryover: up to ``max_blocks`` tick blocks in one
        serving tick, so a bursty tenant's queued backlog drains at
        ``max_blocks`` units/tick instead of silently waiting one tick
        per unit. Each block is a plain ``tick`` — at most one unit per
        tenant per block, FIFO, units never split or merged — so the
        applied sequence is exactly the offered sequence (the atomicity
        contract), just on a faster clock.

        Returns a list of ``(block, served)`` pairs; empty when no
        resident tenant has queued units.
        """
        out = []
        for _ in range(max(1, int(max_blocks))):
            if not any(tenant is not None and self.queues[tenant]
                       for tenant in tenant_at):
                break
            out.append(self.tick(tenant_at))
        return out

    def backlog(self) -> dict:
        """{tenant: queued units} for tenants with a non-empty queue —
        the carryover pressure signal (reported per bucket in §15)."""
        return {t: len(q) for t, q in self.queues.items() if q}


class FleetManager:
    """Session admission/eviction against the fleet's slot capacity.

    Host-side bookkeeping around a ``ForestFleet``: which tenant lives
    in which slot, LRU order, and per-tenant stream cursors. When every
    slot is occupied, ``ensure`` evicts a resident through the §8
    checkpoint path (forest + cursor, atomic publish); re-admission
    restores bit-identically — eviction is invisible to the tenant's
    replayed history (regression-tested).

    Victim choice prefers IDLE least-recently-used residents: evicting a
    tenant that still has pending dispatcher units round-trips a
    checkpoint for nothing (it must be restored before its very next
    tick). Pass ``busy`` (tenant → bool) to ``ensure``/``adopt_ready``;
    when every resident is busy the global LRU resident is evicted
    anyway — liveness over thrash-avoidance. Omitting ``busy``
    reproduces PR 8's plain global-LRU behavior exactly.

    Async admission (§15): ``prefetch`` starts the checkpoint restore on
    a host worker thread while the device runs the current tick;
    ``adopt_ready`` — called at a tick BOUNDARY — installs completed
    restores. A restore finishing mid-tick is never observed early.
    ``schema`` (optional ``FleetSchema``) is stamped into eviction
    manifests and validated on restore, so a tenant checkpointed under
    one bucket schema can't be silently adopted into another.
    """

    def __init__(self, fleet: ForestFleet, ckpt_dir: str | pathlib.Path,
                 *, schema: "FleetSchema | None" = None,
                 executor: concurrent.futures.Executor | None = None):
        self.fleet = fleet
        self.ckpt_dir = pathlib.Path(ckpt_dir)
        self.schema = schema
        self.slot_of: dict[Any, int] = {}
        self.tenant_at: list[Any] = [None] * fleet.n_slots
        self.last_used = [-1] * fleet.n_slots
        self.clock = 0
        self.cursors = collections.Counter()   # tenant → applied batches
        self.seeds: dict[Any, DynamicForest] = {}  # first-admission state
        self.admissions = 0
        self.evictions = 0
        self.restores = 0
        self.prefetches = 0
        self._executor = executor
        self._prefetch: dict[Any, concurrent.futures.Future] = {}

    def _tenant_dir(self, tenant) -> pathlib.Path:
        return self.ckpt_dir / f"tenant_{tenant}"

    def touch(self, tenant) -> None:
        self.clock += 1
        self.last_used[self.slot_of[tenant]] = self.clock

    def has_checkpoint(self, tenant) -> bool:
        return ckpt.latest_step(self._tenant_dir(tenant)) is not None

    def prefetching(self, tenant) -> bool:
        return tenant in self._prefetch

    def pick_victim(self, busy: Callable[[Any], bool] | None = None):
        """The tenant ``evict`` would choose: idle LRU resident if one
        exists, else the global LRU resident; ``None`` if no residents."""
        residents = [s for s, occ in enumerate(self.tenant_at)
                     if occ is not None]
        if not residents:
            return None
        if busy is not None:
            idle = [s for s in residents if not busy(self.tenant_at[s])]
            if idle:
                residents = idle
        slot = min(residents, key=lambda s: self.last_used[s])
        return self.tenant_at[slot]

    def has_room(self, busy: Callable[[Any], bool] | None = None) -> bool:
        """True when an admission would not evict a busy resident."""
        if any(occ is None for occ in self.tenant_at):
            return True
        victim = self.pick_victim(busy)
        return victim is not None and (busy is None or not busy(victim))

    def _slot_for_admit(self,
                        busy: Callable[[Any], bool] | None = None) -> int:
        free = [s for s, occupant in enumerate(self.tenant_at)
                if occupant is None]
        if free:
            return free[0]
        self.evict(self.pick_victim(busy))
        return [s for s, occ in enumerate(self.tenant_at)
                if occ is None][0]

    def ensure(self, tenant,
               busy: Callable[[Any], bool] | None = None) -> int:
        """Make ``tenant`` resident; returns its slot (evicting the
        preferred victim — idle LRU first — if the fleet is full)."""
        if tenant in self.slot_of:
            self.touch(tenant)
            return self.slot_of[tenant]
        if tenant in self._prefetch:
            # A prefetch is in flight: adopt it synchronously rather
            # than racing a second restore against it.
            forest, cursor = self._prefetch.pop(tenant).result()
            slot = self._slot_for_admit(busy)
            self._install(tenant, slot, forest, cursor=cursor,
                          restored=True)
            return slot
        slot = self._slot_for_admit(busy)
        self._admit(tenant, slot)
        return slot

    def evict(self, tenant) -> None:
        """Checkpoint ``tenant``'s forest + cursor and free its slot."""
        slot = self.slot_of.pop(tenant)
        extra = ({"schema": self.schema.to_dict()}
                 if self.schema is not None else None)
        ckpt.save(self._tenant_dir(tenant),
                  {"forest": self.fleet.tenant(slot)},
                  step=self.clock, data_cursor=int(self.cursors[tenant]),
                  keep=1, extra=extra)
        self.fleet = self.fleet.clear_tenant(slot)
        self.tenant_at[slot] = None
        self.last_used[slot] = -1
        self.evictions += 1

    def _check_manifest(self, tenant, manifest) -> None:
        saved = (manifest.get("extra") or {}).get("schema")
        if saved is None or self.schema is None:
            return
        if saved != self.schema.to_dict():
            raise ValueError(
                f"tenant {tenant!r} checkpoint written under schema "
                f"{saved} cannot be admitted into bucket schema "
                f"{self.schema.to_dict()} — route it to its own bucket")

    def _fresh_forest(self, tenant) -> DynamicForest:
        seed = self.seeds.get(tenant)
        if seed is not None:
            return seed
        return forest_empty(self.fleet.n_nodes, self.fleet.capacity)

    def _restore(self, tenant):
        """(worker-thread safe) load tenant's checkpoint → (forest,
        cursor). Pure host work: file read + np decode."""
        fresh = {"forest": forest_empty(self.fleet.n_nodes,
                                        self.fleet.capacity)}
        restored, manifest = ckpt.restore(self._tenant_dir(tenant), fresh)
        self._check_manifest(tenant, manifest)
        return restored["forest"], int(manifest["data_cursor"])

    def _install(self, tenant, slot: int, forest, *, cursor=None,
                 restored: bool = False) -> None:
        if cursor is not None:
            self.cursors[tenant] = int(cursor)
        self.fleet = self.fleet.set_tenant(slot, forest)
        self.slot_of[tenant] = slot
        self.tenant_at[slot] = tenant
        self.admissions += 1
        if restored:
            self.restores += 1
        self.touch(tenant)

    def _admit(self, tenant, slot: int) -> None:
        if self.has_checkpoint(tenant):
            forest, cursor = self._restore(tenant)
            self._install(tenant, slot, forest, cursor=cursor,
                          restored=True)
        else:
            self._install(tenant, slot, self._fresh_forest(tenant))

    # -- async admission (§15) ----------------------------------------------

    def prefetch(self, tenant) -> bool:
        """Start restoring ``tenant`` on the host worker while the
        current tick runs on device. No fleet state changes here — the
        restored forest becomes visible only when ``adopt_ready`` runs
        at a tick boundary. Returns True if a prefetch was started (or
        is already in flight)."""
        if tenant in self.slot_of:
            return False
        if tenant in self._prefetch:
            return True
        if self._executor is not None:
            fut = self._executor.submit(self._restore, tenant)
        else:
            # No executor: run inline but STILL defer adoption to the
            # next boundary — the protocol, minus the overlap.
            fut = concurrent.futures.Future()
            try:
                fut.set_result(self._restore(tenant))
            except Exception as e:          # surfaced at adopt time
                fut.set_exception(e)
        self._prefetch[tenant] = fut
        self.prefetches += 1
        return True

    def adopt_ready(self,
                    busy: Callable[[Any], bool] | None = None) -> list:
        """Tick-boundary adoption: install every COMPLETED prefetch that
        can take a slot (free, or by evicting the preferred victim).
        Unfinished restores stay in flight; restores that finished
        mid-tick land here, never earlier. Returns adopted tenants."""
        adopted = []
        for tenant in list(self._prefetch):
            fut = self._prefetch[tenant]
            if not fut.done():
                continue
            del self._prefetch[tenant]
            forest, cursor = fut.result()   # re-raises restore errors
            slot = self._slot_for_admit(busy)
            self._install(tenant, slot, forest, cursor=cursor,
                          restored=True)
            adopted.append(tenant)
        return adopted

    def note_applied(self, served: dict) -> None:
        """Advance stream cursors after a tick (one unit per tenant)."""
        for tenant in served:
            self.cursors[tenant] += 1


# -- shape-bucketed sub-fleets (DESIGN.md §15) --------------------------------

@dataclasses.dataclass(frozen=True)
class FleetSchema:
    """A fleet shape class: every tenant in a bucket shares these.

    ``ForestFleet`` vmaps all T tenants through ONE ``(n, capacity)``
    schema, so ten thousand 64-node sessions pay the padding (and the
    per-tick ``max_t(rounds)+1`` sync bill) of the single largest
    tenant. A ``FleetSchema`` names one shape class; ``BucketedFleet``
    routes each tenant to the sub-fleet whose schema it fits, so small
    sessions never ride the largest tenant's padding.
    """

    n_nodes: int
    capacity: int
    batch: int

    @property
    def key(self) -> str:
        return f"n{self.n_nodes}_c{self.capacity}_b{self.batch}"

    @property
    def slot_cost(self) -> int:
        """Device rows one resident slot pins: 3 vertex-length arrays
        (parent, rep, dirty) + 4 capacity-length pool arrays — the
        memory proxy behind equal-budget bucketed-vs-single comparisons
        (table9)."""
        return 3 * self.n_nodes + 4 * self.capacity

    def to_dict(self) -> dict:
        return {"n_nodes": int(self.n_nodes),
                "capacity": int(self.capacity), "batch": int(self.batch)}

    @classmethod
    def from_dict(cls, d: dict) -> "FleetSchema":
        return cls(n_nodes=int(d["n_nodes"]), capacity=int(d["capacity"]),
                   batch=int(d["batch"]))


class FleetBucket:
    """One sub-fleet: a ``ForestFleet`` + dispatcher + manager + caches,
    all under a single ``FleetSchema``, ticking independently.

    Each bucket pays its OWN per-tick sync bill (``max over its lanes``
    + 1) with its own ``(T_b, B_b)`` block shape and its own refresh
    cadence; a converged or small bucket never waits on a large one.
    ``tick`` is the whole serving step for the bucket:

      1. tick boundary — adopt prefetched restores that completed during
         the previous tick (``FleetManager.adopt_ready``; a restore
         finishing mid-tick is never observed early);
      2. admission — waiting tenants with traffic claim free slots
         (idle-LRU eviction when full); tenants with a checkpoint start
         an async ``prefetch`` instead of blocking the device;
      3. apply — up to ``max_drain`` dispatcher blocks (cross-tick
         carryover for bursty tenants), each one vmapped
         ``apply_batches`` inside the bucket-labeled ``bucket_tick``
         span;
      4. cadenced refresh — vmapped tour/BCC (+ optional query session)
         on the bucket's own ``CadencePolicy``. Any residency change
         since the last refresh forces a full (non-incremental) rebuild:
         a rotated lane's cached numbering describes the slot's PREVIOUS
         occupant.
    """

    def __init__(self, schema: FleetSchema, n_slots: int,
                 ckpt_dir: str | pathlib.Path, *,
                 cadence: CadencePolicy | None = None,
                 name: str | None = None, use_kernel: bool = False,
                 max_drain: int = 1,
                 executor: concurrent.futures.Executor | None = None):
        self.schema = schema
        self.name = name or schema.key
        self.cadence = cadence or CadencePolicy()
        self.use_kernel = use_kernel
        self.max_drain = max(1, int(max_drain))
        self.manager = FleetManager(
            fleet_empty(n_slots, schema.n_nodes, schema.capacity),
            pathlib.Path(ckpt_dir) / self.name, schema=schema,
            executor=executor)
        self.dispatcher = FleetDispatcher(schema.n_nodes, schema.batch)
        self.tenants: list = []
        self.tn: TourNumbering | None = None
        self.bcc: DynamicBCC | None = None
        self.session: FleetQuerySession | None = None
        self.ticks = 0            # ticks that applied at least one block
        self.blocks = 0
        self.sync_apply = 0
        self.sync_refresh = 0
        self.applied = collections.Counter()   # tenant → applied events
        self.padded_events = 0    # Σ blocks · T_b · B_b (slot-rows fed)
        self.padded_rows = 0      # Σ blocks · T_b · slot_cost (memory·ticks)
        self.max_backlog = 0
        self._lanes_dirty = True  # residency changed since last refresh

    # -- routing -------------------------------------------------------------

    def route(self, tenant, seed: DynamicForest | None = None) -> None:
        """Register ``tenant`` in this bucket. ``seed`` (optional) is
        the forest its FIRST admission installs — e.g. a pre-built
        initial graph state — instead of an edgeless forest; later
        admissions restore from its eviction checkpoint as usual."""
        if tenant in self.tenants:
            return
        self.tenants.append(tenant)
        if seed is not None:
            if (seed.n_nodes != self.schema.n_nodes
                    or seed.capacity != self.schema.capacity):
                raise ValueError(
                    f"seed forest (n={seed.n_nodes}, "
                    f"capacity={seed.capacity}) does not fit bucket "
                    f"schema {self.schema.key}")
            self.manager.seeds[tenant] = seed

    def offer(self, tenant, unit: StreamBatch) -> None:
        if tenant not in self.tenants:
            raise KeyError(f"tenant {tenant!r} not routed to bucket "
                           f"{self.name}")
        self.dispatcher.offer(tenant, unit)

    def busy(self, tenant) -> bool:
        return self.dispatcher.pending(tenant) > 0

    def pending(self) -> int:
        return self.dispatcher.pending() + len(self.manager._prefetch)

    # -- the serving tick ----------------------------------------------------

    def _admit_waiting(self) -> None:
        mgr = self.manager
        room = sum(1 for occ in mgr.tenant_at
                   if occ is None or not self.busy(occ))
        room -= len(mgr._prefetch)   # in-flight restores will claim room
        for tenant in self.tenants:
            if room <= 0:
                break
            if (not self.busy(tenant) or tenant in mgr.slot_of
                    or mgr.prefetching(tenant)):
                continue
            if mgr.has_checkpoint(tenant):
                mgr.prefetch(tenant)   # lands at the NEXT tick boundary
            else:
                mgr.ensure(tenant, busy=self.busy)
                self._lanes_dirty = True
            room -= 1

    def tick(self, step: int | None = None) -> dict:
        """One serving tick; returns {tenant: applied events}."""
        mgr = self.manager
        if mgr.adopt_ready(busy=self.busy):
            self._lanes_dirty = True
        self._admit_waiting()
        served_total = collections.Counter()
        with obs.span("bucket_tick", step=step, bucket=self.name):
            for block, served in self.dispatcher.drain(
                    mgr.tenant_at, max_blocks=self.max_drain):
                mgr.fleet, stats = apply_batches(
                    mgr.fleet, *block, use_kernel=self.use_kernel)
                mgr.note_applied(served)
                self.sync_apply += fleet_sync_cost(stats)
                self.blocks += 1
                self.padded_events += mgr.fleet.n_slots * self.schema.batch
                self.padded_rows += mgr.fleet.n_slots * self.schema.slot_cost
                for tenant, ev in served.items():
                    served_total[tenant] += ev
                    self.applied[tenant] += ev
            if served_total:
                if (self.cadence.tour != "off"
                        and self.cadence.due(self.ticks)):
                    self.refresh(step=step)
                self.ticks += 1
        backlog = self.dispatcher.backlog()
        if backlog:
            self.max_backlog = max(self.max_backlog,
                                   max(backlog.values()))
        return dict(served_total)

    def refresh(self, step: int | None = None) -> None:
        """Vmapped cache refresh for the whole bucket (inside the
        bucket-labeled ``fleet_refresh`` span). Forced callers (end-of-run reporting)
        call this directly, out of cadence."""
        mgr, cad = self.manager, self.cadence
        inc = not self._lanes_dirty
        with obs.span("fleet_refresh", step=step, bucket=self.name), \
                obs.SyncLedger() as led:
            inc_t = cad.tour == "incremental" and self.tn is not None \
                and inc
            self.tn, mgr.fleet = refresh_tours(
                mgr.fleet, self.tn if inc_t else None,
                incremental=inc_t, use_kernel=self.use_kernel)
            if cad.bcc != "off":
                inc_b = cad.bcc == "incremental" and self.bcc is not None \
                    and inc
                self.bcc = refresh_bccs(
                    mgr.fleet, self.bcc if inc_b else None, tour=self.tn,
                    incremental=inc_b, use_kernel=self.use_kernel)
            if cad.queries:
                if self.session is None:
                    self.session = FleetQuerySession.from_fleet(
                        mgr.fleet, self.tn, self.bcc,
                        policy=cad.staleness, use_kernel=self.use_kernel,
                        labels=[t if t is not None else s for s, t
                                in enumerate(mgr.tenant_at)])
                else:
                    for s, tenant in enumerate(mgr.tenant_at):
                        if tenant is not None:
                            self.session.set_label(s, tenant)
                    self.session.restamp(mgr.fleet, self.tn, self.bcc)
        self.sync_refresh += led.total()
        self._lanes_dirty = False

    def slot(self, tenant) -> int:
        """The tenant's resident slot (admitting it if needed)."""
        return self.manager.ensure(tenant, busy=self.busy)


class BucketedFleet:
    """Shape-bucketed sub-fleets behind one serving surface (§15).

    Tenants are routed by ``FleetSchema`` into ``FleetBucket``s; each
    bucket ticks independently with its own block shape, cadence, and
    sync bill. A ``BucketedFleet`` with exactly one bucket is PR 8's
    single-schema fleet, bit-identically (regression-tested) — the
    refactor's compatibility anchor. All buckets share one host worker
    thread for async admission restores.
    """

    def __init__(self, ckpt_dir: str | pathlib.Path, *,
                 use_kernel: bool = False, max_drain: int = 1):
        self.ckpt_dir = pathlib.Path(ckpt_dir)
        self.use_kernel = use_kernel
        self.max_drain = max_drain
        self.buckets: dict[str, FleetBucket] = {}
        self._bucket_of: dict[Any, str] = {}
        self._executor = concurrent.futures.ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="fleet-admit")

    def add_bucket(self, schema: FleetSchema, n_slots: int, *,
                   cadence: CadencePolicy | None = None,
                   name: str | None = None,
                   max_drain: int | None = None) -> FleetBucket:
        name = name or schema.key
        if name in self.buckets:
            raise ValueError(f"bucket {name!r} already exists")
        b = FleetBucket(schema, n_slots, self.ckpt_dir, cadence=cadence,
                        name=name, use_kernel=self.use_kernel,
                        max_drain=(self.max_drain if max_drain is None
                                   else max_drain),
                        executor=self._executor)
        self.buckets[name] = b
        return b

    def route(self, tenant, schema: FleetSchema, *,
              seed: DynamicForest | None = None) -> FleetBucket:
        """Bind ``tenant`` to the bucket matching ``schema`` exactly."""
        if tenant in self._bucket_of:
            b = self.buckets[self._bucket_of[tenant]]
            if b.schema != schema:
                raise ValueError(
                    f"tenant {tenant!r} already routed to bucket "
                    f"{b.name} ({b.schema.key}); cannot re-route to "
                    f"{schema.key}")
            return b
        for b in self.buckets.values():
            if b.schema == schema:
                b.route(tenant, seed=seed)
                self._bucket_of[tenant] = b.name
                return b
        raise KeyError(f"no bucket with schema {schema.key} — "
                       f"add_bucket first (have: "
                       f"{', '.join(self.buckets) or 'none'})")

    def bucket_of(self, tenant) -> FleetBucket:
        return self.buckets[self._bucket_of[tenant]]

    def offer(self, tenant, unit: StreamBatch) -> None:
        self.bucket_of(tenant).offer(tenant, unit)

    def pending(self) -> int:
        return sum(b.pending() for b in self.buckets.values())

    def step(self, step: int | None = None) -> dict:
        """One serving tick: every bucket with traffic ticks once."""
        served: dict = {}
        for b in self.buckets.values():
            if b.pending():
                served.update(b.tick(step))
        return served

    def run(self, max_steps: int = 1_000_000) -> int:
        """Drain every queue; returns the number of steps taken."""
        steps = 0
        while self.pending():
            if steps >= max_steps:
                raise RuntimeError(
                    f"BucketedFleet.run did not drain in {max_steps} "
                    "steps — admission livelock?")
            self.step(steps)
            steps += 1
        return steps

    def finalize(self) -> None:
        """Force a final refresh in every bucket that applied work."""
        for b in self.buckets.values():
            if b.blocks:
                b.refresh()

    def tenant_forest(self, tenant) -> DynamicForest:
        """Tenant's current forest (re-admitting it if evicted)."""
        b = self.bucket_of(tenant)
        slot = b.slot(tenant)          # may evict + restore, swaps fleet
        return b.manager.fleet.tenant(slot)

    # -- fleet-wide accounting ------------------------------------------------

    def sync_total(self) -> int:
        return sum(b.sync_apply + b.sync_refresh
                   for b in self.buckets.values())

    def applied_events(self) -> int:
        return sum(sum(b.applied.values()) for b in self.buckets.values())

    def padded_rows(self) -> int:
        return sum(b.padded_rows for b in self.buckets.values())

    def padded_events(self) -> int:
        return sum(b.padded_events for b in self.buckets.values())

    def close(self) -> None:
        self._executor.shutdown(wait=True)

    def __enter__(self) -> "BucketedFleet":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
