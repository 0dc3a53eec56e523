"""QuerySession: the read path of the batch-dynamic forest (DESIGN.md §12).

``apply_batch`` is the write path; this module serves reads between
writes. A ``QuerySession`` freezes one consistent view of the forest —
the ``core.queries.QueryTables`` index built from a tour refresh, plus
(optionally) the ``DynamicBCC`` labels — and answers query batches with
zero further engine syncs until the forest moves on.

Staleness is a first-class contract, not an accident (the satellite
hazard this module exists to close): every structural mutation bumps
``DynamicForest.version``, the session stamps the version it was built
against, and each query re-checks the stamp. ``from_state``/``rebuild``
additionally snapshot-diff any caller-provided caches against the live
state (the §10 pattern ``refresh_bcc`` uses for dirty detection) so a
session can never be *constructed* over stale intervals either. On a
stamp mismatch the ``policy`` decides:

  * ``"strict"``  — raise ``StaleQueryError`` (default: reads after an
                    un-refreshed edit are a bug, never silent);
  * ``"refresh"`` — transparently rebuild from the current state (full
                    tour + tables + BCC recompute, syncs counted in
                    ``build_syncs_total``), then answer;
  * ``"stale"``   — serve the frozen view and count it
                    (``stale_served``) — bounded-staleness serving for
                    read-heavy loops that refresh on a cadence.

Each read op runs inside ``obs.span("query", op=<method name>)``: the
span covers the staleness gate and the dispatch, not the device's
answer, so it is the session's host cost per read batch.

The session is a host-side mutable object (like
``launch.resilient.ResilientStreamLoop``), deliberately NOT a pytree:
it owns amortization counters (``builds``, ``build_syncs_total``) that
``benchmarks/table7_queries`` and ``serve_stream --read-ratio`` report.
"""
from __future__ import annotations

import dataclasses
import functools

import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import queries as q
from repro.core.compress import DEFAULT_JUMPS
from repro.core.euler import TourNumbering, tour_numbering
from repro.dynamic.bcc import DynamicBCC, refresh_bcc
from repro.dynamic.forest import DynamicForest

POLICIES = ("strict", "refresh", "stale")


class StaleQueryError(RuntimeError):
    """A query hit a session whose caches no longer match the forest."""


def _i32(x) -> jnp.ndarray:
    return jnp.atleast_1d(jnp.asarray(x, jnp.int32))


def _same(a: jnp.ndarray, b: jnp.ndarray) -> bool:
    return bool(np.array_equal(np.asarray(a), np.asarray(b)))


def _read_op(method):
    """Run a read op inside a ``query`` span named by its ``op``."""
    op = method.__name__

    @functools.wraps(method)
    def traced(self, *args, **kwargs):
        with obs.span("query", op=op):
            return method(self, *args, **kwargs)
    return traced


@dataclasses.dataclass
class QuerySession:
    """One consistent, version-stamped read view over a ``DynamicForest``.

    Build with ``from_state`` (reusing the caller's refreshed ``tn`` /
    ``bcc`` caches when available — the build then costs only the
    ancestor/depth tables); re-stamp after each refresh cadence with
    ``rebuild``. All query methods take the *current* state first so the
    staleness check is per-call, batched int32 ids after.
    """

    tables: q.QueryTables
    tn: TourNumbering
    bcc: DynamicBCC | None
    state_version: int
    policy: str = "strict"
    use_kernel: bool = False
    n_jumps: int = DEFAULT_JUMPS
    # amortization / staleness telemetry (host-side counters)
    builds: int = 0
    build_syncs_total: int = 0
    stale_served: int = 0
    auto_refreshes: int = 0

    @classmethod
    def from_state(cls, state: DynamicForest,
                   tn: TourNumbering | None = None,
                   bcc: DynamicBCC | None = None, *,
                   policy: str = "strict", use_kernel: bool = False,
                   n_jumps: int = DEFAULT_JUMPS) -> "QuerySession":
        if policy not in POLICIES:
            raise ValueError(f"policy {policy!r} not in {POLICIES}")
        sess = cls(tables=None, tn=None, bcc=None, state_version=-1,
                   policy=policy, use_kernel=use_kernel, n_jumps=n_jumps)
        sess.rebuild(state, tn=tn, bcc=bcc)
        return sess

    # -- lifecycle ----------------------------------------------------------

    def rebuild(self, state: DynamicForest, *,
                tn: TourNumbering | None = None,
                bcc: DynamicBCC | None = None) -> "QuerySession":
        """(Re)build the index against ``state`` and stamp its version.

        Caller-provided caches are snapshot-diffed against the live
        state before being trusted — a ``tn`` whose parent table is not
        bit-identical to ``state.parent``, or a ``bcc`` whose §10
        snapshots disagree with the live pool, is rejected rather than
        silently serving somebody else's intervals.
        """
        if tn is not None and not _same(tn.parent, state.parent):
            raise ValueError(
                "stale TourNumbering: tn.parent != state.parent — run "
                "refresh_tour(state, tn) before building a QuerySession")
        if bcc is not None and not (
                _same(bcc.parent, state.parent)
                and _same(bcc.pool_src, state.pool_src)
                and _same(bcc.pool_dst, state.pool_dst)
                and _same(bcc.pool_valid, state.pool_valid)
                and _same(bcc.tree_mask, state.tree_mask)):
            raise ValueError(
                "stale DynamicBCC: its §10 snapshots disagree with the "
                "live forest — run refresh_bcc before building a "
                "QuerySession")
        if tn is None:
            # The same compiled program as the refresh path's (which
            # always asks for the syncs): one compile, not two.
            tn, _ = tour_numbering(state.parent, use_kernel=self.use_kernel,
                                   return_syncs=True)
        self.tables = q.build_tables(tn, n_jumps=self.n_jumps)
        self.tn = tn
        self.bcc = bcc
        self.state_version = int(state.version)
        self.builds += 1
        self.build_syncs_total += int(self.tables.build_syncs)
        return self

    def is_fresh(self, state: DynamicForest) -> bool:
        return int(state.version) == self.state_version

    def ensure(self, state: DynamicForest) -> None:
        """Per-query staleness gate — the policy dispatch."""
        if self.is_fresh(state):
            return
        if self.policy == "stale":
            self.stale_served += 1
            return
        if self.policy == "strict":
            raise StaleQueryError(
                f"forest at version {int(state.version)}, session built "
                f"at {self.state_version}: refresh_tour/refresh_bcc and "
                "session.rebuild(...) first (or use policy='refresh' / "
                "'stale')")
        # policy == "refresh": recompute the view from the current state.
        self.auto_refreshes += 1
        bcc = None
        if self.bcc is not None:
            bcc = refresh_bcc(state, None,
                              tour=tour_numbering(
                                  state.parent, use_kernel=self.use_kernel),
                              use_kernel=self.use_kernel)
        self.rebuild(state, bcc=bcc)

    # -- tree queries (tour-interval + doubling tables) ----------------------

    @_read_op
    def connected(self, state: DynamicForest, u, v) -> jnp.ndarray:
        self.ensure(state)
        return q.connected(self.tables, _i32(u), _i32(v))

    @_read_op
    def depth(self, state: DynamicForest, v) -> jnp.ndarray:
        self.ensure(state)
        return q.depth_of(self.tables, _i32(v))

    @_read_op
    def lca(self, state: DynamicForest, u, v) -> jnp.ndarray:
        self.ensure(state)
        return q.lca(self.tables, _i32(u), _i32(v))

    @_read_op
    def is_ancestor(self, state: DynamicForest, a, x) -> jnp.ndarray:
        self.ensure(state)
        return q.is_ancestor(self.tables, _i32(a), _i32(x))

    @_read_op
    def subtree_agg(self, state: DynamicForest, v, payload,
                    op: str = "add") -> jnp.ndarray:
        self.ensure(state)
        return q.subtree_agg(self.tables, _i32(v), jnp.asarray(payload), op)

    @_read_op
    def path_agg(self, state: DynamicForest, u, v, payload,
                 op: str = "add") -> jnp.ndarray:
        self.ensure(state)
        return q.path_agg(self.tables, _i32(u), _i32(v),
                          jnp.asarray(payload), op)

    # -- biconnectivity membership (DynamicBCC labels) ------------------------

    def _require_bcc(self) -> DynamicBCC:
        if self.bcc is None:
            raise ValueError(
                "session built without biconnectivity labels — pass "
                "bcc=refresh_bcc(...) to from_state/rebuild to answer "
                "is_bridge / is_articulation")
        return self.bcc

    @_read_op
    def is_bridge(self, state: DynamicForest, u, v) -> jnp.ndarray:
        """bool[B] — some live (u, v) pool copy is a bridge.

        Matched against the session's *snapshot* pool (self-consistent
        with the bridge flags under the ``stale`` policy). A pair with
        parallel copies is never a bridge — the copies form a cycle —
        and a pair with no live copy answers False.
        """
        self.ensure(state)
        bcc = self._require_bcc()
        cap = bcc.pool_src.shape[0]
        _hit, flagged = q.edge_membership(
            _i32(u), _i32(v), bcc.pool_src, bcc.pool_dst, bcc.pool_valid,
            bcc.bridge[:cap])
        return flagged

    @_read_op
    def is_articulation(self, state: DynamicForest, v) -> jnp.ndarray:
        self.ensure(state)
        bcc = self._require_bcc()
        vq = _i32(v)
        n = bcc.articulation.shape[0]
        return ((vq >= 0) & (vq < n)
                & bcc.articulation[jnp.clip(vq, 0, n - 1)])

    # -- telemetry ------------------------------------------------------------

    def sync_stats(self) -> dict:
        """Amortization counters for benchmarks / the serving loop."""
        return {"builds": self.builds,
                "build_syncs_total": self.build_syncs_total,
                "stale_served": self.stale_served,
                "auto_refreshes": self.auto_refreshes}
