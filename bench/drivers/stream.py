"""Driver of ``kind: stream`` traffic: a write stream with open-loop reads.

The system under test is the serving loop of one dynamic graph,
``repro.launch.resilient.ResilientStreamLoop`` with its ``ForestView``
and the view's ``QuerySession``:

- writes are churn batches (``bench.gen.streams.Churn``), applied closed
  loop through ``loop.step``; the view refreshes the Euler tour, the
  biconnectivity labels and the query session every ``cadence`` batches;
- reads are due at a fixed rate from the window's start (open loop),
  round-robin over the read ops; they are served between write steps,
  where the one host thread and the one device are free, so a read waits
  behind the write path as a user's read would. Its latency runs from its
  due time to its answer being ready.

The window runs whole cadence periods until ``--seconds`` have passed.
"""
from __future__ import annotations

import dataclasses
import time
import weakref

import numpy as np

from bench import reference as ref
from bench.gen import graphs
from bench.gen.streams import READ_OPS, Churn, Reads

#: Harness annotations an idle gap of the trace may be charged to.
LABELS = ("write_batch", "refresh", "read")
#: Traffic keys cut for the CPU rehearsal.
TINY = {"write_batch": 64, "read_batch": 32, "read_rate": 200,
        "checked_reads_per_op": 2}


@dataclasses.dataclass
class Generation:
    """What one query session served from, and the live edges it saw."""

    session: weakref.ref    # the QuerySession, for its identity only
    parent: object          # its tour's parent array, on the device
    live: np.ndarray        # edge ids into the graph's edge array


def dispatch(sess, state, op, u, v, payload):
    """One read batch through the ``QuerySession`` API."""
    if op == "lca":
        return sess.lca(state, u, v)
    if op == "connected":
        return sess.connected(state, u, v)
    if op == "depth":
        return sess.depth(state, u)
    if op == "subtree_add":
        return sess.subtree_agg(state, u, payload)
    if op == "path_add":
        return sess.path_agg(state, u, v, payload)
    if op == "path_min":
        return sess.path_agg(state, u, v, payload, "min")
    if op == "is_bridge":
        return sess.is_bridge(state, u, v)
    if op == "is_articulation":
        return sess.is_articulation(state, u)
    raise ValueError(f"unknown read op {op!r}")


def expected_answer(op, u, v, forest: ref.Forest, bcc, payload, sums):
    """What the plain reference answers for one read batch."""
    if op == "lca":
        return forest.lca(u, v)
    if op == "connected":
        return forest.root[u] == forest.root[v]
    if op == "depth":
        return forest.depth[u]
    if op == "subtree_add":
        return sums[u]
    if op == "path_add":
        return forest.path_sum(u, v, payload)
    if op == "path_min":
        return forest.path_min(u, v, payload)
    if op == "is_bridge":
        return bcc.lookup(u, v)[1]
    if op == "is_articulation":
        return bcc.articulation[u]
    raise ValueError(op)


class Driver:
    def __init__(self, ctx):
        self.ctx = ctx
        self.config = ctx.config
        self.traffic = ctx.traffic
        self.checked = 0

    # -- set-up ---------------------------------------------------------------

    def setup(self):
        import jax
        import jax.numpy as jnp

        from repro.data.streams import EdgeStream
        from repro.dynamic.replay import replay_batch
        from repro.dynamic.view import CadencePolicy
        from repro.launch.resilient import ResilientStreamLoop

        tr = self.traffic
        seed = self.ctx.seed
        data = graphs.generate(self.config, seed)
        self.edges, self.n = data.edges, data.n
        self.churn = Churn(self.edges, self.n, tr["write_batch"],
                           tr["dead_fraction"],
                           np.random.default_rng([seed, 1]))
        self.reads = Reads(self.edges, self.n, tr["read_batch"],
                           tr["pair_hops"], np.random.default_rng([seed, 2]))
        self.sample_rng = np.random.default_rng([seed, 3])
        self.cadence = tr["cadence"]
        init = self.churn.init
        stream = EdgeStream(name="churn", n_nodes=self.n,
                            init_u=init[:, 0], init_v=init[:, 1], batches=())
        apply_fn = replay_batch
        if self.ctx.control:
            # The control: no link round (``max_rounds=0``), so a cut tree
            # edge is never replaced and an insert never joins two trees;
            # the forest stops spanning the live edges. (One round, the
            # subtler cut, is often enough on a mesh with few edges
            # closed, so it does not fail reliably.)
            def apply_fn(state, batch):
                return replay_batch(state, batch, max_rounds=0)
        capacity = Churn.live_bound(data.slots, tr["write_batch"],
                                    tr["dead_fraction"])
        self.loop = ResilientStreamLoop.from_stream(
            stream, capacity=capacity, tour_mode=tr["tour"],
            bcc_mode=tr["bcc"], tour_every=self.cadence,
            apply_fn=self.ctx.annotate("write_batch", apply_fn))
        self.loop.view.policy = CadencePolicy(
            tour=tr["tour"], bcc=tr["bcc"], every=self.cadence,
            queries=True, staleness=tr["staleness"])
        view_refresh = self.loop.view.refresh
        policy = self.loop.view.policy

        def refresh(state, *, step=None, **kw):
            if not policy.due(step):
                return view_refresh(state, step=step, **kw)
            with self.ctx.annotation("refresh"):
                return view_refresh(state, step=step, **kw)

        self.loop.view.refresh = refresh
        self.payload = jnp.asarray(self.reads.payload)
        self.gens: dict[int, Generation] = {}
        self.gen_id = -1
        self.sample: dict[str, list] = {op: [] for op in READ_OPS}
        self.sample_seen = dict.fromkeys(READ_OPS, 0)
        self.step_idx = 0
        # Warm-up: one whole cadence period, then one batch of every op.
        self.period()
        for op in READ_OPS:
            u = np.zeros(tr["read_batch"], np.int32)
            jax.block_until_ready(self._read(op, u, u))

    def _track(self):
        """Open a new generation when the view adopted a new session, and
        drop the old ones that no sampled read refers to."""
        sess = self.loop.view.session
        if sess is None or (self.gen_id in self.gens
                            and self.gens[self.gen_id].session() is sess):
            return
        self.gen_id += 1
        self.gens[self.gen_id] = Generation(weakref.ref(sess),
                                            sess.tn.parent, self.churn.live)
        keep = {r["gen"] for rs in self.sample.values() for r in rs}
        for g in [g for g in self.gens if g not in keep
                  and g != self.gen_id]:
            del self.gens[g]

    def _keep(self, record):
        """Reservoir sample, drawn from the seed, of a few reads per op;
        only a sampled read keeps its answer."""
        k = self.traffic["checked_reads_per_op"]
        op = record["op"]
        self.sample_seen[op] += 1
        res = self.sample[op]
        if len(res) < k:
            res.append(record)
        else:
            j = int(self.sample_rng.integers(0, self.sample_seen[op]))
            if j < k:
                res[j]["out"] = None
                res[j] = record
            else:
                record["out"] = None

    def _read(self, op, u, v):
        return dispatch(self.loop.view.session, self.loop.state, op, u, v,
                        self.payload)

    # -- the window -----------------------------------------------------------

    def period(self, serve=None):
        from repro.data.streams import StreamBatch

        for _ in range(self.cadence):
            iu, iv, du, dv = self.churn.next_batch()
            self.loop.step(self.step_idx, StreamBatch(iu, iv, du, dv))
            self.step_idx += 1
            self._track()
            if serve is not None:
                serve()

    def window(self, seconds: float, sample: bool = True):
        import jax

        tr = self.traffic
        rate = tr["read_rate"]
        records = []
        applied0 = self.loop.applied
        batches0 = self.step_idx
        t0 = time.perf_counter()

        def serve(until=None):
            while True:
                k = len(records)
                due = t0 + k / rate
                now = time.perf_counter()
                if due > (now if until is None else until):
                    return
                if until is None and now - t0 >= seconds:
                    # Time is up: let the period finish; the reads still
                    # due wait for the final drain, and their wait counts.
                    return
                op, u, v = self.reads.next_batch()
                t_disp = time.perf_counter()
                with self.ctx.annotation("read"):
                    out = self._read(op, u, v)
                    jax.block_until_ready(out)
                t_ready = time.perf_counter()
                records.append({"op": op, "u": u, "v": v, "out": out,
                                "gen": self.gen_id, "due": due,
                                "dispatch": t_disp, "ready": t_ready})
                if sample:
                    self._keep(records[-1])

        while True:
            self.period(serve)
            if time.perf_counter() - t0 >= seconds:
                break
        t_end = time.perf_counter()
        serve(until=t_end)
        return {"t0": t0, "t_end": t_end, "window_s": t_end - t0,
                "reads": [r for r in records if r["due"] < t_end],
                "applied": self.loop.applied - applied0,
                "write_batches": self.step_idx - batches0}

    def traced_segment(self):
        """One cadence period with reads, run under the profiler."""
        return self.window(0.0, sample=False)

    def traced_units(self, seg) -> int:
        """Cadence periods in a traced segment."""
        return seg["write_batches"] // self.cadence

    def counts(self, win) -> tuple[int, int]:
        """(attempted, failed): events offered and queries asked; the
        offered events that were not applied failed."""
        tr = self.traffic
        offered = win["write_batches"] * tr["write_batch"]
        return (offered + len(win["reads"]) * tr["read_batch"],
                offered - win["applied"])

    # -- correctness ----------------------------------------------------------

    def collect(self, win) -> dict:
        """Host copies of what the reference judges: the final state, its
        last refresh, and the window's sampled reads with the forests they
        were served from. The device state is dropped after."""
        state, view = self.loop.state, self.loop.view
        host = {k: np.asarray(getattr(state, k)) for k in
                ("parent", "pool_src", "pool_dst", "pool_valid")}
        host["tn"] = {k: np.asarray(getattr(view.tn, k))
                      for k in ("pre", "size", "last", "comp", "parent")}
        host["bcc"] = {k: np.asarray(getattr(view.bcc, k))
                       for k in ("bridge", "edge_bcc", "articulation")}
        sample = [dict(r, out=np.asarray(r["out"]))
                  for op in READ_OPS for r in self.sample[op]]
        host["reads"] = sample
        host["gens"] = {g: (np.asarray(self.gens[g].parent),
                            self.gens[g].live)
                        for g in {r["gen"] for r in sample}}
        host["live"] = self.churn.live_edges()
        self.loop = self.gens = self.sample = None
        for r in win["reads"]:
            r["out"] = None
        return host

    def check(self, host) -> dict:
        """Counts of wrong items against the plain reference."""
        n = self.n
        live = host["live"]
        valid = host["pool_valid"]
        got = np.sort(ref.edge_keys(host["pool_src"][valid],
                                    host["pool_dst"][valid], n))
        want = np.sort(ref.edge_keys(live[:, 0], live[:, 1], n))
        checks = {"live_edges_diff": int(np.setxor1d(got, want).size
                                         + abs(got.size - want.size))}
        forest, counts = ref.spanning_forest_counts(host["parent"], live, n)
        checks.update(counts)
        tn = host["tn"]
        checks["tour_bad"] = ref.tour_bad(
            forest, tn["pre"], tn["size"], tn["last"], tn["comp"]) + int(
                np.count_nonzero(tn["parent"] != forest.parent))
        bcc = host["bcc"]
        checks["bcc_bad"] = ref.bcc_bad(
            ref.Biconnectivity(live, n), host["pool_src"], host["pool_dst"],
            valid, bcc["bridge"], bcc["edge_bcc"], bcc["articulation"])
        checks.update(self._check_reads(host))
        return checks

    def _check_reads(self, host) -> dict:
        cache: dict[int, tuple] = {}
        wrong = forest_bad = 0
        payload = self.reads.payload
        for r in host["reads"]:
            g = r["gen"]
            if g not in cache:
                parent, live_ids = host["gens"][g]
                edges = self.edges[live_ids]
                forest, counts = ref.spanning_forest_counts(parent, edges,
                                                            self.n)
                forest_bad += sum(counts.values())
                cache[g] = (forest, ref.Biconnectivity(edges, self.n),
                            forest.subtree_sums(payload))
            forest, bcc, sums = cache[g]
            want = np.asarray(expected_answer(r["op"], r["u"], r["v"],
                                              forest, bcc, payload, sums))
            wrong += int(np.count_nonzero(r["out"].astype(np.int64)
                                          != want.astype(np.int64)))
        self.checked = len(host["reads"])
        return {"read_answers_wrong": wrong, "read_forest_bad": forest_bad}
