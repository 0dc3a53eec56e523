"""Self-checks of the benchmark, on the CPU at a toy size.

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/test_bench.py

- every cell's run is judged correct as it stands;
- every cell's control, and every fault a cell can have, is judged not
  correct: a step that returns its state unchanged, half of a batch left
  out, an answer altered where it is produced (one chip, so there is no
  exchange between chips to leave out);
- the trace reduction reads a trace recorded on a v5e chip
  (``testdata/rst_grid64_one_build.xplane.pb``: the traced segment of a
  ``rebuild`` run on a 64 x 64 grid, one gconn_euler build inside a
  ``build`` annotation) as a brute-force reading of the same file does,
  and finds the two programs ``cc_ms`` and ``euler_root_ms`` read.
"""
from __future__ import annotations

import os
import pathlib

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from bench import rehearse  # noqa: E402

STREAM = "grid2d.stream"
REBUILDS = ("kron_g500.rebuild", "grid2d.rebuild")
CELLS = (STREAM,) + REBUILDS
TRACE = pathlib.Path(__file__).parent / "testdata" / \
    "rst_grid64_one_build.xplane.pb"


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    out = rehearse.rehearse(cell, seed=12345678901)
    assert out["correct"], out["checks"]
    assert out["checked"] > 0
    assert out["window_compiles"] == 0


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell):
    out = rehearse.rehearse(cell, seed=3, control=True)
    assert not out["correct"], out["checks"]


# -- faults planted under the timed path --------------------------------------

def _stream_unchanged(monkeypatch):
    import repro.dynamic.replay as replay

    real = replay.replay_batch

    def stale(state, batch, **kw):
        _, stats = real(state, batch, **kw)
        return state, stats

    monkeypatch.setattr(replay, "replay_batch", stale)


def _stream_half_batch(monkeypatch):
    import repro.dynamic.replay as replay
    from repro.data.streams import StreamBatch

    real = replay.replay_batch

    def half(state, batch, **kw):
        n = state.n_nodes

        def cut(a):
            a = np.array(a)
            a[a.shape[0] // 4:] = n        # real events fill the first half
            return a

        return real(state, StreamBatch(*(cut(getattr(batch, k)) for k in
                                         ("ins_u", "ins_v", "del_u",
                                          "del_v"))), **kw)

    monkeypatch.setattr(replay, "replay_batch", half)


def _stream_answer(monkeypatch):
    from bench import harness

    drv = harness.load("drivers", "stream")

    real = drv.dispatch

    def altered(*args):
        out = real(*args)
        return out.at[0].set(~out[0] if out.dtype == bool else out[0] + 1)

    monkeypatch.setattr(drv, "dispatch", altered)


def _rebuild_unchanged(monkeypatch):
    import jax.numpy as jnp

    import repro.core.rst as rst

    def unchanged(graph, root, **kw):
        return rst.RSTResult(parent=jnp.arange(graph.n_nodes,
                                               dtype=jnp.int32),
                             method="gconn_euler", steps=jnp.int32(0))

    monkeypatch.setattr(rst, "rooted_spanning_tree", unchanged)


def _rebuild_half_batch(monkeypatch):
    import repro.core.rst as rst
    from repro.core.graph import Graph

    real = rst.rooted_spanning_tree

    def half(graph, root, **kw):
        m = graph.src.shape[0] // 2
        g = Graph.from_undirected(graph.n_nodes, graph.src[:m // 2],
                                  graph.dst[:m // 2])
        return real(g, root, **kw)

    monkeypatch.setattr(rst, "rooted_spanning_tree", half)


def _rebuild_answer(monkeypatch):
    import dataclasses

    import repro.core.rst as rst

    real = rst.rooted_spanning_tree

    def altered(graph, root, **kw):
        res = real(graph, root, **kw)
        x = (root + 1) % graph.n_nodes
        return dataclasses.replace(res, parent=res.parent.at[x].set(x))

    monkeypatch.setattr(rst, "rooted_spanning_tree", altered)


FAULTS = [(STREAM, "state_unchanged", _stream_unchanged),
          (STREAM, "half_batch", _stream_half_batch),
          (STREAM, "answer_altered", _stream_answer)] + [
    (cell, name, fault) for cell in REBUILDS for name, fault in (
        ("state_unchanged", _rebuild_unchanged),
        ("half_batch", _rebuild_half_batch),
        ("answer_altered", _rebuild_answer))]


@pytest.mark.parametrize("cell,name,fault", FAULTS,
                         ids=[f"{c}-{n}" for c, n, _ in FAULTS])
def test_fault_is_not_correct(cell, name, fault, monkeypatch):
    fault(monkeypatch)
    out = rehearse.rehearse(cell, seed=7)
    assert not out["correct"], (name, out["checks"])


# -- the trace reduction -------------------------------------------------------

SYNTHETIC = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 3000000 }
    events { metadata_id: 1 offset_ps: 10000000 duration_ps: 1000000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 4000000 }
    events { metadata_id: 4 offset_ps: 10000000 duration_ps: 1000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "sort.2" } }
  event_metadata { key: 3 value { id: 3 name: "jit_connected_components(1)" } }
  event_metadata { key: 4 value { id: 4 name: "jit_euler_tour_root(2)" } } }
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 20000000 }
    events { metadata_id: 2 offset_ps: 5000000 duration_ps: 4000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench_window" } }
  event_metadata { key: 2 value { id: 2 name: "build" } } }
"""


def test_trace_reduction_of_a_known_trace():
    """Ops [1,3], [2,5], [11,12] us in a [0,20] us window; a build
    annotation over [5,9] us: busy 5 us, idle 6 us in build, 9 us other."""
    import jax

    from bench import tracing

    red = tracing.reduce_profile(
        jax.profiler.ProfileData.from_text_proto(SYNTHETIC), ("build",))
    assert red["chips"] == 1
    assert red["window_s"] == pytest.approx(20e-6)
    assert red["busy_s"] == pytest.approx(5e-6)
    assert red["idle_gaps"] == pytest.approx({"build": 6e-6, "other": 9e-6})
    assert red["ops"] == pytest.approx({"fusion.1": 3e-6, "sort.2": 3e-6})
    assert tracing.module_seconds(red, "connected_components") == \
        pytest.approx(4e-6)
    assert tracing.module_seconds(red, "euler_tour_root") == \
        pytest.approx(1e-6)
    bd = tracing.breakdown(red)
    assert bd["idle_gaps"][0] == ["other", pytest.approx(9e-6)]
    assert tracing.op_label("%while.2 = (s32[4]{0:T(128)}) while((s32[4]"
                            "{0:T(128)}) %tuple.1)") == "%while.2 while"

def _brute_force(path):
    """Busy time and module seconds of the device plane, the slow way."""
    import jax

    from bench import tracing

    pd = jax.profiler.ProfileData.from_file(str(path))
    window = [(e.start_ns, e.start_ns + e.duration_ns)
              for p in pd.planes if p.name.startswith("/host:")
              for ln in p.lines for e in ln.events
              if e.name == tracing.ANNOTATION_WINDOW][0]
    w0, w1 = window
    dev = [p for p in pd.planes if tracing.DEVICE_PLANE.match(p.name)]
    assert len(dev) == 1
    ops, mods = [], {}
    for ln in dev[0].lines:
        for e in ln.events:
            s, t = max(e.start_ns, w0), min(e.start_ns + e.duration_ns, w1)
            if t <= s:
                continue
            if ln.name == tracing.OPS_LINE:
                ops.append((s, t))
            elif ln.name == tracing.MODULES_LINE:
                mods[e.name] = mods.get(e.name, 0) + (t - s) * 1e-9
    # Busy: sweep over the sorted end points, counting open intervals.
    points = sorted([(s, 1) for s, _ in ops] + [(t, -1) for _, t in ops])
    busy, depth, last = 0, 0, None
    for x, d in points:
        if depth > 0:
            busy += x - last
        depth += d
        last = x
    return busy * 1e-9, (w1 - w0) * 1e-9, mods


@pytest.mark.skipif(not TRACE.exists(), reason="no recorded trace")
def test_trace_reduction_matches_brute_force():
    from bench import tracing

    red = tracing.reduce_xspace(str(TRACE), ("build",))
    busy, window, mods = _brute_force(TRACE)
    assert red["chips"] == 1
    assert red["window_s"] == pytest.approx(window, rel=1e-9)
    assert red["busy_s"] == pytest.approx(busy, rel=1e-9)
    assert 0 < red["busy_s"] < red["window_s"]
    assert red["modules"] == pytest.approx(mods, rel=1e-9)
    cc = tracing.module_seconds(red, "connected_components")
    euler = tracing.module_seconds(red, "euler_tour_root")
    assert cc > 0 and euler > 0
    idle = sum(red["idle_gaps"].values())
    assert idle == pytest.approx(red["window_s"] - red["busy_s"], rel=1e-6)
    assert red["idle_gaps"].get("build", 0) > 0
    bd = tracing.breakdown(red)
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    assert bd["device_ops"][0][1] == max(red["ops"].values())


# -- the plain references, against networkx ------------------------------------

@pytest.mark.parametrize("seed", range(4))
def test_biconnectivity_reference_matches_networkx(seed):
    import networkx as nx

    from bench import reference as ref

    rng = np.random.default_rng(seed)
    n = 300
    e = rng.integers(0, n, (330, 2))
    e = e[e[:, 0] != e[:, 1]]
    e = np.unique(np.sort(e, axis=1), axis=0)
    g = nx.Graph()
    g.add_nodes_from(range(n))
    g.add_edges_from(e.tolist())
    b = ref.Biconnectivity(e, n)
    assert set(np.nonzero(b.articulation)[0]) == set(
        nx.articulation_points(g))
    bridges = {tuple(sorted(x)) for x in nx.bridges(g)}
    assert {tuple(x) for x in e[b.edge_bridge]} == bridges
    block = {}
    for i, comp in enumerate(nx.biconnected_component_edges(g)):
        for x in comp:
            block[tuple(sorted(x))] = i
    want = np.array([block[tuple(x)] for x in e])
    assert ref.partition_diff(b.edge_block, want) == 0


@pytest.mark.parametrize("seed", range(3))
def test_forest_reference_matches_networkx(seed):
    import networkx as nx

    from bench import reference as ref

    rng = np.random.default_rng(seed)
    n = 200
    parent = np.arange(n)
    for v in range(1, n):
        if rng.random() < 0.9:
            parent[v] = rng.integers(0, v)       # a forest, roots self-point
    f = ref.Forest(parent)
    g = nx.DiGraph()
    g.add_nodes_from(range(n))
    g.add_edges_from((int(parent[v]), v) for v in range(n) if parent[v] != v)
    pay = rng.integers(1, 100, n)
    u, v = rng.integers(0, n, 64), rng.integers(0, n, 64)
    lca, psum, pmin = f.lca(u, v), f.path_sum(u, v, pay), f.path_min(u, v, pay)
    sums = f.subtree_sums(pay)
    und = g.to_undirected()
    for i in range(64):
        a, b = int(u[i]), int(v[i])
        if not nx.has_path(und, a, b):
            assert lca[i] == -1 and psum[i] == 0
            continue
        path = nx.shortest_path(und, a, b)
        assert psum[i] == pay[path].sum() and pmin[i] == pay[path].min()
        assert lca[i] == min(path, key=lambda x: f.depth[x])
    for x in range(n):
        assert sums[x] == pay[[x, *nx.descendants(g, x)]].sum()
