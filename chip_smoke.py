"""Smoke run of the RST service on a TPU, through its normal entry points.

    python chip_smoke.py                # one chip: every phase below
    python chip_smoke.py --four-chips   # the sharded CC path on 4 chips, alone

One process, one chip owner: every phase runs here and no child process
touches JAX. Phases, in the order they run, at the sizes a graph-service
user calls real:

  1. the fleet: ``serve_fleet.main --buckets`` over two shape classes,
     with ``--validate``;
  2. static RST — ``rooted_spanning_tree`` with bfs / gconn_euler /
     pr_rst on a road-regime ``grid2d(2048)`` (V = 4,194,304) and a
     Graph500-style ``rmat(20, edge_factor=16)`` (V = 1,048,576). Each
     tree passes ``core.validate.validate_rst`` and its partition equals
     an independent host partition (scipy ``connected_components``);
  3. biconnectivity of the grid (exact answer: one block, no bridges, no
     articulation points);
  4. the streaming service: ``serve_stream.main`` at ``grid_2048`` with
     churn, incremental BCC, reads and ``--validate``.

``--four-chips`` runs only ``core.distributed.distributed_cc_spanning_forest``
on a 4-device mesh over ``rmat(22, edge_factor=16)`` (136.7M half-edges,
34.2M per chip) and compares its partition and forest-edge count with
single-device ``connected_components``.

Wall times printed per phase are smoke set-up times, compile included —
not benchmark numbers. Any failed phase raises, so the script exits
non-zero; the last line of a passing run is one JSON object naming the
device. Without a TPU it exits non-zero before any phase.
"""
from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

STREAM_ARGV = ["--graph", "grid_2048", "--stream", "churn", "--batch", "4096",
               "--steps", "4", "--bcc", "incremental", "--read-ratio", "0.5",
               "--read-batch", "1024", "--validate"]
FLEET_ARGV = ["--buckets", "grid_256:8:4,rmat_16:2:2", "--stream", "churn",
              "--batch", "256", "--steps", "2", "--read-ratio", "0.3",
              "--validate"]


def log(msg: str) -> None:
    print(msg, flush=True)


def require_tpu(n_chips: int = 1) -> dict:
    """The device JAX reports, or SystemExit when it is not a TPU."""
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise SystemExit(f"chip_smoke: no TPU found ({e})") from None
    d = devices[0]
    if d.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU found (JAX platform is "
                         f"{d.platform!r}); this smoke runs on the chip "
                         "only")
    if len(devices) < n_chips:
        raise SystemExit(f"chip_smoke: needs {n_chips} TPU devices, "
                         f"found {len(devices)}")
    log(f"device: platform={d.platform} kind={d.device_kind} "
        f"count={len(devices)} jax={jax.__version__}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


@contextlib.contextmanager
def timed(phase: str):
    t0 = time.perf_counter()
    yield
    log(f"[smoke set-up time] {phase}: {time.perf_counter() - t0:.1f} s "
        "(compile included; not a benchmark number)")


def host_partition(g):
    """Component labels from scipy on the host — independent of repro."""
    import numpy as np
    from scipy.sparse import coo_matrix
    from scipy.sparse.csgraph import connected_components

    src = np.asarray(g.src)
    adj = coo_matrix((np.ones(src.shape[0], np.int8),
                      (src, np.asarray(g.dst))),
                     shape=(g.n_nodes, g.n_nodes))
    return connected_components(adj, directed=False)[1]


def same_partition(a, b) -> bool:
    import numpy as np

    from repro.launch.serve_stream import canonical_partition

    return bool(np.array_equal(canonical_partition(np.asarray(a)),
                               canonical_partition(np.asarray(b))))


def static_phase(label: str, g, *, root: int = 0, methods=None) -> dict:
    """Every RST method on ``g``: validated tree, partition == host's."""
    import jax
    import numpy as np

    from repro.core.compress import roots_of
    from repro.core.rst import METHODS, rooted_spanning_tree
    from repro.core.validate import validate_rst

    ref = host_partition(g)
    connected = len(np.unique(ref)) == 1
    out = {}
    for m in methods or METHODS:
        t0 = time.perf_counter()
        res = rooted_spanning_tree(g, root, method=m)
        jax.block_until_ready(res.parent)
        dt = time.perf_counter() - t0
        parent = np.asarray(res.parent)
        v = validate_rst(g, parent, root, connected=connected)
        # BFS marks unreachable vertices -1: they root themselves.
        rooted = np.where(parent < 0, np.arange(g.n_nodes), parent)
        part = same_partition(np.asarray(roots_of(rooted)), ref) \
            if m != "bfs" or connected else True
        log(f"static {label} {m}: V={g.n_nodes} E={g.n_edges} "
            f"steps={int(res.steps)} valid={v['all_ok']} "
            f"partition==host={part} [smoke set-up time {dt:.1f} s, "
            "compile included]")
        if not (v["all_ok"] and part):
            raise AssertionError(f"static {label} {m} failed: {v}, "
                                 f"partition==host={part}")
        out[m] = {"steps": int(res.steps), "seconds": dt}
    return out


def bcc_phase(label: str, g, *, expect: dict | None = None) -> dict:
    """``biconnectivity``; ``expect`` holds exact counts to compare."""
    import numpy as np

    from repro.core.bcc import biconnectivity

    r = biconnectivity(g, 0)
    got = {"n_bcc": int(r.n_bcc),
           "bridges": int(np.asarray(r.bridge).sum()) // 2,
           "articulation": int(np.asarray(r.articulation).sum())}
    log(f"bcc {label}: {got}" + (f" expected {expect}" if expect else ""))
    if expect is not None and got != expect:
        raise AssertionError(f"bcc {label}: {got} != {expect}")
    return got


def _run_main(main, argv) -> str:
    """Run an entry point's ``main(argv)``, echo and return its output."""
    class Tee(io.TextIOBase):
        def __init__(self, out):
            self.out = out
            self.buf = io.StringIO()

        def write(self, s):
            self.out.write(s)
            self.out.flush()
            return self.buf.write(s)

    tee = Tee(sys.stdout)
    with contextlib.redirect_stdout(tee):
        main(argv)
    return tee.buf.getvalue()


def stream_phase(argv=STREAM_ARGV) -> None:
    """``serve_stream.main`` in-process; its ``--validate`` must pass."""
    from repro.launch import serve_stream

    text = _run_main(serve_stream.main, argv)
    if "partition==from-scratch: True" not in text \
            or "'all_ok': True" not in text:
        raise AssertionError("serve_stream: no passing validate line")


def fleet_phase(argv=FLEET_ARGV) -> int:
    """``serve_fleet.main --buckets`` in-process; every tenant validated."""
    from repro.launch import serve_fleet
    from repro.launch.config import FleetConfig

    spec = argv[argv.index("--buckets") + 1]
    tenants = sum(s.tenants
                  for s in FleetConfig(buckets=spec).bucket_specs())
    with tempfile.TemporaryDirectory() as evict:
        text = _run_main(serve_fleet.main, [*argv, "--evict-dir", evict])
    ok = text.count("partition==from-scratch: True")
    if ok != tenants or "partition==from-scratch: False" in text:
        raise AssertionError(f"serve_fleet: {ok} of {tenants} tenants "
                             "validated")
    return tenants


def sharded_phase(g, n_devices: int) -> dict:
    """Sharded CC + spanning forest on a 1-D mesh == one device's."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.connectivity import connected_components
    from repro.core.distributed import distributed_cc_spanning_forest
    from repro.launch.mesh import auto_axis_kwargs

    mesh = jax.make_mesh((n_devices,), ("data",), **auto_axis_kwargs(1),
                         devices=jax.devices()[:n_devices])
    pad = -g.n_half_edges % n_devices
    edges = NamedSharding(mesh, P("data"))
    src = jax.device_put(jnp.concatenate(
        [g.src, jnp.zeros(pad, jnp.int32)]), edges)
    dst = jax.device_put(jnp.concatenate(
        [g.dst, jnp.zeros(pad, jnp.int32)]), edges)
    run = distributed_cc_spanning_forest(mesh, "data")
    t0 = time.perf_counter()
    rep, forest, rounds = run(src, dst, n_nodes=g.n_nodes)
    jax.block_until_ready(rep)
    dt = time.perf_counter() - t0
    on = {d.id for d in forest.sharding.device_set}
    rep1, forest1, _ = connected_components(g)
    n_forest, n_forest1 = int(jnp.sum(forest)), int(jnp.sum(forest1))
    part = same_partition(rep, rep1)
    log(f"sharded CC over {len(on)} devices: V={g.n_nodes} E={g.n_edges} "
        f"rounds={int(rounds)} forest={n_forest} single-device "
        f"forest={n_forest1} partition==single-device={part} "
        f"[smoke set-up time {dt:.1f} s, compile included]")
    if not (part and n_forest == n_forest1 and len(on) == n_devices):
        raise AssertionError("sharded CC disagrees with one device")
    return {"forest": n_forest, "rounds": int(rounds)}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded CC path on a 4-chip mesh")
    args = ap.parse_args(argv)

    device = require_tpu(4 if args.four_chips else 1)

    from repro.launch.compile_cache import use_compile_cache
    log(f"compile cache: {use_compile_cache()}")
    import jax

    events = {"hits": 0, "misses": 0}

    def count(event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            events["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            events["misses"] += 1

    jax.monitoring.register_event_listener(count)

    from repro.data.graphs import grid2d, rmat

    t_all = time.perf_counter()
    if args.four_chips:
        with timed("generate rmat(22, 16)"):
            g = rmat(22, edge_factor=16)
        with timed("sharded CC on 4 chips vs 1"):
            sharded_phase(g, 4)
    else:
        # The short phases first: a fault there shows within a minute.
        with timed("serve_fleet --buckets"):
            fleet_phase()
        with timed("generate grid2d(2048)"):
            grid = grid2d(2048)
        with timed("static RST grid2d(2048)"):
            static_phase("grid2d(2048)", grid)
        with timed("bcc grid2d(2048)"):
            bcc_phase("grid2d(2048)", grid,
                      expect={"n_bcc": 1, "bridges": 0, "articulation": 0})
        del grid
        with timed("generate rmat(20, 16)"):
            g = rmat(20, edge_factor=16)
        with timed("static RST rmat(20, 16)"):
            static_phase("rmat(20, 16)", g)
        del g
        with timed("serve_stream grid_2048"):
            stream_phase()
    log(f"compile cache events: {events['hits']} hits, "
        f"{events['misses']} misses")
    log(f"[smoke set-up time] all phases: "
        f"{time.perf_counter() - t_all:.1f} s")
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
