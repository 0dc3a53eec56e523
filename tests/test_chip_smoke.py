"""``chip_smoke.py``'s phases on the CPU at tiny sizes, same entry points.

Only ``chip_smoke.main()`` insists on a TPU; the phase functions run
anywhere, so their control flow and checks are exercised here.
"""
import importlib.util
import json
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

from repro.data import graphs as G

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("name,g", [("grid", G.grid2d(9)),
                                    ("rmat", G.rmat(7, edge_factor=4,
                                                    seed=3))])
def test_static_phase_validates_every_method(smoke, name, g):
    out = smoke.static_phase(name, g)
    assert set(out) == {"bfs", "gconn_euler", "pr_rst"}


def test_static_phase_disconnected_graph(smoke):
    """Two components: the forest methods span both, BFS only the root's,
    and the host partition still matches."""
    from repro.core.graph import Graph
    g = Graph.from_numpy_undirected(
        7, np.array([(0, 1), (1, 2), (2, 0), (3, 4), (4, 5), (5, 6)]))
    smoke.static_phase("two", g)


def test_host_partition_is_independent_and_right(smoke):
    from repro.core.validate import components_reference
    g = G.erdos_renyi(300, avg_degree=0.8, seed=5)
    assert smoke.same_partition(smoke.host_partition(g),
                                components_reference(g))


def test_bcc_phase_grid_exact_and_mismatch_raises(smoke):
    smoke.bcc_phase("grid", G.grid2d(6),
                    expect={"n_bcc": 1, "bridges": 0, "articulation": 0})
    with pytest.raises(AssertionError):
        smoke.bcc_phase("chain", G.chain(5),
                        expect={"n_bcc": 1, "bridges": 0,
                                "articulation": 0})


def test_stream_phase(smoke, capsys):
    smoke.stream_phase(["--graph", "grid_12", "--stream", "churn",
                        "--batch", "32", "--steps", "4", "--bcc",
                        "incremental", "--read-ratio", "0.5",
                        "--read-batch", "16", "--validate"])
    assert "validate: forest" in capsys.readouterr().out


def test_fleet_phase(smoke):
    n = smoke.fleet_phase(["--buckets", "grid_6:3:2,rmat_5:2:2",
                           "--stream", "churn", "--batch", "16", "--steps",
                           "3", "--read-ratio", "0.3", "--validate"])
    assert n == 5


def test_sharded_phase_one_device(smoke):
    out = smoke.sharded_phase(G.rmat(8, edge_factor=4, seed=1), 1)
    assert out["forest"] == 255


def test_main_refuses_without_tpu(smoke):
    with pytest.raises(SystemExit, match="no TPU found"):
        smoke.main([])


def test_script_exits_nonzero_on_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                          capture_output=True, text=True, timeout=120,
                          env=env, cwd=ROOT)
    assert proc.returncode != 0
    assert "no TPU found" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_compile_cache_dir(monkeypatch):
    import jax

    from repro.launch import compile_cache
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert compile_cache.use_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compile_cache.use_compile_cache()
        assert path == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_serve_stream_accepts_graph_patterns(capsys):
    from repro.launch import serve_stream
    serve_stream.main(["--graph", "rmat_6", "--stream", "churn", "--batch",
                       "8", "--steps", "2", "--validate"])
    out = capsys.readouterr().out
    assert "graph rmat_6 (power-law low-diameter): V=64" in out
    with pytest.raises(SystemExit):
        serve_stream.main(["--graph", "torus_9"])


def test_serve_fleet_accepts_graph_patterns(tmp_path, capsys):
    from repro.launch import serve_fleet
    serve_fleet.main(["--graph", "grid_5", "--stream", "churn", "--batch",
                      "8", "--steps", "2", "--tenants", "2", "--slots", "2",
                      "--evict-dir", str(tmp_path), "--validate"])
    out = capsys.readouterr().out
    assert "graph grid_5 (high-diameter road-like): V=25" in out
    assert out.count("partition==from-scratch: True") == 2
