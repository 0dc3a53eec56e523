"""Multi-device tests (subprocess: needs 8 fake host devices, which must be
set before jax initializes — the main test process keeps 1 device)."""
import json
import os
import pathlib
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import Graph
from repro.core.distributed import distributed_cc_spanning_forest
from repro.core.validate import components_reference
from repro.data.graphs import grid2d, rmat

out = {}

# --- distributed connectivity + spanning forest --------------------------
from repro.launch.mesh import auto_axis_kwargs

mesh = jax.make_mesh((8,), ("data",), **auto_axis_kwargs(1))
run = distributed_cc_spanning_forest(mesh, "data")
for name, g in [("grid", grid2d(20)), ("rmat", rmat(9, 4, seed=2))]:
    m2 = g.n_half_edges
    pad = -m2 % 8
    src = jnp.concatenate([g.src, jnp.zeros(pad, jnp.int32)])
    dst = jnp.concatenate([g.dst, jnp.zeros(pad, jnp.int32)])
    rep, forest, rounds = run(src, dst, n_nodes=g.n_nodes)
    ref = components_reference(g)
    ncomp = len(set(ref.tolist()))
    rep_np = np.asarray(rep)
    part_ok = True
    rng = np.random.default_rng(0)
    for i, j in rng.integers(0, g.n_nodes, (500, 2)):
        if (rep_np[i] == rep_np[j]) != (ref[i] == ref[j]):
            part_ok = False
    out[name] = dict(part_ok=part_ok,
                     forest=int(np.asarray(forest).sum()),
                     expected=g.n_nodes - ncomp,
                     rounds=int(rounds))

# --- sharded smoke train step (2x4 mesh, LM smoke config) ----------------
import dataclasses as dc
from repro.configs import get_arch
from repro.train.step import build_cell
from repro.models import transformer as tfm
from repro.optim.adamw import adamw_init
from repro.launch.train import SMOKE_SHAPES, synthetic_batches

mesh2 = jax.make_mesh((2, 4), ("data", "model"), **auto_axis_kwargs(2))
spec = get_arch("qwen3-1.7b")
cfg = spec.make_smoke_config()
shape = dict(SMOKE_SHAPES["lm"])
spec = dc.replace(spec, shapes={"smoke": shape})
step_fn, state_abs, _ = build_cell(spec, "smoke", mesh2, smoke=True)
params = tfm.init_params(cfg, jax.random.key(0))
state = {"params": params, "opt": adamw_init(params)}
_, batch = next(synthetic_batches(spec, shape, cfg))
with jax.set_mesh(mesh2):
    new_state, metrics = jax.jit(step_fn)(state, batch)
out["sharded_train"] = dict(loss=float(metrics["loss"]),
                            finite=bool(jnp.isfinite(metrics["loss"])))
print("RESULT:" + json.dumps(out))
"""


@pytest.fixture(scope="module")
def multi_device_results():
    # The child inherits the parent's environment but is held to the CPU:
    # a child that reached for the TPU library while this worker (or a
    # compile test in it) holds it would fail or hang.
    src = str(pathlib.Path(__file__).resolve().parents[1] / "src")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   filter(None, [src, os.environ.get("PYTHONPATH")])))
    proc = subprocess.run(
        [sys.executable, "-c", _SCRIPT], capture_output=True, text=True,
        timeout=600, env=env)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT:")]
    assert line, proc.stdout
    return json.loads(line[0][len("RESULT:"):])


def test_distributed_connectivity_partition(multi_device_results):
    for name in ("grid", "rmat"):
        r = multi_device_results[name]
        assert r["part_ok"], r
        assert r["forest"] == r["expected"], r
        assert r["rounds"] <= 20


def test_sharded_train_step(multi_device_results):
    r = multi_device_results["sharded_train"]
    assert r["finite"], r
