"""Benchmark runner — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows:
  table2/*            graph statistics (Table II analogue)
  fig1/*              runtime comparison BFS / PR-RST / GConn+Euler (Fig. 1)
  fig2/*              spanning-tree depth comparison (Fig. 2)
  table1/*            measured step counts vs theory (Table I)
  table3/*            downstream biconnectivity cost per RST flavor
                      (the Tarjan–Vishkin layer, DESIGN.md §4)
  table4_dynamic/*    batch-dynamic maintenance vs from-scratch rebuild per
                      stream × batch size (DESIGN.md §9)
  table5_dynamic_bcc/* incremental vs recomputed biconnectivity on the
                      dynamic pool, with sync/round counts (DESIGN.md §10)
  table6_robustness/* self-healing cost: audit syncs, scoped repair vs
                      full rebuild on injected faults (DESIGN.md §11)
  table7_queries/*    batched tree-query serving: amortized QueryTables
                      vs per-read-batch recompute (DESIGN.md §12)
  table8_fleet/*      multi-tenant fleet: vmapped T-tenant apply vs T
                      sequential loops, sync accounting (DESIGN.md §13)
  table9_buckets/*    shape-bucketed sub-fleets vs one wide schema at
                      equal device-memory budget: sync + padded-slot
                      work on a mixed tenant population (DESIGN.md §15)
  kernels/*           Pallas kernel micro-benchmarks (incl. compress_* engine
                      rows; interpret mode off-TPU)
  ablation_compress/* amortized vs per-hop convergence checks (engine k=5
                      vs k=1, with measured ``jnp.any`` sync counts)
  ablation_hooking/*  paper's min/max alternation vs pure-min hooking
  roofline/*          dry-run roofline terms, if artifacts/dryrun exists

Flags:
  --json PATH   also write all rows as JSON records (machine-readable perf
                trajectory, e.g. ``--json BENCH_rst.json``); each record
                is stamped with a ``meta`` provenance dict (git sha, jax
                version, backend/device kind, schema version) and the
                list is sorted by name for stable diffs
  --smoke       one tiny graph per fig/table + small microbenches — fast
                enough for CI, exercises every perf path
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys


def kernel_microbench(n: int = 1 << 16) -> list[str]:
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.common import csv_row, time_fn
    from repro.kernels.pointer_jump.ops import pointer_jump_k
    from repro.kernels.list_rank.ops import list_rank_k
    from repro.kernels.embed_bag.ops import embed_bag

    rng = np.random.default_rng(0)
    rows = []
    tag = f"{n >> 10}k"
    p = jnp.asarray(rng.integers(0, n, n), jnp.int32)
    rows.append(csv_row(f"kernels/pointer_jump_{tag}_x5",
                        time_fn(pointer_jump_k, p) * 1e6))
    succ = jnp.asarray(np.roll(np.arange(n), -1), jnp.int32).at[-1].set(-1)
    d0 = jnp.ones(n, jnp.int32).at[-1].set(0)
    rows.append(csv_row(f"kernels/list_rank_{tag}_x5",
                        time_fn(list_rank_k, succ, d0) * 1e6))
    b = min(4096, max(256, n // 16))   # smoke shrinks this bench too
    v = min(10_000, 4 * n)
    idx = jnp.asarray(rng.integers(0, v, (b, 8)), jnp.int32)
    tab = jnp.asarray(rng.standard_normal((v, 64)), jnp.float32)
    rows.append(csv_row(f"kernels/embed_bag_{b}x8x64",
                        time_fn(embed_bag, idx, tab) * 1e6))
    return rows


def compress_microbench(n: int = 1 << 16) -> list[str]:
    """Engine rows: full compression on the worst case (a depth-n chain),
    XLA vs Pallas path, plus the amortized-vs-per-hop sync-count ablation."""
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.common import csv_row, time_fn
    from repro.core.compress import compress_full

    rows = []
    tag = f"{n >> 10}k"
    chain = jnp.asarray(np.maximum(np.arange(n) - 1, 0), jnp.int32)

    for label, kwargs in (
            (f"kernels/compress_full_{tag}_xla", dict()),
            (f"kernels/compress_full_{tag}_kernel", dict(use_kernel=True)),
    ):
        _, syncs = compress_full(chain, return_syncs=True, **kwargs)
        t = time_fn(compress_full, chain, **kwargs)
        rows.append(csv_row(label, t * 1e6, f"syncs={int(syncs)}"))

    # Ablation: per-hop (k=1, the seed's hand-rolled loops) vs amortized k=5.
    for label, k in (("per_hop_k1", 1), ("amortized_k5", 5)):
        _, syncs = compress_full(chain, n_jumps=k, return_syncs=True)
        t = time_fn(compress_full, chain, n_jumps=k)
        rows.append(csv_row(f"ablation_compress/chain_{tag}/{label}",
                            t * 1e6, f"syncs={int(syncs)}"))
    return rows


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--json", metavar="PATH", default=None,
                        help="also write rows as JSON records")
    parser.add_argument("--smoke", action="store_true",
                        help="tiny graphs + small microbenches (CI path)")
    args = parser.parse_args(argv)

    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()

    from benchmarks import (ablation_hooking, fig1_runtime, fig2_depth,
                            table1_steps, table2_stats, table3_bcc,
                            table4_dynamic, table5_dynamic_bcc,
                            table6_robustness, table7_queries,
                            table8_fleet, table9_buckets)
    from benchmarks.common import bench_meta, rows_to_records
    from repro.data import graphs as G

    if args.smoke:
        suite = {"smoke_chain_256": G.chain(256),
                 "smoke_rmat_6": G.rmat(6, edge_factor=4, seed=0)}
        # The scoped-vs-full comparison needs a state deep enough that
        # the full rebuild is off its sync floor — one mid-size grid
        # instead of the micro graphs (still < 10 s on CI).
        t6_suite = {"grid_32": G.grid2d(32)}
        micro_n = 1 << 12
    else:
        suite = None  # modules build the full Table-II suite
        t6_suite = None
        micro_n = 1 << 16

    rows: list[str] = []

    def emit(new_rows):
        for row in new_rows:
            rows.append(row)
            print(row)
            sys.stdout.flush()

    print("name,us_per_call,derived")
    emit(table2_stats.run(suite))
    emit(table1_steps.run(suite))
    emit(fig2_depth.run(suite))
    emit(fig1_runtime.run(suite))
    emit(table3_bcc.run(suite))
    emit(table4_dynamic.run(suite))
    emit(table5_dynamic_bcc.run(suite))
    emit(table6_robustness.run(t6_suite))
    emit(table7_queries.run(suite))
    emit(table8_fleet.run(suite))
    emit(table9_buckets.run(smoke=args.smoke))
    emit(ablation_hooking.run(suite))
    emit(kernel_microbench(micro_n))
    emit(compress_microbench(micro_n))
    if pathlib.Path("artifacts/dryrun").exists():
        from benchmarks import roofline
        emit(roofline.run())

    if args.json:
        pathlib.Path(args.json).write_text(
            json.dumps(rows_to_records(rows, meta=bench_meta()), indent=1)
            + "\n")
        print(f"# wrote {len(rows)} rows to {args.json}", file=sys.stderr)


if __name__ == "__main__":
    main()
