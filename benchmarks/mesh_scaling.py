"""Virtual-mesh scaling table: the fused distributed step at 1/2/4/8
devices (VERDICT r4 #9).

Strong scaling at fixed TOTAL rows: each subprocess forces an N-device
virtual CPU mesh and times the fused filter->partial-agg->all_to_all->
final-agg program plus the distributed hash join, post-compile.  On one
physical core the virtual devices add collective/program overhead rather
than parallel speedup — the table is an overhead curve (what the mesh
machinery costs); on real ICI the per-device shard work shrinks by n.

Usage: python -m benchmarks.mesh_scaling [--rows N] [--iters K]
Prints one JSON line per device count, then a summary table.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _child(n_dev: int, rows: int, iters: int) -> None:
    import time

    import numpy as np

    import jax

    from arrow_ballista_tpu.parallel import (
        distributed_grouped_aggregate,
        distributed_hash_join,
        make_mesh,
        row_sharding,
    )

    mesh = make_mesh(n_dev)
    rng = np.random.default_rng(3)
    g = rng.integers(0, 10_000, rows).astype(np.int64)
    x = rng.integers(1, 50, rows).astype(np.int64)
    mask = np.ones(rows, dtype=bool)
    place = lambda a: jax.device_put(a, row_sharding(mesh))

    run = distributed_grouped_aggregate(
        mesh, ["g"], [("x", "sum"), ("x", "count")],
        partial_capacity=1 << 14, final_capacity=1 << 13)
    args = ({"g": place(g), "x": place(x)}, place(mask))
    t0 = time.perf_counter()
    out = run(*args)
    jax.block_until_ready(out)
    compile_s = time.perf_counter() - t0
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = run(*args)
        jax.block_until_ready(out)
        ts.append(time.perf_counter() - t0)
    agg_ms = float(np.median(ts)) * 1000

    # join: probe rows against a dim of rows//8 with ~1 match each
    n_build = rows // 8
    pk = rng.integers(0, n_build, rows).astype(np.int64)
    bk = np.arange(n_build, dtype=np.int64)
    probe = ({"__jk0": place(pk), "v": place(x)},
             place(np.ones(rows, dtype=bool)))
    build = ({"__jk0": place(bk), "w": place(bk * 2)},
             place(np.ones(n_build, dtype=bool)))
    # a send capacity is PER (device, bucket) SLOT: expected load is
    # rows/n^2, 4x headroom; out_capacity is per device: ~rows/n matches
    jrun = distributed_hash_join(
        mesh, 1, ["__jk0", "v"], ["__jk0", "w"], "inner",
        probe_capacity=max(1024, 4 * rows // (n_dev * n_dev)),
        build_capacity=max(1024, 4 * n_build // (n_dev * n_dev)),
        out_capacity=max(2048, 2 * rows // n_dev), build_fill={"w": 0})
    t0 = time.perf_counter()
    out = jrun(*probe, *build)
    jax.block_until_ready(out)
    jcompile_s = time.perf_counter() - t0
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = jrun(*probe, *build)
        jax.block_until_ready(out)
        ts.append(time.perf_counter() - t0)
    join_ms = float(np.median(ts)) * 1000

    print(json.dumps({
        "devices": n_dev, "rows": rows,
        "agg_ms": round(agg_ms, 1), "agg_compile_s": round(compile_s, 1),
        "join_ms": round(join_ms, 1), "join_compile_s": round(jcompile_s, 1),
    }), flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=1 << 20)
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--child", type=int, default=0)
    args = ap.parse_args()
    if args.child:
        _child(args.child, args.rows, args.iters)
        return
    sys.path.insert(0, REPO)
    from __graft_entry__ import _scrubbed_cpu_env

    results = []
    for n in (1, 2, 4, 8):
        r = subprocess.run(
            [sys.executable, "-m", "benchmarks.mesh_scaling",
             "--child", str(n), "--rows", str(args.rows),
             "--iters", str(args.iters)],
            cwd=REPO, env=_scrubbed_cpu_env(n), capture_output=True,
            text=True, timeout=1200)
        if r.returncode != 0:
            print(f"[mesh-scaling] {n}-device child failed:\n{r.stderr[-2000:]}",
                  file=sys.stderr)
            continue
        line = [l for l in r.stdout.splitlines() if l.startswith("{")][-1]
        results.append(json.loads(line))
        print(line, flush=True)
    if results:
        print("\ndevices  agg_ms  join_ms  (total rows fixed at "
              f"{args.rows})")
        for r in results:
            print(f"{r['devices']:>7}  {r['agg_ms']:>6}  {r['join_ms']:>7}")


if __name__ == "__main__":
    main()
