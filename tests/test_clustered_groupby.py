"""Clustered group-by early-HAVING rewrite (q18's subquery shape).

When parquet stats prove the scan is clustered on the single group key,
partial aggregates over contiguous partitions are final for all keys
outside neighbor-overlap windows, so the HAVING predicate applies
in-task and the exchange ships ~nothing (physical_planner.py
_clustered_having_pushdown).
"""
import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from arrow_ballista_tpu.client.context import BallistaContext
from arrow_ballista_tpu.utils.config import BallistaConfig


def _write_clustered(path, n_keys=5000, seed=3):
    rng = np.random.default_rng(seed)
    # 1-7 rows per key, rows sorted by key (lineitem-like clustering);
    # small row groups so keys straddle row-group boundaries
    reps = rng.integers(1, 8, n_keys)
    keys = np.repeat(np.arange(n_keys, dtype=np.int64), reps)
    qty = rng.integers(1, 50, len(keys)).astype(np.int64)
    pq.write_table(pa.table({"k": keys, "q": qty}), path,
                   row_group_size=1000)
    return pd.DataFrame({"k": keys, "q": qty})


SQL = ("select k, sum(q) as sq from t group by k "
       "having sum(q) > 150 order by k")


def _oracle(df):
    g = df.groupby("k").q.sum()
    g = g[g > 150]
    return g


@pytest.mark.parametrize("partitions", ["4", "auto"])
def test_clustered_having_matches_oracle(tmp_path, partitions):
    path = str(tmp_path / "t.parquet")
    df = _write_clustered(path)
    ctx = BallistaContext.standalone(
        BallistaConfig({"ballista.shuffle.partitions": partitions}),
        concurrent_tasks=2)
    ctx.register_parquet("t", path)
    out = ctx.sql(SQL).to_pandas()
    ora = _oracle(df)
    assert out.k.tolist() == ora.index.tolist()
    assert out.sq.tolist() == ora.values.tolist()
    # the rewrite actually engaged: the partial-agg stage's shuffle wrote
    # only survivors + window keys, not every state
    sched = ctx._standalone.scheduler
    graph = sched.jobs.get_graph(list(sched.jobs._status)[-1])
    wrote = []
    early = 0
    for st in graph.stages.values():
        m = st.aggregate_metrics()
        ef = sum(v for k, v in m.items()
                 if k.endswith("clustered_early_filters"))
        early += ef
        if ef:
            wrote.append(sum(v for k, v in m.items()
                             if k.endswith("ShuffleWriterExec.output_rows")))
    if partitions == "4":  # auto collapses this small table to 1 partition
        assert early > 0, "rewrite did not engage"
        survivors = len(_oracle(df))
        assert wrote and sum(wrote) < survivors + 200  # vs ~5000 states
    ctx.shutdown()


def test_unclustered_data_bails_and_stays_correct(tmp_path):
    path = str(tmp_path / "t.parquet")
    rng = np.random.default_rng(5)
    keys = rng.integers(0, 5000, 20_000).astype(np.int64)  # NOT sorted
    qty = rng.integers(1, 50, len(keys)).astype(np.int64)
    pq.write_table(pa.table({"k": keys, "q": qty}), path, row_group_size=1000)
    df = pd.DataFrame({"k": keys, "q": qty})
    ctx = BallistaContext.standalone(
        BallistaConfig({"ballista.shuffle.partitions": "4"}),
        concurrent_tasks=2)
    ctx.register_parquet("t", path)
    out = ctx.sql(SQL).to_pandas()
    ora = _oracle(df)
    assert out.k.tolist() == ora.index.tolist()
    assert out.sq.tolist() == ora.values.tolist()
    sched = ctx._standalone.scheduler
    graph = sched.jobs.get_graph(list(sched.jobs._status)[-1])
    early = sum(v for st in graph.stages.values()
                for k, v in st.aggregate_metrics().items()
                if k.endswith("clustered_early_filters"))
    assert early == 0  # unclustered: the rule must bail
    ctx.shutdown()


def test_serde_round_trips_annotation(tmp_path):
    from arrow_ballista_tpu import serde
    from arrow_ballista_tpu.catalog import SchemaCatalog, ParquetTable
    from arrow_ballista_tpu.scheduler.physical_planner import PhysicalPlanner
    from arrow_ballista_tpu.sql.optimizer import optimize
    from arrow_ballista_tpu.sql.planner import SqlToRel
    from arrow_ballista_tpu.sql.parser import parse_sql
    from arrow_ballista_tpu.ops import operators as O

    path = str(tmp_path / "t.parquet")
    _write_clustered(path)
    cat = SchemaCatalog()
    cat.register(ParquetTable("t", path))
    planned = PhysicalPlanner(cat, BallistaConfig(
        {"ballista.shuffle.partitions": "4"})).plan_query(
        optimize(SqlToRel(cat).plan(parse_sql(SQL))))

    def find_clustered(p):
        if isinstance(p, O.HashAggregateExec) \
                and getattr(p, "clustered", None) is not None:
            return p
        for c in p.children():
            got = find_clustered(c)
            if got is not None:
                return got
        return None

    agg = find_clustered(planned.plan)
    assert agg is not None, "rewrite did not annotate the plan"
    rt = serde.plan_from_obj(serde.plan_to_obj(planned.plan))
    agg2 = find_clustered(rt)
    assert agg2 is not None
    assert agg2.clustered[1] == agg.clustered[1]
    # the contiguous regrouping survives serde too
    from arrow_ballista_tpu.ops.physical import ParquetScanExec

    def find_scan(p):
        if isinstance(p, ParquetScanExec):
            return p
        for c in p.children():
            got = find_scan(c)
            if got is not None:
                return got
        return None

    assert find_scan(rt).groups == find_scan(planned.plan).groups


def test_within_rowgroup_disorder_falls_back(tmp_path):
    """Row-group stats can prove range disjointness while rows INSIDE a
    group are unordered; the presorted grouping detects the disorder at
    runtime and re-runs the sorted path — results stay exact."""
    rng = np.random.default_rng(11)
    parts = []
    for lo in range(0, 5000, 1000):
        block = np.repeat(np.arange(lo, lo + 1000, dtype=np.int64),
                          rng.integers(1, 4, 1000))
        rng.shuffle(block)  # disjoint rg ranges, unsorted inside
        parts.append(block)
    keys = np.concatenate(parts)
    qty = rng.integers(1, 50, len(keys)).astype(np.int64)
    path = str(tmp_path / "t.parquet")
    writer = pq.ParquetWriter(path, pa.schema([("k", pa.int64()),
                                               ("q", pa.int64())]))
    off = 0
    for block in parts:
        n = len(block)
        writer.write_table(pa.table({"k": keys[off:off+n],
                                     "q": qty[off:off+n]}))
        off += n
    writer.close()
    df = pd.DataFrame({"k": keys, "q": qty})
    ctx = BallistaContext.standalone(
        BallistaConfig({"ballista.shuffle.partitions": "4"}),
        concurrent_tasks=2)
    ctx.register_parquet("t", path)
    out = ctx.sql(SQL).to_pandas()
    ora = _oracle(df)
    assert out.k.tolist() == ora.index.tolist()
    assert out.sq.tolist() == ora.values.tolist()
    sched = ctx._standalone.scheduler
    graph = sched.jobs.get_graph(list(sched.jobs._status)[-1])
    metrics = {k: v for st in graph.stages.values()
               for k, v in st.aggregate_metrics().items()}
    assert any(k.endswith("presort_fallbacks") and v > 0
               for k, v in metrics.items()), metrics
    ctx.shutdown()


def test_null_keys_never_early_filtered(tmp_path):
    """NULL keys ride an in-band sentinel that parquet stats exclude, so
    NULL-group partials can split across partitions; the rewrite must ship
    them through the exchange (sentinel interval), never treat a partial
    NULL-group state as final."""
    rng = np.random.default_rng(17)
    keys = np.repeat(np.arange(4000, dtype=np.float64),
                     rng.integers(1, 4, 4000))
    # scatter NULLs throughout: each partition's null partial-sum stays
    # under the HAVING threshold while the merged sum passes it
    null_pos = np.arange(50, len(keys), len(keys) // 16)
    keys[null_pos] = np.nan
    qty = np.full(len(keys), 1, dtype=np.int64)
    qty[null_pos] = 40  # 16 nulls x 40 = 640 total, ~160/partition
    pa_keys = pa.array([None if np.isnan(v) else int(v) for v in keys],
                       type=pa.int64())
    path = str(tmp_path / "t.parquet")
    pq.write_table(pa.table({"k": pa_keys, "q": pa.array(qty)}), path,
                   row_group_size=1000)
    ctx = BallistaContext.standalone(
        BallistaConfig({"ballista.shuffle.partitions": "4"}),
        concurrent_tasks=2)
    ctx.register_parquet("t", path)
    out = ctx.sql("select k, sum(q) as sq from t group by k "
                  "having sum(q) > 300 order by k").to_pandas()
    # only the NULL group passes the threshold
    assert len(out) == 1
    assert np.isnan(out.k.iloc[0])
    assert out.sq.iloc[0] == 16 * 40
    ctx.shutdown()


def _find_op(plan, pred):
    if pred(plan):
        return plan
    for c in plan.children():
        got = _find_op(c, pred)
        if got is not None:
            return got
    return None


def _plan(path, partitions="4"):
    from arrow_ballista_tpu.catalog import ParquetTable, SchemaCatalog
    from arrow_ballista_tpu.scheduler.physical_planner import PhysicalPlanner
    from arrow_ballista_tpu.sql.optimizer import optimize
    from arrow_ballista_tpu.sql.parser import parse_sql
    from arrow_ballista_tpu.sql.planner import SqlToRel

    cat = SchemaCatalog()
    cat.register(ParquetTable("t", path))
    cfg = BallistaConfig({"ballista.shuffle.partitions": partitions})
    planned = PhysicalPlanner(cat, cfg).plan_query(
        optimize(SqlToRel(cat).plan(parse_sql(SQL))))
    return planned, cfg


def test_single_range_probe_rejected_without_partition_collapse(tmp_path):
    """A probe whose contiguous regroup collapses to ONE range (a huge
    trailing row group absorbs the whole regroup) is rejected by the
    planner — and, being side-effect free, must leave the scan's original
    partitioning untouched instead of serializing the whole scan."""
    from arrow_ballista_tpu.catalog import ParquetTable
    from arrow_ballista_tpu.ops import operators as O
    from arrow_ballista_tpu.ops.physical import ParquetScanExec

    rng = np.random.default_rng(3)
    reps = rng.integers(1, 8, 2000)
    keys = np.repeat(np.arange(2000, dtype=np.int64), reps)
    qty = rng.integers(1, 50, len(keys)).astype(np.int64)
    table = pa.table({"k": keys, "q": qty})
    path = str(tmp_path / "t.parquet")
    writer = pq.ParquetWriter(path, table.schema)
    writer.write_table(table.slice(0, 10))      # tiny row group ...
    writer.write_table(table.slice(10))         # ... then one huge one
    writer.close()

    scan = ParquetTable("t", path).scan(None, [], 2)
    before = [list(g) for g in scan.groups]
    assert len(before) == 2
    probe = scan.clustered_ranges("k")
    assert probe is not None
    groups, ranges = probe
    assert len(ranges) == 1, "regroup should collapse to one range here"
    assert [list(g) for g in scan.groups] == before, \
        "probe must not mutate the scan's partitioning"

    # planner end-to-end: annotation rejected, scan parallelism preserved
    planned, _cfg = _plan(path, partitions="2")
    agg = _find_op(planned.plan,
                   lambda p: isinstance(p, O.HashAggregateExec)
                   and getattr(p, "clustered", None) is not None)
    assert agg is None, "single-range annotation must be rejected"
    scan_op = _find_op(planned.plan,
                       lambda p: isinstance(p, ParquetScanExec))
    assert len(scan_op.groups) == 2, "rejected probe collapsed the scan"

    # and the query is still correct
    df = pd.DataFrame({"k": keys, "q": qty})
    ctx = BallistaContext.standalone(
        BallistaConfig({"ballista.shuffle.partitions": "2"}),
        concurrent_tasks=2)
    ctx.register_parquet("t", path)
    out = ctx.sql(SQL).to_pandas()
    ora = _oracle(df)
    assert out.k.tolist() == ora.index.tolist()
    assert out.sq.tolist() == ora.values.tolist()
    ctx.shutdown()


def test_stale_declared_ranges_disable_early_filter(tmp_path):
    """Stale parquet stats guard: when a partition's observed key min/max
    leaves the range the annotation declared (file rewritten after
    planning), the runtime check must drop the early HAVING filter —
    trusting stale overlap windows would silently drop boundary groups."""
    from arrow_ballista_tpu.ops import operators as O
    from arrow_ballista_tpu.scheduler.standalone import StandaloneCluster

    path = str(tmp_path / "t.parquet")
    df = _write_clustered(path)
    planned, cfg = _plan(path)
    agg = _find_op(planned.plan,
                   lambda p: isinstance(p, O.HashAggregateExec)
                   and getattr(p, "clustered", None) is not None)
    assert agg is not None, "rewrite did not annotate the plan"
    pred, _intervals, ranges = agg.clustered
    # simulate a post-planning rewrite: declared ranges (and the overlap
    # windows derived from them) no longer describe the file's keys
    shifted = [(lo + 10_000_000, hi + 10_000_000) for lo, hi in ranges]
    agg.clustered = (pred, [], shifted)

    cluster = StandaloneCluster(cfg, concurrent_tasks=2)
    try:
        batches = cluster.execute(planned)
        out = pd.concat([b.to_pandas() for b in batches],
                        ignore_index=True).sort_values("k")
        ora = _oracle(df)
        assert out.k.tolist() == ora.index.tolist()
        assert out.sq.tolist() == ora.values.tolist()
        graph = cluster.scheduler.jobs.get_graph(
            list(cluster.scheduler.jobs._status)[-1])
        metrics = {k: v for st in graph.stages.values()
                   for k, v in st.aggregate_metrics().items()}
        assert any(k.endswith("clustered_range_mismatches") and v > 0
                   for k, v in metrics.items()), metrics
        assert sum(v for k, v in metrics.items()
                   if k.endswith("clustered_early_filters")) == 0, \
            "stale ranges must disable the early filter"
    finally:
        cluster.shutdown()


# --- no closure of the clustered path is built per operator instance -------

_KEYED = ("sort.range_check", "agg.clustered_keep")


def _built(since):
    """What the process built since ``since`` = (STATS snapshot, ns):
    programs traced (compiles and retraces), closures ``shared_program``
    had to build, and the signatures of the ``compile`` spans."""
    from arrow_ballista_tpu.obs import device as device_obs
    from arrow_ballista_tpu.obs.tracing import RING

    s0, t0 = since
    s1 = device_obs.STATS.snapshot()
    delta = {k: s1[k] - s0[k] for k in ("jit_compiles", "jit_retraces",
                                        "program_cache_misses")}
    sigs = {s.attrs.get("sig") for s in RING.snapshot()
            if s.name.startswith("compile ") and s.start_ns >= t0}
    return (delta["jit_compiles"] + delta["jit_retraces"],
            delta["program_cache_misses"], sigs)


def _mark():
    import time

    from arrow_ballista_tpu.obs import device as device_obs

    return device_obs.STATS.snapshot(), time.time_ns()


def _cold_program_cache():
    """Other tests of this process have run the same query: start the
    closures cold, so that the first run is seen to build them."""
    from arrow_ballista_tpu.ops import physical

    with physical._program_cache_lock:
        physical._program_cache.clear()


def _tpch_twice(tmp_path):
    """q3 and q18 as the chip benchmark's join mix runs them, at SF0.2: at
    test_correct.py's SF0.02 lineitem is one row group and the planner
    annotates nothing."""
    import os

    from benchmarks.chip import compare, datagen, traffic
    from benchmarks.chip.oracles import q3 as oracle_q3, q18 as oracle_q18

    tables = ["customer", "lineitem", "orders"]
    ddir = datagen.write_data(str(tmp_path), 0.2, 2147496535, tables)
    ctx = BallistaContext.standalone(
        BallistaConfig({"ballista.shuffle.partitions": "8"}),
        concurrent_tasks=4)
    for t in tables:
        ctx.register_parquet(t, os.path.join(ddir, f"{t}.parquet"))
    want = {"q3": oracle_q3.answer(ddir), "q18": oracle_q18.answer(ddir)}

    def run():
        for q in ("q3", "q18"):
            got = compare.table_rows(
                ctx.sql(traffic.load_query(q)["sql"]).to_arrow())
            fault, _gap = compare.compare(got, want[q])
            assert fault is None, (q, fault)

    return ctx, run


def _clustered_twice(tmp_path, partitions):
    path = str(tmp_path / "t.parquet")
    ora = _oracle(_write_clustered(path))
    ctx = BallistaContext.standalone(
        BallistaConfig({"ballista.shuffle.partitions": partitions}),
        concurrent_tasks=2)
    ctx.register_parquet("t", path)

    def run():
        out = ctx.sql(SQL).to_pandas()
        assert out.k.tolist() == ora.index.tolist()
        assert out.sq.tolist() == ora.values.tolist()

    return ctx, run


@pytest.mark.parametrize("case", ["partitions_4", "partitions_auto",
                                  "tpch_q3_q18"])
def test_second_run_builds_no_program(tmp_path, case):
    """Plan instances are per job; every closure of the clustered path is
    keyed in ``shared_program``, so the second run of a statement traces
    nothing and builds nothing."""
    if case == "tpch_q3_q18":
        ctx, run = _tpch_twice(tmp_path)
    else:
        ctx, run = _clustered_twice(tmp_path, case.split("_")[1])
    try:
        _cold_program_cache()
        first = _mark()
        run()
        _traced, misses, sigs = _built(first)
        assert misses > 0
        if case != "partitions_auto":  # auto: one partition, no annotation
            assert set(_KEYED) <= sigs, sigs
        second = _mark()
        run()
        traced, misses, sigs = _built(second)
        assert (traced, misses, sigs) == (0, 0, set())
    finally:
        ctx.shutdown()


def _write_nullable(path):
    """``_write_clustered``'s rows with a NULL key every 997th row."""
    df = _write_clustered(path)
    null = np.zeros(len(df), dtype=bool)
    null[50::997] = True
    pq.write_table(pa.table({
        "k": pa.array(df.k.to_numpy(), type=pa.int64(), mask=null),
        "q": pa.array(df.q.to_numpy())}), path, row_group_size=1000)
    df = df.astype({"k": "float64"})
    df.loc[null, "k"] = np.nan
    return df


def _answer(ctx, key, limit):
    out = ctx.sql(f"select {key}, sum(q) as sq from t group by {key} "
                  f"having sum(q) > {limit} order by {key}").to_pandas()
    return _nulls_last((None if np.isnan(k) else int(k), int(v))
                       for k, v in zip(out[key], out.sq))


def _nulls_last(rows):
    return sorted(rows, key=lambda r: (r[0] is None, r[0]))


def _expected(df, key, limit):
    g = df.groupby(key, dropna=False).q.sum()
    g = g[g > limit]
    return _nulls_last((None if np.isnan(k) else int(k), int(v))
                       for k, v in g.items())


@pytest.mark.parametrize("case", ["having_constant", "group_key_column",
                                  "nullable_key"])
def test_shared_closures_are_never_stale(tmp_path, case):
    """Two statements whose early filter or range check differ must not
    meet in one program: each answer is the oracle's, the second statement
    builds closures of its own, and its early filter still engages (a range
    check compiled for another key would see a mismatch and latch off)."""
    path, path2 = str(tmp_path / "t.parquet"), str(tmp_path / "t2.parquet")
    df = _write_clustered(path)
    if case == "group_key_column":
        df["k2"] = df.k * 3 + 10_000    # clustered too, other ranges
        pq.write_table(pa.Table.from_pandas(df, preserve_index=False), path,
                       row_group_size=1000)
    # (file, key, HAVING constant) of the two statements
    a, b = {"having_constant": ((path, "k", 150), (path, "k", 100)),
            "group_key_column": ((path, "k", 150), (path, "k2", 150)),
            "nullable_key": ((path, "k", 150), (path2, "k", 150))}[case]
    frames = {path: df}
    if case == "nullable_key":
        frames[path2] = _write_nullable(path2)

    def early_filters(ctx):
        sched = ctx._standalone.scheduler
        graph = sched.jobs.get_graph(list(sched.jobs._status)[-1])
        return sum(v for st in graph.stages.values()
                   for k, v in st.aggregate_metrics().items()
                   if k.endswith("clustered_early_filters"))

    _cold_program_cache()
    for i, (file, key, limit) in enumerate((a, b, a)):
        ctx = BallistaContext.standalone(
            BallistaConfig({"ballista.shuffle.partitions": "4"}),
            concurrent_tasks=2)
        try:
            ctx.register_parquet("t", file)
            since = _mark()
            assert _answer(ctx, key, limit) == \
                _expected(frames[file], key, limit)
            _traced, misses, sigs = _built(since)
            assert early_filters(ctx) > 0
        finally:
            ctx.shutdown()
        if i < 2:
            # the first two statements share nothing of the clustered path
            assert misses > 0
            # (the early filter reads the partial aggregate's own columns,
            # __g0 and __a0: another key column is the same program)
            want = {"having_constant": {"agg.clustered_keep"},
                    "group_key_column": {"sort.range_check"},
                    "nullable_key": set(_KEYED)}[case] if i else set(_KEYED)
            assert want <= sigs, sigs
        else:
            # the first statement again, after the other: its own closures
            assert not (set(_KEYED) & sigs), sigs
