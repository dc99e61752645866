"""Physical operator tests with real mini-data vs pandas oracles
(modeled on the reference's operator unit tests, e.g.
shuffle_writer.rs:437-532, with TempDir-scale data)."""
import numpy as np
import pandas as pd
import pyarrow as pa
import pytest

from arrow_ballista_tpu import BallistaConfig, Field, INT64, STRING, Schema, decimal
from arrow_ballista_tpu.models import expr as E
from arrow_ballista_tpu.ops.operators import (
    AggSpec,
    CoalescePartitionsExec,
    FilterExec,
    HashAggregateExec,
    JoinExec,
    LimitExec,
    ProjectionExec,
    SortExec,
)
from arrow_ballista_tpu.ops.physical import MemoryScanExec, TaskContext


def ctx():
    return TaskContext(config=BallistaConfig())


def lineitem_like(n=500, seed=7):
    # logical values: decimal columns carry dollars (scan scales to cents)
    rng = np.random.default_rng(seed)
    return pd.DataFrame({
        "k": rng.integers(0, 50, n).astype(np.int64),
        "qty": (rng.integers(100, 5000, n) / 100.0),
        "price": (rng.integers(1000, 100000, n) / 100.0),
        "flag": rng.choice(["A", "N", "R"], n),
    })


SCHEMA = Schema([
    Field("k", INT64), Field("qty", decimal(2)), Field("price", decimal(2)),
    Field("flag", STRING),
])


def scan_of(df, partitions=2):
    return MemoryScanExec(SCHEMA, pa.Table.from_pandas(df), partitions)


def run_all(plan, c=None):
    c = c or ctx()
    out = []
    for p in range(plan.output_partition_count()):
        out.extend(plan.execute(p, c))
    frames = [b.to_pandas() for b in out]
    return pd.concat(frames, ignore_index=True)


def test_scan_roundtrip():
    df = lineitem_like()
    got = run_all(scan_of(df, 3))
    assert len(got) == len(df)
    np.testing.assert_array_equal(np.sort(got["k"]), np.sort(df["k"]))


def test_filter_and_project():
    df = lineitem_like()
    plan = FilterExec(scan_of(df), E.BinOp(">", E.Column("qty"), E.Lit(30.0)))
    plan = ProjectionExec(plan, [(E.Column("k"), "k"),
                                 (E.BinOp("*", E.Column("price"), E.Column("qty")), "v")])
    got = run_all(plan).sort_values(["k", "v"]).reset_index(drop=True)
    exp_mask = df["qty"] > 30.0
    exp = pd.DataFrame({
        "k": df["k"][exp_mask],
        "v": df["price"][exp_mask] * df["qty"][exp_mask],
    }).sort_values(["k", "v"]).reset_index(drop=True)
    pd.testing.assert_frame_equal(got, exp, check_dtype=False, atol=1e-6)


def test_aggregate_partial_final_matches_pandas():
    df = lineitem_like()
    scan = scan_of(df, 2)
    partial = HashAggregateExec(
        scan,
        [(E.Column("flag"), "flag")],
        [AggSpec("sum", E.Column("qty"), "s"), AggSpec("count", None, "c"),
         AggSpec("min", E.Column("price"), "mn")],
        mode="partial",
    )
    # merge partials in a single final (simulating post-shuffle single partition)
    from arrow_ballista_tpu.ops.operators import CoalescePartitionsExec

    final = HashAggregateExec(
        CoalescePartitionsExec(partial),
        [(E.Column("flag"), "flag")],
        [AggSpec("sum", E.Column("qty"), "s"), AggSpec("count", None, "c"),
         AggSpec("min", E.Column("price"), "mn")],
        mode="final",
    )
    got = run_all(final).sort_values("flag").reset_index(drop=True)
    exp = (df.groupby("flag", as_index=False)
           .agg(s=("qty", "sum"), c=("qty", "count"), mn=("price", "min"))
           .sort_values("flag").reset_index(drop=True))
    pd.testing.assert_frame_equal(got, exp, check_dtype=False, atol=1e-6)


def test_global_aggregate_empty_input_returns_one_row():
    df = lineitem_like(10)
    plan = FilterExec(scan_of(df, 1), E.BinOp(">", E.Column("qty"), E.Lit(10**9)))
    agg = HashAggregateExec(plan, [], [AggSpec("count", None, "c")], mode="single")
    got = run_all(agg)
    assert len(got) == 1 and got["c"][0] == 0


_GLOBAL_AGGS = [
    AggSpec("sum", E.BinOp("*", E.Column("price"), E.Column("qty")), "rev"),
    AggSpec("count", None, "c"),
    AggSpec("min", E.Column("price"), "mn"),
    AggSpec("max", E.Column("qty"), "mx"),
]
# the same aggregates as a final pass reads them: states named as outputs
_GLOBAL_MERGE = [AggSpec(a.func, E.Column(a.name), a.name)
                 for a in _GLOBAL_AGGS]


def test_keyless_partial_is_one_row_of_capacity_one():
    """No group keys: the partial state leaves as a batch of capacity 1
    (so pack, D2H and the partition file are one row wide), and the
    operator counts that the reduction path ran."""
    df = lineitem_like()
    partial = HashAggregateExec(scan_of(df, 2), [], _GLOBAL_AGGS, "partial")
    c = ctx()
    outs = [partial.execute(p, c) for p in range(2)]
    assert [[b.capacity for b in out] for out in outs] == [[1], [1]]
    assert partial.metrics().to_dict()["global_reductions"] == 2
    final = HashAggregateExec(CoalescePartitionsExec(partial), [],
                              _GLOBAL_MERGE, "final")
    got = run_all(final)
    assert len(got) == 1 and got["c"][0] == len(df)
    cents = lambda s: np.round(s * 100).astype(np.int64)  # noqa: E731
    assert round(got["rev"][0] * 10**4) == int(
        (cents(df["price"]) * cents(df["qty"])).sum())
    assert got["mn"][0] == df["price"].min() and got["mx"][0] == df["qty"].max()
    # a partial over no live rows emits no row
    none = HashAggregateExec(
        FilterExec(scan_of(df, 1), E.BinOp(">", E.Column("qty"), E.Lit(10**9))),
        [], _GLOBAL_AGGS, "partial")
    (b,) = none.execute(0, c)
    assert b.capacity == 1 and b.num_rows == 0


@pytest.mark.parametrize("case,backend,expected", [
    ("dense_domain_sums_and_counts", "tpu", 2),
    ("sort_path_sums", "tpu", 0),
    ("keyless", "tpu", 0),
    ("dense_domain_sums_and_counts", "cpu", 0),
])
def test_mxu_grouped_sums_counts_kernel_calls_on_the_matrix_unit(
        request, case, backend, expected):
    """One a kernel call whose int64 sums and counts take the contraction
    (``kernels.i64_sum_path``): a dense domain on the TPU backend; the sort
    path (its capacity of 1024 rows or more is past the contraction's
    slots), a keyless reduction and the CPU backend never."""
    if backend == "tpu":
        request.getfixturevalue("tpu_branches")
    df = lineitem_like()
    aggs = [AggSpec("sum", E.Column("qty"), "s"), AggSpec("count", None, "c")]
    keys = {"dense_domain_sums_and_counts": [(E.Column("flag"), "flag")],
            "sort_path_sums": [(E.Column("k"), "k")],
            "keyless": []}[case]
    partial = HashAggregateExec(scan_of(df, 2), keys, aggs, "partial")
    got = run_all(partial)
    assert partial.metrics().to_dict().get("mxu_grouped_sums", 0) == expected
    if keys:
        name = keys[0][1]
        assert got.groupby(name)["c"].sum().to_dict() \
            == df.groupby(name).size().to_dict()


@pytest.mark.parametrize("case,expected", [
    ("sort_path_sums", 2),
    ("dense_domain_sums_and_counts", 0),
    ("keyless", 0),
])
def test_run_scan_aggregates_counts_keyed_non_dense_kernel_calls(
        case, expected):
    """One a kernel call whose groups were runs of equal keys reduced by
    segmented scans: keys present and no dense domain (what
    ``kernels.grouped_aggregate`` branches on), on any backend; q1's
    dictionary keys (a dense domain) and q6 (no keys) never."""
    df = lineitem_like()
    aggs = [AggSpec("sum", E.Column("qty"), "s"), AggSpec("count", None, "c")]
    keys = {"dense_domain_sums_and_counts": [(E.Column("flag"), "flag")],
            "sort_path_sums": [(E.Column("k"), "k")],
            "keyless": []}[case]
    partial = HashAggregateExec(scan_of(df, 2), keys, aggs, "partial")
    got = run_all(partial)
    assert partial.metrics().to_dict().get("run_scan_aggregates", 0) \
        == expected
    if keys:
        name = keys[0][1]
        assert got.groupby(name)["c"].sum().to_dict() \
            == df.groupby(name).size().to_dict()


@pytest.mark.parametrize("mode", ["single", "final"])
def test_keyless_aggregate_over_empty_input_is_one_row(mode):
    """SQL: count = 0 and sum/min/max = NULL, from 'single' directly and
    from 'final' over partials that emitted nothing."""
    df = lineitem_like(10)
    plan = FilterExec(scan_of(df, 1), E.BinOp(">", E.Column("qty"), E.Lit(10**9)))
    aggs = _GLOBAL_AGGS
    if mode == "final":
        plan = HashAggregateExec(plan, [], aggs, "partial")
        aggs = _GLOBAL_MERGE
    got = run_all(HashAggregateExec(plan, [], aggs, mode))
    assert len(got) == 1 and got["c"][0] == 0
    assert got[["rev", "mn", "mx"]].isna().all(axis=None)


def test_keyless_aggregate_spilled_equals_in_memory(tmp_path):
    """A denied reservation aggregates batch by batch and merges the
    spilled one-row states: the same bits as the one-shot reduction."""
    from arrow_ballista_tpu.memory.governor import MemoryGovernor

    df = lineitem_like(3000, seed=3)
    df.loc[:999, "k"] = 40
    # three batches a partition, the first with no live row
    src = FilterExec(CoalescePartitionsExec(scan_of(df, 3)),
                     E.BinOp("<", E.Column("k"), E.Lit(25)))
    frames = {}
    for leg, gov in (("inmem", None),
                     ("spilled", MemoryGovernor(host_budget=1))):
        agg = HashAggregateExec(src, [], _GLOBAL_AGGS, "single")
        c = TaskContext(config=BallistaConfig(), governor=gov,
                        work_dir=str(tmp_path), job_id=leg)
        (b,) = agg.execute(0, c)
        frames[leg] = b.compacted_numpy()
        assert ("spill_runs" in agg.metrics().to_dict()) == (gov is not None)
    assert frames["inmem"]["c"][0] == (df["k"] < 25).sum() > 0
    for name, arr in frames["inmem"].items():
        assert arr.tobytes() == frames["spilled"][name].tobytes(), name


def test_inner_join_matches_pandas():
    left = pd.DataFrame({"k": np.array([1, 2, 2, 3, 5], np.int64),
                         "lv": np.array([10, 20, 21, 30, 50], np.int64)})
    right = pd.DataFrame({"rk": np.array([2, 2, 3, 4], np.int64),
                          "rv": np.array([200, 201, 300, 400], np.int64)})
    ls = Schema([Field("k", INT64), Field("lv", INT64)])
    rs = Schema([Field("rk", INT64), Field("rv", INT64)])
    j = JoinExec(
        MemoryScanExec(ls, pa.Table.from_pandas(left), 1),
        MemoryScanExec(rs, pa.Table.from_pandas(right), 1),
        on=[(E.Column("k"), E.Column("rk"))], join_type="inner", dist="broadcast",
    )
    got = run_all(j).sort_values(["k", "lv", "rv"]).reset_index(drop=True)
    exp = (left.merge(right, left_on="k", right_on="rk")
           .sort_values(["k", "lv", "rv"]).reset_index(drop=True))
    pd.testing.assert_frame_equal(got[["k", "lv", "rk", "rv"]], exp[["k", "lv", "rk", "rv"]],
                                  check_dtype=False)


def test_semi_and_anti_join():
    left = pd.DataFrame({"k": np.array([1, 2, 3, 4], np.int64)})
    right = pd.DataFrame({"rk": np.array([2, 4, 4], np.int64)})
    ls = Schema([Field("k", INT64)])
    rs = Schema([Field("rk", INT64)])
    mk = lambda jt: JoinExec(
        MemoryScanExec(ls, pa.Table.from_pandas(left), 1),
        MemoryScanExec(rs, pa.Table.from_pandas(right), 1),
        on=[(E.Column("k"), E.Column("rk"))], join_type=jt, dist="broadcast",
    )
    semi = run_all(mk("semi"))["k"].tolist()
    anti = run_all(mk("anti"))["k"].tolist()
    assert sorted(semi) == [2, 4]
    assert sorted(anti) == [1, 3]


def test_left_join_keeps_unmatched():
    left = pd.DataFrame({"k": np.array([1, 2], np.int64)})
    right = pd.DataFrame({"rk": np.array([2], np.int64), "rv": np.array([7], np.int64)})
    j = JoinExec(
        MemoryScanExec(Schema([Field("k", INT64)]), pa.Table.from_pandas(left), 1),
        MemoryScanExec(Schema([Field("rk", INT64), Field("rv", INT64)]),
                       pa.Table.from_pandas(right), 1),
        on=[(E.Column("k"), E.Column("rk"))], join_type="left", dist="broadcast",
    )
    got = run_all(j).sort_values("k").reset_index(drop=True)
    assert len(got) == 2
    assert got["rv"].tolist()[1] == 7


def test_join_with_residual_filter():
    left = pd.DataFrame({"k": np.array([1, 1, 2], np.int64), "lv": np.array([5, 15, 9], np.int64)})
    right = pd.DataFrame({"rk": np.array([1, 2], np.int64), "rv": np.array([10, 10], np.int64)})
    j = JoinExec(
        MemoryScanExec(Schema([Field("k", INT64), Field("lv", INT64)]), pa.Table.from_pandas(left), 1),
        MemoryScanExec(Schema([Field("rk", INT64), Field("rv", INT64)]), pa.Table.from_pandas(right), 1),
        on=[(E.Column("k"), E.Column("rk"))], join_type="inner", dist="broadcast",
        filter=E.BinOp(">", E.Column("lv"), E.Column("rv")),
    )
    got = run_all(j)
    assert got[["lv"]].values.tolist() == [[15]]


def test_sort_with_fetch():
    df = lineitem_like(100)
    plan = SortExec(scan_of(df, 2), [(E.Column("qty"), False), (E.Column("k"), True)], fetch=5)
    got = run_all(plan)
    exp = df.sort_values(["qty", "k"], ascending=[False, True]).head(5)
    np.testing.assert_array_equal(got["k"].to_numpy(), exp["k"].to_numpy())


def test_limit():
    df = lineitem_like(100)
    got = run_all(LimitExec(scan_of(df, 2), 7))
    assert len(got) == 7


def test_string_sort_via_codes():
    df = pd.DataFrame({"flag": ["R", "A", "N", "A"], "v": np.arange(4, dtype=np.int64)})
    s = Schema([Field("flag", STRING), Field("v", INT64)])
    plan = SortExec(MemoryScanExec(s, pa.Table.from_pandas(df), 1),
                    [(E.Column("flag"), True)])
    got = run_all(plan)
    assert got["flag"].tolist() == ["A", "A", "N", "R"]


def test_aggregate_adaptive_capacity():
    """A high-cardinality GROUP BY succeeds with no capacity to configure:
    an aggregate's group slots are bounded by its input's rows (the key
    ``ballista.agg.capacity`` that once bounded them is gone, and a session
    that still sets it is told so)."""
    import numpy as np
    import pyarrow as pa

    from arrow_ballista_tpu.client.context import BallistaContext
    from arrow_ballista_tpu.utils.config import BallistaConfig

    from arrow_ballista_tpu.utils.errors import ConfigurationError

    with pytest.raises(ConfigurationError, match="unknown configuration key"):
        BallistaConfig({"ballista.agg.capacity": "16"})
    n = 5000
    ctx = BallistaContext.local(BallistaConfig())
    ctx.register_table("big", pa.table({
        "k": pa.array(np.arange(n, dtype=np.int64)),
        "v": pa.array(np.ones(n, dtype=np.int64)),
    }))
    out = ctx.sql("select k, sum(v) as s from big group by k").to_pandas()
    assert len(out) == n
    assert out.s.sum() == n


def test_count_literal_operand():
    """count(1) / sum(literal): scalar-compiled operands broadcast to rows
    (regression: examples/standalone_sql.py hit a 0-dim index error)."""
    from arrow_ballista_tpu.client.context import BallistaContext

    ctx = BallistaContext.local()
    ctx.register_table("t", pa.table({"g": np.arange(30, dtype=np.int64) % 3}))
    out = ctx.sql("select g, count(1) as n, sum(2) as s from t "
                  "group by g order by g").to_pandas()
    assert out.n.tolist() == [10, 10, 10]
    assert out.s.tolist() == [20, 20, 20]
    # literal group keys broadcast too
    out2 = ctx.sql("select 7 as k, count(*) as n from t group by k").to_pandas()
    assert out2.k.tolist() == [7] and out2.n.tolist() == [30]


def test_partial_agg_passthrough_activates_for_siblings():
    """The adaptive partial-agg skip: once a task observes near-zero
    reduction on a large input, sibling tasks emit per-row states.  The
    probe is deferred until the result's count is host-known (the packed
    fetch normally sets it); resolution happens at the metrics snapshot."""
    import numpy as np

    from arrow_ballista_tpu.models.schema import Field, INT64, Schema
    from arrow_ballista_tpu.ops.operators import HashAggregateExec
    from arrow_ballista_tpu.ops.physical import MemoryScanExec, TaskContext
    from arrow_ballista_tpu.models import expr as E
    import pyarrow as pa

    n = 1 << 18  # 2 partitions x 2^17 (the large-input threshold each)
    tbl = pa.table({"k": pa.array(np.arange(n), type=pa.int64()),
                    "v": pa.array(np.ones(n, dtype=np.int64))})
    scan = MemoryScanExec(Schema([Field("k", INT64), Field("v", INT64)]),
                          tbl, partitions=2)
    agg = HashAggregateExec.partial(scan, [(E.Column("k"), "k")],
                                    [("sum", E.Column("v"), "s")]) \
        if hasattr(HashAggregateExec, "partial") else None
    if agg is None:
        from arrow_ballista_tpu.ops.operators import AggSpec

        agg = HashAggregateExec(scan, [(E.Column("k"), "k")],
                                [AggSpec("sum", E.Column("v"), "s")],
                                mode="partial")
    ctx = TaskContext()
    out0 = agg.execute(0, ctx)
    # resolve the deferred probe: materialize the count, then snapshot
    for b in out0:
        b.compacted_numpy()
    agg.metrics().to_dict()
    assert getattr(agg, "_passthrough", False), \
        "all-distinct keys on a 2^17-row input must trigger passthrough"
    out1 = agg.execute(1, ctx)
    snap = agg.metrics().to_dict()
    assert snap.get("passthrough_partials", 0) >= 1
    # passthrough partials still merge correctly at the final
    from arrow_ballista_tpu.models.batch import concat_batches

    rows = sum(b.num_rows for b in out0 + out1)
    assert rows == n
