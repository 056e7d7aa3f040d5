"""Whole-stage fusion gate (plan/fusion.py; docs/fusion.md).

Bit-identity is the contract: every pipeline the pass rewrites must
produce byte-for-byte the batches the eager operators produce, across
schemas, NULL patterns, capacity buckets, dictionary passthrough, the
partial-agg input rewrite, the dense-prep hand-off (including forced
re-anchors and a forced compaction-bucket mispredict downstream), and
the blocking-boundary rules. The retrace guard's accounting
(fusion_stats) is pinned here too: replaying a stream must not add
compiles, and compile count is bounded by programs x capacity buckets.
"""

from __future__ import annotations

import numpy as np
import pandas as pd
import pytest

from auron_tpu import types as T
from auron_tpu.columnar.batch import Batch
from auron_tpu.exec.agg_exec import AggExpr, HashAggExec
from auron_tpu.exec.basic import (
    FilterExec,
    LimitExec,
    MemoryScanExec,
    ProjectExec,
    RenameColumnsExec,
)
from auron_tpu.exec.joins import BroadcastHashJoinExec
from auron_tpu.exec.sort_exec import SortExec
from auron_tpu.exprs import ir
from auron_tpu.exprs.ir import BinaryOp, Case, Column, If, In, IsNull, Literal, Not
from auron_tpu.ops.sortkeys import SortSpec
from auron_tpu.plan import fusion
from auron_tpu.plan.fusion import (
    FusedStageExec,
    expr_trace_safe,
    fuse_exec_tree,
    fusion_stats,
    reset_fusion_stats,
)
from auron_tpu.utils.config import Configuration

ON = Configuration({"exec.fuse.enable": "on"})


def _walk(op):
    yield op
    for c in op.children:
        yield from _walk(c)


def _types(op):
    return [type(o).__name__ for o in _walk(op)]


def _frame(n, seed, nulls=False):
    rng = np.random.default_rng(seed)
    k = rng.integers(0, 50, n).astype(np.int64)
    v = rng.normal(size=n)
    q = rng.integers(0, 100, n).astype(np.int32)
    s = [f"s{int(x) % 9}" for x in rng.integers(0, 40, n)]
    d = {
        "k": k.tolist(), "v": v.tolist(), "q": q.tolist(), "s": s,
    }
    if nulls:
        d["k"] = [None if i % 7 == 0 else x for i, x in enumerate(d["k"])]
        d["v"] = [None if i % 5 == 0 else x for i, x in enumerate(d["v"])]
        d["s"] = [None if i % 11 == 0 else x for i, x in enumerate(d["s"])]
    schema = T.Schema((
        T.Field("k", T.INT64, True), T.Field("v", T.FLOAT64, True),
        T.Field("q", T.INT32, True), T.Field("s", T.STRING, True),
    ))
    return Batch.from_pydict(d, schema)


def _ab(build, sort_cols=None):
    """Collect the tree eager vs fused; assert identical; return fused."""
    plain = build().collect().to_pandas()
    fused_tree = fuse_exec_tree(build(), ON)
    fused = fused_tree.collect().to_pandas()
    if sort_cols:
        plain = plain.sort_values(sort_cols).reset_index(drop=True)
        fused = fused.sort_values(sort_cols).reset_index(drop=True)
    pd.testing.assert_frame_equal(plain, fused)
    return fused_tree


# ---------------------------------------------------------------------------
# bit-identity fuzz
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("nulls", [False, True])
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_chain_bit_identity_fuzz(seed, nulls):
    """filter->project->filter->rename chains over varying capacity
    buckets and NULL patterns: fused output is bit-identical, including a
    dictionary-encoded passthrough column riding through the segment."""
    rng = np.random.default_rng(seed * 101)
    batches = [
        _frame(int(rng.integers(100, 3000)), seed * 10 + i, nulls)
        for i in range(4)
    ]

    def build():
        scan = MemoryScanExec([list(batches)], batches[0].schema)
        f1 = FilterExec(scan, [
            BinaryOp("gt", Column(1, "v"), Literal(-0.5, T.FLOAT64)),
            In(Column(2, "q"), tuple(range(0, 90)), False),
        ])
        p = ProjectExec(f1, [
            BinaryOp("add", Column(0, "k"), Literal(1, T.INT64)),
            Case(((BinaryOp("lt", Column(2, "q"), Literal(10, T.INT32)),
                   Literal(0.0, T.FLOAT64)),), Column(1, "v")),
            Column(3, "s"),          # dict passthrough
            Not(IsNull(Column(0, "k"))),
        ], ["k1", "vc", "s", "kn"])
        f2 = FilterExec(p, [Column(3, "kn")])
        return RenameColumnsExec(f2, ["K", "V", "S", "KN"])

    tree = _ab(build)
    assert isinstance(tree, FusedStageExec), _types(tree)
    assert tree.fused_op_names() == ["FilterExec", "ProjectExec", "FilterExec"]


@pytest.mark.parametrize("seed", [0, 1])
def test_agg_prefusion_bit_identity(seed):
    """scan->filter->partial agg->final agg with the grouping/argument
    expressions compiled into the stage (incl. dense prep on the CPU
    host-scatter substrate): identical to the eager pipeline."""
    batches = [_frame(1500, seed * 7 + i, nulls=True) for i in range(5)]

    def build():
        scan = MemoryScanExec([list(batches)], batches[0].schema)
        f = FilterExec(scan, [BinaryOp("gt", Column(2, "q"), Literal(20, T.INT32))])
        key = If(BinaryOp("lt", Column(2, "q"), Literal(60, T.INT32)),
                 Literal(None, T.INT64), Column(0, "k"))
        p = HashAggExec(f, [(key, "g")], [
            (AggExpr("sum", Column(1, "v")), "s"),
            (AggExpr("count_star", None), "c"),
            (AggExpr("min", Column(2, "q")), "lo"),
            (AggExpr("max", Column(1, "v")), "hi"),
            (AggExpr("avg", Column(1, "v")), "a"),
            (AggExpr("count", Column(1, "v")), "cv"),
        ], "partial")
        return HashAggExec(p, [(Column(0, "g"), "g")], [
            (AggExpr("sum", Column(1, "s")), "s"),
            (AggExpr("count_star", None), "c"),
            (AggExpr("min", Column(2, "lo")), "lo"),
            (AggExpr("max", Column(3, "hi")), "hi"),
            (AggExpr("avg", Column(4, "a")), "a"),
            (AggExpr("count", Column(6, "cv")), "cv"),
        ], "final")

    tree = _ab(build, sort_cols=["g"])
    partial = tree.children[0]
    assert isinstance(partial, HashAggExec)
    assert isinstance(partial.children[0], FusedStageExec)
    # the rewritten aggregate consumes bare column refs
    assert all(isinstance(g, Column) for g, _ in partial.groupings)
    assert partial.children[0].dense_link is not None


def test_dense_reanchor_under_prefusion():
    """Key range explodes mid-stream: the dense table drains, re-anchors
    and re-publishes; stale-epoch prepped batches refold via the raw path.
    Results stay identical to the eager pipeline."""
    frames = []
    for i in range(6):
        lo = 0 if i < 2 else 10_000_000 * i  # range jumps force restarts
        k = (np.arange(800) % 37 + lo).astype(np.int64)
        frames.append(Batch.from_pydict({
            "k": k.tolist(),
            "v": np.linspace(0, 1, 800).tolist(),
        }))

    def build():
        scan = MemoryScanExec([list(frames)], frames[0].schema)
        p = HashAggExec(scan, [(Column(0, "k"), "k")], [
            (AggExpr("sum", Column(1, "v")), "s"),
            (AggExpr("count_star", None), "c"),
        ], "partial")
        return HashAggExec(p, [(Column(0, "k"), "k")], [
            (AggExpr("sum", Column(1, "s")), "s"),
            (AggExpr("count_star", None), "c"),
        ], "final")

    _ab(build, sort_cols=["k"])


def test_fused_stage_feeding_join_chain_mispredict(monkeypatch):
    """A fused filter below a BHJ whose selectivity jumps ~0 -> ~100%
    mid-stream: the downstream compaction-bucket mispredict repair sees
    exactly the batches the eager filter would emit (bit-identical end
    result) — fusion must not disturb the predictor protocol."""
    n = 6000
    k0 = np.where(np.arange(n) < 1000, 999, np.arange(n) % 8).astype(np.int64)
    fact = pd.DataFrame({"k0": k0, "amt": np.arange(n, dtype=np.int64)})
    dim = pd.DataFrame({"id": np.arange(8, dtype=np.int64),
                        "dv": np.arange(8, dtype=np.int64) * 10})
    fact_b = [Batch.from_pandas(fact.iloc[i:i + 1000])
              for i in range(0, n, 1000)]
    dim_b = [Batch.from_pandas(dim)]

    def build():
        scan = MemoryScanExec([list(fact_b)], fact_b[0].schema)
        flt = FilterExec(scan, [BinaryOp(
            "gteq", Column(1, "amt"), Literal(0, T.INT64))])
        return BroadcastHashJoinExec(
            flt, MemoryScanExec([list(dim_b)], dim_b[0].schema),
            [Column(0, "k0")], [Column(0, "id")], "inner",
            build_side="right",
        )

    tree = _ab(build, sort_cols=None)
    assert "FusedStageExec" in _types(tree)


# ---------------------------------------------------------------------------
# blocking boundaries & trace safety
# ---------------------------------------------------------------------------


def test_segments_never_cross_blocking_boundaries():
    """Sort, join build and limit are boundaries: chains above and below
    fuse separately, never THROUGH the boundary operator."""
    batches = [_frame(500, 3)]

    def build():
        scan = MemoryScanExec([list(batches)], batches[0].schema)
        f1 = FilterExec(scan, [BinaryOp("gt", Column(1, "v"), Literal(0.0, T.FLOAT64))])
        srt = SortExec(f1, [Column(0, "k")], [SortSpec(True, True)])
        f2 = FilterExec(srt, [BinaryOp("lt", Column(2, "q"), Literal(90, T.INT32))])
        lim = LimitExec(f2, 100)
        p = ProjectExec(lim, [Column(0, "k"), Column(1, "v")], ["k", "v"])
        return p

    tree = fuse_exec_tree(build(), ON)
    names = _types(tree)
    # project above limit fused alone; filter between sort and limit fused
    # alone; filter below sort fused alone — boundaries intact in between
    assert names.count("FusedStageExec") == 3
    i_sort = names.index("SortExec")
    i_lim = names.index("LimitExec")
    assert i_lim < i_sort  # limit sits above sort in this walk order
    for seg in (s for s in _walk(tree) if isinstance(s, FusedStageExec)):
        assert len(seg.fused_op_names()) == 1  # nothing fused ACROSS


def test_unsafe_exprs_split_segments():
    """A host-evaluated expression (LIKE over a dict column) splits the
    chain: safe runs around it fuse, the unsafe operator stays eager."""
    batches = [_frame(400, 4)]

    def build():
        scan = MemoryScanExec([list(batches)], batches[0].schema)
        f1 = FilterExec(scan, [BinaryOp("gt", Column(1, "v"), Literal(-9.0, T.FLOAT64))])
        f2 = FilterExec(f1, [ir.Like(Column(3, "s"), "s1%", False, "\\")])
        f3 = FilterExec(f2, [BinaryOp("lt", Column(2, "q"), Literal(95, T.INT32))])
        return f3

    tree = _ab(build)
    names = _types(tree)
    assert names[:4] == ["FusedStageExec", "FilterExec", "FusedStageExec",
                         "MemoryScanExec"]


def test_trace_safety_rules():
    schema = _frame(10, 0).schema
    assert expr_trace_safe(BinaryOp("gt", Column(1, "v"), Literal(0.0, T.FLOAT64)), schema)
    assert expr_trace_safe(In(Column(2, "q"), (1, 2, 3), True), schema)
    # dict-encoded column: bare ref only with allow_dict_out
    assert not expr_trace_safe(Column(3, "s"), schema)
    assert expr_trace_safe(Column(3, "s"), schema, allow_dict_out=True)
    # IsNull over a dict column reads only validity — safe
    assert expr_trace_safe(IsNull(Column(3, "s")), schema)
    # string compare transforms dictionaries — not fusable
    assert not expr_trace_safe(
        BinaryOp("eq", Column(3, "s"), Literal("s1", T.STRING)), schema)
    # host UDFs never fuse
    assert not expr_trace_safe(
        ir.HostUDF("f", (Column(0, "k"),), T.INT64), schema)
    # row-offset context never fuses
    assert not expr_trace_safe(ir.RowNum(), schema)


def test_cost_model_substrate_selection():
    """auto on XLA:CPU fuses only segments whose eager dispatch estimate
    reaches exec.fuse.min.ops; on/off override unconditionally."""
    batches = [_frame(200, 5)]

    def build():
        scan = MemoryScanExec([list(batches)], batches[0].schema)
        return ProjectExec(scan, [Column(0, "k")], ["k"])

    # 1 op + 1 expr node = cost 2; min.ops 50 rejects, 1 accepts (CPU auto)
    t1 = fuse_exec_tree(build(), Configuration(
        {"exec.fuse.enable": "auto", "exec.fuse.min.ops": 50}))
    assert not isinstance(t1, FusedStageExec)
    t2 = fuse_exec_tree(build(), Configuration(
        {"exec.fuse.enable": "auto", "exec.fuse.min.ops": 1}))
    assert isinstance(t2, FusedStageExec)
    t3 = fuse_exec_tree(build(), Configuration({"exec.fuse.enable": "off"}))
    assert not isinstance(t3, FusedStageExec)


# ---------------------------------------------------------------------------
# retrace discipline & metric attribution
# ---------------------------------------------------------------------------


def test_replay_adds_no_compiles():
    """The (schema, segment signature, capacity bucket) cache key is
    stable: replaying the same stream adds ZERO fused-segment compiles,
    and compile count stays bounded by programs x distinct buckets —
    the tools/perfcheck.py retrace guard's invariant."""
    batches = [_frame(100, 6), _frame(1000, 7), _frame(100, 8)]

    def build():
        scan = MemoryScanExec([list(batches)], batches[0].schema)
        return FilterExec(scan, [BinaryOp("gt", Column(1, "v"), Literal(0.0, T.FLOAT64))])

    reset_fusion_stats()
    tree = fuse_exec_tree(build(), ON)
    tree.collect()
    s1 = fusion_stats()
    assert s1["programs"] == 1
    assert s1["compiles"] == 2  # two distinct capacity buckets
    tree.collect()  # replay: same signatures, same buckets
    tree2 = fuse_exec_tree(build(), ON)  # same segment, fresh tree
    tree2.collect()
    s2 = fusion_stats()
    assert s2["compiles"] == s1["compiles"], "replay must not retrace"
    assert s2["compiles"] <= s2["programs"] * 2


def test_metric_attribution_splits_per_operator():
    """Fused-program time lands on the CONSTITUENT operators' metric
    nodes (top_ops must see FilterExec/ProjectExec, not one opaque
    stage), the flight recorder receives the same nanos as op events,
    and the residual stage
    overhead NOT covered by the per-constituent split lands on the STAGE
    node — metric conservation: program splits + stage residual ==
    measured stage wall, exactly (a stage that reports 0.0 in top_ops
    while carrying fused_batches was dropping its residual)."""
    from auron_tpu.exec.base import ExecutionContext
    from auron_tpu.exec.metrics import MetricNode

    batches = [_frame(2000, 9)]

    def build():
        scan = MemoryScanExec([list(batches)], batches[0].schema)
        f = FilterExec(scan, [BinaryOp("gt", Column(1, "v"), Literal(0.0, T.FLOAT64))])
        return ProjectExec(f, [BinaryOp("add", Column(0, "k"), Literal(1, T.INT64))], ["k1"])

    tree = fuse_exec_tree(build(), ON)
    ctx = ExecutionContext()
    ctx.metrics.name = tree.name
    list(tree.execute(0, ctx))
    per_op: dict = {}
    MetricNode.accumulate_op_totals(ctx.metrics.snapshot(), per_op)
    assert "FilterExec" in per_op and "ProjectExec" in per_op
    total = per_op["FilterExec"].get("elapsed_compute", 0) + \
        per_op["ProjectExec"].get("elapsed_compute", 0)
    assert total > 0
    stage = per_op["FusedStageExec"]
    assert stage.get("fused_batches") == 1
    # conservation: sum of per-constituent splits + the stage's residual
    # equals the measured wall nanos of the stage's per-batch work
    assert stage.get("elapsed_compute", 0) > 0
    assert total + stage["elapsed_compute"] == stage["stage_wall"]


# ---------------------------------------------------------------------------
# probe-prologue & writer-repartition stage extensions (ISSUE 10)
# ---------------------------------------------------------------------------


def _probe_frame(seed, n=6000, jump=False):
    """Probe side with NULL keys; ``jump`` flips selectivity ~0 -> ~50%
    mid-stream so the compaction predictor under-sizes a bucket (forced
    mispredict repair)."""
    rng = np.random.default_rng(seed)
    k = rng.integers(1, 200, n).astype(object)
    if jump:
        k[: n // 3] = 10_000  # out of the build's key range: no matches
    probe = pd.DataFrame({"k": k, "v": rng.normal(size=n)})
    probe.loc[probe.index % 7 == 0, "k"] = None  # NULL keys never join
    schema = T.Schema((T.Field("k", T.INT64, True), T.Field("v", T.FLOAT64, True)))
    return [
        Batch.from_pydict(
            {"k": probe.k.iloc[i:i + 1000].tolist(),
             "v": probe.v.iloc[i:i + 1000].tolist()}, schema)
        for i in range(0, n, 1000)
    ]


def _assert_rows_equal(a, b):
    assert len(a) == len(b)
    cols = list(a.columns)
    a = a.sort_values(cols, na_position="first").reset_index(drop=True)
    b = b.sort_values(cols, na_position="first").reset_index(drop=True)
    for c in cols:
        assert ((a[c].isna() & b[c].isna()) | (a[c] == b[c])).all(), c


@pytest.mark.parametrize("jump", [False, True], ids=["steady", "mispredict"])
@pytest.mark.parametrize(
    "join_type", ["inner", "left", "left_semi", "left_anti", "existence"]
)
def test_probe_prologue_bit_identity(join_type, jump):
    """The fused probe prologue (key eval + canon + unique lookup +
    gather/compact-take inside ONE stage program) is bit-identical to the
    eager per-op jit chain across join types, through the predicted-
    compaction window and its forced-mispredict repair."""
    dim = pd.DataFrame({"id": np.arange(1, 101, dtype=np.int64),
                        "b": np.arange(1, 101) * 2.0})
    dim_b = [Batch.from_pandas(dim)]

    def build():
        pb = _probe_frame(3, jump=jump)
        scan = MemoryScanExec([pb], pb[0].schema)
        flt = FilterExec(scan, [BinaryOp(
            "gt", Column(1, "v"), Literal(-10.0, T.FLOAT64))])
        return BroadcastHashJoinExec(
            flt, MemoryScanExec([list(dim_b)], dim_b[0].schema),
            [Column(0, "k")], [Column(0, "id")], join_type,
            build_side="right",
        )

    from auron_tpu.exec.base import ExecutionContext

    eager = build().collect().to_pandas()
    reset_fusion_stats()
    tree = fuse_exec_tree(build(), ON)
    ctx = ExecutionContext()
    ctx.metrics.name = tree.name
    out = list(tree.execute(0, ctx))
    fused = (
        pd.concat([b.to_pandas() for b in out], ignore_index=True)
        if out else eager.iloc[:0]
    )
    _assert_rows_equal(eager, fused)
    st = fusion_stats()
    assert st["probe_segments"] >= 1
    # teeth: the stage program actually dispatched (a silent publish
    # failure would pass bit-identity via the eager fallback)
    assert ctx.metrics.total("fused_batches") > 0
    if jump and join_type == "inner":
        # the selectivity jump must exercise the repair protocol
        assert ctx.metrics.total("sel_mispredicts") > 0


@pytest.mark.parametrize("join_type", ["inner", "left", "left_semi"])
@pytest.mark.parametrize("n_live, width", [(64, 64), (100, 256), (1024, 1024),
                                           (1025, 0)])
def test_probe_stage_compares_against_a_small_build(
        monkeypatch, lookups_compare, join_type, n_live, width):
    """Under the patched lookup rule the fused probe stage carries a small
    build's live key list (its width a static of the program, 0 one over
    the ladder: the LUT as ever) and hands the driver the same (bi, ok,
    sel_out, live) batch for batch as the stage on the LUT alone; the
    rows are the eager chain's. NULL keys and keys outside the build's
    range never join."""
    from auron_tpu.exec.base import ExecutionContext

    dim = pd.DataFrame({"id": np.arange(1, n_live + 1, dtype=np.int64) * 2 - 40,
                        "b": np.arange(1, n_live + 1) * 2.0})
    dim_b = [Batch.from_pandas(dim)]
    jitted = fusion._stage_program_probe
    calls = []

    def spy(*args, **kw):
        res = jitted(*args, **kw)
        calls.append((kw["probe"], res[-1][:4]))
        return res

    monkeypatch.setattr(fusion, "_stage_program_probe", spy)

    def build():
        pb = _probe_frame(3)
        scan = MemoryScanExec([pb], pb[0].schema)
        flt = FilterExec(scan, [BinaryOp(
            "gt", Column(1, "v"), Literal(-10.0, T.FLOAT64))])
        return BroadcastHashJoinExec(
            flt, MemoryScanExec([list(dim_b)], dim_b[0].schema),
            [Column(0, "k")], [Column(0, "id")], join_type,
            build_side="right")

    def staged():
        calls.clear()
        ctx = ExecutionContext()
        out = list(fuse_exec_tree(build(), ON).execute(0, ctx))
        assert ctx.metrics.total("fused_batches") == 6
        return pd.concat([b.to_pandas() for b in out], ignore_index=True), \
            list(calls)

    eager = build().collect().to_pandas()
    plain, plain_calls = staged()
    with lookups_compare():
        fused, fused_calls = staged()
    _assert_rows_equal(eager, fused)
    _assert_rows_equal(plain, fused)
    assert len(fused_calls) == len(plain_calls) == 6
    # the static half: (..., use_lut, cmp_width, ...)
    assert {probe[3:5] for probe, _ in plain_calls} == {(True, 0)}
    assert {probe[3:5] for probe, _ in fused_calls} == {(True, width)}
    for (_, got), (_, want) in zip(fused_calls, plain_calls):
        for g, w in zip(got, want):
            assert (np.asarray(g) == np.asarray(w)).all()


@pytest.fixture(scope="module")
def dated_star_batches():
    """The specification-typed tiny star (tests/test_sql_decimal_serve.py:
    NULL keys that never join, NULL group keys, DECIMAL money) with the
    fact rows in date order and pruned to the three columns query 42
    reads, in batches of 8,192 rows; the filtered dimensions as Spark
    broadcasts them."""
    import test_sql_decimal_serve as star

    frames = star.make_frames(seed=13, n_fact=60_000)
    ss = frames["store_sales"].sort_values(
        "ss_sold_date_sk", na_position="last", kind="stable"
    ).reset_index(drop=True)[
        ["ss_sold_date_sk", "ss_item_sk", "ss_ext_sales_price"]]
    dd = frames["date_dim"]
    dd = dd[((dd.d_moy == 11) & (dd.d_year == 2000)).fillna(False)]
    it = frames["item"]
    it = it[(it.i_manager_id == 1).fillna(False)][
        ["i_item_sk", "i_category_id", "i_category"]]

    def pruned(table, cols):
        by_name = {f.name: f for f in star.SCHEMAS[table]}
        return T.Schema(tuple(by_name[c] for c in cols))

    fact = [Batch.from_pandas(ss.iloc[i:i + 8192],
                              schema=pruned("store_sales", list(ss.columns)))
            for i in range(0, len(ss), 8192)]
    dates = Batch.from_pandas(dd[["d_date_sk", "d_year"]],
                              schema=pruned("date_dim", ["d_date_sk", "d_year"]))
    items = Batch.from_pandas(it, schema=pruned("item", list(it.columns)))
    want = (ss.dropna(subset=["ss_sold_date_sk"])
              .merge(dd, left_on="ss_sold_date_sk", right_on="d_date_sk")
              .merge(it, left_on="ss_item_sk", right_on="i_item_sk"))
    return fact, dates, items, want


@pytest.mark.parametrize("chip", [False, True], ids=["cpu_rule", "chip_rule"])
@pytest.mark.parametrize("compact", ["on", "off"])
def test_fused_bhj_stages_on_the_tiny_star_are_row_exact(
        monkeypatch, joins_stay_dense, dated_star_batches, chip, compact):
    """Two fused probe stages, date_dim then item, over a stream whose
    first batches pass nothing and whose November batch passes a
    thousand (seed on an empty batch, mispredict, repair): the stage's
    in-program take and the eager driver's agree row for row, compacting
    by the rule ("on": the quarter rule and the chip's rule over shapes)
    or dense whatever it says ("off")."""
    import contextlib
    import time

    from auron_tpu import obs
    from auron_tpu.columnar import batch as batch_mod
    from auron_tpu.exec.base import ExecutionContext

    fact, dates, items, want = dated_star_batches
    monkeypatch.setattr(batch_mod, "_gather_bound", lambda: chip)

    def build():
        scan = MemoryScanExec([list(fact)], fact[0].schema)
        f1 = FilterExec(scan, [Not(IsNull(Column(1, "ss_item_sk")))])
        j1 = BroadcastHashJoinExec(
            f1, MemoryScanExec([[dates]], dates.schema),
            [Column(0, "ss_sold_date_sk")], [Column(0, "d_date_sk")],
            "inner", build_side="right", projection=[1, 2, 4])
        f2 = FilterExec(j1, [Not(IsNull(Column(0, "ss_item_sk")))])
        return BroadcastHashJoinExec(
            f2, MemoryScanExec([[items]], items.schema),
            [Column(0, "ss_item_sk")], [Column(0, "i_item_sk")],
            "inner", build_side="right", projection=[1, 2, 4, 5])

    saved_mode = obs.mode()
    obs.set_mode("recorder")
    rule = joins_stay_dense if compact == "off" else contextlib.nullcontext
    try:
        with rule():
            eager = build().collect().to_pandas()
            tree = fuse_exec_tree(build(), ON)
            ctx = ExecutionContext()
            ctx.metrics.name = tree.name
            t0 = time.perf_counter()
            out = list(tree.execute(0, ctx))
            ws = obs.window_summary(t0, time.perf_counter())
    finally:
        obs.set_mode(saved_mode)
    fused = pd.concat([b.to_pandas() for b in out], ignore_index=True)
    _assert_rows_equal(eager, fused)
    assert _types(tree).count("FusedStageExec") == 2
    assert ctx.metrics.total("fused_batches") > 0
    # against plain pandas: money to the cent, the NULL category kept
    assert len(fused) == len(want) > 300
    assert sorted(fused.ss_ext_sales_price.dropna()) == sorted(
        want.ss_ext_sales_price.dropna())
    assert fused.i_category.isna().sum() == want.i_category.isna().sum() > 0
    n = len(fact)
    if compact == "off":
        assert ws["join_takes"] == {"seed": 2, "dense": 2 * n - 2}
        assert ws["join_gather_rows"] == 2 * sum(b.capacity for b in fact)
    else:
        takes = ws["join_takes"]
        assert takes["seed"] == 2                 # one a probe stream
        assert takes.get("repair", 0) >= 1        # nothing, then a thousand
        assert ctx.metrics.total("sel_mispredicts") == takes["repair"]
        assert ws["join_gather_rows"] < sum(b.capacity for b in fact) / 2


@pytest.mark.parametrize("chip", [False, True], ids=["cpu_rule", "chip_rule"])
@pytest.mark.parametrize("fused", [False, True], ids=["eager", "stage"])
def test_batches_behind_a_burst_are_not_gathered_at_capacity(
        monkeypatch, fused, chip):
    """One batch of a stream passes half its rows, the batches behind it
    none. The predictor's bucket stays wide for its shrink patience, and a
    bucket too wide to pay used to mean a gather of every build column at
    capacity for batches that hold nothing; now such a batch's take waits
    in the window for its own count, which compacts it into the least
    bucket. Only the burst itself is gathered at capacity."""
    import time

    from auron_tpu import obs
    from auron_tpu.columnar import batch as batch_mod
    from auron_tpu.exec.base import ExecutionContext
    from auron_tpu.obs import core
    from auron_tpu.utils.config import TRANSFER_WINDOW_DEPTH, active_conf

    cap, live = 8192, [10, 4000, 0, 0, 0, 0]
    rng = np.random.default_rng(5)
    frames = []
    for n in live:
        k = np.full(cap, 10_000, dtype=np.int64)
        k[rng.choice(cap, n, replace=False)] = rng.integers(0, 64, n)
        frames.append(pd.DataFrame({"k": k, "v": rng.integers(0, 1 << 30, cap)}))
    dim = pd.DataFrame({"id": np.arange(64, dtype=np.int64),
                        "d": np.arange(64, dtype=np.int64) * 3})
    probe_b = [Batch.from_pandas(f) for f in frames]
    dim_b = Batch.from_pandas(dim)
    monkeypatch.setattr(batch_mod, "_gather_bound", lambda: chip)

    def build():
        scan = MemoryScanExec([list(probe_b)], probe_b[0].schema)
        flt = FilterExec(scan, [BinaryOp(
            "gteq", Column(1, "v"), Literal(0, T.INT64))])
        return BroadcastHashJoinExec(
            flt, MemoryScanExec([[dim_b]], dim_b.schema),
            [Column(0, "k")], [Column(0, "id")], "inner", build_side="right")

    conf = active_conf()
    saved = (conf.get(TRANSFER_WINDOW_DEPTH), obs.mode())
    conf.set(TRANSFER_WINDOW_DEPTH, 1)
    obs.set_mode("recorder")
    try:
        tree = fuse_exec_tree(build(), ON) if fused else build()
        ctx = ExecutionContext()
        ctx.metrics.name = tree.name
        t0 = time.perf_counter_ns()
        out = list(tree.execute(0, ctx))
        t1 = time.perf_counter_ns()
        takes = [ev[7] for _r, evs in core.snapshot_events() for ev in evs
                 if ev[2] == "take" and t0 <= ev[0] < t1]
    finally:
        conf.set(TRANSFER_WINDOW_DEPTH, saved[0])
        obs.set_mode(saved[1])
    got = pd.concat([b.to_pandas() for b in out], ignore_index=True)
    want = pd.concat(frames).merge(dim, left_on="k", right_on="id")
    _assert_rows_equal(got[["k", "v", "d"]], want[["k", "v", "d"]])
    if fused:
        assert ctx.metrics.total("fused_batches") == len(live)
    at_capacity = [t for t in takes if t["rows"] == cap]
    assert [t["mode"] for t in at_capacity] == ["repair"]     # the burst
    assert sorted(t["rows"] for t in takes if t["rows"] != cap) == [128] * 6
    assert sum(t["mode"] == "seed" for t in takes) == 1


def test_probe_prologue_exists_lut_bit_identity():
    """Duplicate-keyed build probed by semi/anti: the existence-LUT probe
    rides the stage program (payload kind "exists")."""
    dup = pd.DataFrame({"id": np.tile(np.arange(1, 51, dtype=np.int64), 3),
                        "b": np.arange(150) * 1.0})
    dim_b = [Batch.from_pandas(dup)]

    for join_type in ("left_semi", "left_anti"):
        def build():
            pb = _probe_frame(5)
            scan = MemoryScanExec([pb], pb[0].schema)
            flt = FilterExec(scan, [BinaryOp(
                "gt", Column(1, "v"), Literal(-10.0, T.FLOAT64))])
            return BroadcastHashJoinExec(
                flt, MemoryScanExec([list(dim_b)], dim_b[0].schema),
                [Column(0, "k")], [Column(0, "id")], join_type,
                build_side="right",
            )

        from auron_tpu.exec.base import ExecutionContext

        eager = build().collect().to_pandas()
        reset_fusion_stats()
        tree = fuse_exec_tree(build(), ON)
        ctx = ExecutionContext()
        ctx.metrics.name = tree.name
        out = list(tree.execute(0, ctx))
        fused = pd.concat([b.to_pandas() for b in out], ignore_index=True)
        _assert_rows_equal(eager, fused)
        assert fusion_stats()["probe_segments"] >= 1
        assert ctx.metrics.total("fused_batches") > 0, join_type


def test_fused_probe_deferred_agg_spill_midstream():
    """End-to-end q93 shape under memory pressure: fused probe prologue
    (LEFT join, null-heavy keys) feeding a bool-key partial aggregate on
    the DEFERRED count path, with a tiny MemManager budget forcing table
    spills mid-stream — eager and fused agree with the pandas reference
    row-exactly (counts bit-equal; float sums compared at 1e-9 —
    predictive compaction re-buckets the reduces, re-associating float
    adds the same way any merge-boundary shift does). The exactly-once
    staging contract through spill parks is the teeth here."""
    from auron_tpu.exec.agg_exec import AggExpr, HashAggExec
    from auron_tpu.memory.memmgr import MemManager

    dim = pd.DataFrame({"id": np.arange(1, 101, dtype=np.int64),
                        "b": np.arange(1, 101) * 2.0})
    dim_b = [Batch.from_pandas(dim)]

    def build():
        pb = _probe_frame(11, n=12000, jump=True)
        scan = MemoryScanExec([pb], pb[0].schema)
        j = BroadcastHashJoinExec(
            scan, MemoryScanExec([list(dim_b)], dim_b[0].schema),
            [Column(0, "k")], [Column(0, "id")], "left", build_side="right",
        )
        p = HashAggExec(
            j, [(IsNull(Column(0, "k")), "k_null")],
            [(AggExpr("count_star", None), "rows"),
             (AggExpr("sum", Column(1, "v")), "s")], "partial")
        return HashAggExec(
            p, [(Column(0, "k_null"), "k_null")],
            [(AggExpr("count_star", None), "rows"),
             (AggExpr("sum", Column(1, "s")), "s")], "final")

    # a left join on a unique build keeps every probe row once
    probe = pd.concat([b.to_pandas() for b in _probe_frame(
        11, n=12000, jump=True)], ignore_index=True)
    want = (probe.assign(k_null=probe.k.isna()).groupby("k_null")
            .agg(rows=("v", "size"), s=("v", "sum")).reset_index())
    MemManager.init(budget_bytes=64 << 10)  # forces mid-stream spills
    try:
        eager = build().collect().to_pandas()
        fused = fuse_exec_tree(build(), ON).collect().to_pandas()
    finally:
        MemManager.init()
    for got in (eager, fused):
        got = got.sort_values("k_null").reset_index(drop=True)
        assert got["k_null"].tolist() == want["k_null"].tolist()
        assert got["rows"].tolist() == want["rows"].tolist()  # exactly-once
        for a, b in zip(got["s"], want["s"]):
            assert a == pytest.approx(b, rel=1e-9)


def test_writer_stage_counted_and_byte_identical(tmp_path):
    """Fused repartition (pids + clustering inside the stage program)
    produces byte-identical shuffle files to the eager writer, for hash
    and round-robin partitionings."""
    import os

    from auron_tpu.exec.base import ExecutionContext
    from auron_tpu.exec.shuffle.partitioning import (
        HashPartitioning, RoundRobinPartitioning,
    )
    from auron_tpu.exec.shuffle.writer import ShuffleWriterExec

    frames = [_frame(2000, s) for s in (1, 2, 3)]

    def run(conf, part, d):
        scan = MemoryScanExec([list(frames)], frames[0].schema)
        prj = ProjectExec(scan, [Column(0, "k"), Column(1, "v")], ["k", "v"])
        w = ShuffleWriterExec(prj, part, str(d / "x.data"), str(d / "x.index"))
        tree = fuse_exec_tree(w, conf)
        list(tree.execute(0, ExecutionContext()))
        return (d / "x.data").read_bytes(), (d / "x.index").read_bytes()

    from auron_tpu.utils.config import Configuration

    OFF = Configuration({"exec.fuse.enable": "off"})
    for name, mk in (("hash", lambda: HashPartitioning([Column(0, "k")], 3)),
                     ("rr", lambda: RoundRobinPartitioning(3))):
        d_on = tmp_path / f"{name}_on"
        d_off = tmp_path / f"{name}_off"
        d_on.mkdir(), d_off.mkdir()
        reset_fusion_stats()
        on_data, on_idx = run(ON, mk(), d_on)
        assert fusion_stats()["writer_segments"] >= 1, name
        off_data, off_idx = run(OFF, mk(), d_off)
        # the trailing 16 bytes carry a random attempt pair tag
        assert on_data[:-16] == off_data[:-16], name
        assert len(on_idx) == len(off_idx), name


# ---------------------------------------------------------------------------
# scope names inside the stage programs (what a device trace names ops by)
# ---------------------------------------------------------------------------


def _first_call(monkeypatch, program: str, run):
    """Run ``run()`` with ``fusion.<program>`` wrapped to keep its first
    call: ``(the jitted program, args, kwargs)``."""
    jitted = getattr(fusion, program)
    calls = []

    def capture(*args, **kw):
        calls.append((args, kw))
        return jitted(*args, **kw)

    monkeypatch.setattr(fusion, program, capture)
    run()
    assert calls, f"{program} never dispatched"
    return (jitted, *calls[0])


def test_probe_stage_program_carries_its_scope_names(monkeypatch):
    """The lowered text of the probe stage program holds every
    ``auron.stage.*`` / ``auron.probe.*`` scope name (metadata only)."""
    from auron_tpu.exec.base import ExecutionContext

    dim = pd.DataFrame({"id": np.arange(1, 101, dtype=np.int64),
                        "b": np.arange(1, 101) * 2.0})
    dim_b = [Batch.from_pandas(dim)]

    def run():
        pb = _probe_frame(3)
        scan = MemoryScanExec([pb], pb[0].schema)
        flt = FilterExec(scan, [BinaryOp(
            "gt", Column(1, "v"), Literal(-10.0, T.FLOAT64))])
        join = BroadcastHashJoinExec(
            flt, MemoryScanExec([list(dim_b)], dim_b[0].schema),
            [Column(0, "k")], [Column(0, "id")], "inner", build_side="right")
        list(fuse_exec_tree(join, ON).execute(0, ExecutionContext()))

    jitted, args, kw = _first_call(monkeypatch, "_stage_program_probe", run)
    # the predicted compact-take is the variant that runs every step
    compact = dict(kw, probe=kw["probe"][:-1] + (("compact", 256),))
    text = jitted.lower(*args, **compact).as_text(debug_info=True)
    for scope in ("auron.stage.step0.filter", "auron.probe.pack",
                  "auron.probe.lookup", "auron.probe.compact",
                  "auron.probe.gather"):
        assert scope in text, scope


def test_shuffle_stage_program_carries_its_scope_names(monkeypatch, tmp_path):
    from auron_tpu.exec.base import ExecutionContext
    from auron_tpu.exec.shuffle.partitioning import HashPartitioning
    from auron_tpu.exec.shuffle.writer import ShuffleWriterExec

    frames = [_frame(2000, s) for s in (1, 2)]

    def run():
        scan = MemoryScanExec([list(frames)], frames[0].schema)
        twice = BinaryOp("mul", Column(1, "v"), Literal(2.0, T.FLOAT64))
        prj = ProjectExec(scan, [Column(0, "k"), twice], ["k", "v2"])
        w = ShuffleWriterExec(prj, HashPartitioning([Column(0, "k")], 3),
                              str(tmp_path / "x.data"), str(tmp_path / "x.index"))
        list(fuse_exec_tree(w, ON).execute(0, ExecutionContext()))

    jitted, args, kw = _first_call(monkeypatch, "_stage_program_shuffle", run)
    text = jitted.lower(*args, **kw).as_text(debug_info=True)
    for scope in ("auron.stage.step0.project", "auron.shuffle.partition"):
        assert scope in text, scope
