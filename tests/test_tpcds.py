"""TPC-DS-class differential integration tests (the in-process analog of the
reference's TPC-DS result-check gate, QueryResultComparator.scala:39-110)."""

import tempfile

import pandas as pd
import pytest

from auron_tpu.models import tpcds


@pytest.fixture(scope="module")
def data():
    return tpcds.generate(sf=0.003, seed=7)


def test_q1_class_matches_oracle(data):
    got = tpcds.run_q1_class(data, n_partitions=3, year=2000)
    want = tpcds.q1_class_oracle(data, year=2000)
    assert len(got) == 1
    assert got["cnt"][0] == want["cnt"][0]
    assert got["total"][0] == pytest.approx(want["total"][0], rel=1e-9)
    assert got["mean"][0] == pytest.approx(want["mean"][0], rel=1e-9)


def test_q3_class_matches_oracle(data, tmp_path):
    got = tpcds.run_q3_class(data, n_map=3, n_reduce=2, work_dir=str(tmp_path))
    want = tpcds.q3_class_oracle(data)
    assert len(got) == len(want)
    assert got["d_year"].tolist() == want["d_year"].tolist()
    assert got["i_brand_id"].tolist() == want["i_brand_id"].tolist()
    for g, w in zip(got["s"], want["s"]):
        assert g == pytest.approx(w, rel=1e-9)


def test_q72_class_matches_oracle(data, tmp_path):
    got, sr = tpcds.run_q72_class(data, n_map=2, n_reduce=3, work_dir=str(tmp_path))
    want = tpcds.q72_class_oracle(data, sr)
    assert len(got) == len(want)
    assert got["item"].tolist() == want["item"].tolist()
    assert got["cnt"].tolist() == want["cnt"].tolist()
    assert got["qty"].tolist() == want["qty"].tolist()
    for g, w in zip(got["p_avg"], want["p_avg"]):
        assert g == pytest.approx(w, rel=1e-9)


def test_q95_class_matches_oracle(data, tmp_path):
    got = tpcds.run_q95_class(data, n_map=2, n_reduce=2, work_dir=str(tmp_path))
    want = tpcds.q95_class_oracle(data)
    assert len(got) == len(want)
    gk = [None if pd.isna(x) else int(x) for x in got["customer"]]
    wk = [None if pd.isna(x) else int(x) for x in want["customer"]]
    assert gk == wk
    assert got["cnt"].tolist() == want["cnt"].tolist()


def test_windowed_query_matches_oracle(data):
    got = tpcds.run_windowed_query(data)
    want = tpcds.windowed_query_oracle(data)
    assert len(got) == len(want)
    assert got["d"].tolist() == want["d"].tolist()
    assert got["item"].tolist() == want["item"].tolist()
    assert got["rk"].tolist() == want["rk"].tolist()
    for g, w in zip(got["rev"], want["rev"]):
        assert g == pytest.approx(w, rel=1e-9)


def test_q3_concurrent_maps_with_spills(monkeypatch):
    """Map tasks run concurrently; a tiny memory budget forces cross-thread
    spill cascades through MemManager — results must stay exact (regression
    for the per-consumer locking added in round 2)."""
    from auron_tpu.exec import agg_exec
    from auron_tpu.memory.memmgr import MemManager

    # the plan's integer keys would fold in the dense table, which is
    # unspillable and stages nothing until its final drain: a spill then
    # needs two tasks to hold memory at the same instant, and under a
    # loaded host none did (num_spills == 0, the one tier-1 failure of PR
    # 25-26's runs). With the dense table refusing, every batch stages an
    # intermediate through the generic path (the deferred window, the
    # table's own spills), so each map task spills whatever the timing.
    monkeypatch.setattr(agg_exec._DenseAggState, "LIMIT", 0)
    data = tpcds.generate(sf=0.05, seed=9)
    MemManager.init(budget_bytes=4096)  # tiny: every staged inter spills
    orig = tpcds.to_batches
    tpcds.to_batches = lambda df, n, batch_rows=4096, _o=orig: _o(df, n, batch_rows)
    try:
        with tempfile.TemporaryDirectory() as wd:
            got = tpcds.run_q3_class(data, n_map=4, n_reduce=2, work_dir=wd)
        want = tpcds.q3_class_oracle(data)
        assert len(got) == len(want)
        for g, w in zip(got["s"], want["s"]):
            assert abs(float(g) - float(w)) <= 1e-6 * max(1.0, abs(float(w)))
        assert MemManager.get().num_spills > 0
    finally:
        tpcds.to_batches = orig
        MemManager.init()  # restore default budget


def test_q6_class_matches_oracle(data):
    got = tpcds.run_q6_class(data)
    want = tpcds.q6_class_oracle(data)
    assert tpcds._cmp_frames(got, want) is None


def test_q18_class_matches_oracle(data, tmp_path):
    got = tpcds.run_q18_class(data, work_dir=str(tmp_path))
    want = tpcds.q18_class_oracle(data)
    assert tpcds._cmp_frames(got, want) is None


def test_generate_class_matches_oracle(data):
    got = tpcds.run_generate_class(data)
    want = tpcds.generate_class_oracle(data)
    assert tpcds._cmp_frames(got, want) is None


def test_windowed2_class_matches_oracle(data):
    got = tpcds.run_windowed2_class(data)
    want = tpcds.windowed2_class_oracle(data)
    assert tpcds._cmp_frames(got, want) is None


def test_q14b_intersect_except_matches_oracle(data):
    got = tpcds.run_q14b_class(data)
    want = tpcds.q14b_class_oracle(data)
    assert tpcds._cmp_frames(got, want) is None


def test_q67b_cube_matches_oracle(data):
    got = tpcds.run_q67b_class(data)
    want = tpcds.q67b_class_oracle(data)
    assert tpcds._cmp_frames(got, want) is None


def test_q93_null_skew_matches_oracle(data, tmp_path):
    got = tpcds.run_q93_class(data, work_dir=str(tmp_path))
    want = tpcds.q93_class_oracle(data)
    assert tpcds._cmp_frames(got, want) is None
    # the rewrite must actually produce the skew: most keys NULL
    null_row = got[got.k_null]
    assert len(null_row) == 1 and null_row.iloc[0]["rows"] > got["rows"].sum() * 0.7


def test_q9b_decimal_wide_matches_oracle(data):
    got = tpcds.run_q9b_class(data)
    want = tpcds.q9b_class_oracle(data)
    assert tpcds._cmp_frames(got, want) is None
    # the poisoned group's sum overflowed 38 digits -> NULL (non-ANSI)
    assert pd.isna(got[got.g == 7]["s"].iloc[0])
    assert got[got.g != 7]["s"].notna().all()


def test_gate_runs_all_classes():
    """The single-command differential gate (QueryRunner analog): every
    query class executes and matches its oracle."""
    res = tpcds.run_gate(sf=0.02, verbose=False)
    assert len(res) >= 40  # VERDICT r4 #6: the widened differential surface
    failures = [(n, e) for n, ok, e, _ in res if not ok]
    assert not failures, failures


def test_q18_plan_stability_golden(data, tmp_path):
    """Golden explain for the q18 map-stage plan (pruned): native-coverage
    regressions in the agg+join pipeline fail here."""
    import os as _os

    from auron_tpu.plan.explain import check_stability
    from auron_tpu.plan.planner import plan_from_proto
    from auron_tpu.plan.optimizer import prune_columns
    from auron_tpu.plan import builders as B
    from auron_tpu.exprs.ir import col

    fact_schema = tpcds._schema_of(data.store_sales)
    dd_schema = tpcds._schema_of(data.date_dim)
    it_schema = tpcds._schema_of(data.item)
    scan = B.memory_scan(fact_schema, "g_fact")
    j1 = B.hash_join(scan, B.memory_scan(dd_schema, "g_dd"),
                     [col(0)], [col(0)], "inner", build_side="right")
    j2 = B.hash_join(j1, B.memory_scan(it_schema, "g_item"),
                     [col(1)], [col(0)], "inner", build_side="right")
    proj = B.project(j2, [(col(10), "cat"), (col(6), "d_year"),
                          (col(3), "qty"), (col(4), "price")])
    partial = prune_columns(B.hash_agg(
        proj, [(col(0), "cat"), (col(1), "d_year")],
        [("avg", col(2), "q_avg"), ("sum", col(3), "p_sum")], "partial"))
    golden = _os.path.join(_os.path.dirname(__file__), "goldens", "q18_map_plan.txt")
    check_stability(plan_from_proto(partial), golden)


def _golden(name):
    import os as _os

    return _os.path.join(_os.path.dirname(__file__), "goldens", name)


def test_new_classes_match_oracles(data):
    for run, oracle in [
        (tpcds.run_q67_class, tpcds.q67_class_oracle),
        (tpcds.run_q9_class, tpcds.q9_class_oracle),
        (tpcds.run_q88_class, tpcds.q88_class_oracle),
        (tpcds.run_q37_class, tpcds.q37_class_oracle),
        (tpcds.run_q23_class, tpcds.q23_class_oracle),
    ]:
        got, want = run(data), oracle(data)
        assert tpcds._cmp_frames(got, want) is None, run.__name__


def test_q67_rollup_plan_golden(data):
    from auron_tpu.exprs.ir import Literal, col
    from auron_tpu.plan import builders as B
    from auron_tpu.plan.explain import check_stability
    from auron_tpu.plan.optimizer import prune_columns
    from auron_tpu.plan.planner import plan_from_proto
    from auron_tpu import types as T

    fact_schema = tpcds._schema_of(data.store_sales)
    scan = B.memory_scan(fact_schema, "g_fact")
    null_i64 = Literal(None, T.INT64)
    ex = B.expand(scan, [
        [col(0), col(1), col(4), tpcds.lit(0)],
        [col(0), null_i64, col(4), tpcds.lit(1)],
        [null_i64, null_i64, col(4), tpcds.lit(3)],
    ], ["d", "i", "price", "gid"])
    p = prune_columns(B.hash_agg(
        ex, [(col(0), "d"), (col(1), "i"), (col(3), "gid")],
        [("sum", col(2), "s")], "partial"))
    check_stability(plan_from_proto(p), _golden("q67_rollup_plan.txt"))


def test_q23_window_topk_plan_golden(data):
    from auron_tpu.exprs.ir import col
    from auron_tpu.ops.sortkeys import SortSpec
    from auron_tpu.plan import builders as B
    from auron_tpu.plan.explain import check_stability
    from auron_tpu.plan.optimizer import prune_columns
    from auron_tpu.plan.planner import plan_from_proto

    fact_schema = tpcds._schema_of(data.store_sales)
    it_schema = tpcds._schema_of(data.item)
    j = B.hash_join(B.memory_scan(fact_schema, "g_fact"),
                    B.memory_scan(it_schema, "g_item"),
                    [col(1)], [col(0)], "inner", build_side="right")
    proj = B.project(j, [(col(7), "cat"), (col(6), "brand"), (col(4), "price")])
    p = B.hash_agg(proj, [(col(0), "cat"), (col(1), "brand")],
                   [("sum", col(2), "rev")], "partial")
    f = B.hash_agg(p, [(col(0), "cat"), (col(1), "brand")],
                   [("sum", col(2), "rev")], "final")
    w = prune_columns(B.window(
        f, [col(0)], [(col(2), SortSpec(asc=False)), (col(1), SortSpec())],
        [("rank", None, None, 1, False, "rk")]))
    check_stability(plan_from_proto(w), _golden("q23_window_topk_plan.txt"))


def test_q14_stage1_plan_golden(data):
    from auron_tpu.exprs.ir import col
    from auron_tpu.plan import builders as B
    from auron_tpu.plan.explain import check_stability
    from auron_tpu.plan.optimizer import prune_columns
    from auron_tpu.plan.planner import plan_from_proto

    fact_schema = tpcds._schema_of(data.store_sales)
    dd_schema = tpcds._schema_of(data.date_dim)
    scan = B.memory_scan(fact_schema, "g_fact")
    j = B.hash_join(scan, B.memory_scan(dd_schema, "g_dd"),
                    [col(0)], [col(0)], "inner", build_side="right")
    proj = B.project(j, [(col(6), "y"), (col(1), "i")])
    p1 = prune_columns(B.hash_agg(proj, [(col(0), "y"), (col(1), "i")],
                                  [("count_star", None, "c")], "partial"))
    check_stability(plan_from_proto(p1), _golden("q14_stage1_plan.txt"))


def test_q9_scalar_subquery_plan_golden(data):
    from auron_tpu.exprs.ir import BinaryOp, ScalarSubquery, col
    from auron_tpu.plan import builders as B
    from auron_tpu.plan.explain import check_stability
    from auron_tpu.plan.optimizer import prune_columns
    from auron_tpu.plan.planner import plan_from_proto
    from auron_tpu import types as T

    fact_schema = tpcds._schema_of(data.store_sales)
    flt = B.filter_(B.memory_scan(fact_schema, "g_fact"),
                    [BinaryOp("gt", col(4), ScalarSubquery("g_avg", T.FLOAT64))])
    p = prune_columns(B.hash_agg(flt, [], [("count_star", None, "c"),
                                           ("sum", col(4), "s")], "partial"))
    check_stability(plan_from_proto(p), _golden("q9_scalar_plan.txt"))
