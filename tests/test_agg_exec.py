"""Hash-aggregate tests, differential against pandas groupby."""

import numpy as np
import pandas as pd
import pyarrow as pa
import pytest

from auron_tpu import types as T
from auron_tpu.columnar import Batch
from auron_tpu.exec.agg_exec import FINAL, PARTIAL, PARTIAL_MERGE, AggExpr, HashAggExec
from auron_tpu.exec.base import ExecutionContext
from auron_tpu.exec.basic import MemoryScanExec
from auron_tpu.exprs.ir import col
from auron_tpu.utils.config import (
    PARTIAL_AGG_SKIPPING_MIN_ROWS,
    PARTIAL_AGG_SKIPPING_RATIO,
)


def _agg_pipeline(batches, groupings, aggs):
    """partial -> (simulated exchange) -> final, like Spark plans it."""
    scan = MemoryScanExec.single(batches)
    partial = HashAggExec(scan, groupings, aggs, PARTIAL)
    shuffled = MemoryScanExec.single(list(partial.execute(0, ExecutionContext())) or
                                     [Batch.empty(partial.inter_schema)])
    final = HashAggExec(shuffled, groupings, aggs, FINAL)
    return final.collect().to_pandas()


def _sorted(df, by):
    return df.sort_values(by).reset_index(drop=True)


def test_sum_count_avg_min_max_basic():
    data = {
        "k": ["a", "b", "a", "c", "b", "a"],
        "v": [1, 2, 3, None, 5, 6],
    }
    b = Batch.from_pydict(
        data, schema=T.Schema.of(T.Field("k", T.STRING), T.Field("v", T.INT64))
    )
    got = _agg_pipeline(
        [b],
        [(col(0), "k")],
        [
            (AggExpr("sum", col(1)), "s"),
            (AggExpr("count", col(1)), "c"),
            (AggExpr("count_star", None), "cs"),
            (AggExpr("avg", col(1)), "a"),
            (AggExpr("min", col(1)), "mn"),
            (AggExpr("max", col(1)), "mx"),
        ],
    )
    df = pd.DataFrame(data)
    want = df.groupby("k", dropna=False).agg(
        s=("v", "sum"), c=("v", "count"), cs=("v", "size"),
        a=("v", "mean"), mn=("v", "min"), mx=("v", "max"),
    ).reset_index()
    got = _sorted(got, "k")
    want = _sorted(want, "k")
    assert got["k"].tolist() == want["k"].tolist()
    # c group has sum NULL (all inputs null), count 0
    assert got["s"].tolist()[:2] == [10, 7] and pd.isna(got["s"][2])
    assert got["c"].tolist() == [3, 2, 0]
    assert got["cs"].tolist() == [3, 2, 1]
    assert got["a"].tolist()[:2] == [pytest.approx(10 / 3), pytest.approx(3.5)]
    assert pd.isna(got["a"][2])
    assert got["mn"].tolist()[:2] == [1, 2]
    assert got["mx"].tolist()[:2] == [6, 5]


def test_multi_batch_multi_key_random_vs_pandas():
    rng = np.random.default_rng(0)
    n = 5000
    k1 = rng.integers(0, 50, n)
    k2 = rng.choice(["x", "y", "z", "w"], n)
    v = rng.normal(size=n)
    vmask = rng.random(n) < 0.1
    vs = pd.array(v, dtype="Float64")
    vs[vmask] = pd.NA
    df = pd.DataFrame({"k1": k1, "k2": k2, "v": vs})
    batches = []
    for i in range(0, n, 1000):
        chunk = df.iloc[i : i + 1000]
        batches.append(
            Batch.from_arrow(pa.RecordBatch.from_pandas(chunk, preserve_index=False))
        )
    got = _agg_pipeline(
        batches,
        [(col(0), "k1"), (col(1), "k2")],
        [
            (AggExpr("sum", col(2)), "s"),
            (AggExpr("count", col(2)), "c"),
            (AggExpr("min", col(2)), "mn"),
            (AggExpr("max", col(2)), "mx"),
        ],
    )
    want = (
        df.groupby(["k1", "k2"], dropna=False)
        .agg(s=("v", "sum"), c=("v", "count"), mn=("v", "min"), mx=("v", "max"))
        .reset_index()
    )
    got = _sorted(got, ["k1", "k2"])
    want = _sorted(want, ["k1", "k2"])
    assert len(got) == len(want)
    assert got["k1"].tolist() == want["k1"].tolist()
    assert got["k2"].tolist() == want["k2"].tolist()
    assert got["c"].tolist() == want["c"].tolist()
    # pandas sum over all-NA group gives 0.0 with count 0; ours gives NULL
    for g, w, c in zip(got["s"], want["s"], want["c"]):
        if c == 0:
            assert pd.isna(g)
        else:
            assert g == pytest.approx(w, rel=1e-9)
    for colname in ("mn", "mx"):
        for g, w in zip(got[colname], want[colname]):
            assert (pd.isna(g) and pd.isna(w)) or g == pytest.approx(w)


def test_null_group_key():
    data = {"k": [1, None, 1, None], "v": [1.0, 2.0, 3.0, 4.0]}
    b = Batch.from_pydict(
        data, schema=T.Schema.of(T.Field("k", T.INT32), T.Field("v", T.FLOAT64))
    )
    got = _agg_pipeline([b], [(col(0), "k")], [(AggExpr("sum", col(1)), "s")])
    got = got.sort_values("k", na_position="last").reset_index(drop=True)
    assert got["s"].tolist() == [4.0, 6.0]
    assert got["k"][0] == 1 and pd.isna(got["k"][1])


def test_global_agg_and_empty_input():
    b = Batch.from_pydict({"v": [1, 2, 3]},
                          schema=T.Schema.of(T.Field("v", T.INT64)))
    got = _agg_pipeline([b], [], [(AggExpr("sum", col(0)), "s"),
                                  (AggExpr("count", col(0)), "c")])
    assert got["s"].tolist() == [6] and got["c"].tolist() == [3]
    # empty input: global agg still yields one row: sum NULL, count 0
    e = Batch.empty(b.schema)
    got2 = _agg_pipeline([e], [], [(AggExpr("sum", col(0)), "s"),
                                   (AggExpr("count", col(0)), "c")])
    assert len(got2) == 1
    assert pd.isna(got2["s"][0]) and got2["c"].tolist() == [0]


def test_decimal_sum_avg():
    import decimal as d

    data = {"k": [1, 1, 2], "v": [d.Decimal("1.10"), d.Decimal("2.05"), d.Decimal("-0.50")]}
    b = Batch.from_pydict(
        data,
        schema=T.Schema.of(T.Field("k", T.INT32), T.Field("v", T.decimal(7, 2))),
    )
    got = _agg_pipeline([b], [(col(0), "k")],
                        [(AggExpr("sum", col(1)), "s"), (AggExpr("avg", col(1)), "a")])
    got = _sorted(got, "k")
    assert got["s"].tolist() == [d.Decimal("3.15"), d.Decimal("-0.50")]
    # avg type decimal(11,6)
    assert got["a"].tolist() == [d.Decimal("1.575000"), d.Decimal("-0.500000")]


def test_first_and_first_ignores_null():
    data = {"k": [1, 1, 2], "v": [None, 5, None]}
    b = Batch.from_pydict(
        data, schema=T.Schema.of(T.Field("k", T.INT32), T.Field("v", T.INT64))
    )
    got = _agg_pipeline(
        [b], [(col(0), "k")], [(AggExpr("first_ignores_null", col(1)), "f")]
    )
    got = _sorted(got, "k")
    assert got["f"].tolist()[0] == 5
    assert pd.isna(got["f"][1])


def test_partial_merge_mode():
    """partial -> partial_merge -> final three-stage plan."""
    data = {"k": [1, 2, 1], "v": [1.0, 2.0, 3.0]}
    b = Batch.from_pydict(
        data, schema=T.Schema.of(T.Field("k", T.INT32), T.Field("v", T.FLOAT64))
    )
    scan = MemoryScanExec.single([b])
    p = HashAggExec(scan, [(col(0), "k")], [(AggExpr("avg", col(1)), "a")], PARTIAL)
    mid = MemoryScanExec.single(list(p.execute(0, ExecutionContext())))
    pm = HashAggExec(mid, [(col(0), "k")], [(AggExpr("avg", col(1)), "a")], PARTIAL_MERGE)
    fin_in = MemoryScanExec.single(list(pm.execute(0, ExecutionContext())))
    fin = HashAggExec(fin_in, [(col(0), "k")], [(AggExpr("avg", col(1)), "a")], FINAL)
    got = _sorted(fin.collect().to_pandas(), "k")
    assert got["a"].tolist() == [pytest.approx(2.0), pytest.approx(2.0)]


def test_partial_skipping_still_correct():
    """High-cardinality keys trigger pass-through partials; final agg must
    still produce exact results."""
    from auron_tpu.utils.config import Configuration, conf_scope

    n = 4000
    rng = np.random.default_rng(1)
    # all distinct -> ratio 1.0; spread over a huge range so the dense
    # direct-address path (which makes skipping moot) stays ineligible
    k = rng.permutation(n) * 1_000_003
    v = rng.integers(0, 100, n)
    df = pd.DataFrame({"k": k, "v": v})
    batches = [
        Batch.from_arrow(
            pa.RecordBatch.from_pandas(df.iloc[i : i + 500], preserve_index=False)
        )
        for i in range(0, n, 500)
    ]
    conf = Configuration().set(PARTIAL_AGG_SKIPPING_MIN_ROWS, 1000)
    scan = MemoryScanExec.single(batches)
    partial = HashAggExec(scan, [(col(0), "k")], [(AggExpr("sum", col(1)), "s")], PARTIAL)
    ctx = ExecutionContext(conf=conf)
    partial_out = list(partial.execute(0, ctx))
    assert ctx.metrics.values.get("partial_agg_skipped", 0) == 1
    shuffled = MemoryScanExec.single(partial_out)
    final = HashAggExec(shuffled, [(col(0), "k")], [(AggExpr("sum", col(1)), "s")], FINAL)
    got = _sorted(final.collect().to_pandas(), "k")
    want = _sorted(df.groupby("k").agg(s=("v", "sum")).reset_index(), "k")
    assert got["k"].tolist() == want["k"].tolist()
    assert got["s"].tolist() == want["s"].tolist()


def test_collect_list_and_set():
    data = {"k": [1, 1, 2, 1, 2], "v": [5, 3, 7, 3, None]}
    b = Batch.from_pydict(
        data, schema=T.Schema.of(T.Field("k", T.INT32), T.Field("v", T.INT64))
    )
    got = _agg_pipeline(
        [b], [(col(0), "k")],
        [(AggExpr("collect_list", col(1)), "cl"),
         (AggExpr("collect_set", col(1)), "cs")],
    )
    got = _sorted(got, "k")
    assert sorted(got["cl"][0]) == [3, 3, 5]
    assert list(got["cl"][1]) == [7]
    assert list(got["cs"][0]) == [3, 5]
    assert list(got["cs"][1]) == [7]


def test_collect_list_multi_batch():
    b1 = Batch.from_pydict({"k": [1, 2], "v": [1.0, 2.0]})
    b2 = Batch.from_pydict({"k": [1, 1], "v": [3.0, 4.0]})
    got = _agg_pipeline([b1, b2], [(col(0), "k")],
                        [(AggExpr("collect_list", col(1)), "cl")])
    got = _sorted(got, "k")
    assert sorted(got["cl"][0]) == [1.0, 3.0, 4.0]
    assert list(got["cl"][1]) == [2.0]


def test_host_udaf_fallback():
    from auron_tpu.bridge.udf import register_udaf

    # geometric mean — something the native agg set doesn't provide
    register_udaf(
        "geomean",
        lambda vals: float(np.exp(np.mean(np.log([v for v in vals if v is not None]))))
        if any(v is not None for v in vals) else None,
        T.FLOAT64,
    )
    data = {"k": [1, 1, 2, 1], "v": [2.0, 8.0, 5.0, 4.0]}
    b = Batch.from_pydict(
        data, schema=T.Schema.of(T.Field("k", T.INT32), T.Field("v", T.FLOAT64))
    )
    got = _agg_pipeline(
        [b], [(col(0), "k")],
        [(AggExpr("host_udaf", col(1), udaf="geomean"), "g")],
    )
    got = _sorted(got, "k")
    assert got["g"][0] == pytest.approx((2 * 8 * 4) ** (1 / 3))
    assert got["g"][1] == pytest.approx(5.0)


def test_wide_decimal_sum_no_wrap():
    """sum(decimal(18,0)) over values near int64 range: plain int64
    accumulation would silently wrap; limb accumulation stays exact."""
    import decimal as d

    big = d.Decimal(5 * 10**13)  # 200k rows -> sum 1e19 > int64 max (wraps)
    n = 200_000
    data = {"k": [1] * n + [2] * 3,
            "v": [big] * n + [d.Decimal(5)] * 3}
    b = Batch.from_pydict(
        data, schema=T.Schema.of(T.Field("k", T.INT32), T.Field("v", T.decimal(18, 0)))
    )
    got = _agg_pipeline([b], [(col(0), "k")],
                        [(AggExpr("sum", col(1)), "s"), (AggExpr("avg", col(1)), "a")])
    got = _sorted(got, "k")
    # round 2: sums beyond the decimal64 domain emit EXACTLY through the
    # wide-decimal dictionary representation (previously NULL)
    assert got["s"][0] == d.Decimal(10) ** 19
    assert int(got["a"][0]) == 5 * 10**13
    # group 2 small values flow through exactly
    assert got["s"][1] == d.Decimal(15)
    assert int(got["a"][1]) == 5


def test_wide_sum_within_domain_is_exact():
    import decimal as d

    vals = [d.Decimal(10**16 + i) for i in range(50)]  # sum ~5e17, fits
    data = {"k": [1] * 50, "v": vals}
    b = Batch.from_pydict(
        data, schema=T.Schema.of(T.Field("k", T.INT32), T.Field("v", T.decimal(18, 0)))
    )
    got = _agg_pipeline([b], [(col(0), "k")], [(AggExpr("sum", col(1)), "s")])
    assert got["s"][0] == sum(vals)


def test_min_max_over_strings_lexicographic():
    # ADVICE r1 (high): dict codes are first-occurrence ordered; min/max
    # must reduce in lexicographic rank space
    data = {
        "k": [1, 1, 1, 2, 2],
        "s": ["zebra", "apple", "mango", "pear", None],
    }
    b = Batch.from_pydict(
        data, schema=T.Schema.of(T.Field("k", T.INT64), T.Field("s", T.STRING))
    )
    got = _agg_pipeline(
        [b],
        [(col(0), "k")],
        [(AggExpr("min", col(1)), "mn"), (AggExpr("max", col(1)), "mx")],
    )
    got = _sorted(got, ["k"])
    assert list(got["mn"]) == ["apple", "pear"]
    assert list(got["mx"]) == ["zebra", "pear"]


def test_udaf_accumulator_across_shuffle_bounded_state():
    """VERDICT r2 item 5: incremental accumulator UDAF with partial/merge/
    final states across a real exchange, matching a pandas oracle, with the
    serialized per-group state bounded regardless of input size."""
    import pickle

    import pandas as pd

    from auron_tpu.bridge.udf import register_udaf_accumulator
    from auron_tpu.parallel.mesh import make_mesh
    from auron_tpu.parallel.mesh_driver import MeshQueryDriver
    from auron_tpu.plan import builders as B

    # Welford-style mean accumulator: state = (count, total) — constant size
    register_udaf_accumulator(
        "acc_mean",
        init=lambda: (0, 0.0),
        update=lambda st, v: (st[0] + 1, st[1] + v) if v is not None else st,
        merge=lambda a, b: (a[0] + b[0], a[1] + b[1]),
        finish=lambda st: (st[1] / st[0]) if st[0] else None,
        out_dtype=T.FLOAT64,
    )

    rng = np.random.default_rng(11)
    n = 40_000
    df = pd.DataFrame(
        {
            "k": rng.integers(0, 37, n).astype(np.int64),
            "v": rng.normal(10.0, 3.0, n),
        }
    )
    n_dev = 8
    schema = T.Schema.of(T.Field("k", T.INT64), T.Field("v", T.FLOAT64))
    per = (n + n_dev - 1) // n_dev
    parts = [
        [Batch.from_arrow(pa.RecordBatch.from_pandas(
            df.iloc[p * per : (p + 1) * per], preserve_index=False))]
        for p in range(n_dev)
    ]
    scan = B.memory_scan(schema, "udaf_fact")
    partial = B.hash_agg(
        scan, [(col(0), "k")],
        [("host_udaf", col(1), "m", "acc_mean"), ("count_star", None, "c")],
        "partial",
    )
    ex = B.mesh_exchange(partial, B.hash_partitioning([col(0)], n_dev), "udaf_ex")
    final = B.hash_agg(
        ex, [(col(0), "k")],
        [("host_udaf", col(1), "m", "acc_mean"), ("count_star", None, "c")],
        "final",
    )
    driver = MeshQueryDriver(make_mesh(n_dev))
    got = driver.collect(final, {"udaf_fact": parts}).sort_values("k").reset_index(drop=True)

    want = (
        df.groupby("k").agg(m=("v", "mean"), c=("v", "size")).reset_index()
        .sort_values("k").reset_index(drop=True)
    )
    assert got["k"].tolist() == want["k"].tolist()
    assert got["c"].tolist() == want["c"].tolist()
    for g, w in zip(got["m"], want["m"]):
        assert g == pytest.approx(w, rel=1e-9)

    # memory bound: inspect the ENGINE's actual partial-stage state column —
    # every serialized per-group state must be O(1) bytes even though each
    # group folded ~1000 inputs (a collect-based fallback would hold the
    # raw values and grow with the input count)
    scan2 = B.memory_scan(schema, "udaf_fact")
    partial2 = B.hash_agg(
        scan2, [(col(0), "k")],
        [("host_udaf", col(1), "m", "acc_mean")], "partial",
    )
    from auron_tpu.bridge import api as _api

    _api.put_resource("udaf_fact", parts)
    try:
        h = _api.call_native(B.task(partial2, partition_id=0).SerializeToString())
        state_sizes = []
        while (rb := _api.next_batch(h)) is not None:
            for blob in rb.column(1).to_pylist():
                if blob:
                    state_sizes.append(len(blob))
        _api.finalize_native(h)
    finally:
        _api.remove_resource("udaf_fact")
    assert state_sizes, "partial stage produced no states"
    assert max(state_sizes) < 100, max(state_sizes)


def test_udaf_accumulator_state_spills(tmp_path):
    """Accumulator state batches ride the normal spill machinery."""
    from auron_tpu.bridge.udf import register_udaf_accumulator
    from auron_tpu.memory.memmgr import MemManager

    register_udaf_accumulator(
        "acc_sum",
        init=lambda: 0.0,
        update=lambda st, v: st + (v or 0.0),
        merge=lambda a, b: a + b,
        finish=lambda st: st,
        out_dtype=T.FLOAT64,
    )
    rng = np.random.default_rng(5)
    n = 20_000
    ks = rng.integers(0, 50, n).astype(np.int64)
    vs = rng.normal(size=n)
    # many small batches so states accumulate under a tiny budget
    chunk = 512
    batches = [
        Batch.from_pydict({"k": ks[i : i + chunk].tolist(),
                           "v": vs[i : i + chunk].tolist()})
        for i in range(0, n, chunk)
    ]
    MemManager.init(budget_bytes=8192)
    try:
        partial = HashAggExec(
            MemoryScanExec.single(batches),
            [(col(0), "k")],
            [(AggExpr("host_udaf", col(1), udaf="acc_sum"), "s")],
            "partial",
        )
        final = HashAggExec(
            partial, [(col(0), "k")],
            [(AggExpr("host_udaf", col(1), udaf="acc_sum"), "s")],
            "final",
        )
        out = final.collect().to_pandas().sort_values("k").reset_index(drop=True)
        import pandas as pd

        want = (
            pd.DataFrame({"k": ks, "v": vs}).groupby("k")["v"].sum()
            .reset_index().sort_values("k").reset_index(drop=True)
        )
        assert out["k"].tolist() == want["k"].tolist()
        for g, w in zip(out["s"], want["v"]):
            assert g == pytest.approx(w, rel=1e-9)
    finally:
        MemManager.init()


@pytest.fixture(params=["auto", "off"], ids=["hostfold", "devicefold"])
def dense_fold_substrate(request):
    """Run a dense-agg test under BOTH fold substrates. On the CPU CI
    backend AGG_DENSE_HOST_SCATTER=auto resolves to the host numpy
    bincount fold, which would leave the accelerator device-scatter path
    (_dense_update_jit dispatch + its deferred-flag protocol) with zero
    coverage — the 'off' pin keeps that path exercised here."""
    from auron_tpu.utils.config import AGG_DENSE_HOST_SCATTER, active_conf

    conf = active_conf()
    saved = conf.get(AGG_DENSE_HOST_SCATTER)
    conf.set(AGG_DENSE_HOST_SCATTER, request.param)
    try:
        yield request.param
    finally:
        conf.set(AGG_DENSE_HOST_SCATTER, saved)


def test_dense_agg_deferred_restart_no_double_fold(dense_fold_substrate):
    """Dense-table folds are deferred (flag read one batch late). A batch
    whose keys outgrow the anchored range must fold EXACTLY once after the
    drain+re-anchor — both mid-stream and when the growth lands on the
    last batch (resolved at end of stream). Regression: the q88-class last
    band was double-counted."""
    # min/max ride along so BOTH fold substrates (np.minimum/maximum.at
    # on the host, segment_min/max on device) face the restart protocol
    aggs = [
        (AggExpr("count_star", None), "c"), (AggExpr("sum", col(1)), "s"),
        (AggExpr("min", col(1)), "mn"), (AggExpr("max", col(1)), "mx"),
    ]

    def run(key_batches):
        batches = [
            Batch.from_pydict({"k": ks, "v": [float(k % 7) for k in ks]})
            for ks in key_batches
        ]
        agg = HashAggExec(
            MemoryScanExec.single(batches), [(col(0), "k")], aggs, "partial",
        )
        final = HashAggExec(agg, [(col(0), "k")], aggs, "final")
        return (final.collect().to_pandas()
                .sort_values("k").reset_index(drop=True))

    def want(key_batches):
        ks = [k for band in key_batches for k in band]
        return (
            pd.DataFrame({"k": ks, "v": [float(k % 7) for k in ks]})
            .groupby("k")
            .agg(c=("v", "size"), s=("v", "sum"), mn=("v", "min"),
                 mx=("v", "max"))
            .reset_index().sort_values("k").reset_index(drop=True)
        )

    for key_batches in (
        # growth on the LAST batch: its restart resolves at end of stream
        [[0, 0, 1], [1, 1], [100000, 100000]],
        # growth mid-stream: restart then more in-range batches
        [[5, 5], [900000], [5, 6], [900001]],
    ):
        out, exp = run(key_batches), want(key_batches)
        assert out["k"].tolist() == exp["k"].tolist()
        assert out["c"].tolist() == exp["c"].tolist()
        assert out["s"].tolist() == exp["s"].tolist()
        assert out["mn"].tolist() == exp["mn"].tolist()
        assert out["mx"].tolist() == exp["mx"].tolist()


# the dense arm behind its compaction boundary (exec/selectivity.py), a
# stream of 4,096-row batches by their live rows on XLA:CPU's quarter rule:
# (the takes' modes, the widths the folds ran at, repairs, compacted takes)
_DENSE_BOUNDARY_CASES = {
    # the head of a date-ordered fact table behind a year's filter
    "full_tenth_empty_empty": dict(
        live=(4096, 400, 0, 0),
        want=(["seed", "compact", "empty", "empty"], [4096, 512, 0, 0], 0, 1)),
    # taken at 128 rows on the word of two empty batches: re-taken whole
    "growing": dict(
        live=(0, 0, 4096),
        want=(["empty", "empty", "repair"], [0, 0, 4096], 1, 2)),
    # a stream with nothing to drop loses nothing: folded as it came
    "all_live": dict(
        live=(4096, 4096, 4096),
        want=(["seed", "dense", "dense"], [4096, 4096, 4096], 0, 0)),
    # shorter than the window: every fold comes out of the boundary's drain
    "three_sparse": dict(
        live=(400, 300, 500),
        want=(["seed", "compact", "compact"], [512, 1024, 1024], 0, 3)),
}


def _sparse_int_frames(lives, cap=4096, key_base=(0,), seed=35):
    """Batches of ``cap`` rows of which ``lives[i]`` pass ``live IS NOT
    NULL``; (frames, the live (k, v) rows)."""
    rng = np.random.default_rng(seed)
    frames, rows = [], []
    for i, n_live in enumerate(lives):
        ks = key_base[i % len(key_base)] + rng.integers(0, 37, cap)
        alive = np.zeros(cap, dtype=bool)
        alive[rng.choice(cap, n_live, replace=False)] = True
        vs = rng.integers(1, 10_000, cap)
        frames.append(Batch.from_pydict({
            "k": [int(k) for k in ks],
            "v": [int(v) for v in vs],
            "live": [1 if a else None for a in alive],
        }))
        rows += [(int(k), int(v)) for k, v, a in zip(ks, vs, alive) if a]
    return frames, rows


def _run_dense_partial(frames):
    """scan -> filter -> PARTIAL -> FINAL over integer keys with the rings
    on; (groups, the partial's metrics, its dense fold events in order)."""
    import time

    from auron_tpu import obs
    from auron_tpu.exec.basic import FilterExec
    from auron_tpu.exprs.ir import IsNotNull
    from auron_tpu.obs import core

    aggs = [(AggExpr("count_star", None), "c"), (AggExpr("sum", col(1)), "s"),
            (AggExpr("min", col(1)), "mn")]
    scan = MemoryScanExec.single(
        [Batch(b.schema, b.device, b.dicts) for b in frames])
    flt = FilterExec(scan, [IsNotNull(col(2))])
    p = HashAggExec(flt, [(col(0), "k")], aggs, PARTIAL)
    f = HashAggExec(p, [(col(0), "k")], aggs, FINAL)
    assert p._dense_eligible() and p._fold_columns() == ((0, 1), 6)
    saved = obs.mode()
    obs.set_mode("recorder")
    try:
        ctx = ExecutionContext()
        ctx.metrics.name = f.name
        t0 = time.perf_counter()
        out = f.collect(ctx=ctx).to_pandas().sort_values("k").reset_index(drop=True)
        t1 = time.perf_counter()
        lo, hi = int(t0 * 1e9), int(t1 * 1e9)
        events = sorted((ev for _ring, evs in core.snapshot_events() for ev in evs
                         if lo <= ev[0] < hi), key=lambda ev: ev[0])
        ws = obs.window_summary(t0, t1)
    finally:
        obs.set_mode(saved)
    folds = [ev[7] for ev in events
             if ev[2] == "fold" and ev[3] == "agg.partial"]
    assert all(f["path"] == "dense" for f in folds)
    return out, ctx.metrics, folds, ws


def _want_groups(rows):
    return (
        pd.DataFrame(rows, columns=["k", "v"])
        .groupby("k").agg(c=("v", "size"), s=("v", "sum"), mn=("v", "min"))
        .reset_index().sort_values("k").reset_index(drop=True)
    )


@pytest.mark.parametrize("case", sorted(_DENSE_BOUNDARY_CASES))
def test_dense_arm_folds_at_the_width_of_its_live_rows(case, dense_fold_substrate):
    """The dense arm takes its batches through a CompactionBoundary: a batch
    folds at the bucket of its live rows (the columns the fold reads, and no
    other, compacted), an empty one leaves its event and folds nothing, a
    truncating prediction is re-taken from the held batch, an all-live
    stream folds every batch as it came, and a stream shorter than the
    window is folded whole by the boundary's drain, which runs before
    ``finish_pending``. The groups are pandas' in every case, on both fold
    substrates."""
    from auron_tpu.utils.config import TRANSFER_WINDOW_DEPTH, active_conf

    spec = _DENSE_BOUNDARY_CASES[case]
    frames, rows = _sparse_int_frames(spec["live"])
    conf = active_conf()
    saved_depth = conf.get(TRANSFER_WINDOW_DEPTH)
    conf.set(TRANSFER_WINDOW_DEPTH, 4)
    try:
        got, metrics, folds, ws = _run_dense_partial(frames)
    finally:
        conf.set(TRANSFER_WINDOW_DEPTH, saved_depth)
    want = _want_groups(rows)
    for c in ("k", "c", "s", "mn"):
        assert got[c].tolist() == want[c].tolist(), c
    modes, widths, repairs, compacted = spec["want"]
    assert [f["take"] for f in folds] == modes
    assert [f["rows"] for f in folds] == widths
    assert all(f["in_rows"] == 4096 for f in folds)
    # the boundary has read every count by the time a batch is folded
    assert [f["live"] for f in folds] == list(spec["live"])
    assert metrics.total("sel_mispredicts") == repairs
    assert metrics.total("agg_compacted_batches") == compacted
    # the FINAL aggregate above is dense too: one state, seeded, all live
    want_modes = {m: modes.count(m) for m in set(modes)}
    want_modes["seed"] = want_modes.get("seed", 0) + 1
    assert ws["agg_dense_folds"] == want_modes
    assert ws["agg_folds"]["dense"]["n"] == len(folds) + 1


def test_dense_fold_events_say_how_the_sums_were_scattered(dense_fold_substrate):
    """The ``fold`` event of a device fold of the dense table carries the
    32-bit and the 64-bit planes it scattered at its width (count(*) one
    int32 plane, the int64 sum ``limb_plan``'s limbs, the int64 minimum one
    wide plane, three flag planes), ``window_summary`` sums rows x planes;
    the host substrate scatters nothing on the device and says so."""
    from auron_tpu.ops.segments import limb_plan

    frames, _rows = _sparse_int_frames((4096, 400))
    _got, _metrics, folds, ws = _run_dense_partial(frames)
    assert [f["rows"] for f in folds] == [4096, 512]
    total = ws["agg_dense_scatter_rows"]
    if dense_fold_substrate == "auto":
        assert all(f["narrow"] is None and f["wide"] is None for f in folds)
        assert total == {"narrow": 0, "wide": 0}
        return
    assert [limb_plan(64, r).limbs for r in (4096, 512)] == [4, 3]
    assert [(f["narrow"], f["wide"]) for f in folds] == [(3 + 1 + 4, 1), (3 + 1 + 3, 1)]
    # the FINAL aggregate above folds once, merging: both counts by limbs
    final_rows = ws["agg_folds"]["dense"]["rows"] - 4096 - 512
    assert total["wide"] == 4096 + 512 + final_rows
    assert total["narrow"] == 4096 * 8 + 512 * 7 + final_rows * (
        3 + 2 * limb_plan(64, final_rows).limbs)


def test_dense_arm_restart_in_a_compacted_stream_folds_no_batch_twice(
        dense_fold_substrate):
    """Sparse batches whose keys jump between far-apart ranges: the restart
    protocol sees the narrower batches the boundary hands it, folds the held
    ones again after the re-anchor (events with no take), and the totals
    stay pandas'."""
    frames, rows = _sparse_int_frames(
        (300,) * 9, key_base=(0, 500_000, 0, 900_000))
    got, metrics, folds, _ws = _run_dense_partial(frames)
    want = _want_groups(rows)
    for c in ("k", "c", "s", "mn"):
        assert got[c].tolist() == want[c].tolist(), c
    taken = [f for f in folds if f["take"] is not None]
    assert len(taken) == 9 and all(f["rows"] <= 1024 for f in taken)
    assert len(folds) > len(taken), "no restart: the test's shape regressed"
    assert metrics.total("agg_compacted_batches") == 9


def test_compact_batch_gathers_the_named_columns_alone():
    """The dense arm's take: live rows of the columns the fold reads in a
    prefix of the bucket, every other column all NULL (filled, not
    gathered), the schema and the dictionaries as they were."""
    from auron_tpu.columnar.batch import compact_batch

    frames, rows = _sparse_int_frames((100,), cap=1024)
    from auron_tpu.exec.basic import FilterExec
    from auron_tpu.exprs.ir import IsNotNull

    (b,) = list(FilterExec(MemoryScanExec.single(frames),
                           [IsNotNull(col(2))]).execute(0, ExecutionContext()))
    assert b.capacity == 1024
    out = compact_batch(b, 128, cols=(0, 1))
    assert out.capacity == 128 and out.schema == b.schema
    got = out.to_pandas()
    assert list(zip(got.k, got.v)) == rows
    assert got.live.isna().all()
    whole = compact_batch(b, 128).to_pandas()
    assert whole.live.notna().all() and whole.k.tolist() == got.k.tolist()
    assert compact_batch(b, 1024, cols=(0,)) is b


def test_dense_arm_rule_at_query_65s_shapes(monkeypatch):
    """On the TPU the arm's rule counts gathered elements, and the fold's
    price is what the program really scatters at the batch's capacity: a
    DECIMAL(7,2) sum by two int64 keys folds a dead row of a 4,194,304-row
    batch for 5 of them (three 9-bit int32 limbs and the two flag scatters,
    an element each: 8.4-8.8 ns a dead row a plane on the v5e; one int64
    scatter-add would be nine) and takes 9 planes, so the batch compacts at
    an eighth of its capacity or less: query 65's tenth-live batch is taken
    at 524,288 rows, where the sum is two 12-bit limbs."""
    from auron_tpu.columnar import batch as batch_mod
    from auron_tpu.exec import agg_exec as agg_mod
    from auron_tpu.ops import segments as seg_mod

    frames, _ = _sparse_int_frames((8,), cap=128)
    (b,) = frames
    priced = Batch(T.Schema.of(
        T.Field("k", T.INT64), T.Field("v", T.decimal(7, 2)),
        T.Field("live", T.INT64)), b.device, b.dicts)
    scan = MemoryScanExec.single([priced])
    p = HashAggExec(scan, [(col(0), "a"), (col(2), "b")],
                    [(AggExpr("sum", col(1)), "s")], PARTIAL)
    cols, planes = p._fold_columns()
    assert (cols, planes) == ((0, 1, 2), 9)
    state = agg_mod._DenseAggState(p, ExecutionContext())
    assert state.fold_scatters(4194304) == (5, 0)
    fold = state.fold_planes(4194304)
    assert fold == 5 * seg_mod.SCATTER_NARROW == 5.0
    # taken at 524,288 rows the same sum is two 12-bit limbs
    assert state.fold_scatters(524288) == (4, 0)
    # the same sum of a physical int64 column: seven limbs, none wide; the
    # int64 minimum beside it keeps its 64-bit scatter
    q = HashAggExec(MemoryScanExec.single(frames), [(col(0), "a"), (col(2), "b")],
                    [(AggExpr("sum", col(1)), "s"), (AggExpr("min", col(1)), "m")],
                    PARTIAL)
    wider = agg_mod._DenseAggState(q, ExecutionContext())
    assert wider.fold_scatters(4194304) == (1 + 1 + 7 + 1, 1)
    assert wider.fold_planes(4194304) == 10 * seg_mod.SCATTER_NARROW + seg_mod.SCATTER_WIDE
    monkeypatch.setattr(batch_mod, "_gather_bound", lambda: True)
    rule = lambda n: batch_mod.compaction_bucket(
        n, 4194304, dense_planes=fold, taken_planes=planes + fold)
    assert rule(420_000) == 524288
    assert rule(600_000) is None
    assert rule(1) == batch_mod.MIN_CAPACITY


def _priced_frames(cents, keys, alive, valid, dtype):
    """One batch a list of (k, v, live) rows: ``v`` the int64 plane of
    ``cents`` under the declared ``dtype`` (Arrow would refuse a DECIMAL
    outside its precision: the plane is labelled, not converted), NULL where
    ``valid`` is False; ``live`` NULL on the rows a filter drops."""
    out = []
    for c, k, a, ok in zip(cents, keys, alive, valid):
        b = Batch.from_pydict({
            "k": [int(x) for x in k],
            "v": [int(x) if o else None for x, o in zip(c, ok)],
            "live": [1 if x else None for x in a],
        })
        out.append(Batch(T.Schema.of(
            T.Field("k", T.INT64), T.Field("v", dtype), T.Field("live", T.INT64)),
            b.device, b.dicts))
    return out


_LIMB_FOLD_CASES = {
    # dtype, the values a row draws from
    "dec7_at_its_precision": (T.decimal(7, 2), [10 ** 7 - 1, -(10 ** 7 - 1), 1, -1, 0]),
    "dec7_on_limb_edges": (T.decimal(7, 2), [
        (1 << 12) - 1, 1 << 12, -(1 << 12), (1 << 24) - 1, -(1 << 24), 1 << 23]),
    "dec7_outside_its_precision": (T.decimal(7, 2), [
        10 ** 7, -(10 ** 9), 1 << 40, -(1 << 55), 5]),
    "int64_extremes": (T.INT64, [
        np.iinfo(np.int64).max, np.iinfo(np.int64).min, -1, 1, 1 << 62]),
}


@pytest.mark.parametrize("one_slot", [False, True], ids=["spread", "one_slot"])
@pytest.mark.parametrize("case", sorted(_LIMB_FOLD_CASES))
def test_dense_sums_are_exact_at_every_width(case, one_slot, dense_fold_substrate):
    """sum, avg's sum and count, count and count(*) through the dense table,
    raw and merged (PARTIAL then PARTIAL_MERGE), with NULLs and dead rows:
    the ``#sum`` and ``#count`` planes are numpy's wrapping int64 sums on
    both substrates (the device fold takes them by int32 limbs, the host
    fold by ``_bincount_i64``), also where a DECIMAL plane breaks its
    declared precision (the limbs' guard) and where int64 sums wrap."""
    from auron_tpu.exec.basic import FilterExec
    from auron_tpu.exprs.ir import IsNotNull

    dtype, pool = _LIMB_FOLD_CASES[case]
    rng = np.random.default_rng(len(case) + one_slot)
    n_batches, cap = 3, 512
    cents = [rng.choice(np.asarray(pool, np.int64), cap) for _ in range(n_batches)]
    keys = [np.zeros(cap, np.int64) + 4 if one_slot else rng.integers(0, 9, cap)
            for _ in range(n_batches)]
    alive = [rng.random(cap) < 0.8 for _ in range(n_batches)]
    valid = [rng.random(cap) < 0.9 for _ in range(n_batches)]
    aggs = [(AggExpr("sum", col(1)), "s"), (AggExpr("avg", col(1)), "a"),
            (AggExpr("count", col(1)), "c"), (AggExpr("count_star", None), "n")]
    flt = FilterExec(MemoryScanExec.single(
        _priced_frames(cents, keys, alive, valid, dtype)), [IsNotNull(col(2))])
    p = HashAggExec(flt, [(col(0), "k")], aggs, PARTIAL)
    m = HashAggExec(p, [(col(0), "k")], aggs, PARTIAL_MERGE)
    assert p._dense_eligible() and m._dense_eligible()
    got = {}
    for b in m.execute(0, ExecutionContext()):
        sel = np.asarray(b.device.sel)
        planes = [np.asarray(v)[sel] for v in b.device.values]
        for row in zip(*planes):
            got[int(row[0])] = tuple(int(x) for x in row[1:])
    want = {}
    with np.errstate(over="ignore"):
        for c, k, a, ok in zip(cents, keys, alive, valid):
            for ci, ki, ai, oki in zip(c, k, a, ok):
                if not ai:
                    continue
                s, cnt, n = want.get(int(ki), (np.int64(0), 0, 0))
                want[int(ki)] = (s + ci if oki else s, cnt + bool(oki), n + 1)
    assert sorted(got) == sorted(want)
    for k, (s, cnt, n) in want.items():
        # s#sum, a#sum, a#count, c#count, n#count
        assert got[k] == (int(s), int(s), cnt, cnt, n), k


def test_dense_agg_sentinel_key_extremes(dense_fold_substrate):
    """A key near the int64 extremes must trigger the dense table's
    re-anchor (then permanent fallback), never fold into a clamped slot:
    the fused guard compares against host-computed bounds instead of
    doing device int64 arithmetic that wraps."""
    big = (1 << 63) - 1
    agg = HashAggExec(
        MemoryScanExec.single([
            Batch.from_pydict({"k": [0, 1, 2, 2]}),
            Batch.from_pydict({"k": [big, 0]}),
        ]),
        [(col(0), "k")],
        [(AggExpr("count_star", None), "c")],
        "partial",
    )
    final = HashAggExec(
        agg, [(col(0), "k")], [(AggExpr("count_star", None), "c")], "final")
    out = (final.collect().to_pandas()
           .sort_values("k").reset_index(drop=True))
    assert out["k"].tolist() == [0, 1, 2, big]
    assert out["c"].tolist() == [2, 1, 2, 1]


def test_probe_scatter_k_deep_interleaved_misses():
    """Probe/scatter mirror of the dense k-deep test below: once a compact
    has produced an fp-sorted state, hit batches scatter straight into the
    state while miss batches resolve k batches LATE through the async
    window and re-enter the generic path with their selection narrowed to
    the miss rows. Interleaving known-key and new-band batches at several
    window depths, every row must still count exactly once vs pandas."""
    import pandas as pd

    from auron_tpu.utils.config import (
        AGG_INCREMENTAL_FINGERPRINT,
        AGG_INCREMENTAL_MERGEPATH,
        AGG_INCREMENTAL_PROBE,
        BATCH_SIZE,
        PARTIAL_AGG_SKIPPING_ENABLE,
        TRANSFER_WINDOW_DEPTH,
        Configuration,
        conf_scope,
    )

    rng = np.random.default_rng(4)
    key_batches = []
    # phase 1: enough distinct keys to cross the staging threshold (the
    # 1<<15 merge floor) so compact() builds the probe-able state
    pool = np.arange(40_000) * 1_000_003 + 7  # dense-ineligible spread
    for i in range(17):
        key_batches.append(pool[i * 2048:(i + 1) * 2048].tolist())
    # phase 2: interleave state hits with new-band misses so multiple
    # in-flight deferred folds keep resolving against a moving state
    for i in range(12):
        if i % 3 == 2:
            band = 900_000_000_000 + i * 10_000  # brand-new keys: misses
            key_batches.append((band + rng.integers(0, 200, 512)).tolist())
        else:
            key_batches.append(rng.choice(pool[:34_000], 512).tolist())
    all_k = [k for ks in key_batches for k in ks]
    want = (
        pd.DataFrame({"k": all_k, "v": [1.0] * len(all_k)})
        .groupby("k").agg(c=("v", "size"), s=("v", "sum")).reset_index()
        .sort_values("k").reset_index(drop=True)
    )

    aggs = [(AggExpr("count_star", None), "c"), (AggExpr("sum", col(1)), "s")]
    probed_depths = []
    for depth in (1, 3, 6):
        conf = (Configuration().set(TRANSFER_WINDOW_DEPTH, depth)
                .set(BATCH_SIZE, 2048)
                # incremental mechanisms pinned on (auto = accelerator-only)
                .set(AGG_INCREMENTAL_FINGERPRINT, "on")
                .set(AGG_INCREMENTAL_PROBE, "on")
                .set(AGG_INCREMENTAL_MERGEPATH, "on")
                # phase 1 is all-distinct by construction — the pass-through
                # heuristic would drain the state this test probes into
                .set(PARTIAL_AGG_SKIPPING_ENABLE, False))
        with conf_scope(conf):
            batches = [
                Batch.from_pydict({"k": ks, "v": [1.0] * len(ks)})
                for ks in key_batches
            ]
            agg = HashAggExec(
                MemoryScanExec.single(batches), [(col(0), "k")], aggs, "partial")
            ctx = ExecutionContext(conf=conf)
            mid = list(agg.execute(0, ctx))
            final = HashAggExec(
                MemoryScanExec.single(mid), [(col(0), "k")], aggs, "final")
            out = pd.concat(
                b.to_pandas() for b in final.execute(0, ExecutionContext(conf=conf))
            ).sort_values("k").reset_index(drop=True)
        assert out["k"].tolist() == want["k"].tolist(), f"depth={depth}"
        assert out["c"].tolist() == want["c"].tolist(), f"depth={depth}"
        assert out["s"].tolist() == [float(x) for x in want["s"]], f"depth={depth}"
        probed_depths.append(ctx.metrics.values.get("probe_hit_rows", 0))
    # the probe actually engaged (phase-2 hit batches scattered into state)
    assert all(p > 0 for p in probed_depths), probed_depths


def test_probe_scatter_all_agg_kinds_bit_identical():
    """Every probe-foldable aggregate kind through an ACTUALLY-probing
    stream (state built, then repeating-key batches scatter into it):
    sum/count/count_star/avg/min/max/first_ignores_null must come out
    bit-identical to the legacy path. Dyadic values keep float sums exact,
    so the scatter's summation order can't legally differ."""
    import pandas as pd

    from auron_tpu.utils.config import (
        AGG_INCREMENTAL_ENABLE,
        AGG_INCREMENTAL_FINGERPRINT,
        AGG_INCREMENTAL_MERGEPATH,
        AGG_INCREMENTAL_PROBE,
        BATCH_SIZE,
        PARTIAL_AGG_SKIPPING_ENABLE,
        Configuration,
        conf_scope,
    )

    rng = np.random.default_rng(8)
    pool = np.arange(36_000) * 1_000_003 + 13
    key_batches = [pool[i * 2048:(i + 1) * 2048].tolist() for i in range(17)]
    for i in range(8):
        key_batches.append(rng.choice(pool[:30_000], 512).tolist())
    val_batches = [
        (rng.integers(-(1 << 20), 1 << 20, len(ks)) / 1024.0).tolist()
        for ks in key_batches
    ]
    aggs = [
        (AggExpr("sum", col(1)), "s"), (AggExpr("count", col(1)), "c"),
        (AggExpr("count_star", None), "cs"), (AggExpr("avg", col(1)), "a"),
        (AggExpr("min", col(1)), "mn"), (AggExpr("max", col(1)), "mx"),
        (AggExpr("first_ignores_null", col(1)), "f"),
    ]

    def run(enable):
        mode = "on" if enable else "off"
        conf = (Configuration().set(BATCH_SIZE, 2048)
                .set(AGG_INCREMENTAL_ENABLE, enable)
                .set(AGG_INCREMENTAL_FINGERPRINT, mode)
                .set(AGG_INCREMENTAL_PROBE, mode)
                .set(AGG_INCREMENTAL_MERGEPATH, mode)
                .set(PARTIAL_AGG_SKIPPING_ENABLE, False))
        with conf_scope(conf):
            batches = [
                Batch.from_pydict({"k": ks, "v": vs})
                for ks, vs in zip(key_batches, val_batches)
            ]
            agg = HashAggExec(
                MemoryScanExec.single(batches), [(col(0), "k")], aggs, "partial")
            ctx = ExecutionContext(conf=conf)
            mid = list(agg.execute(0, ctx))
            final = HashAggExec(
                MemoryScanExec.single(mid), [(col(0), "k")], aggs, "final")
            out = pd.concat(
                b.to_pandas() for b in final.execute(0, ExecutionContext(conf=conf))
            ).sort_values("k").reset_index(drop=True)
        return out, ctx.metrics.values.get("probe_hit_rows", 0)

    inc, hits = run(True)
    leg, _ = run(False)
    assert hits > 0, "stream never probed — test shape regressed"
    assert len(inc) == len(leg)
    for c in inc.columns:
        for a, b in zip(inc[c], leg[c]):
            assert (pd.isna(a) and pd.isna(b)) or a == b, (c, a, b)


def test_dense_agg_k_deep_window_interleaved_restarts(dense_fold_substrate):
    """The deferred-fold window is now k batches deep (async flag
    harvests, runtime/transfer.py): interleaved out-of-range batches mean
    MULTIPLE in-flight folds can fail and each must re-fold exactly once
    after the drain+re-anchor — totals stay equal to pandas at every
    window depth."""
    import pandas as pd

    from auron_tpu.utils.config import TRANSFER_WINDOW_DEPTH, active_conf

    rng = __import__("numpy").random.default_rng(3)
    key_batches = []
    # alternate between three far-apart ranges so deferred folds keep
    # landing out-of-range mid-window
    for i in range(12):
        base = [0, 500_000, 2_000_000_000][i % 3]
        key_batches.append((base + rng.integers(0, 50, 40)).tolist())
    all_k = [k for ks in key_batches for k in ks]
    want = (
        pd.DataFrame({"k": all_k, "v": [1.0] * len(all_k)})
        .groupby("k").agg(c=("v", "size"), s=("v", "sum")).reset_index()
        .sort_values("k").reset_index(drop=True)
    )

    conf = active_conf()
    saved = conf.get(TRANSFER_WINDOW_DEPTH)
    try:
        for depth in (1, 3, 6):
            conf.set(TRANSFER_WINDOW_DEPTH, depth)
            batches = [
                Batch.from_pydict({"k": ks, "v": [1.0] * len(ks)})
                for ks in key_batches
            ]
            agg = HashAggExec(
                MemoryScanExec.single(batches),
                [(col(0), "k")],
                [(AggExpr("count_star", None), "c"),
                 (AggExpr("sum", col(1)), "s")],
                "partial",
            )
            final = HashAggExec(
                agg, [(col(0), "k")],
                [(AggExpr("count_star", None), "c"),
                 (AggExpr("sum", col(1)), "s")],
                "final",
            )
            out = (final.collect().to_pandas()
                   .sort_values("k").reset_index(drop=True))
            assert out["k"].tolist() == want["k"].tolist(), f"depth={depth}"
            assert out["c"].tolist() == want["c"].tolist(), f"depth={depth}"
            assert out["s"].tolist() == [float(x) for x in want["s"]], \
                f"depth={depth}"
    finally:
        conf.set(TRANSFER_WINDOW_DEPTH, saved)


def test_probe_scatter_spill_park_preserves_first_stream_order(monkeypatch):
    """A spill can park the state mid-window (probe goes un-ready while
    deferred miss batches are still in flight). The NEXT batch then stages
    generically right away — so the probe must drain its window first, or
    a key whose stream-FIRST occurrence sits in a pending miss batch would
    stage after a later batch's rows and `first` would pick the wrong
    value. Simulated by clearing the state's _fp_order right after the
    miss batch's fold (what a real cross-thread spill does to the probe's
    view), then feeding the same keys again with different values."""
    import pandas as pd

    from auron_tpu.exec import agg_exec as agg_mod
    from auron_tpu.utils.config import (
        AGG_INCREMENTAL_FINGERPRINT,
        AGG_INCREMENTAL_MERGEPATH,
        AGG_INCREMENTAL_PROBE,
        BATCH_SIZE,
        PARTIAL_AGG_SKIPPING_ENABLE,
        TRANSFER_WINDOW_DEPTH,
        Configuration,
        conf_scope,
    )

    pool = np.arange(40_000) * 1_000_003 + 7  # dense-ineligible spread
    frames = []
    # phase 1: cross the staging threshold so compact() builds the state
    for i in range(17):
        frames.append((pool[i * 2048:(i + 1) * 2048], 0.0))
    frames.append((pool[:512], 0.0))            # 18: hits — probe engaged
    band = 900_000_000_000 + np.arange(512)
    frames.append((band, 1.0))                  # 19: miss batch, defers
    frames.append((band, 2.0))                  # 20: post-park, same keys
    PARK_AFTER = 19

    calls = {"n": 0, "folded": {}}
    orig_fold = agg_mod._ProbeScatter.fold

    def fold_wrap(self, b):
        res = orig_fold(self, b)
        calls["n"] += 1
        calls["folded"][calls["n"]] = res[0]
        if calls["n"] == PARK_AFTER:
            with self.table._lock:
                st = self.table.state
                assert st is not None and getattr(st, "_fp_order", False), \
                    "test shape regressed: state not probe-able at the park point"
                st._fp_order = False  # what a spill does to the probe's view
        return res

    monkeypatch.setattr(agg_mod._ProbeScatter, "fold", fold_wrap)

    aggs = [(AggExpr("first", col(1)), "f"), (AggExpr("count_star", None), "c")]
    conf = (Configuration().set(TRANSFER_WINDOW_DEPTH, 6)
            .set(BATCH_SIZE, 2048)
            .set(AGG_INCREMENTAL_FINGERPRINT, "on")
            .set(AGG_INCREMENTAL_PROBE, "on")
            .set(AGG_INCREMENTAL_MERGEPATH, "on")
            .set(PARTIAL_AGG_SKIPPING_ENABLE, False))
    with conf_scope(conf):
        batches = [
            Batch.from_pydict({"k": ks.tolist(), "v": [v] * len(ks)})
            for ks, v in frames
        ]
        agg = HashAggExec(
            MemoryScanExec.single(batches), [(col(0), "k")], aggs, "partial")
        mid = list(agg.execute(0, ExecutionContext(conf=conf)))
        final = HashAggExec(
            MemoryScanExec.single(mid), [(col(0), "k")], aggs, "final")
        out = pd.concat(
            b.to_pandas() for b in final.execute(0, ExecutionContext(conf=conf))
        ).sort_values("k").reset_index(drop=True)

    assert calls["folded"][PARK_AFTER], "miss batch did not probe-fold"
    assert not calls["folded"][PARK_AFTER + 1], "park did not disengage probe"
    got_band = out[out["k"] >= 900_000_000_000]
    assert got_band["c"].tolist() == [2] * len(band)   # no row lost or doubled
    # stream-first value is the PENDING miss batch's 1.0, not the
    # post-park batch's 2.0
    assert got_band["f"].tolist() == [1.0] * len(band)


def test_deferred_partial_counts_k_deep_interleaved_mispredicts():
    """The deferred arm: the PARTIAL generic path's (live count, group
    count) read rides the k-deep transfer window (mirroring the dense-flag
    deque of PR 2); interleaved selectivity jumps mean MULTIPLE in-flight
    batches can be truncated by an under-sized predicted bucket and each
    must recompute exactly once — counts stay exact vs pandas at every
    window depth."""
    import pandas as pd

    from auron_tpu.utils.config import TRANSFER_WINDOW_DEPTH, active_conf

    rng = np.random.default_rng(17)
    key_batches = []
    for i in range(14):
        if i % 3 == 2:
            # dense batch right after sparse ones: the EWMA's bucket is
            # tiny, so this batch truncates and must repair mid-window
            ks = rng.integers(0, 40, 1200)
        else:
            ks = rng.integers(0, 40, 1200)
            ks[120:] = -1  # dead marker: filtered below
        key_batches.append(ks)
    frames = []
    schema = None
    for ks in key_batches:
        live = [int(k) if k >= 0 else None for k in ks]
        b = Batch.from_pydict({"k": live, "v": [1.0] * len(live)})
        schema = b.schema
        frames.append(b)

    # IsNotNull filter upstream keeps dead rows out; keys 0..39 force the
    # bool/dense-ineligible... (int key IS dense-eligible — widen the range)
    from auron_tpu.exec.basic import FilterExec
    from auron_tpu.exprs.ir import IsNotNull

    conf = active_conf()
    saved_depth = conf.get(TRANSFER_WINDOW_DEPTH)

    def run(depth):
        conf.set(TRANSFER_WINDOW_DEPTH, depth)
        # spread keys so the dense direct-address table refuses and the
        # GENERIC sort-segmentation path (the deferred read's home) runs
        from auron_tpu.exprs.ir import BinaryOp, Literal

        wide = BinaryOp("mul", col(0), Literal(1_000_003, T.INT64))
        scan = MemoryScanExec.single([Batch(b.schema, b.device, b.dicts) for b in frames])
        flt = FilterExec(scan, [IsNotNull(col(0))])
        p = HashAggExec(flt, [(wide, "k")],
                        [(AggExpr("count_star", None), "c"),
                         (AggExpr("sum", col(1)), "s")], "partial")
        f = HashAggExec(p, [(col(0), "k")],
                        [(AggExpr("count_star", None), "c"),
                         (AggExpr("sum", col(1)), "s")], "final")
        from auron_tpu.exec.base import ExecutionContext

        ctx = ExecutionContext()
        ctx.metrics.name = f.name
        out = f.collect(ctx=ctx).to_pandas().sort_values("k").reset_index(drop=True)
        return out, ctx.metrics.total("sel_mispredicts")

    all_k = [int(k) * 1_000_003 for ks in key_batches for k in ks if k >= 0]
    want = (
        pd.DataFrame({"k": all_k, "v": 1.0})
        .groupby("k").agg(c=("v", "size"), s=("v", "sum")).reset_index()
        .sort_values("k").reset_index(drop=True)
    )
    try:
        mispredicted = 0
        for depth in (1, 3, 6):
            got, mis = run(depth)
            mispredicted += mis
            assert got["k"].tolist() == want["k"].tolist(), f"depth={depth}"
            assert got["c"].tolist() == want["c"].tolist(), f"depth={depth}"
            assert got["s"].tolist() == [
                pytest.approx(float(x)) for x in want["s"]], f"depth={depth}"
        # teeth: the sparse->dense jumps actually exercised the repair
        assert mispredicted > 0
    finally:
        conf.set(TRANSFER_WINDOW_DEPTH, saved_depth)


# live rows of each of the stream's three 4096-row batches. ``want``: seed reads, mispredict repairs, batches
# compacted at dispatch, and the capacities the raw folds ran at, in order
# (a repair's fold comes at the drain, after the stream's three).
_SEED_CASES = {
    "sparse": dict(live=(100, 90, 110), want=(1, 0, 3, [256, 256, 256])),
    "dense": dict(live=(3000, 2900, 3100), want=(1, 0, 0, [4096, 4096, 4096])),
    "growing": dict(live=(60, 600, 60), want=(1, 1, 3, [128, 128, 128, 1024])),
    "empty_first": dict(live=(0, 1000, 0), want=(1, 1, 3, [128, 128, 128, 1024])),
}


@pytest.mark.parametrize("case", sorted(_SEED_CASES))
def test_deferred_partial_seeds_the_predictor_on_a_streams_first_batch(case):
    """A stream shorter than the transfer window (3 batches, depth 4) never
    harvests before its drain, so the deferred arm reads the FIRST batch's
    live count (one blocking read a stream) and compacts from batch 1 on:
    the grouped reduce folds live rows, not batch capacity. Row- and
    count-exact against pandas in every case."""
    import os
    import time

    import auron_tpu
    from auron_tpu import obs
    from auron_tpu.exec.basic import FilterExec
    from auron_tpu.exprs.ir import IsNotNull
    from auron_tpu.obs import core
    from auron_tpu.utils.config import TRANSFER_WINDOW_DEPTH, active_conf
    from auron_tpu.utils.profiling import EngineCounters

    spec = _SEED_CASES[case]
    cap = 4096
    rng = np.random.default_rng(27)
    frames, rows = [], []
    for n_live in spec["live"]:
        ks = [f"brand#{int(k)}" for k in rng.integers(0, 37, cap)]
        alive = np.zeros(cap, dtype=bool)
        alive[rng.choice(cap, n_live, replace=False)] = True
        vs = rng.integers(1, 10_000, cap)
        frames.append(Batch.from_pydict({
            "k": ks,
            "v": [int(v) for v in vs],
            "live": [1 if a else None for a in alive],
        }))
        rows += [(k, int(v)) for k, v, a in zip(ks, vs, alive) if a]
    want = (
        pd.DataFrame(rows, columns=["k", "v"])
        .groupby("k").agg(c=("v", "size"), s=("v", "sum")).reset_index()
        .sort_values("k").reset_index(drop=True)
    )

    EngineCounters.install()
    conf = active_conf()
    saved_depth, saved_mode = conf.get(TRANSFER_WINDOW_DEPTH), obs.mode()

    def run():
        scan = MemoryScanExec.single(
            [Batch(b.schema, b.device, b.dicts) for b in frames])
        flt = FilterExec(scan, [IsNotNull(col(2))])
        aggs = [(AggExpr("count_star", None), "c"), (AggExpr("sum", col(1)), "s")]
        p = HashAggExec(flt, [(col(0), "k")], aggs, PARTIAL)
        f = HashAggExec(p, [(col(0), "k")], aggs, FINAL)
        ctx = ExecutionContext()
        ctx.metrics.name = f.name
        t0 = time.perf_counter()
        out = f.collect(ctx=ctx).to_pandas().sort_values("k").reset_index(drop=True)
        return out, ctx.metrics, (t0, time.perf_counter())

    try:
        obs.set_mode("recorder")
        conf.set(TRANSFER_WINDOW_DEPTH, 4)
        got, metrics, (t0, t1) = run()
    finally:
        conf.set(TRANSFER_WINDOW_DEPTH, saved_depth)
        obs.set_mode(saved_mode)

    assert got["k"].tolist() == want["k"].tolist()
    assert got["c"].tolist() == want["c"].tolist()
    assert got["s"].tolist() == want["s"].tolist()

    seeds, repairs, compacted, fold_caps = spec["want"]
    assert metrics.total("sel_seed_reads") == seeds
    assert metrics.total("sel_mispredicts") == repairs
    assert metrics.total("agg_compacted_batches") == compacted
    lo, hi = int(t0 * 1e9), int(t1 * 1e9)
    events = sorted((ev for _ring, evs in core.snapshot_events() for ev in evs
                     if lo <= ev[0] < hi), key=lambda ev: ev[0])
    every = [ev[7] for ev in events if ev[2] == "fold"]
    folds = [f for f in every if f["path"] == "deferred"]
    assert [f["rows"] for f in folds] == fold_caps
    assert all(f["in_rows"] == cap for f in folds)
    # the FINAL aggregate above it folds the partial's one state on the
    # blocking path: a fold event of its own (PR 34), in the sum as well
    final = [f for f in every if f["path"] != "deferred"]
    assert {f["path"] for f in final} <= {"sort"}
    ws = obs.window_summary(t0, t1)
    assert ws["agg_folds"].get("deferred", {"rows": 0})["rows"] == sum(fold_caps)
    assert ws["agg_fold_rows"] == sum(fold_caps) + sum(f["rows"] for f in final)
    # the arm's blocking reads, by the sync-point lines the hook names:
    # one seed read a stream, one more for each repair, and no other
    src = os.path.join(os.path.dirname(auron_tpu.__file__), "exec/agg_exec.py")
    with open(src) as f:
        lines = f.readlines()
    arm_reads = [
        lines[int(ev[3].rsplit(":", 1)[1]) - 1].split("sync-point", 1)[1]
        for ev in events
        if ev[8] == "sync" and ev[3].startswith("exec/agg_exec.py:")
        and "deferred-agg" in lines[int(ev[3].rsplit(":", 1)[1]) - 1]
    ]
    assert sum("deferred-agg seed" in r for r in arm_reads) == seeds
    assert sum("mispredict repair" in r for r in arm_reads) == repairs
    assert len(arm_reads) == seeds + repairs
