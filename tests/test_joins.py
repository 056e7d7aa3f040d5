"""Join matrix tests: {SMJ, BHJ-build-left, BHJ-build-right} x 7 join types,
differential against pandas merge (the reference tests the same matrix in
datafusion-ext-plans/src/joins/test.rs)."""

import numpy as np
import pandas as pd
import pyarrow as pa
import pytest

from auron_tpu import types as T
from auron_tpu.columnar import Batch
from auron_tpu.exec.basic import MemoryScanExec
from auron_tpu.exec.joins import BroadcastHashJoinExec, SortMergeJoinExec
from auron_tpu.exec.joins.core import (
    EXISTENCE, FULL, INNER, LEFT, LEFT_ANTI, LEFT_SEMI, RIGHT,
)
from auron_tpu.exprs.ir import BinaryOp, col, lit


def _mk(df, chunk=None):
    if chunk is None:
        return MemoryScanExec.single(
            [Batch.from_arrow(pa.RecordBatch.from_pandas(df, preserve_index=False))]
        )
    bs = [
        Batch.from_arrow(
            pa.RecordBatch.from_pandas(df.iloc[i : i + chunk], preserve_index=False)
        )
        for i in range(0, len(df), chunk)
    ]
    return MemoryScanExec.single(bs or [Batch.from_arrow(
        pa.RecordBatch.from_pandas(df, preserve_index=False))])


def _join(kind, ldf, rdf, jt, lkeys, rkeys, condition=None, chunk=None):
    left = _mk(ldf, chunk)
    right = _mk(rdf, chunk)
    lk = [col(i) for i in lkeys]
    rk = [col(i) for i in rkeys]
    if kind == "smj":
        op = SortMergeJoinExec(left, right, lk, rk, jt, condition=condition)
    elif kind == "bhj_right":
        op = BroadcastHashJoinExec(left, right, lk, rk, jt, build_side="right",
                                   condition=condition)
    else:
        op = BroadcastHashJoinExec(left, right, lk, rk, jt, build_side="left",
                                   condition=condition)
    return op.collect().to_pandas()


LDF = pd.DataFrame(
    {
        "k": pd.array([1, 2, 2, 3, None, 5], dtype="Int64"),
        "lv": ["a", "b", "c", "d", "e", "f"],
    }
)
RDF = pd.DataFrame(
    {
        "k2": pd.array([2, 2, 3, 4, None], dtype="Int64"),
        "rv": [20.0, 21.0, 30.0, 40.0, 50.0],
    }
)

KINDS = ["smj", "bhj_right", "bhj_left"]


def sql_merge(ldf, rdf, how, lk="k", rk="k2"):
    """pandas merge with SQL NULL semantics (NULL keys never match)."""
    lnn = ldf[ldf[lk].notna()]
    rnn = rdf[rdf[rk].notna()]
    if how == "inner":
        return lnn.merge(rnn, left_on=lk, right_on=rk, how="inner")
    if how == "left":
        return ldf.merge(rnn, left_on=lk, right_on=rk, how="left")
    if how == "right":
        return lnn.merge(rdf, left_on=lk, right_on=rk, how="right")
    if how == "outer":
        left_part = ldf.merge(rnn, left_on=lk, right_on=rk, how="left", indicator=False)
        matched_rkeys = set(lnn[lk].dropna()) & set(rnn[rk].dropna())
        right_unmatched = rdf[~rdf[rk].isin(matched_rkeys) | rdf[rk].isna()]
        pad = pd.DataFrame({c: [None] * len(right_unmatched) for c in ldf.columns})
        pad.index = right_unmatched.index
        right_part = pd.concat([pad, right_unmatched], axis=1)
        return pd.concat([left_part, right_part], ignore_index=True)
    raise ValueError(how)


def _norm(df, cols):
    return (
        df.sort_values(cols, na_position="last")
        .reset_index(drop=True)
        .where(lambda d: d.notna(), None)
    )


@pytest.mark.parametrize("kind", KINDS)
def test_inner(kind):
    got = _join(kind, LDF, RDF, INNER, [0], [0])
    want = sql_merge(LDF, RDF, "inner")
    got = _norm(got, ["k", "lv", "rv"])
    want = _norm(want, ["k", "lv", "rv"])
    pd.testing.assert_frame_equal(got, want, check_dtype=False)


@pytest.mark.parametrize("kind", KINDS)
def test_left(kind):
    got = _join(kind, LDF, RDF, LEFT, [0], [0])
    want = sql_merge(LDF, RDF, "left")
    got = _norm(got, ["k", "lv", "rv"])
    want = _norm(want, ["k", "lv", "rv"])
    pd.testing.assert_frame_equal(got, want, check_dtype=False)


@pytest.mark.parametrize("kind", KINDS)
def test_right(kind):
    got = _join(kind, LDF, RDF, RIGHT, [0], [0])
    want = sql_merge(LDF, RDF, "right")
    got = _norm(got, ["k2", "rv", "lv"])
    want = _norm(want, ["k2", "rv", "lv"])
    pd.testing.assert_frame_equal(got, want, check_dtype=False)


def _row_multiset(df, cols):
    from collections import Counter

    rows = []
    for _, r in df[cols].iterrows():
        rows.append(
            tuple(
                None if pd.isna(v) else (float(v) if isinstance(v, (int, float, np.number)) else v)
                for v in r
            )
        )
    return Counter(rows)


@pytest.mark.parametrize("kind", KINDS)
def test_full(kind):
    got = _join(kind, LDF, RDF, FULL, [0], [0])
    want = sql_merge(LDF, RDF, "outer")
    cols = ["k", "lv", "k2", "rv"]
    assert _row_multiset(got, cols) == _row_multiset(want, cols)


@pytest.mark.parametrize("kind", KINDS)
def test_semi_anti_existence(kind):
    got_semi = _join(kind, LDF, RDF, LEFT_SEMI, [0], [0])
    # keys present in right: 2, 3 (null never matches)
    assert sorted(got_semi["lv"].tolist()) == ["b", "c", "d"]
    got_anti = _join(kind, LDF, RDF, LEFT_ANTI, [0], [0])
    assert sorted(got_anti["lv"].tolist()) == ["a", "e", "f"]
    got_ex = _join(kind, LDF, RDF, EXISTENCE, [0], [0])
    ex = dict(zip(got_ex["lv"], got_ex["exists"]))
    assert ex == {"a": False, "b": True, "c": True, "d": True, "e": False, "f": False}


@pytest.mark.parametrize("kind", KINDS)
def test_condition_join(kind):
    # residual predicate: rv > 20 — pairs failing it do not count as matches
    cond = BinaryOp("gt", col(3), lit(20.0))
    got = _join(kind, LDF, RDF, LEFT, [0], [0], condition=cond)
    want_pairs = LDF.merge(RDF, left_on="k", right_on="k2")
    want_pairs = want_pairs[want_pairs.rv > 20]
    matched = set(want_pairs["lv"])
    n_expected = len(want_pairs) + (len(LDF) - len(set(LDF.lv) & matched))
    assert len(got) == n_expected
    # row 'b' (k=2) keeps only the rv=21 pair
    b_rows = got[got.lv == "b"]
    assert b_rows["rv"].dropna().tolist() == [21.0]


@pytest.mark.parametrize("kind", KINDS)
def test_string_keys_multibatch(kind):
    rng = np.random.default_rng(11)
    n, m = 500, 300
    ldf = pd.DataFrame(
        {
            "k": rng.choice(["aa", "bb", "cc", "dd", "ee", "zz"], n),
            "lv": rng.integers(0, 1000, n),
        }
    )
    rdf = pd.DataFrame(
        {
            "k2": rng.choice(["bb", "cc", "dd", "qq"], m),
            "rv": rng.normal(size=m),
        }
    )
    got = _join(kind, ldf, rdf, INNER, [0], [0], chunk=128)
    want = ldf.merge(rdf, left_on="k", right_on="k2", how="inner")
    assert len(got) == len(want)
    gs = got.groupby("k").size().to_dict()
    ws = want.groupby("k").size().to_dict()
    assert gs == ws
    assert got["lv"].sum() == want["lv"].sum()
    assert got["rv"].sum() == pytest.approx(want["rv"].sum())


@pytest.mark.parametrize("kind", ["smj", "bhj_right"])
def test_multi_key_join(kind):
    ldf = pd.DataFrame({"a": [1, 1, 2, 2], "b": ["x", "y", "x", "y"], "lv": [1, 2, 3, 4]})
    rdf = pd.DataFrame({"a2": [1, 2, 2], "b2": ["y", "x", "q"], "rv": [10, 20, 30]})
    got = _join(kind, ldf, rdf, INNER, [0, 1], [0, 1])
    want = ldf.merge(rdf, left_on=["a", "b"], right_on=["a2", "b2"])
    got = _norm(got, ["a", "b"])
    want = _norm(want, ["a", "b"])
    pd.testing.assert_frame_equal(got, want, check_dtype=False)


@pytest.mark.parametrize("kind", KINDS)
def test_empty_sides(kind):
    empty = LDF.iloc[0:0]
    got = _join(kind, empty, RDF, LEFT, [0], [0])
    assert len(got) == 0
    got2 = _join(kind, LDF, RDF.iloc[0:0], LEFT, [0], [0])
    assert len(got2) == len(LDF)
    assert got2["rv"].isna().all()
    got3 = _join(kind, LDF, RDF.iloc[0:0], INNER, [0], [0])
    assert len(got3) == 0


def test_cached_build_lock_evicted_with_resource():
    """Executor-shared broadcast builds mint one lock per cached_build_id;
    the host's resource-removal path must evict the lock with the resource
    or a long-lived executor leaks one Lock per broadcast (ADVICE r3)."""
    from auron_tpu.bridge import api
    from auron_tpu.exec.joins import bhj

    ldf = pd.DataFrame({"k": [1, 2, 3], "lv": [10, 20, 30]})
    rdf = pd.DataFrame({"k2": [1, 2], "rv": [5, 6]})
    left, right = _mk(ldf), _mk(rdf)
    op = BroadcastHashJoinExec(
        left, right, [col(0)], [col(0)], INNER,
        build_side="right", cached_build_id="bcast_evict_test",
    )
    from auron_tpu.exec.base import ExecutionContext

    shared = {}
    ctx = ExecutionContext(shared=shared)
    got = op.collect(0, ctx).to_pandas()
    assert len(got) == 2
    assert "bcast_evict_test" in shared  # build cached executor-wide
    assert "bcast_evict_test" in bhj._key_locks
    # host destroys the broadcast -> resource AND lock must go
    api.put_resource("bcast_evict_test", shared["bcast_evict_test"])
    api.remove_resource("bcast_evict_test")
    assert "bcast_evict_test" not in bhj._key_locks


def test_fused_chain_fallback_memo_cleared_on_completion():
    """On non-unique-build fallback the chain stashes prepared builds in
    ctx.resources; the chain top must clear leftovers when its per-operator
    execution ends so unreached entries can't pin batches (ADVICE r3)."""
    from auron_tpu.exec.base import ExecutionContext

    # duplicate build keys force the fused-chain fallback
    ldf = pd.DataFrame({"k": [1, 1, 2, 3], "lv": [1, 2, 3, 4]})
    mdf = pd.DataFrame({"k2": [1, 1, 2], "mv": [10, 11, 20]})  # dup key 1
    rdf = pd.DataFrame({"k3": [1, 2], "rv": [100, 200]})
    j1 = BroadcastHashJoinExec(
        _mk(ldf), _mk(mdf), [col(0)], [col(0)], INNER, build_side="right"
    )
    top = BroadcastHashJoinExec(
        j1, _mk(rdf), [col(0)], [col(0)], INNER, build_side="right"
    )
    ctx = ExecutionContext()
    got = top.collect(0, ctx).to_pandas()
    want = ldf.merge(mdf, left_on="k", right_on="k2").merge(
        rdf, left_on="k", right_on="k3"
    )
    assert len(got) == len(want)
    leftovers = [
        k for k in ctx.resources
        if isinstance(k, tuple) and k and str(k[0]).startswith("fusion_build_memo")
    ]
    assert leftovers == [], leftovers


def test_condition_with_case_remaps_columns():
    """Residual conditions evaluate over a reduced schema of only their
    referenced columns; Columns nested inside Case.branches (tuple of
    tuples) must be remapped too (regression: they kept combined-schema
    indices and read the wrong column or crashed)."""
    import jax.numpy as jnp

    from auron_tpu import types as T
    from auron_tpu.columnar import Batch
    from auron_tpu.exec.basic import MemoryScanExec
    from auron_tpu.exec.joins.bhj import BroadcastHashJoinExec
    from auron_tpu.exprs import ir

    left = Batch.from_pydict({"k": [1, 1, 2], "a": [10, 20, 30]})
    right = Batch.from_pydict({"k": [1, 1, 2], "b": [5, 25, 40]})
    # CASE WHEN a > 15 THEN b < a ELSE b > a END  (refs a=col1, b=col3)
    cond = ir.Case(
        branches=(
            (ir.BinaryOp("gt", ir.Column(1), ir.Literal(15, T.INT64)),
             ir.BinaryOp("lt", ir.Column(3), ir.Column(1))),
        ),
        orelse=ir.BinaryOp("gt", ir.Column(3), ir.Column(1)),
    )
    j = BroadcastHashJoinExec(
        MemoryScanExec.single([left]), MemoryScanExec.single([right]),
        [ir.col(0)], [ir.col(0)], "inner", condition=cond,
        build_side="right",
    )
    out = j.collect().to_pandas().sort_values(["a", "b"]).reset_index(drop=True)
    rows = set(zip(out["a"], out["b"]))
    # a=10 (else: b>a): (10,25); a=20 (then: b<a): (20,5); a=30: b=40 not <30
    assert rows == {(10, 25), (20, 5)}


def test_unique_build_residual_condition_noncompact_emit():
    """Unique build + residual condition: needs_all_pairs forces the
    NON-compacted unique emit path with proj = full output (regression:
    the _unique_probe_cfg refactor once dropped the local full_n this
    branch sizes its projection with)."""
    import pandas as pd

    from auron_tpu.exec.basic import MemoryScanExec
    from auron_tpu.exec.joins import BroadcastHashJoinExec
    from auron_tpu.exprs.ir import BinaryOp, Column, Literal

    left = pd.DataFrame({"k": np.arange(8, dtype=np.int64),
                         "lv": np.arange(8, dtype=np.int64) * 10})
    right = pd.DataFrame({"rk": np.arange(8, dtype=np.int64),
                          "rv": np.arange(8, dtype=np.int64) * 5})
    j = BroadcastHashJoinExec(
        MemoryScanExec.single([Batch.from_pandas(left)]),
        MemoryScanExec.single([Batch.from_pandas(right)]),
        [Column(0, "k")], [Column(0, "rk")], "inner", build_side="right",
        condition=BinaryOp("gt", Column(3, "rv"), Literal(14, T.INT64)),
    )
    got = j.collect().to_pandas().sort_values("k").reset_index(drop=True)
    want = left.merge(right, left_on="k", right_on="rk")
    want = want[want.rv > 14].sort_values("k").reset_index(drop=True)
    assert got["k"].tolist() == want["k"].tolist()
    assert got["rv"].tolist() == want["rv"].tolist()


# ---------------------------------------------------------------------------
# the output boundary of the unique-build probe: which take each stream of
# probe batches gets, what the seed reads, and the paths that never compact
# ---------------------------------------------------------------------------


def _takes_of(run):
    """(result, the window's take events as (mode, rows, in_rows), the
    window's summary with the compaction boundary's own host reads as
    ``join_reads``: (site, bytes)) of ``run()`` under the flight recorder."""
    import time

    from auron_tpu import obs
    from auron_tpu.obs import core
    from auron_tpu.utils.profiling import EngineCounters

    EngineCounters.install()        # the hook that names the host reads
    saved = obs.mode()
    obs.set_mode("recorder")
    try:
        t0 = time.perf_counter()
        out = run()
        t1 = time.perf_counter()
        lo, hi = int(t0 * 1e9), int(t1 * 1e9)
        window = [ev for _r, evs in core.snapshot_events() for ev in evs
                  if lo <= ev[0] < hi]
        evs = sorted((ev for ev in window if ev[2] == "take"),
                     key=lambda ev: ev[0])
        ws = obs.window_summary(t0, t1)
        ws["join_reads"] = [(ev[3], ev[7]["bytes"]) for ev in window
                            if ev[8] == "sync"
                            and ev[3].startswith("exec/selectivity.py:")]
    finally:
        obs.set_mode(saved)
    return out, [(e[7]["mode"], e[7]["rows"], e[7]["in_rows"]) for e in evs], ws


def _sparse_probe(n=4096, live_from=None, seed=3):
    """Probe frames of 1,024-row batches over a build of keys 0..63: rows
    before ``live_from`` carry a key the build lacks."""
    k = np.full(n, 10_000)
    k[::97] = np.arange(len(k[::97])) % 64    # a few survivors in every batch
    if live_from is not None:
        k[:live_from] = 10_000
    probe = pd.DataFrame({"k": k.astype(np.int64),
                          "v": np.arange(n, dtype=np.int64)})
    dim = pd.DataFrame({"id": np.arange(64, dtype=np.int64),
                        "d": np.arange(64, dtype=np.int64) * 3})
    return probe, dim


@pytest.mark.parametrize("case, modes", [
    # a left join keeps every probe row (its seed finds the batch full), a
    # residual condition needs every pair: both stay dense at capacity,
    # batch after batch, as before
    ("outer_probe", ["seed"] + ["dense"] * 3),
    ("residual", ["dense"] * 4),
    # a duplicate-key build expands ragged pairs: not this boundary at all
    ("duplicate_build", []),
    # the inner unique join: a seed, then the predicted arm
    ("inner_unique", ["seed", "compact", "compact", "compact"]),
], ids=lambda v: v if isinstance(v, str) else None)
def test_paths_that_never_compact_still_take_theirs(case, modes):
    probe, dim = _sparse_probe()
    jt, cond = INNER, None
    if case == "outer_probe":
        jt = LEFT
    elif case == "residual":
        cond = BinaryOp("gt", col(3), lit(-1, T.INT64))
    elif case == "duplicate_build":
        dim = pd.concat([dim, dim.assign(d=dim.d + 1)], ignore_index=True)

    def run():
        op = BroadcastHashJoinExec(
            _mk(probe, 1024), _mk(dim), [col(0)], [col(0)], jt,
            build_side="right", condition=cond)
        return op.collect().to_pandas()

    got, takes, ws = _takes_of(run)
    want = probe.merge(dim, left_on="k", right_on="id",
                       how="left" if jt == LEFT else "inner")
    assert len(got) == len(want)
    assert sorted(got["v"].tolist()) == sorted(want["v"].tolist())
    assert [m for m, _, _ in takes] == modes
    assert all(cap == 1024 for _, _, cap in takes)
    if "dense" in modes:
        assert [rows for _, rows, _ in takes] == [1024] * 4
        assert ws["join_gather_rows"] == 4 * 1024
    assert ws["join_takes"] == {m: modes.count(m) for m in set(modes)}


@pytest.mark.parametrize("live_from, want_modes", [
    # steady: one seed, then predicted compact takes riding the window
    (None, ["seed", "compact", "compact", "compact"]),
    # the first batch is empty, the later ones hold two hundred: the seed
    # compacts into the least bucket, and each batch dispatched at it
    # before the first harvest (all three: the window is four deep)
    # repairs at the bucket of its own count
    (1024, ["seed"] + ["compact"] * 3 + ["repair"] * 3),
], ids=["steady", "empty_first_then_jump"])
def test_the_seed_reads_one_scalar_and_no_mask(live_from, want_modes):
    """The first batch of a stream has no prediction: the join reads its
    live count (one scalar), never the selection mask (a byte a row), and
    takes on the device at that count's bucket."""
    from auron_tpu.utils.config import TRANSFER_WINDOW_DEPTH, active_conf

    probe, dim = _sparse_probe(live_from=live_from)
    if live_from is not None:       # of the later rows a fifth survives
        late = probe.index[(probe.index >= live_from) & (probe.index % 5 == 0)]
        probe.loc[late, "k"] = probe.loc[late, "v"] % 64
    conf = active_conf()
    saved = conf.get(TRANSFER_WINDOW_DEPTH)
    conf.set(TRANSFER_WINDOW_DEPTH, 4)

    def run():
        op = BroadcastHashJoinExec(
            _mk(probe, 1024), _mk(dim), [col(0)], [col(0)], INNER,
            build_side="right")
        return op.collect().to_pandas()

    try:
        got, takes, ws = _takes_of(run)
    finally:
        conf.set(TRANSFER_WINDOW_DEPTH, saved)
    want = probe.merge(dim, left_on="k", right_on="id")
    assert sorted(got["v"].tolist()) == sorted(want["v"].tolist())
    assert got["d"].tolist() == (got["k"] * 3).tolist()
    assert [m for m, _, _ in takes] == want_modes
    assert all(rows == 256 for m, rows, _ in takes if m == "repair")
    # the boundary's own blocking read: the seed, one int64, from one line
    # of exec/selectivity.py; nothing the size of a mask (a byte a row)
    assert [b for _, b in ws["join_reads"]] == [8]
    assert len({site for site, _ in ws["join_reads"]}) == 1


# ---------------------------------------------------------------------------
# the unique probe's lookup: a small build's live key list (compare) against
# its LUT and the binary search over its sorted words
# ---------------------------------------------------------------------------


def _dim_keys(n_live, base):
    """``n_live`` distinct ascending keys from ``base`` up that skip 0 (the
    value a pad slot of the 64-bit list holds) and leave gaps."""
    keys = base + np.arange(n_live + 8, dtype=np.int64) * 3
    return keys[keys != 0][:n_live]


def _probe_keys(keys, rng, n=2048):
    """Probe keys around a build's: hits, gaps between them, keys below
    the LUT's base and above its range (far enough to wrap an int32), a 0
    (a pad slot's value), and NULLs."""
    lo, hi = int(keys.min()), int(keys.max())
    k = rng.choice(keys, n)
    k[1::7] = rng.integers(lo - 50, hi + 50, len(k[1::7]))
    k[2::31] = lo - 1 - rng.integers(0, 1 << 40, len(k[2::31]))
    k[3::31] = hi + 1 + rng.integers(0, 1 << 40, len(k[3::31]))
    k[4::31] = lo + (1 << 32)              # aliases lo in the low 32 bits
    k[5::31] = 0
    k[6::31] = [lo, hi] * (len(k[6::31]) // 2) + [lo] * (len(k[6::31]) % 2)
    valid = rng.random(n) > 0.05
    return k, valid


@pytest.mark.parametrize("probe_outer", [False, True], ids=["inner", "left"])
@pytest.mark.parametrize("n_live, base, width", [
    (1, 7, 64), (63, -90, 64), (64, 1, 64), (65, -1000, 256),
    (256, 1 << 33, 256), (257, -5, 1024), (1024, -2000, 1024),
    (1025, 1, None),        # one over the ladder: the build keeps its LUT
])
def test_compare_map_is_the_twin_of_the_lut_and_the_search(
        lookups_compare, n_live, base, width, probe_outer):
    """On one build and one probe batch the three key -> row maps of the
    unique probe give the same (bi, ok): the compare map and the LUT bit
    for bit, the search wherever a row matched (where none did its bi is
    the insertion point, read by nobody)."""
    import jax.numpy as jnp

    from auron_tpu.exec.joins import core

    keys = _dim_keys(n_live, base)
    # in the order of the key WORDS (negative keys after the others), so
    # that the build's words also serve the search
    keys = np.concatenate([keys[keys >= 0], keys[keys < 0]])
    dim = Batch.from_pandas(pd.DataFrame({"id": keys, "d": keys * 3}))
    with lookups_compare():
        build = core.prepare_build([dim], [col(0)], dim.schema)
    assert build.unique and build.lut is not None and build.n_live == n_live
    if width is None:
        assert build.key_list is None and core.lookup_kind(build) == "lut"
        return
    assert core.lookup_kind(build) == "compare"
    assert [a.shape for a in build.key_list] == [(width,), (width,)]
    rows = np.asarray(build.key_list[1])
    assert (rows[:n_live] == np.arange(n_live)).all() and (rows[n_live:] == -1).all()
    list64 = core._key_list_jit(
        build.words[0], dim.device.sel, None, width=width)
    k, valid = _probe_keys(keys, np.random.default_rng(n_live))
    psel = np.ones(len(k), bool)
    psel[::13] = False

    def probe(use_lut, key_list):
        out = core._unique_probe_jit(
            (jnp.asarray(k),), (jnp.asarray(valid),), jnp.asarray(psel),
            build.lut if use_lut else None,
            jnp.int64(build.lut_base) if use_lut else None,
            build.words, jnp.int32(n_live), key_list,
            bcap=dim.capacity, use_lut=use_lut, probe_outer=probe_outer,
            key_kinds=("int",))
        return [np.asarray(x) for x in out]

    lut, cmp32 = probe(True, None), probe(True, build.key_list)
    cmp64, search = probe(False, list64), probe(False, None)
    want_ok = psel & valid & np.isin(k, keys)
    assert want_ok.sum() > 100 and (lut[1] == want_ok).all()
    for got in (cmp32, cmp64, search):
        assert (got[1] == want_ok).all()                      # ok
        assert (got[0][want_ok] == lut[0][want_ok]).all()     # bi
        assert (got[2] == (psel if probe_outer else want_ok)).all()
        assert got[3] == lut[3]                               # live count
    assert (cmp32[0] == lut[0]).all()       # unmatched rows too: bit for bit
    assert (keys[lut[0][want_ok]] == k[want_ok]).all()


def test_compare_map_of_a_build_without_a_lut_compares_whole_words(
        lookups_compare):
    """Keys too far apart for a LUT: the build is sorted, its list holds
    the 64-bit words (negative keys as their two's complement) and the
    rows of the CLUSTERED build, dead and NULL-keyed rows last."""
    from auron_tpu.exec.joins import core

    keys = np.array([5, -(1 << 45), 1 << 50, -3, 0, 77], dtype=np.int64)
    df = pd.DataFrame({"id": pd.array(list(keys) + [None], dtype="Int64"),
                       "d": np.arange(7)})
    dim = Batch.from_pandas(df)
    with lookups_compare():
        build = core.prepare_build([dim], [col(0)], dim.schema)
        plain = core.prepare_build([dim], [col(0)], dim.schema)
    assert build.unique and build.lut is None and build.n_live == 6
    assert core.lookup_kind(build) == "compare"
    words, rows = (np.asarray(a) for a in build.key_list)
    assert words.dtype == np.uint64 and words.shape == (64,)
    assert sorted(words[:6].view(np.int64)) == sorted(keys)
    assert (rows[:6] == np.arange(6)).all() and (rows[6:] == -1).all()
    assert (np.asarray(plain.words[0]) == np.asarray(build.words[0])).all()


def _small_dim_join(jt, n_live, chunk=512):
    rng = np.random.default_rng(n_live)
    keys = _dim_keys(n_live, -20)
    k, valid = _probe_keys(keys, rng, n=4 * chunk)
    probe = pd.DataFrame({
        "k": pd.array([int(x) if ok else None for x, ok in zip(k, valid)],
                      dtype="Int64"),
        "v": np.arange(len(k), dtype=np.int64)})
    dim = pd.DataFrame({"id": keys, "d": keys * 3})

    def run():
        return BroadcastHashJoinExec(
            _mk(probe, chunk), _mk(dim), [col(0)], [col(0)], jt,
            build_side="right").collect().to_pandas()

    want = probe.merge(dim, left_on="k", right_on="id",
                       how="left" if jt == LEFT else "inner")
    return run, want


@pytest.mark.parametrize("jt", [INNER, LEFT])
@pytest.mark.parametrize("n_live, kind", [
    (64, "compare"), (65, "compare"), (1024, "compare"), (1025, "lut")])
def test_driver_probes_a_small_build_by_comparing(
        lookups_compare, lookup_events, jt, n_live, kind):
    """The BHJ driver over a unique build: under the patched rule a build
    of up to 1,024 live keys is probed by comparing, one over keeps its
    LUT, and either gives the rows the LUT alone gives and the oracle's;
    one ``lookup`` event a probed batch at the batch's capacity."""
    run, want = _small_dim_join(jt, n_live)
    plain, plain_evs = lookup_events(run)
    with lookups_compare():
        got, evs = lookup_events(run)
    assert plain_evs == [("lut", 512)] * 4     # XLA:CPU's own rule: never
    assert evs == [(kind, 512)] * 4
    cols = ["k", "v", "id", "d"]
    assert _row_multiset(got, cols) == _row_multiset(plain, cols)
    assert _row_multiset(got, cols) == _row_multiset(want, cols)
    assert len(got) == len(want) > 500


@pytest.mark.parametrize("kind", KINDS)
def test_empty_build_under_the_compare_rule(lookups_compare, lookup_events,
                                            kind):
    """A build with no live key is not unique: no list, no lookup, and the
    joins' rows as ever."""
    empty = RDF.iloc[:0]
    with lookups_compare():
        inner, evs = lookup_events(
            lambda: _join(kind, LDF, empty, INNER, [0], [0]))
        left = _join(kind, LDF, empty, LEFT, [0], [0])
    assert len(inner) == 0 and len(left) == len(LDF)
    assert left["rv"].isna().all()
    assert evs == []
