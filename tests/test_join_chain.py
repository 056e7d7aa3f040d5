"""Fused star-schema join chains (exec/joins/chain.py): fused vs
per-operator fallback differential, build reuse on fallback, dense guard.

Oracle: pandas merges over the same frames.
"""

import numpy as np
import pandas as pd
import pyarrow as pa
import pytest

from auron_tpu.columnar import Batch
from auron_tpu.exec.basic import MemoryScanExec
from auron_tpu.exec.joins import BroadcastHashJoinExec
from auron_tpu.exec.joins import chain as chain_mod
from auron_tpu.exec.joins.driver import EquiJoinDriver
from auron_tpu.exprs.ir import col


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
    return MemoryScanExec.single(bs)


def _star(fact, dims, dim_keys, unique=True):
    """fact JOIN dim0 ON fact.k0 = dim0.id JOIN dim1 ON fact.k1 = dim1.id ..."""
    node = _mk(fact, chunk=37)
    nleft = len(fact.columns)
    for i, (dim, fk) in enumerate(zip(dims, dim_keys)):
        node = BroadcastHashJoinExec(
            node, _mk(dim), [col(fk)], [col(0)], "inner", build_side="right"
        )
        nleft += len(dim.columns)
    return node


def _oracle(fact, dims, dim_key_names):
    out = fact
    for dim, k in zip(dims, dim_key_names):
        out = out.merge(dim, left_on=k, right_on=dim.columns[0], how="inner")
    return out


def _collect_sorted(op):
    got = op.collect_pydict()
    df = pd.DataFrame(got)
    return df.sort_values(list(df.columns)).reset_index(drop=True)


def _fact_dims(n=500, nd1=40, nd2=25, seed=0):
    rng = np.random.default_rng(seed)
    fact = pd.DataFrame({
        "k0": rng.integers(0, nd1 + 5, n),  # some keys miss (5 dangling ids)
        "k1": rng.integers(0, nd2 + 5, n),
        "amt": rng.normal(size=n).round(3),
    })
    d1 = pd.DataFrame({"id1": np.arange(nd1), "d1v": np.arange(nd1) * 10})
    d2 = pd.DataFrame({"id2": np.arange(nd2), "d2v": np.arange(nd2) * 7})
    return fact, d1, d2


def test_fused_two_level_chain_matches_oracle():
    fact, d1, d2 = _fact_dims()
    top = _star(fact, [d1, d2], [0, 1])
    calls = {"fused": 0}
    orig = chain_mod._run_chain

    def spy(*a, **k):
        calls["fused"] += 1
        return orig(*a, **k)

    chain_mod._run_chain, saved = spy, orig
    try:
        got = _collect_sorted(top)
    finally:
        chain_mod._run_chain = saved
    assert calls["fused"] == 1, "fused path must engage for a unique star chain"
    exp = _oracle(fact, [d1, d2], ["k0", "k1"])
    exp.columns = got.columns
    exp = exp.sort_values(list(exp.columns)).reset_index(drop=True)
    pd.testing.assert_frame_equal(got, exp, check_dtype=False)


def test_non_unique_build_falls_back_without_rebuilding():
    fact, d1, d2 = _fact_dims(n=300)
    # duplicate a dim row: build no longer unique -> fusion must fall back
    d2_dup = pd.concat([d2, d2.iloc[[3]]], ignore_index=True)
    top = _star(fact, [d1, d2_dup], [0, 1])

    prepares = {"n": 0}
    orig_prepare = EquiJoinDriver.prepare

    def counting_prepare(self, batches, conf=None):
        prepares["n"] += 1
        return orig_prepare(self, batches, conf=conf)

    EquiJoinDriver.prepare = counting_prepare
    try:
        got = _collect_sorted(top)
    finally:
        EquiJoinDriver.prepare = orig_prepare
    # 2 joins -> exactly 2 builds even though fusion was attempted and
    # abandoned (the memo hands the prepared maps to the fallback path)
    assert prepares["n"] == 2, f"builds ran {prepares['n']} times, expected 2"
    exp = _oracle(fact, [d1, d2_dup], ["k0", "k1"])
    exp.columns = got.columns
    exp = exp.sort_values(list(exp.columns)).reset_index(drop=True)
    pd.testing.assert_frame_equal(got, exp, check_dtype=False)


def test_dense_survival_chain_matches_oracle():
    # every fact row matches every dim -> n_live == capacity -> dense path
    rng = np.random.default_rng(1)
    n = 256
    fact = pd.DataFrame({
        "k0": rng.integers(0, 8, n),
        "k1": rng.integers(0, 4, n),
        "amt": np.arange(n),
    })
    d1 = pd.DataFrame({"id1": np.arange(8), "d1v": np.arange(8) * 10})
    d2 = pd.DataFrame({"id2": np.arange(4), "d2v": np.arange(4) * 7})
    top = _star(fact, [d1, d2], [0, 1])
    got = _collect_sorted(top)
    exp = _oracle(fact, [d1, d2], ["k0", "k1"])
    exp.columns = got.columns
    exp = exp.sort_values(list(exp.columns)).reset_index(drop=True)
    pd.testing.assert_frame_equal(got, exp, check_dtype=False)


def test_three_level_chain_with_nulls():
    rng = np.random.default_rng(2)
    n = 400
    fact = pd.DataFrame({
        "k0": pd.array(
            [None if i % 11 == 0 else int(rng.integers(0, 20)) for i in range(n)],
            dtype="Int64",
        ),
        "k1": rng.integers(0, 15, n),
        "k2": rng.integers(0, 10, n),
        "amt": rng.normal(size=n).round(3),
    })
    d1 = pd.DataFrame({"id1": np.arange(20), "d1v": np.arange(20) * 10})
    d2 = pd.DataFrame({"id2": np.arange(15), "d2v": np.arange(15) * 7})
    d3 = pd.DataFrame({"id3": np.arange(10), "d3v": np.arange(10) * 3})
    top = _star(fact, [d1, d2, d3], [0, 1, 2])
    got = _collect_sorted(top)
    exp = fact.dropna(subset=["k0"]).astype({"k0": "int64"})
    exp = _oracle(exp, [d1, d2, d3], ["k0", "k1", "k2"])
    exp.columns = got.columns
    exp = exp.sort_values(list(exp.columns)).reset_index(drop=True)
    pd.testing.assert_frame_equal(got, exp, check_dtype=False)


# ---------------------------------------------------------------------------
# sync-free predicted compaction (exec/selectivity.py + runtime/transfer.py)
# ---------------------------------------------------------------------------


from auron_tpu.exec.selectivity import SelectivityPredictor as _RealPredictor


class _SpyPredictor:
    """Wraps SelectivityPredictor construction so tests can assert the
    predicted path (and its mispredict/repair protocol) actually ran."""

    instances: list = []

    def __new__(cls):
        p = _RealPredictor()
        cls.instances.append(p)
        return p


def _with_spy(monkeypatch):
    import auron_tpu.exec.selectivity as sel_mod

    _SpyPredictor.instances = []
    monkeypatch.setattr(sel_mod, "SelectivityPredictor", _SpyPredictor)
    return _SpyPredictor


def _run_both_modes(top_builder, joins_stay_dense):
    """Collect with the predicted compaction and with every output dense
    by the rule: the two must produce identical row sets."""
    got_pred = _collect_sorted(top_builder())
    with joins_stay_dense():
        got_dense = _collect_sorted(top_builder())
    return got_pred, got_dense


def test_chain_predictor_forced_mispredict_repair(monkeypatch, joins_stay_dense):
    """Selectivity jumps from ~0 to ~100% mid-stream: the predicted bucket
    is far too small, the repair path must re-emit and the results stay
    bit-identical to the dense twin AND the pandas oracle."""
    spy = _with_spy(monkeypatch)
    n = 6000
    # chunk 0 (1000 rows, capacity 1024): almost nothing survives (seeds a
    # tiny bucket, and compaction pays at cap 1024); later chunks: every
    # row survives -> guaranteed bucket-too-small repair
    k0 = np.where(np.arange(n) < 1000, 999, np.arange(n) % 8)
    fact = pd.DataFrame({"k0": k0, "k1": np.arange(n) % 4, "amt": np.arange(n)})
    d1 = pd.DataFrame({"id1": np.arange(8), "d1v": np.arange(8) * 10})
    d2 = pd.DataFrame({"id2": np.arange(4), "d2v": np.arange(4) * 7})

    def build():
        node = _mk(fact, chunk=1000)
        for dim, fk in [(d1, 0), (d2, 1)]:
            node = BroadcastHashJoinExec(
                node, _mk(dim), [col(fk)], [col(0)], "inner",
                build_side="right",
            )
        return node

    got_pred, got_sync = _run_both_modes(build, joins_stay_dense)
    pd.testing.assert_frame_equal(got_pred, got_sync, check_dtype=False)
    exp = _oracle(fact, [d1, d2], ["k0", "k1"])
    exp.columns = got_pred.columns
    exp = exp.sort_values(list(exp.columns)).reset_index(drop=True)
    pd.testing.assert_frame_equal(got_pred, exp, check_dtype=False)
    assert any(p.mispredicts > 0 for p in spy.instances), \
        "selectivity jump must exercise the bucket-too-small repair path"
    assert any(p.predictions > 0 for p in spy.instances)


def test_chain_predictor_parity_fuzz(monkeypatch, joins_stay_dense):
    """Randomized selectivity patterns: predictor-compacted vs dense
    output row sets are identical (and match pandas) across seeds."""
    spy = _with_spy(monkeypatch)
    for seed in range(4):
        rng = np.random.default_rng(seed)
        n = int(rng.integers(800, 4000))
        nd1 = int(rng.integers(4, 60))
        nd2 = int(rng.integers(4, 40))
        # per-chunk selectivity regime shifts (chunk size 257 is coprime
        # with the regime length so bucket demand keeps moving)
        regime = rng.integers(1, 4, size=n)
        hi = nd1 + int(rng.integers(1, 30))
        k0 = np.where(regime == 1, rng.integers(0, max(nd1 // 4, 1), n),
             np.where(regime == 2, rng.integers(0, hi, n),
                      rng.integers(nd1, hi, n)))
        fact = pd.DataFrame({
            "k0": k0,
            "k1": rng.integers(0, nd2 + 3, n),
            "amt": rng.normal(size=n).round(3),
        })
        d1 = pd.DataFrame({"id1": np.arange(nd1), "d1v": np.arange(nd1) * 10})
        d2 = pd.DataFrame({"id2": np.arange(nd2), "d2v": np.arange(nd2) * 7})

        def build():
            node = _mk(fact, chunk=257)
            for dim, fk in [(d1, 0), (d2, 1)]:
                node = BroadcastHashJoinExec(
                    node, _mk(dim), [col(fk)], [col(0)], "inner",
                    build_side="right",
                )
            return node

        got_pred, got_sync = _run_both_modes(build, joins_stay_dense)
        pd.testing.assert_frame_equal(got_pred, got_sync, check_dtype=False)
        exp = _oracle(fact, [d1, d2], ["k0", "k1"])
        exp.columns = got_pred.columns
        exp = exp.sort_values(list(exp.columns)).reset_index(drop=True)
        pd.testing.assert_frame_equal(got_pred, exp, check_dtype=False)
    assert any(p.predictions > 0 for p in spy.instances)


def test_bhj_driver_predictor_parity_with_mispredict(monkeypatch, joins_stay_dense):
    """Single unique-build BHJ (driver._emit_unique_compacted path): the
    pipelined predicted compaction must match the dense twin and the
    oracle, including a forced bucket-too-small repair."""
    spy = _with_spy(monkeypatch)
    n = 6000
    # chunk 0 (capacity 1024) nearly empty output; later chunks ~full
    k0 = np.where(np.arange(n) < 1000, 99999, np.arange(n) % 16)
    fact = pd.DataFrame({"k0": k0, "amt": np.arange(n) * 1.5})
    d1 = pd.DataFrame({"id1": np.arange(16), "d1v": np.arange(16) * 10})

    def build():
        return BroadcastHashJoinExec(
            _mk(fact, chunk=1000), _mk(d1), [col(0)], [col(0)], "inner",
            build_side="right",
        )

    got_pred, got_sync = _run_both_modes(build, joins_stay_dense)
    pd.testing.assert_frame_equal(got_pred, got_sync, check_dtype=False)
    exp = _oracle(fact, [d1], ["k0"])
    exp.columns = got_pred.columns
    exp = exp.sort_values(list(exp.columns)).reset_index(drop=True)
    pd.testing.assert_frame_equal(got_pred, exp, check_dtype=False)
    assert any(p.mispredicts > 0 for p in spy.instances)


def test_chain_window_depth_one_matches(monkeypatch):
    """Window depth 1 (classic one-deep pipeline) stays correct."""
    from auron_tpu.utils.config import TRANSFER_WINDOW_DEPTH, active_conf

    conf = active_conf()
    saved = conf.get(TRANSFER_WINDOW_DEPTH)
    conf.set(TRANSFER_WINDOW_DEPTH, 1)
    try:
        fact, d1, d2 = _fact_dims(n=700, seed=5)
        got = _collect_sorted(_star(fact, [d1, d2], [0, 1]))
    finally:
        conf.set(TRANSFER_WINDOW_DEPTH, saved)
    exp = _oracle(fact, [d1, d2], ["k0", "k1"])
    exp.columns = got.columns
    exp = exp.sort_values(list(exp.columns)).reset_index(drop=True)
    pd.testing.assert_frame_equal(got, exp, check_dtype=False)


# ---------------------------------------------------------------------------
# the chain's output boundary on the specification-typed tiny star
# (tests/test_sql_decimal_serve.py: NULL keys, NULL group keys, DECIMAL money)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def dated_star():
    """The tiny star with the fact table in date order, as the benchmark's
    is, in batches of 8,192 rows: November 2000 (texts 42, 52) is one run
    of rows in the middle of the stream, so the first batches pass nothing
    and one batch passes a thousand."""
    import test_sql_decimal_serve as star

    from auron_tpu.serve.server import SqlServer

    frames = star.make_frames(seed=11, n_fact=90_000)
    ss = frames["store_sales"].sort_values(
        "ss_sold_date_sk", na_position="last", kind="stable"
    ).reset_index(drop=True)
    frames = {**frames, "store_sales": ss}
    tables = {
        t: [Batch.from_pandas(df.iloc[i:i + 8192], schema=star.SCHEMAS[t])
            for i in range(0, len(df), 8192)]
        for t, df in frames.items()
    }
    return star, frames, SqlServer(star.make_catalog(frames), tables, n_parts=1)


def _answer_and_takes(star, server, name):
    import time

    from auron_tpu import obs

    saved = obs.mode()
    obs.set_mode("recorder")
    try:
        t0 = time.perf_counter()
        rec = server.execute_json({"sql": star._text(name), "tenant": "c"})
        ws = obs.window_summary(t0, time.perf_counter())
    finally:
        obs.set_mode(saved)
    return rec["rows"], ws


@pytest.mark.parametrize("chip", [False, True], ids=["cpu_rule", "chip_rule"])
@pytest.mark.parametrize("name, first_batch_empty", [
    ("q3", False),      # November of either year: live rows from batch 1
    ("q42", True),      # November 2000 alone: none, none, ..., a thousand
    ("q55", False),     # November 1999: a thousand early, then none
])
def test_chain_compact_and_dense_arms_are_row_exact_twins(
        monkeypatch, joins_stay_dense, dated_star, chip, name,
        first_batch_empty):
    """Through POST /sql's executor (the fused chain): compacting by the
    rule and dense whatever it says give the reference's rows to the cent, NULL keys never joining and
    NULL group keys one group, under the quarter rule and under the chip's
    rule over shapes, through a seed, a mispredict and its repair."""
    from auron_tpu.columnar import batch as batch_mod

    star, frames, server = dated_star
    monkeypatch.setattr(batch_mod, "_gather_bound", lambda: chip)
    want = star.star_reference(frames, name)
    assert len(want) > 3
    on, ws_on = _answer_and_takes(star, server, name)
    with joins_stay_dense():
        off, ws_off = _answer_and_takes(star, server, name)
    assert on == want
    assert off == want
    n_batches = len(server.tables["store_sales"])
    assert ws_off["join_takes"] == {"seed": 1, "dense": n_batches - 1}
    assert ws_off["join_gather_rows"] == 2 * 8192 * (n_batches - 1) + 2 * \
        server.tables["store_sales"][-1].capacity
    takes = ws_on["join_takes"]
    assert takes["seed"] == 1 and sum(takes.values()) >= n_batches
    assert ws_on["join_gather_rows"] < ws_off["join_gather_rows"] / 3
    if first_batch_empty:
        # seeded on nothing, then a thousand rows arrive: repaired from the
        # state the window holds, at the count's own bucket or dense
        assert takes.get("repair", 0) >= 1


# ---------------------------------------------------------------------------
# one protocol (exec/selectivity.py CompactionBoundary), three consumers
# ---------------------------------------------------------------------------


def _take_modes(tree):
    """(rows, the ``take`` events' modes in call order) of one run of
    ``tree`` under the flight recorder."""
    import time

    from auron_tpu import obs
    from auron_tpu.exec.base import ExecutionContext
    from auron_tpu.obs import core

    saved = obs.mode()
    obs.set_mode("recorder")
    try:
        ctx = ExecutionContext()
        ctx.metrics.name = tree.name
        t0 = time.perf_counter_ns()
        out = [b.to_pandas() for b in tree.execute(0, ctx)]
        t1 = time.perf_counter_ns()
        evs = sorted((ev for _r, evs in core.snapshot_events() for ev in evs
                      if ev[2] == "take" and t0 <= ev[0] < t1),
                     key=lambda ev: ev[0])
    finally:
        obs.set_mode(saved)
    return pd.concat(out, ignore_index=True), [ev[7]["mode"] for ev in evs], ctx


@pytest.mark.parametrize("live, want", [
    ([300] * 5, ["seed"] + ["compact"] * 4),
    # most rows survive: no bucket pays, every batch waits for its count
    ([6000] * 5, ["seed"] + ["dense"] * 4),
    # PR 29's burst: the two batches dispatched at the seed's bucket, the
    # burst's repair between them, then the batches that waited
    ([10, 4000, 0, 0, 0, 0],
     ["seed", "compact", "compact", "repair", "compact", "compact",
      "compact"]),
], ids=["steady", "dense", "burst"])
def test_chain_bhj_and_fused_stage_take_the_same_modes(live, want):
    """One sequence of live counts through the three consumers of the
    compaction boundary (the fused star chain, the eager unique-build BHJ
    and the BHJ behind a fused probe stage) asks for the same takes in
    the same order: the protocol is written once."""
    from auron_tpu import types as T
    from auron_tpu.exec.basic import FilterExec
    from auron_tpu.exprs.ir import BinaryOp, Column, Literal
    from auron_tpu.plan.fusion import fuse_exec_tree
    from auron_tpu.utils.config import (
        TRANSFER_WINDOW_DEPTH, Configuration, active_conf,
    )

    cap = 8192
    rng = np.random.default_rng(5)
    frames = []
    for n in live:
        k = np.full(cap, 10_000, dtype=np.int64)
        k[rng.choice(cap, n, replace=False)] = rng.integers(0, 64, n)
        frames.append(pd.DataFrame({
            "k": k, "k1": np.arange(cap, dtype=np.int64) % 4,
            "v": rng.integers(0, 1 << 30, cap)}))
    probe_b = [Batch.from_pandas(f) for f in frames]
    d1 = pd.DataFrame({"id": np.arange(64, dtype=np.int64),
                       "d": np.arange(64, dtype=np.int64) * 3})
    d2 = pd.DataFrame({"id2": np.arange(4, dtype=np.int64),
                       "d2": np.arange(4, dtype=np.int64) * 7})

    def bhj(child, dim, key):
        return BroadcastHashJoinExec(
            child, _mk(dim), [col(key)], [col(0)], "inner", build_side="right")

    def scan():
        return MemoryScanExec([list(probe_b)], probe_b[0].schema)

    def staged():
        flt = FilterExec(scan(), [BinaryOp(
            "gteq", Column(2, "v"), Literal(0, T.INT64))])
        return fuse_exec_tree(
            bhj(flt, d1, 0), Configuration({"exec.fuse.enable": "on"}))

    conf = active_conf()
    saved = conf.get(TRANSFER_WINDOW_DEPTH)
    conf.set(TRANSFER_WINDOW_DEPTH, 1)
    try:
        got = {
            "chain": _take_modes(bhj(bhj(scan(), d1, 0), d2, 1)),
            "eager": _take_modes(bhj(scan(), d1, 0)),
            "stage": _take_modes(staged()),
        }
    finally:
        conf.set(TRANSFER_WINDOW_DEPTH, saved)
    assert got["stage"][2].metrics.total("fused_batches") == len(live)
    assert {name: modes for name, (_, modes, _) in got.items()} == {
        "chain": want, "eager": want, "stage": want}
    n_rows = len(pd.concat(frames).merge(d1, left_on="k", right_on="id"))
    assert [len(rows) for rows, _, _ in got.values()] == [n_rows] * 3


# ---------------------------------------------------------------------------
# the chain's lookups: a small build's live key list beside a level on its LUT
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n1, n2, kinds", [
    (40, 1025, ["compare", "lut"]),      # the control: one level over the ladder
    (64, 256, ["compare", "compare"]),
    (65, 1024, ["compare", "compare"]),
])
def test_chain_probes_small_builds_by_comparing(
        monkeypatch, lookups_compare, lookup_events, n1, n2, kinds):
    """The fused chain under the patched lookup rule: each level picks its
    map from its own build's live key count, the probe program returns
    the same (sel, bis, live) batch for batch as on the LUTs alone, and
    the rows are the oracle's; NULL and dangling keys never join."""
    rng = np.random.default_rng(n1 + n2)
    n = 900
    fact = pd.DataFrame({
        "k0": pd.array(rng.integers(-3, n1 + 5, n), dtype="Int64"),
        "k1": pd.array(rng.integers(-3, n2 + 5, n) * 5 - 100, dtype="Int64"),
        "amt": rng.integers(0, 1000, n)})
    fact.loc[rng.random(n) < 0.05, "k0"] = None
    fact.loc[rng.random(n) < 0.05, "k1"] = None
    d1 = pd.DataFrame({"id1": np.arange(n1), "d1v": np.arange(n1) * 10})
    d2 = pd.DataFrame({"id2": np.arange(n2) * 5 - 100, "d2v": np.arange(n2) * 7})

    probed = []
    jitted = chain_mod._chain_probe_all_jit
    monkeypatch.setattr(
        chain_mod, "_chain_probe_all_jit",
        lambda *a, **k: probed.append(jitted(*a, **k)) or probed[-1])

    def run():
        probed.clear()
        rows = _collect_sorted(_star(fact, [d1, d2], [0, 1]))
        return rows, list(probed)

    (plain_rows, plain_out), plain_evs = lookup_events(run)
    with lookups_compare():
        (rows, out), evs = lookup_events(run)
    n_batches = -(-n // 37)
    assert len(out) == len(plain_out) == n_batches
    for (sel, bis, live), (p_sel, p_bis, p_live) in zip(out, plain_out):
        assert (np.asarray(sel) == np.asarray(p_sel)).all()
        assert int(live) == int(p_live)
        for bi, p_bi in zip(bis, p_bis):
            assert (np.asarray(bi) == np.asarray(p_bi)).all()
    assert plain_evs == [("lut", 128)] * 2 * n_batches    # a batch's capacity
    assert evs == [(kind, 128) for kind in kinds] * n_batches
    want = _oracle(fact.dropna(), [d1, d2], ["k0", "k1"])
    want = want.astype(rows.dtypes.to_dict())
    want = want.sort_values(list(want.columns)).reset_index(drop=True)
    assert len(rows) == len(want) > 100
    pd.testing.assert_frame_equal(rows, plain_rows)
    pd.testing.assert_frame_equal(rows, want, check_dtype=False)


def test_chain_probe_program_names_its_lookups(monkeypatch):
    """The lowered text of the chain's probe program holds the scope the
    fused stage names its lookup by (metadata only)."""
    fact, d1, d2 = _fact_dims()
    calls = []
    jitted = chain_mod._chain_probe_all_jit
    monkeypatch.setattr(
        chain_mod, "_chain_probe_all_jit",
        lambda *a, **k: calls.append((a, k)) or jitted(*a, **k))
    _star(fact, [d1, d2], [0, 1]).collect_pydict()
    args, kw = calls[0]
    text = jitted.lower(*args, **kw).as_text(debug_info=True)
    assert text.count("auron.probe.lookup") >= 2
