"""Sort / TakeOrdered tests, differential against pandas sort_values."""

import numpy as np
import pandas as pd
import pyarrow as pa
import pytest

from auron_tpu import types as T
from auron_tpu.columnar import Batch
from auron_tpu.exec.base import ExecutionContext
from auron_tpu.exec.basic import MemoryScanExec
from auron_tpu.exec.sort_exec import SortExec
from auron_tpu.exprs.ir import col
from auron_tpu.ops.sortkeys import SortSpec


def _sort(batches, exprs, specs, fetch=None, spill_rows=1 << 21):
    scan = MemoryScanExec.single(batches)
    s = SortExec(scan, exprs, specs, fetch=fetch, spill_threshold_rows=spill_rows)
    return s.collect().to_pandas()


def test_basic_asc_desc_nulls():
    df = pd.DataFrame({"x": [3, None, 1, 2, None], "y": list("abcde")})
    b = Batch.from_arrow(pa.RecordBatch.from_pandas(df, preserve_index=False))
    asc_nf = _sort([b], [col(0)], [SortSpec(asc=True, nulls_first=True)])
    assert asc_nf["y"].tolist() == ["b", "e", "c", "d", "a"]
    asc_nl = _sort([b], [col(0)], [SortSpec(asc=True, nulls_first=False)])
    assert asc_nl["y"].tolist() == ["c", "d", "a", "b", "e"]
    desc_nl = _sort([b], [col(0)], [SortSpec(asc=False, nulls_first=False)])
    assert desc_nl["y"].tolist() == ["a", "d", "c", "b", "e"]


def test_multikey_random_vs_pandas():
    rng = np.random.default_rng(2)
    n = 3000
    df = pd.DataFrame(
        {
            "a": rng.integers(-5, 5, n),
            "b": rng.normal(size=n),
            "c": rng.choice(["pq", "ab", "zz", "mm"], n),
        }
    )
    batches = [
        Batch.from_arrow(
            pa.RecordBatch.from_pandas(df.iloc[i : i + 700], preserve_index=False)
        )
        for i in range(0, n, 700)
    ]
    got = _sort(
        batches,
        [col(0), col(2), col(1)],
        [SortSpec(asc=True), SortSpec(asc=False), SortSpec(asc=True)],
    )
    want = df.sort_values(
        ["a", "c", "b"], ascending=[True, False, True], kind="stable"
    ).reset_index(drop=True)
    pd.testing.assert_frame_equal(got, want, check_dtype=False)


def test_float_nan_sorts_greatest():
    rb = pa.record_batch(
        {"x": pa.array([1.0, float("nan"), -1.0, float("inf"), -float("inf")],
                       type=pa.float64())}
    )
    b = Batch.from_arrow(rb)
    got = _sort([b], [col(0)], [SortSpec(asc=True)])
    vals = got["x"].tolist()
    assert vals[0] == -float("inf") and vals[-2] == float("inf") and np.isnan(vals[-1])


def test_take_ordered():
    df = pd.DataFrame({"x": [5, 3, 9, 1, 7]})
    b = Batch.from_arrow(pa.RecordBatch.from_pandas(df, preserve_index=False))
    got = _sort([b], [col(0)], [SortSpec()], fetch=3)
    assert got["x"].tolist() == [1, 3, 5]


def test_spilled_runs_merge():
    rng = np.random.default_rng(3)
    n = 4000
    df = pd.DataFrame({"x": rng.integers(0, 10_000, n),
                       "s": rng.choice(["u", "v", "w"], n)})
    batches = [
        Batch.from_arrow(
            pa.RecordBatch.from_pandas(df.iloc[i : i + 500], preserve_index=False)
        )
        for i in range(0, n, 500)
    ]
    # tiny spill threshold forces multiple host runs + merge
    got = _sort([batches_i for batches_i in batches], [col(0)], [SortSpec()], spill_rows=900)
    want = df.sort_values("x", kind="stable").reset_index(drop=True)
    assert got["x"].tolist() == want["x"].tolist()
    # string column survives the merge with unified dictionaries
    assert sorted(set(got["s"])) == ["u", "v", "w"]
    cnt_got = got.groupby("s").size().to_dict()
    cnt_want = want.groupby("s").size().to_dict()
    assert cnt_got == cnt_want


def test_emit_chunks_multiple_batches():
    n = 20000
    df = pd.DataFrame({"x": np.random.default_rng(4).permutation(n)})
    b = Batch.from_arrow(pa.RecordBatch.from_pandas(df, preserve_index=False))
    scan = MemoryScanExec.single([b])
    s = SortExec(scan, [col(0)], [SortSpec()])
    ctx = ExecutionContext()
    # chunked emission is the behavior under test: force a batch size
    # smaller than the input regardless of the engine default
    from auron_tpu.utils.config import BATCH_SIZE

    ctx.conf.set(BATCH_SIZE, 4096)
    out = list(s.execute(0, ctx))
    assert len(out) > 1
    allv = []
    for ob in out:
        allv += ob.to_pydict()["x"]
    assert allv == list(range(n))


def test_spilled_sort_on_string_keys():
    """Per-run dictionary ranks are not globally comparable; sorting BY a
    string column across spilled runs must still produce global order."""
    rng = np.random.default_rng(9)
    words = [f"w{i:04d}" for i in range(400)]
    vals = rng.choice(words, 2000)
    df = pd.DataFrame({"s": vals, "x": np.arange(2000)})
    batches = [
        Batch.from_arrow(
            pa.RecordBatch.from_pandas(df.iloc[i : i + 250], preserve_index=False)
        )
        for i in range(0, 2000, 250)
    ]
    got = _sort(batches, [col(0)], [SortSpec()], spill_rows=500)
    want = df.sort_values("s", kind="stable").reset_index(drop=True)
    assert got["s"].tolist() == want["s"].tolist()


def test_negative_nan_bits_sort_greatest():
    import jax.numpy as jnp

    from auron_tpu.ops.sortkeys import orderable_word
    from auron_tpu.exprs.eval import ColumnVal
    from auron_tpu import types as T

    neg_nan = np.array([0xFFF8000000000000], dtype=np.uint64).view(np.float64)[0]
    vals = jnp.asarray([1.0, neg_nan, -np.inf, np.inf])
    cv = ColumnVal(vals, jnp.ones(4, bool), T.FLOAT64)
    w = np.asarray(orderable_word(cv))
    order = np.argsort(w)
    # ascending: -inf, 1.0, inf, NaN (greatest) — even for negative-bit NaN
    assert order.tolist() == [2, 0, 3, 1]


@pytest.mark.parametrize("on_tpu", [False, True])
def test_f64_key_words_order_and_equality(monkeypatch, on_tpu):
    """ops/floatbits: the uint64 words of a float64 key order like the
    values and are equal iff the values are — with the IEEE bitcast, and
    with the float32-pair construction a TPU needs (it has no float64
    bitcast). The pair holds ~48 mantissa bits, so the TPU leg uses values
    a TPU can hold: float32 pairs."""
    import jax
    import jax.numpy as jnp

    from auron_tpu.ops import floatbits

    if on_tpu:
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    rng = np.random.default_rng(5)
    hi = rng.normal(scale=1e6, size=4000).astype(np.float32)
    lo = (hi * np.float32(2.0**-26) * rng.random(4000).astype(np.float32))
    vals = hi.astype(np.float64) + lo.astype(np.float64)
    vals = np.concatenate([
        vals, vals[:500], -vals[:500], [0.0, 1.0, -1.0, 0.01, 123456.78],
        [np.inf, -np.inf, np.nan, 3.4e38, -3.4e38, 1e-30, -1e-30]])
    f = jnp.asarray(vals)
    order = np.asarray(floatbits.f64_orderable_word(f))
    equal = np.asarray(floatbits.f64_equality_word(f))
    want = np.argsort(vals, kind="stable")  # numpy sorts NaN last, like SQL
    got = np.argsort(order, kind="stable")
    assert np.array_equal(vals[got], vals[want], equal_nan=True)
    for words in (order, equal):
        same_word = words[:, None] == words[None, :]
        same_val = (vals[:, None] == vals[None, :]) | (
            np.isnan(vals)[:, None] & np.isnan(vals)[None, :])
        assert np.array_equal(same_word, same_val)


def test_wide_sorts_take_their_order_from_the_host_on_a_tpu(monkeypatch):
    """ops/hostsort's width rule reaches a plan's Sort and a join's sorted
    build: on an accelerator (the back end patched to name itself "tpu") a
    sort of 10^5 rows and a duplicate-keyed build of as many take the
    host's lexsort, where a device sort of that width compiles for minutes
    on XLA:TPU; the answers are the same."""
    import time

    import jax

    from auron_tpu import obs
    from auron_tpu.exec.joins import core

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    rng = np.random.default_rng(17)
    df = pd.DataFrame({"k": rng.integers(0, 10**12, 100_000)})
    dup = pd.DataFrame({"k": np.concatenate([df.k.to_numpy()[:-1], df.k[:1]])})
    before = obs.mode()
    obs.set_mode(obs.MODE_RECORDER)
    t0 = time.perf_counter()
    try:
        got = _sort([Batch.from_pandas(df)], [col(0)], [SortSpec()])
        build = core.prepare_build([Batch.from_pandas(dup)], [col(0)],
                                   T.Schema.from_arrow(pa.Schema.from_pandas(dup)))
        s = obs.window_summary(t0, time.perf_counter())
    finally:
        obs.set_mode(before)
    assert got.k.tolist() == sorted(df.k.tolist())
    assert not build.unique and build.n_live == 100_000
    words = np.asarray(build.words[0])[:100_000].view(np.int64)
    assert (np.diff(words) >= 0).all()
    assert s["sort_rows"]["sort_exec"] == {"host": 131072}
    assert s["sort_rows"]["join_build"] == {"host": 131072}
