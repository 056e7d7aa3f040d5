"""The exact integer segment sum by int32 limbs (``ops/segments.py
limb_plan`` / ``seg_sum_limbs``): the plan at the shapes query 65 folds at,
and the sums against numpy's wrapping int64 sums."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from auron_tpu import types as T
from auron_tpu.columnar.batch import MIN_CAPACITY
from auron_tpu.exec import agg_exec
from auron_tpu.ops import segments as S

D7 = agg_exec._sum_bits(True, T.decimal(7, 2))     # a raw sum of prices
D17 = agg_exec._sum_bits(False, T.decimal(7, 2))   # a merge of such sums


def test_sum_bits_follow_from_the_type():
    """DECIMAL(p): ceil(log2(10^p)) bits and a sign, checked; a merge reads
    ``sum_type``'s digits; physical integers their width, unchecked; merged
    integer sums and counts all 64; a float sum keeps its one scatter."""
    assert D7 == (25, True) and D17 == (58, True)
    assert agg_exec._sum_bits(True, T.decimal(18, 0)) == (61, True)
    assert agg_exec._sum_bits(True, T.INT8) == (8, False)
    assert agg_exec._sum_bits(True, T.INT32) == (32, False)
    assert agg_exec._sum_bits(True, T.INT64) == (64, False)
    assert agg_exec._sum_bits(False, T.INT64) == (64, False)
    assert agg_exec._sum_bits(True, T.FLOAT64) is None
    assert agg_exec._MERGED_COUNT == (64, False)


@pytest.mark.parametrize("bits, rows, want", [
    (D7[0], 1 << 22, (9, 3)),        # query 65's map side
    (D17[0], 1 << 17, (14, 5)),      # its reduce side's merges
    (64, 1 << 22, (9, 7)),           # an int64 sum, every bit
    (32, 1 << 12, (19, 2)),
    (D7[0], MIN_CAPACITY, (24, 1)),  # the value itself, one int32 plane
    (2, 1 << 22, (9, 1)),            # a count of rows
    (64, 1 << 17, (14, 5)),
    (64, 3, (29, 3)),                # rows between powers of two round up
])
def test_limb_plan_at_the_real_shapes(bits, rows, want):
    """k and b by the type's bits and the rows; every limb's worst sum (all
    rows in one slot) inside its int32 accumulator; the limbs hold the
    value's bits."""
    plan = S.limb_plan(bits, rows)
    assert (plan.bits, plan.limbs) == want
    b, k, cover = plan
    assert cover == min(k * b + 1, 64) >= min(bits, 64)
    if k > 1:
        assert rows * ((1 << b) - 1) < 1 << 31          # a low limb, unsigned
    top = cover - (k - 1) * b                           # the top limb, signed
    assert top <= b + 1 and rows * (1 << (top - 1)) <= 1 << 31


def test_limb_plan_refuses_what_does_not_pay(monkeypatch):
    """More limbs than one wide scatter is worth: None, the 64-bit scatter
    stays; so does it where a batch is too wide for any limb."""
    assert S.limb_plan(64, 1 << 22).limbs <= S.LIMBS_PAY_UP_TO
    monkeypatch.setattr(S, "LIMBS_PAY_UP_TO", 6)
    assert S.limb_plan(64, 1 << 22) is None
    assert S.limb_plan(D7[0], 1 << 22).limbs == 3
    assert S.limb_plan(2, 1 << 31) is None


def _np_sums(v, ids, nseg):
    want = np.zeros(nseg, np.int64)
    with np.errstate(over="ignore"):
        np.add.at(want, ids, v.astype(np.int64))
    return want


def _limb_sums(v, ids, nseg, bits, checked):
    f = jax.jit(lambda v, ids: S.seg_sum_limbs(v, ids, nseg, bits, checked))
    return np.asarray(f(jnp.asarray(v), jnp.asarray(ids)))


@pytest.mark.parametrize("sign", [1, -1], ids=["max", "min"])
def test_every_row_in_one_slot_at_query_65s_batch(sign):
    """The bound the plan rests on, at the real shape: 4,194,304 prices of
    +-(10^7 - 1) cents, all in one slot, three 9-bit limbs."""
    rows = 1 << 22
    v = np.full(rows, sign * (10 ** 7 - 1), np.int64)
    ids = np.full(rows, 3, np.int32)
    got = _limb_sums(v, ids, 5, *D7)
    assert got.tolist() == [0, 0, 0, sign * rows * (10 ** 7 - 1), 0]


_EDGE_CASES = {
    # bits, checked, rows, values drawn from
    "dec7_mixed_signs": (D7, 4096, lambda b: [-(10 ** 7 - 1), 10 ** 7 - 1, -1, 0, 1]),
    "dec7_limb_edges": (D7, 4096, lambda b: [
        s * ((1 << (i * b)) + d) for i in (1, 2) for d in (-1, 0, 1) for s in (1, -1)]),
    "dec17_merge": (D17, 2048, lambda b: [
        -(10 ** 17 - 1), 10 ** 17 - 1, 1 << b, -(1 << (2 * b)), (1 << (3 * b)) - 1]),
    "int64_extremes_wrap": ((64, False), 4096, lambda b: [
        np.iinfo(np.int64).max, np.iinfo(np.int64).min, -1, 1, 1 << 62]),
    "int32_physical": ((32, False), 512, lambda b: [
        np.iinfo(np.int32).max, np.iinfo(np.int32).min, -1, 1 << b]),
    "row_count": ((2, False), 1024, lambda b: [0, 1]),
}


@pytest.mark.parametrize("case", sorted(_EDGE_CASES))
def test_limb_sums_equal_numpys_wrapping_int64_sums(case):
    """Values on every limb's edge and at the type's extremes, uniform over
    a few slots, in one slot, and with a drop segment: the wrapping int64
    sums of numpy, which the host substrate's ``_bincount_i64`` gives too."""
    (bits, checked), rows, pool = _EDGE_CASES[case]
    rng = np.random.default_rng(len(case))
    pool = np.asarray(pool(S.limb_plan(bits, rows).bits), np.int64)
    v = rng.choice(pool, rows)
    for ids in (rng.integers(0, 7, rows), np.zeros(rows), rng.choice([2, 7], rows)):
        ids = ids.astype(np.int32)
        got = _limb_sums(v, ids, 8, bits, checked)
        assert got.tolist() == _np_sums(v, ids, 8).tolist()
        host = agg_exec._bincount_i64(ids, v, 8)
        assert got[:8].tolist() == host.tolist()


@pytest.mark.parametrize("bad", [1 << 27, -(1 << 27) - 1, 1 << 62,
                                 np.iinfo(np.int64).min])
def test_a_plane_that_breaks_its_declared_precision_sums_exactly(bad):
    """A DECIMAL(7,2) plane holding a value its three limbs at 4,096 rows
    (cover 2 x 19 + ... bits) or its one limb at 128 rows cannot hold: the
    guard takes the 64-bit scatter, and the sums are numpy's."""
    for rows in (128, 4096):
        rng = np.random.default_rng(rows)
        v = rng.integers(-(10 ** 7) + 1, 10 ** 7, rows, dtype=np.int64)
        v[rows // 3] = bad
        ids = rng.integers(0, 5, rows).astype(np.int32)
        assert S.limb_plan(D7[0], rows).cover < 64
        got = _limb_sums(v, ids, 6, *D7)
        assert got.tolist() == _np_sums(v, ids, 6).tolist()


def test_an_unchecked_promise_would_be_wrong():
    """What the guard is for: the same plane summed as if its declared
    precision held (``checked`` False) loses the value's high bits."""
    v = np.zeros(128, np.int64)
    v[5] = 1 << 40
    ids = np.zeros(128, np.int32)
    assert _limb_sums(v, ids, 2, D7[0], False)[0] != 1 << 40
    assert _limb_sums(v, ids, 2, D7[0], True)[0] == 1 << 40
