"""Unit coverage for the sync-free pipeline pieces: the selectivity
predictor (exec/selectivity.py) and the async transfer window
(runtime/transfer.py)."""

import jax.numpy as jnp
import pytest

from auron_tpu.columnar import batch as batch_mod
from auron_tpu.columnar.batch import compaction_bucket
from auron_tpu.exec.selectivity import SelectivityPredictor, predictor_enabled
from auron_tpu.runtime.transfer import TransferWindow, harvest
from auron_tpu.utils.config import (
    Configuration,
    JOIN_COMPACT_OUTPUT,
    SELECTIVITY_EWMA_ALPHA,
    SELECTIVITY_HEADROOM,
    SELECTIVITY_PREDICTOR_ENABLE,
    SELECTIVITY_SHRINK_PATIENCE,
)


def _conf(**kv):
    c = Configuration()
    for k, v in kv.items():
        c.set(k, v)
    return c


def test_compaction_bucket_policy():
    # the one shared dense-vs-compact threshold (chain + driver + predictor)
    assert compaction_bucket(100, 1024) == 128
    assert compaction_bucket(0, 1024) == 128       # clamp to min bucket
    assert compaction_bucket(200, 1024) == 256
    assert compaction_bucket(300, 1024) is None    # 512*4 > 1024: dense
    assert compaction_bucket(100, 128) is None     # tiny batch: dense


M4, M1 = 4194304, 1048576


@pytest.mark.parametrize("chip, capacity, n_live, dense, taken, want", [
    # the chip counts gathered elements: bucket * (bits(capacity) + taken)
    # against capacity * dense (PERF.md, unit costs)
    # query 3's date probe: 650 k live of 4,194,304, two build planes
    (True, M4, 650_000, 2, 8, None),
    (True, M4, 200_000, 2, 8, 262144),      # a sixteenth: 2^18 x 30 <= 2^22 x 2
    (True, M4, 262_145, 2, 8, None),        # the next bucket up: dense
    # its item probe: a thousand live, four build planes
    (True, M4, 1_000, 4, 10, 1024),
    (True, M4, 0, 4, 10, 128),              # nothing alive: the least bucket
    # more build planes move the break-even up, more probe planes down
    (True, M4, 500_000, 4, 10, 524288),     # 2^19 x 32 == 2^22 x 4: the edge
    (True, M4, 500_000, 3, 10, None),       # 2^19 x 32 > 2^22 x 3
    (True, M4, 200_000, 2, 46, None),       # a 23-column probe side
    (True, M1, 150, 4, 12, 256),            # the chain's batch, two levels
    (True, M1, 70_000, 4, 12, 131072),      # 2^17 x 32 == 2^20 x 4
    (True, M1, 140_000, 4, 12, None),       # 2^18 x 32 > 2^20 x 4
    (True, 1024, 100, 2, 8, None),          # a tiny batch never pays there
    (True, 8192, 100, 2, 8, 128),           # 128 x 21 <= 8192 x 2
    # XLA:CPU keeps its measured quarter, whatever the planes
    (False, M4, 650_000, 2, 8, 1048576),
    (False, M4, 1_048_577, 2, 8, None),
    (False, 1024, 100, 2, 8, 128),
    # no planes named (the aggregate's boundary): the quarter on both
    (True, M4, 650_000, None, 0, 1048576),
    (True, M4, 1_048_577, None, 0, None),
    (False, M4, 650_000, None, 0, 1048576),
])
def test_compaction_bucket_rule_over_shapes(monkeypatch, chip, capacity,
                                            n_live, dense, taken, want):
    monkeypatch.setattr(batch_mod, "_gather_bound", lambda: chip)
    assert compaction_bucket(n_live, capacity, dense, taken) == want


def test_predictor_seeds_then_predicts_and_grows_immediately():
    p = SelectivityPredictor(_conf())
    assert p.predict(1 << 20) is None              # no history: seed path
    p.observe(100)
    b1 = p.predict(1 << 20)
    assert b1 is not None and b1 >= 128
    # overflow -> immediate growth (never two repairs for one regime shift)
    p.observe(50_000, predicted=b1)
    assert p.mispredicts == 1
    assert p.predict(1 << 20) >= 50_000


def test_predictor_shrinks_only_after_patience():
    c = _conf(**{SELECTIVITY_SHRINK_PATIENCE.key: 3,
                 SELECTIVITY_EWMA_ALPHA.key: 1.0,
                 SELECTIVITY_HEADROOM.key: 1.0})
    p = SelectivityPredictor(c)
    p.observe(10_000)
    big = p.predict(1 << 20)
    p.observe(10)   # 1 low batch
    assert p.predict(1 << 20) == big
    p.observe(10)   # 2
    assert p.predict(1 << 20) == big
    p.observe(10)   # 3 -> shrink
    assert p.predict(1 << 20) < big


def test_predictor_clamped_to_input_capacity():
    p = SelectivityPredictor(_conf())
    p.observe(1 << 20)
    assert p.predict(1024) <= 1024


def test_predictor_enabled_knob_follows_compaction():
    on = _conf(**{SELECTIVITY_PREDICTOR_ENABLE.key: "on"})
    off = _conf(**{SELECTIVITY_PREDICTOR_ENABLE.key: "off"})
    auto_off = _conf(**{JOIN_COMPACT_OUTPUT.key: "off"})
    assert predictor_enabled(on)
    assert not predictor_enabled(off)
    assert not predictor_enabled(auto_off)


def test_transfer_window_fifo_and_depth():
    w = TransferWindow(2)
    got = []
    for i in range(6):
        got += w.push((jnp.int32(i),), f"p{i}")
    # depth 2: pushes 3..6 each evict the oldest
    assert [pl for _, pl in got] == ["p0", "p1", "p2", "p3"]
    got += list(w.drain())
    assert [pl for _, pl in got] == [f"p{i}" for i in range(6)]
    assert [int(r[0]) for r, _ in got] == list(range(6))
    assert len(w) == 0


def test_transfer_window_empty_arrays_and_harvest():
    w = TransferWindow(1)
    out = w.push((), "a") + w.push((), "b")
    assert [pl for _, pl in out] == ["a"]
    (v,) = harvest(jnp.arange(3))
    assert list(v) == [0, 1, 2]


def test_predictor_enabled_auto_follows_compaction_auto():
    """The predictor's auto arm resolves through the compaction knob's
    OWN tri-state (resolve_tri composition, not a manual == chain): with
    both knobs at auto on the CPU backend, compaction is on, so the
    predictor is too; forcing compaction on keeps it on."""
    assert predictor_enabled(_conf())  # both auto -> CPU -> on
    assert predictor_enabled(_conf(**{JOIN_COMPACT_OUTPUT.key: "on"}))


@pytest.mark.parametrize("n", [128, 1024, 2048, 8192, 1 << 17])
def test_running_count_equals_the_flat_cumsum(n):
    """compaction_index's running count in two levels (rows of 1,024, then
    their totals) is the flat cumsum's integers, at every density, and the
    index built on it names the live rows in order."""
    import numpy as np

    from auron_tpu.columnar.batch import _running_count, compaction_index

    rng = np.random.default_rng(n)
    for density in (0.0, 0.003, 0.5, 1.0):
        mask = rng.random(n) < density
        got = np.asarray(_running_count(jnp.asarray(mask)))
        assert got.dtype == np.int32
        np.testing.assert_array_equal(got, np.cumsum(mask))
        idx, sel_out = compaction_index(jnp.asarray(mask), 128)
        live = np.flatnonzero(mask)[:128]
        np.testing.assert_array_equal(np.asarray(idx)[:len(live)], live)
        assert int(np.asarray(sel_out).sum()) == len(live)
