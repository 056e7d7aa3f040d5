"""Unit coverage for the sync-free pipeline pieces: the selectivity
predictor and the compaction boundary that drives it (exec/selectivity.py)
and the async transfer window (runtime/transfer.py)."""

import jax.numpy as jnp
import numpy as np
import pytest

from auron_tpu.columnar import batch as batch_mod
from auron_tpu.columnar.batch import compaction_bucket, lookup_compare_width
from auron_tpu.exec import selectivity as sel_mod
from auron_tpu.exec.metrics import MetricNode
from auron_tpu.exec.selectivity import (
    CompactionBoundary, SelectivityPredictor, TakePlan,
)
from auron_tpu.runtime.transfer import TransferWindow, harvest
from auron_tpu.utils.config import TRANSFER_WINDOW_DEPTH, Configuration


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


@pytest.mark.parametrize("chip, n_live, search_capacity, want", [
    # the chip, a build with a LUT: int32 compares against ONE gathered
    # element a row (PERF.md section 5, unit costs); the ladder's three
    # widths, and nothing over it
    (True, 1, None, 64), (True, 18, None, 64), (True, 64, None, 64),
    (True, 65, None, 256), (True, 180, None, 256), (True, 256, None, 256),
    (True, 257, None, 1024), (True, 1024, None, 1024),
    (True, 1025, None, None),
    (True, 6000, None, None),               # text 3's date level: the LUT
    # the chip, sorted words: 64-bit compares against a gathered element
    # for every bit of the build's capacity
    (True, 30, 131072, 64), (True, 1024, 131072, 1024),
    (True, 1025, 131072, None),
    # XLA:CPU gathers cheaply and compares dearly: a LUT always stays, a
    # search gives way to the least width over a build of 2^23 rows
    (False, 1, None, None), (False, 18, None, None), (False, 64, None, None),
    (False, 30, 131072, None), (False, 30, 4194304, None),
    (False, 30, 8388608, 64), (False, 65, 8388608, None),
])
def test_lookup_rule_breaks_even_by_unit_costs(monkeypatch, chip, n_live,
                                               search_capacity, want):
    monkeypatch.setattr(batch_mod, "_gather_bound", lambda: chip)
    assert lookup_compare_width(n_live, search_capacity) == want


@pytest.mark.parametrize("costs, n_live, search_capacity, want", [
    # compare iff width x (a compare-select) < gathers x (a gathered element)
    ((8.0, 0.125, 0.25), 60, None, None),        # 64 x 0.125 == 8: no gain
    ((8.0, 0.124, 0.25), 60, None, 64),
    ((8.0, 0.01, 0.25), 200, None, 256),
    ((8.0, 0.04, 0.25), 200, None, None),        # 256 x 0.04 > 8
    ((8.0, 0.01, 0.25), 60, 1024, 64),           # 64 x 0.25 < 10 x 8
    ((8.0, 0.01, 0.25), 200, 128, None),         # 256 x 0.25 > 7 x 8
    ((8.0, 0.01, 0.25), 200, 256, None),         # == 8 x 8: no gain
    ((8.0, 0.01, 0.25), 200, 257, 256),          # nine passes
])
def test_lookup_rule_is_one_inequality_over_the_costs(monkeypatch, costs,
                                                      n_live, search_capacity,
                                                      want):
    """The rule itself, on made-up unit costs; and it reads no option."""
    from auron_tpu.utils.config import generate_doc

    monkeypatch.setattr(batch_mod, "_lookup_costs", lambda: costs)
    assert lookup_compare_width(n_live, search_capacity) == want
    assert not [line for line in generate_doc().splitlines()
                if line.startswith("| `") and "lookup" in line.split("|")[1]]


def test_predictor_seeds_then_predicts_and_grows_immediately():
    p = SelectivityPredictor()
    assert p.predict(1 << 20) is None              # no history: seed path
    p.observe(100)
    b1 = p.predict(1 << 20)
    assert b1 is not None and b1 >= 128
    # overflow -> immediate growth (never two repairs for one regime shift)
    p.observe(50_000, predicted=b1)
    assert p.mispredicts == 1
    assert p.predict(1 << 20) >= 50_000


def test_predictor_shrinks_only_after_patience():
    p = SelectivityPredictor(alpha=1.0, headroom=1.0, patience=3)
    p.observe(10_000)
    big = p.predict(1 << 20)
    p.observe(10)   # 1 low batch
    assert p.predict(1 << 20) == big
    p.observe(10)   # 2
    assert p.predict(1 << 20) == big
    p.observe(10)   # 3 -> shrink
    assert p.predict(1 << 20) < big


def test_predictor_clamped_to_input_capacity():
    p = SelectivityPredictor()
    p.observe(1 << 20)
    assert p.predict(1024) <= 1024


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


class _RecordingPredictor(SelectivityPredictor):
    """The real predictor, with every call written down."""

    def __init__(self):
        super().__init__()
        self.predicts, self.observes = [], []

    def predict(self, in_capacity):
        self.predicts.append(in_capacity)
        return super().predict(in_capacity)

    def observe(self, n_live, predicted=None):
        self.observes.append((n_live, predicted))
        super().observe(n_live, predicted)


K8, K64 = 8192, 65536   # the quarter rule compacts into <= 2048 / <= 16384

# name: (capacity, window depth, live counts, planned upstream,
#        takes in call order as (batch, mode, out_cap),
#        batches ready after each submit, batches drained, mispredicts)
_PROTOCOL = {
    # no observation yet: read, observe, take at the count's own bucket and
    # emit at once; nothing enters the window
    "seed_emits_at_once": (
        K8, 4, [100], False,
        [(0, "seed", 128)], [[0]], [], 0),
    # a bucket that pays is taken at dispatch and rides the window
    "predicted_rides_the_window": (
        K8, 2, [100, 100, 100, 100], False,
        [(0, "seed", 128), (1, "compact", 256), (2, "compact", 256),
         (3, "compact", 256)],
        [[0], [], [], [1]], [2, 3], 0),
    # a bucket too wide to pay: nothing is taken until the batch's own
    # count lands, which then decides dense or compact
    "too_wide_waits_for_its_count": (
        K8, 1, [4000, 4000, 50], False,
        [(0, "seed", None), (1, "dense", None), (2, "compact", 128)],
        [[0], [], [1]], [2], 0),
    # PR 29's finding (1): the batches behind a burst hold nothing while
    # the bucket waits out its shrink patience. One gather at capacity (the
    # burst's repair), not one a batch
    "burst_then_empty": (
        K8, 1, [10, 4000, 0, 0, 0, 0], False,
        [(0, "seed", 128), (1, "compact", 128), (2, "compact", 128),
         (1, "repair", None), (3, "compact", 128), (4, "compact", 128),
         (5, "compact", 128)],
        [[0], [], [1], [2], [3], [4]], [5], 1),
    # an overflow is repaired once a batch, from the state the window held,
    # and the very next plan is the grown bucket
    "mispredict_repairs_and_grows": (
        K64, 1, [100, 3000, 3000, 3000], False,
        [(0, "seed", 128), (1, "compact", 256), (2, "compact", 256),
         (1, "repair", 4096), (3, "compact", 4096), (2, "repair", 4096)],
        [[0], [], [1], [2]], [3], 2),
    "drain_is_fifo": (
        K8, 4, [100] * 7, False,
        [(0, "seed", 128)] + [(i, "compact", 256) for i in range(1, 7)],
        [[0], [], [], [], [], [1], [2]], [3, 4, 5, 6], 0),
    # the fused stage plans (and takes) upstream: the boundary predicts
    # once a batch all the same and takes only what the stage could not
    "upstream_plan_is_not_predicted_again": (
        K64, 1, [100, 100, 3000], True,
        [(0, "seed", 128), (2, "repair", 4096)],
        [[0], [], [1]], [2], 1),
}


@pytest.mark.parametrize("name", list(_PROTOCOL))
def test_boundary_protocol(monkeypatch, name):
    """CompactionBoundary under a fake ``take`` and host scalars for the
    live counts: which takes it asks for and when, what it emits and in
    what order, and that every batch is predicted once and observed once."""
    capacity, depth, live, upstream, want_takes, want_ready, want_drained, \
        want_mispredicts = _PROTOCOL[name]
    monkeypatch.setattr(sel_mod, "SelectivityPredictor", _RecordingPredictor)
    conf = Configuration()
    conf.set(TRANSFER_WINDOW_DEPTH.key, depth)
    metrics = MetricNode("join")
    boundary = CompactionBoundary(conf, compaction_bucket, metrics)
    takes = []

    def take_of(i):
        def take(mode, out_cap):
            takes.append((i, mode, out_cap))
            return (i, mode, out_cap)
        return take

    ready, emitted = [], []
    for i, n in enumerate(live):
        plan = taken = None
        if upstream:
            plan = boundary.plan_take(capacity)
            assert isinstance(plan, TakePlan)
            if plan.cap is not None:
                taken = (i, "stage", plan.cap)
                takes.append(taken)
        out = boundary.offer(
            np.int32(n), capacity, take_of(i), i, plan, taken)
        emitted += out
        ready.append([state for state, _ in out])
    tail = list(boundary.drain())
    drained = [state for state, _ in tail]
    # a batch is emitted with what its LAST take returned
    for state, got in emitted + tail:
        assert got == [t for t in takes if t[0] == state][-1]
    takes = [t for t in takes if t[1] != "stage"]
    assert takes == want_takes
    assert ready == want_ready
    assert drained == want_drained
    assert [s for r in ready for s in r] + drained == list(range(len(live)))
    assert list(boundary.drain()) == []
    pred = boundary._pred
    assert pred.predicts == [capacity] * len(live)       # once a batch
    assert [n for n, _ in pred.observes] == live         # once, in order
    assert boundary.predictions == len(live) - 1
    assert pred.mispredicts == want_mispredicts
    assert metrics.values.get("sel_mispredicts", 0) == want_mispredicts


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
