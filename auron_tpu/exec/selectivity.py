"""The compaction boundary: sync-free compaction of sparse join outputs.

A join's output boundary used to block on ``device_get(sel)`` every batch
just to learn the live count and pick an output capacity bucket, the
dominant host-coordination tax of a selective star join. Steady-state
selectivity is highly autocorrelated across batches of one stream, so the
bucket is *predictable*:

- ``SelectivityPredictor`` keeps an EWMA of observed live counts and
  predicts the next batch's compacted capacity bucket with a headroom
  multiplier (absorbs noise) and shrink hysteresis (a bucket only shrinks
  after ``patience`` consecutive low-demand batches, so oscillating
  selectivity doesn't thrash jit shapes);
- ``CompactionBoundary`` owns one probe stream's predictor, its k-deep
  ``runtime/transfer.TransferWindow`` and the protocol between them:
  compact INTO the predicted bucket entirely on device, read the actual
  live count asynchronously k batches later, repair a too-small bucket at
  harvest, before the batch is emitted downstream, by re-taking at the
  correct bucket from the still-held device state: results are
  bit-identical to a blocking read a batch.

Every join that compacts (the unique-build probe of BHJ and SMJ, eager or
behind a fused stage, and the fused star chain) drives one boundary with
its own ``take`` callback; nothing else calls ``predict``, ``observe``,
``push`` or ``drain`` for a join (docs/pipeline.md section 1). The
aggregate's dense arm (exec/agg_exec.py ``HashAggExec._execute``) is the
boundary's fourth client: its ``take`` is ``compact_batch`` of the columns
the fold reads, and what the boundary emits is folded into the dense
table, so a batch folds at the bucket of its live rows and an empty one
not at all. The partial aggregate's deferred arm keeps its own halves
(ROADMAP D2 says why).
"""

from __future__ import annotations

from typing import Any, Callable, Iterator, NamedTuple

import jax

from auron_tpu.columnar.batch import bucket_capacity
from auron_tpu.runtime.transfer import TransferWindow
from auron_tpu.utils.config import TRANSFER_WINDOW_DEPTH

# one value each has ever been in use outside the unit tests
EWMA_ALPHA = 0.3        # weight of the newest batch's live count
HEADROOM = 1.5          # bucket margin over the EWMA: absorbs batch noise
SHRINK_PATIENCE = 4     # consecutive low batches before a bucket shrinks


# auronlint: thread-owned -- one predictor per operator instance, driven by the single thread executing that query's batch stream (pump or serving thread, never both at once)
class SelectivityPredictor:
    """EWMA live-count tracker -> predicted compaction capacity bucket.

    ``observe`` feeds every batch's actual live count; ``predict`` returns
    the capacity bucket the next batch should compact into, or None before
    the first observation (caller takes the blocking path once).
    Growth is immediate (an overflow already cost a repair — never two);
    shrinking waits out ``patience`` consecutive low batches."""

    def __init__(self, alpha: float = EWMA_ALPHA, headroom: float = HEADROOM,
                 patience: int = SHRINK_PATIENCE):
        self.alpha = alpha
        self.headroom = headroom
        self.patience = patience
        self.ewma: float | None = None
        self._bucket: int | None = None
        self._low_streak = 0
        # the count observed since the last prediction (None: none yet)
        self.observed: int | None = None
        # counters surfaced in operator metrics / tests
        self.predictions = 0
        self.mispredicts = 0

    def predict(self, in_capacity: int) -> int | None:
        """Predicted live-count capacity bucket for the next batch, or None
        before the first observation (the caller then takes the blocking
        path once to seed the EWMA). The caller applies the shared
        ``compaction_bucket`` threshold to decide compact-vs-dense — a
        dense prediction still emits WITHOUT a sync."""
        self.observed = None
        if self._bucket is None:
            return None
        self.predictions += 1
        return min(self._bucket, bucket_capacity(max(in_capacity, 1)))

    def observe(self, n_live: int, predicted: int | None = None) -> None:
        """Feed one batch's actual live count. ``predicted`` is the bucket
        the batch was compacted into (None = blocking/dense path) — an
        overflow there counts as a mispredict."""
        self.observed = n_live
        if predicted is not None and n_live > predicted:
            self.mispredicts += 1
        self.ewma = (
            float(n_live)
            if self.ewma is None
            else self.alpha * n_live + (1.0 - self.alpha) * self.ewma
        )
        want = bucket_capacity(max(int(self.ewma * self.headroom), n_live, 1))
        if self._bucket is None or want > self._bucket:
            self._bucket = want          # grow immediately
            self._low_streak = 0
        elif want <= self._bucket // 2:
            self._low_streak += 1        # shrink with hysteresis
            if self._low_streak >= self.patience:
                self._bucket = max(want, bucket_capacity(1))
                self._low_streak = 0
        else:
            self._low_streak = 0


class TakePlan(NamedTuple):
    """What one batch does at dispatch, from the one ``predict`` call it
    gets: ``seed`` (no observation yet: read the count now), else compact
    at ``cap`` now, or (``cap`` None) look up only and let the batch's own
    count decide at harvest."""

    seed: bool
    cap: int | None


class CompactionBoundary:
    """One probe stream's compaction protocol: predict -> take -> push ->
    harvest -> observe exactly once -> repair.

    ``bucket_of(n_live, capacity)`` is the join's bucket rule
    (``columnar.batch.compaction_bucket`` with that join's plane counts).
    ``offer`` is given the device scalar holding a batch's live count and
    a ``take(mode, out_cap)`` callback that gathers the batch's output at
    the bucket ``out_cap`` (None: dense, at the batch's capacity) and
    notes the take in the rings under ``mode``:

    ==========  ==========================================================
    ``seed``    no observation yet: the count is read here (eight bytes,
                once a stream: waiting for the first harvest instead
                leaves a stream no longer than the window unseeded to its
                end), observed, and the batch taken at the count's own
                bucket and emitted at once. Exact, so it never repairs and
                need not ride the window, which is still empty.
    ``compact`` the predicted bucket pays: taken at it at dispatch, no
                host read; or, for a batch that waited, taken at harvest
                at its own count's bucket.
    ``dense``   a batch that waited, whose own count stays dense.
    ``repair``  the count overflowed the predicted bucket (rows were
                truncated): one re-take at the count's bucket from the
                state the window held. No extra read.
    ==========  ==========================================================

    A batch waits where the rule says its predicted bucket is too wide to
    pay: a wrong "dense" costs a whole capacity of gathers for a batch
    that may hold nothing (the batches behind a burst, while the
    predictor's bucket waits out its shrink patience), and the batch
    stays in the window until its count lands anyway, so the count itself
    decides there.

    Emission lags dispatch by up to the window's depth and stays FIFO; the
    consumer MUST ``drain`` after its last batch.

    ``live`` is the count the boundary has read of the batch it is taking
    or has just emitted (the seed's read, a harvest), None while a batch
    is taken at its predicted bucket at dispatch: a consumer that can do
    without a batch of no rows (the dense aggregate) looks there."""

    def __init__(self, conf, bucket_of: Callable[[int, int], "int | None"],
                 metrics=None):
        self._pred = SelectivityPredictor()
        self._window = TransferWindow(conf.get(TRANSFER_WINDOW_DEPTH))
        self._bucket_of = bucket_of
        self._metrics = metrics

    @property
    def predictions(self) -> int:
        return self._pred.predictions

    @property
    def live(self) -> int | None:
        return self._pred.observed

    def plan_take(self, capacity: int) -> TakePlan:
        """This batch's ONE ``predict`` call. A consumer that dispatches
        upstream of ``offer`` (the fused probe stage, whose program is
        traced per take) asks here and hands the plan back to ``offer``."""
        pred_cap = self._pred.predict(capacity)
        if pred_cap is None:
            return TakePlan(True, None)
        return TakePlan(False, self._bucket_of(pred_cap, capacity))

    def offer(self, live, capacity: int, take: Callable, state: Any,
              plan: TakePlan | None = None, taken: Any = None,
              ) -> list[tuple[Any, Any]]:
        """Dispatch one batch; returns the ``(state, taken)`` of every
        batch that is ready to emit, oldest first. ``plan`` and ``taken``
        come from a consumer that planned (and, for a ``cap``, took)
        upstream."""
        if plan is None:
            plan = self.plan_take(capacity)
        if plan.seed:
            # auronlint: disable=R9 -- first batch of a stream only: plan.seed is true only before the first observation
            n_live = int(jax.device_get(live))  # auronlint: sync-point(4/task) -- compaction seed read: the first batch's live count, once a boundary (a task's unique-build joins and its dense aggregate each drive one: query 65's last stage has three and one)
            self._pred.observe(n_live)
            return [(state, take("seed", self._bucket_of(n_live, capacity)))]
        if plan.cap is not None and taken is None:
            taken = take("compact", plan.cap)
        return [
            self._harvest(resolved, entry)
            for resolved, entry in self._window.push(
                (live,), (state, take, capacity, plan.cap, taken))
        ]

    def drain(self) -> Iterator[tuple[Any, Any]]:
        """End of stream: resolve what is still in flight, FIFO."""
        for resolved, entry in self._window.drain():
            yield self._harvest(resolved, entry)

    def _harvest(self, resolved, entry) -> tuple[Any, Any]:
        state, take, capacity, cap, taken = entry
        n_live = int(resolved[0])
        self._pred.observe(n_live, predicted=cap)
        if cap is None:
            out_cap = self._bucket_of(n_live, capacity)
            taken = take("dense" if out_cap is None else "compact", out_cap)
        elif n_live > cap:
            if self._metrics is not None:
                self._metrics.add("sel_mispredicts", 1)
            taken = take("repair", self._bucket_of(n_live, capacity))
        return state, taken
