"""Per-task execution runtime: the batch pump.

Analog of the reference's NativeExecutionRuntime (native-engine/auron/src/
rt.rs:76-303): a task ships a TaskDefinition, the runtime builds the exec
tree, drives it on a background thread into a bounded queue (the reference
uses a 1-slot sync_channel inside a per-task tokio runtime, rt.rs:175-195),
and the host pulls batches one at a time (``next_batch`` — the analog of the
JNI nextBatch entry, exec.rs:122). Errors anywhere in the operator stream
are captured and re-raised on the consumer side (panic -> host-exception
relay, lib.rs:30-73); ``finalize`` cancels the stream, joins the thread and
hands back the harvested metric tree (metrics.rs:7-35).
"""

from __future__ import annotations

import queue
import threading
from typing import Iterator

import pyarrow as pa

from auron_tpu import obs
from auron_tpu.columnar.batch import Batch
from auron_tpu.exec.base import ExecOperator, ExecutionContext, TaskCancelled
from auron_tpu.exec.metrics import MetricNode
from auron_tpu.proto import plan_pb2 as pb
from auron_tpu.utils.config import TOKIO_EQUIV_PREFETCH_DEPTH, Configuration, conf_scope

_END = object()


class TaskRuntime:
    def __init__(
        self,
        task: pb.TaskDefinition | bytes,
        resources: dict | None = None,
        shared: dict | None = None,
    ):
        if isinstance(task, (bytes, bytearray)):
            t = pb.TaskDefinition()
            t.ParseFromString(bytes(task))
            task = t
        from auron_tpu.plan.planner import task_from_proto

        self.plan, stage_id, partition_id, conf = task_from_proto(task)
        self.ctx = ExecutionContext(
            stage_id=stage_id,
            partition_id=partition_id,
            conf=conf,
            metrics=MetricNode(self.plan.name),
            resources=resources or {},
            shared=shared,
        )
        # session-set obs knobs (mode / ring size) must apply BEFORE the
        # pump thread starts: a task that carries obs.mode=trace would
        # otherwise race its own mode switch — the pump's span __enter__
        # could still see mode off and the whole task would record
        # span-less (trace_id 0), the exact misattribution this
        # subsystem exists to prevent
        obs.apply_conf(conf)
        # span attribution for the pump thread (docs/observability.md):
        # capture the CALLER's span here (call_native runs on the query's
        # thread), and resolve the owning trace from the conf-threaded
        # obs.trace.id — the R7 hand-off that keeps a task dispatched
        # from a foreign thread attributed to its query
        self._obs_parent = obs.current_span()
        self._obs_trace = obs.get_trace(conf.get(obs.OBS_TRACE_ID))
        if self._obs_trace is None and self._obs_parent is not None:
            self._obs_trace = self._obs_parent.trace
        depth = conf.get(TOKIO_EQUIV_PREFETCH_DEPTH)
        self._queue: queue.Queue = queue.Queue(maxsize=max(depth, 1))
        self._error: BaseException | None = None
        self._finalized = False
        # flipped by the first next_arrow(): the pump then starts the
        # device->host copy of each batch BEFORE enqueueing it, so the
        # consumer's to_arrow finds the bytes already landed (the copy
        # overlaps the next batch's device compute instead of stalling
        # inside device_get — the pump-side half of the async transfer
        # window, runtime/transfer.py)
        self._host_prefetch = False
        self._thread = threading.Thread(target=self._pump, daemon=True, name="auron-task-pump")
        self._thread.start()

    # ------------------------------------------------------------------

    def _pump(self) -> None:  # auronlint: thread-root(conf-scoped) -- task pump thread; installs conf_scope(self.ctx.conf) before touching engine code
        from auron_tpu.utils.logging import clear_task_context, set_task_context

        try:
            # INSIDE the try: if context installation itself raises, the
            # finally below must still enqueue _END — a pump that dies
            # before the sentinel leaves next_batch blocked forever (R12)
            set_task_context(self.ctx.stage_id, self.ctx.partition_id)
            with conf_scope(self.ctx.conf), obs.span(
                f"task s{self.ctx.stage_id}p{self.ctx.partition_id}",
                cat="task", parent=self._obs_parent, trace=self._obs_trace,
                arg={"stage": self.ctx.stage_id,
                     "partition": self.ctx.partition_id},
            ):
                # INVARIANT: no compiled program launched from a pump may
                # carry a host callback (pure_callback) — concurrent
                # callback-bearing XLA:CPU computations wedge the intra-op
                # pool (reproduced; tests/test_runtime.py concurrent-
                # hostsort test). Host sorts therefore compute their order
                # EAGERLY and pass it into the jit as data
                # (ops/segments.py host_order).
                from auron_tpu.utils.profiling import EngineCounters

                counters = EngineCounters._installed
                # one pump:batch span per PULL of the operator tree: the
                # loop drives the iterator with next() inside the span, so
                # the region holds the tree's host work for one batch and
                # never spans a yield (a region must nest on its thread).
                # The last pull, which ends the stream, is a span too: a
                # shuffle-writing task does its writes there
                batches = iter(self.plan.execute(self.ctx.partition_id,
                                                 self.ctx))
                while True:
                    with obs.span("batch", cat="pump"):
                        batch = next(batches, _END)
                        if batch is not _END and self._host_prefetch:
                            batch.prefetch_host()
                    if batch is _END:
                        break
                    if counters is not None:
                        # per-batch denominator for sync-budget checks
                        # (tools/perfcheck.py); no-op unless profiling is on
                        counters.note_batch()
                    obs.note_pump_batch()
                    with obs.span("queue_put", cat="wait"):
                        self._queue.put(batch)
        except TaskCancelled:
            pass
        except BaseException as e:  # noqa: BLE001 — relayed to the consumer
            self._error = e  # auronlint: guarded-by(self._queue) -- published BEFORE the _END sentinel; the consumer reads it only after get() returns _END (queue happens-before)
        finally:
            clear_task_context()
            self._queue.put(_END)

    def _check_error(self) -> None:
        if self._error is not None:
            # auronlint: guarded-by(self._queue) -- consumer side of the pump's error relay: only reached after get() returned _END, which the pump enqueues AFTER the write (queue happens-before)
            err, self._error = self._error, None
            raise RuntimeError(
                f"task stage={self.ctx.stage_id} partition={self.ctx.partition_id} failed"
            ) from err

    # ------------------------------------------------------------------

    def next_batch(self) -> Batch | None:
        """Next device batch, or None at end of stream."""
        if self._finalized:
            return None
        with obs.span("queue_get", cat="wait"):
            item = self._queue.get()
        if item is _END:
            self._check_error()
            return None
        return item

    def next_arrow(self) -> pa.RecordBatch | None:
        """Next batch materialized to Arrow — the host FFI boundary.
        Signals the pump to prefetch device->host copies for every
        subsequent batch (this consumer is going to materialize them all)."""
        self._host_prefetch = True
        b = self.next_batch()
        return None if b is None else b.to_arrow()

    def __iter__(self) -> Iterator[Batch]:
        while (b := self.next_batch()) is not None:
            yield b

    def finalize(self) -> dict:
        """Cancel, drain, join; returns the metric-tree snapshot."""
        self._finalized = True
        self.ctx.cancel()
        # keep draining so the pump can observe cancellation instead of
        # blocking on a full queue
        deadline = 30.0
        while self._thread.is_alive() and deadline > 0:
            try:
                while True:
                    self._queue.get_nowait()
            except queue.Empty:
                pass
            self._thread.join(timeout=0.05)
            deadline -= 0.05
        self._check_error()
        snap = self.ctx.metrics.snapshot()
        if self._obs_trace is not None:
            # fold this task's metric rollup into the owning query trace
            self._obs_trace.add_task_metrics(snap)
        return snap


# auronlint: thread-owned -- _error/exhausted are written by the pump while it lives and by stop() only after Thread.join() (sequential handoff); status() readers never write
class StreamTaskRuntime:
    """Long-running pump for a continuous streaming pipeline
    (auron_tpu/stream): the batch TaskRuntime's shape — one daemon
    thread owning the engine work, conf-scoped, error relayed to the
    owner — but the loop is ``pipeline.step()`` forever instead of
    draining a finite operator tree, and the consumer-facing surface is
    ``status()``/``stop()`` instead of a batch queue (emissions leave
    through the pipeline's sink, not through here).

    The whole stream runs under ONE query trace named
    ``stream.<view>``: the pipeline's per-emission and per-checkpoint
    spans (watermark, lag, emit_seq) attribute to it, and the summary
    lands on /queries when the stream ends.
    """

    def __init__(self, pipeline, name: str | None = None):
        self.pipeline = pipeline
        self.name = name or pipeline.plan.name
        obs.apply_conf(pipeline.conf)
        self._stop = threading.Event()
        self._error: BaseException | None = None
        self.exhausted = False
        self._thread = threading.Thread(
            target=self._pump_stream, daemon=True,
            name=f"auron-stream-{self.name}")
        self._thread.start()

    def _pump_stream(self) -> None:  # auronlint: thread-root(conf-scoped) -- stream pump thread; installs conf_scope(pipeline.conf) before driving the engine
        try:
            with conf_scope(self.pipeline.conf), obs.query_trace(
                f"stream.{self.name}", conf=self.pipeline.conf
            ):
                while not self._stop.is_set():
                    if not self.pipeline.step():
                        self.exhausted = True
                        return
        except BaseException as e:  # noqa: BLE001 — relayed via status()/stop()
            self._error = e

    # ------------------------------------------------------------------

    def status(self) -> dict:
        """Live stream state for /stream inspect: progress counters,
        watermark, and the error (if the pump died)."""
        p = self.pipeline
        return {
            "name": self.name,
            "alive": self._thread.is_alive(),
            "exhausted": self.exhausted,
            "steps": p.steps,
            "emit_seq": p.emit_seq,
            "watermark_ms": p.tracker.watermark_ms,
            "open_groups": len(p.store),
            "checkpoints": p.ckpt_seq,
            "metrics": dict(p.metrics),
            "error": repr(self._error) if self._error is not None else None,
        }

    def stop(self, timeout: float = 30.0, drain: bool = False) -> dict:
        """Stop the pump, close the pipeline, return the final status.
        ``drain=True`` force-closes all open windows first (finite
        sources / orderly shutdown)."""
        self._stop.set()
        self._thread.join(timeout=timeout)
        if drain and self._error is None and not self._thread.is_alive():
            self.pipeline.drain()
        try:
            self.pipeline.close()
        except BaseException as e:  # noqa: BLE001 — surfaced below with the pump error taking precedence
            if self._error is None:
                self._error = e
        st = self.status()
        if self._error is not None:
            err, self._error = self._error, None
            raise RuntimeError(f"stream {self.name} failed") from err
        return st
