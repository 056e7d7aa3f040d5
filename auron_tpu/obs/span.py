"""Query/task-scoped spans and per-query trace accumulators.

Span model (docs/observability.md): a ``Trace`` is one query's (or one
standalone task's) identity — an integer id threaded through the stack
the same way a task's ``Configuration`` is (R7 discipline): explicitly,
never via ambient thread state that a foreign thread would misread. A
``Span`` is one timed region inside a trace (sql.parse, a task pump, a
spill). The *current* span rides a ``contextvars.ContextVar`` so
everything running on the opening thread attributes automatically;
crossing a thread hop requires an explicit hand-off:

- same thread / nested calls         -> nothing to do (contextvar)
- task dispatch (bridge call_native) -> TaskRuntime captures the caller's
  span and re-installs it on the pump thread (runtime/task.py)
- spill dispatch                     -> MemManager captures the OWNING
  task's span at consumer registration and installs it around spill()
  (memory/memmgr.py), so a spill performed by a foreign thread still
  lands in the owner's trace
- async-transfer harvest             -> TransferWindow captures the span
  at push() and installs it at harvest (runtime/transfer.py)
- spill containers                   -> carry the owning conf, and with
  it ``obs.trace.id`` (conf-id attribution, no live Span needed)

Layers: a span's ``cat`` is its LAYER, one of the closed set ``LAYERS``
(the rows of PERF.md section 3). Every span is also a region on the
profiler's clock — a ``jax.profiler.TraceAnnotation`` named
``auron:<layer>:<name>`` — so the host timeline these rings keep and the
``/host:CPU`` plane of a ``jax.profiler`` trace can be laid over each
other. A region nests on its thread: a span NEVER stays open across a
``yield``.

Every accumulator mutation on a ``Trace`` takes the trace's own lock:
events arrive from pump threads, spill threads and harvest threads
concurrently (the R8 contract; the lesson of the ``sync_sites`` race
this PR also fixes in utils/profiling.py).
"""

from __future__ import annotations

import contextvars
import itertools
import threading
import time
from collections import deque

from auron_tpu.obs import core

_span_var: contextvars.ContextVar = contextvars.ContextVar(
    "auron_obs_span", default=None
)

#: the closed set of layers (docs/observability.md). ``sync`` and
#: ``compile`` spans are opened by the EngineCounters hooks
#: (utils/profiling.py) around each host read and each compile
LAYERS = frozenset({
    "entry", "plan", "pump", "exchange", "wait", "sync", "compile", "spill",
    "sql", "stream", "task", "query", "serve",
})

#: ``jax.profiler.TraceAnnotation``, imported when the first span opens
_annotation = None

_id_seq = itertools.count(1)
_span_seq = itertools.count(1)

_traces_lock = threading.Lock()
_traces: dict[int, "Trace"] = {}

#: recent per-query summary records served at /queries (newest last);
#: maxlen is fixed at module load — obs.queries.keep resizes via
#: set_queries_keep (utils/config value applied by query_trace)
_recent: deque = deque(maxlen=64)
_recent_lock = threading.Lock()


def set_queries_keep(n: int) -> None:
    global _recent
    n = max(1, int(n))
    with _recent_lock:
        if _recent.maxlen != n:
            _recent = deque(_recent, maxlen=n)


def recent_queries() -> list[dict]:
    """Most-recent-first summaries of finished query traces."""
    with _recent_lock:
        return list(reversed(_recent))


class Span:
    """``arg`` may be set until the region closes (bytes known only at
    the end of a write): it lands on the ring event and, as metadata, on
    the profiler's region."""

    __slots__ = ("trace", "trace_id", "span_id", "parent_id",
                 "name", "cat", "arg", "t0_ns")

    def __init__(self, name: str, cat: str, arg, trace: "Trace | None",
                 trace_id: int, parent_id: int):
        self.name = name
        self.cat = cat
        self.arg = arg
        self.trace = trace
        self.trace_id = trace_id
        self.span_id = next(_span_seq)
        self.parent_id = parent_id
        self.t0_ns = time.perf_counter_ns()


class Trace:
    """Per-query accumulator: per-operator metric totals folded in at
    task finalize (``op_totals``, the MetricNode rollup) plus event
    counters. Per-EVENT accumulation (sync/compile/spill/batch counters)
    happens only in TRACE mode — recorder mode never takes this lock on a
    hot path; its summaries carry the per-task side (wall, tasks,
    op_seconds from finalize rollups) with the event counters at zero.
    Where the host's time went by layer is not kept here: it is read from
    the rings (``obs.window_summary``)."""

    __slots__ = ("id", "name", "kind", "t0_ns", "_lock",
                 "syncs", "sync_ns", "async_reads", "async_ns",
                 "compiles", "compile_ns",
                 "spills", "spill_ns", "spill_bytes",
                 "batches", "tasks", "op_totals")

    def __init__(self, name: str, kind: str = "query"):
        self.id = next(_id_seq)
        self.name = name
        self.kind = kind
        self.t0_ns = time.perf_counter_ns()
        self._lock = threading.Lock()
        self.syncs = 0
        self.sync_ns = 0
        self.async_reads = 0
        self.async_ns = 0
        self.compiles = 0
        self.compile_ns = 0
        self.spills = 0
        self.spill_ns = 0
        self.spill_bytes = 0
        self.batches = 0
        self.tasks = 0
        self.op_totals: dict[str, dict[str, int]] = {}

    # -- accumulators (all cross-thread; every write under self._lock) --

    def note_sync(self, dur_ns: int, is_async: bool) -> None:
        with self._lock:
            if is_async:
                self.async_reads += 1
                self.async_ns += dur_ns
            else:
                self.syncs += 1
                self.sync_ns += dur_ns

    def note_compile(self, dur_ns: int) -> None:
        with self._lock:
            self.compiles += 1
            self.compile_ns += dur_ns

    def note_spill(self, dur_ns: int, nbytes: int) -> None:
        with self._lock:
            self.spills += 1
            self.spill_ns += dur_ns
            self.spill_bytes += int(nbytes)

    def note_batch(self) -> None:
        with self._lock:
            self.batches += 1

    def add_task_metrics(self, snapshot: dict) -> None:
        from auron_tpu.exec.metrics import MetricNode

        with self._lock:
            self.tasks += 1
            MetricNode.accumulate_op_totals(snapshot, self.op_totals)

    # -- readers --

    def metric_op_seconds(self) -> dict[str, float]:
        """Per-op timer seconds from the finalize-time metric rollup —
        THE shared MetricNode.op_seconds definition."""
        from auron_tpu.exec.metrics import MetricNode

        with self._lock:
            return {op: MetricNode.op_seconds(tot)
                    for op, tot in self.op_totals.items()}

    def summary(self) -> dict:
        wall_ns = time.perf_counter_ns() - self.t0_ns
        ops = self.metric_op_seconds()
        top = sorted(ops.items(), key=lambda kv: -kv[1])[:5]
        with self._lock:
            return {
                "trace_id": self.id,
                "name": self.name,
                "kind": self.kind,
                "wall_s": round(wall_ns / 1e9, 4),
                "tasks": self.tasks,
                "batches": self.batches,
                "op_seconds": {k: round(v, 4) for k, v in ops.items()},
                "top_ops": {k: round(v, 4) for k, v in top},
                "host_syncs": self.syncs,
                "host_sync_s": round(self.sync_ns / 1e9, 4),
                "async_reads": self.async_reads,
                "async_read_s": round(self.async_ns / 1e9, 4),
                "compiles": self.compiles,
                "compile_s": round(self.compile_ns / 1e9, 4),
                "spills": self.spills,
                "spill_s": round(self.spill_ns / 1e9, 4),
                "spill_bytes": self.spill_bytes,
            }


def get_trace(trace_id: int) -> Trace | None:
    """Live trace by id (conf-threaded ``obs.trace.id`` resolution)."""
    if not trace_id:
        return None
    with _traces_lock:
        return _traces.get(int(trace_id))


def current_span() -> Span | None:
    return _span_var.get()


def current_trace() -> Trace | None:
    sp = _span_var.get()
    return sp.trace if sp is not None else None


_UNSET = object()


class span:
    """Open a child span for a ``with`` region of layer ``cat``. ``parent``
    defaults to the calling thread's current span; pass ``parent=``/
    ``trace=`` explicitly when opening on a new thread (the task pump) or
    for an owner that is not the executing thread's (spills);
    ``trace_id`` alone attributes to a trace that is known only by its
    conf-threaded id and may have closed (spill containers). No-ops in
    mode off."""

    __slots__ = ("name", "cat", "arg", "sp", "_tok", "_region")

    def __init__(self, name: str, cat: str, arg=None,
                 parent=_UNSET, trace: Trace | None = None,
                 trace_id: int = 0):
        self.sp = None
        if core._mode == core.MODE_OFF:
            return
        if cat not in LAYERS:
            raise ValueError(f"unknown obs layer {cat!r}")
        self.name = name
        self.cat = cat
        self.arg = arg
        if parent is _UNSET:
            parent = _span_var.get()
        if trace is None and parent is not None:
            trace = parent.trace
        self.sp = (parent, trace, int(trace_id))

    def __enter__(self) -> Span | None:
        global _annotation
        if self.sp is None or core._mode == core.MODE_OFF:
            self.sp = None
            return None
        parent, trace, tid = self.sp
        if trace is not None:
            tid = trace.id
        elif parent is not None:
            tid = parent.trace_id
        sp = Span(self.name, self.cat, self.arg, trace, tid,
                  parent.span_id if parent is not None else 0)
        self.sp = sp
        self._tok = _span_var.set(sp)
        # the profiler-clock half: with no profiler session an enter and
        # exit is a flag test (0.7 us measured with two arguments)
        if _annotation is None:
            import jax

            _annotation = jax.profiler.TraceAnnotation
        self._region = _annotation(f"auron:{sp.cat}:{sp.name}",
                                   span=sp.span_id, parent=sp.parent_id,
                                   trace=tid)
        self._region.__enter__()
        return sp

    def __exit__(self, *exc):
        sp = self.sp
        if sp is None:
            return False
        if isinstance(sp.arg, dict):
            self._region.set_metadata(**sp.arg)
        self._region.__exit__(*exc)
        _span_var.reset(self._tok)
        if core._mode != core.MODE_OFF:
            end = time.perf_counter_ns()
            core.record("span", sp.name, end - sp.t0_ns, sp.trace_id,
                        sp.span_id, sp.parent_id, sp.arg, sp.cat, end)
        return False


class use_span:
    """Install an EXISTING span on this thread (the cross-thread hand-off
    primitive: spill dispatch, transfer harvest). ``use_span(None)``
    CLEARS the ambient span — work owned by an untraced producer must not
    attribute to whatever foreign span the executing thread happens to
    carry (the misattribution this subsystem exists to kill)."""

    __slots__ = ("sp", "_tok")

    def __init__(self, sp: Span | None):
        self.sp = sp
        self._tok = None

    def __enter__(self):
        self._tok = _span_var.set(self.sp)
        return self.sp

    def __exit__(self, *exc):
        if self._tok is not None:
            _span_var.reset(self._tok)
        return False


class query_trace:
    """Open a query-scoped trace: registers a live ``Trace``, installs a
    conf scope carrying ``obs.trace.id`` (so task/spill confs attribute),
    and opens the root span on the calling thread. On exit the trace's
    summary lands in the recent-queries ring (``/queries``).

    Inert in mode off — ``.trace`` stays None and nothing records."""

    def __init__(self, name: str, conf=None, keep: bool = True):
        self.name = name
        self.keep = keep
        self.trace: Trace | None = None
        self.summary: dict | None = None
        #: the conf actually installed (base conf + obs.trace.id) — pass
        #: it to runners that take an EXPLICIT conf instead of reading
        #: the ambient scope (sqlgate's execute)
        self.conf = None
        self._conf = conf
        self._cs = None
        self._root = None

    def __enter__(self) -> "query_trace":
        if core._mode == core.MODE_OFF:
            return self
        from auron_tpu.obs import OBS_TRACE_ID
        from auron_tpu.utils.config import active_conf, conf_scope

        tr = Trace(self.name)
        with _traces_lock:
            _traces[tr.id] = tr
        self.trace = tr
        conf = (self._conf if self._conf is not None
                else active_conf()).copy().set(OBS_TRACE_ID, tr.id)
        self.conf = conf
        # NOTE: the /queries ring is process-global; its size is applied
        # by obs.apply_conf (session-set only), NOT per query — one
        # query's conf must not truncate every other session's history
        self._cs = conf_scope(conf)
        self._cs.__enter__()
        self._root = span(self.name, cat="query", parent=None, trace=tr)
        self._root.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb):
        if self.trace is None:
            return False
        self._root.__exit__(exc_type, exc, tb)
        self._cs.__exit__(exc_type, exc, tb)
        with _traces_lock:
            _traces.pop(self.trace.id, None)
        self.summary = self.trace.summary()
        # a query that died must not masquerade as a fast success in the
        # /queries ring — operators triage from these entries
        self.summary["error"] = (
            None if exc_type is None
            else f"{exc_type.__name__}: {exc}"[:200]
        )
        if self.keep:
            with _recent_lock:
                _recent.append(self.summary)
        return False
