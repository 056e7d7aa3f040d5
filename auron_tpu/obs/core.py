"""Flight-recorder core: mode switch + per-thread event rings.

The recorder's job is to keep the *last N events per thread* available at
all times for near-zero cost, so a production incident can be examined
after the fact (the Dapper/Canopy "cheap always-on sampling" posture,
PAPERS.md) without having had tracing "on". Three modes:

- ``off``      — every instrumentation site short-circuits on one module
                 global; nothing is recorded.
- ``recorder`` — the default: events land in a lock-free (GIL-append)
                 per-thread ring buffer with bounded memory; the last
                 ring-full of events per thread is always retrievable
                 (``/trace?last=...`` on the HTTP service).
- ``trace``    — full tracing: same rings, plus per-query ``Trace``
                 accumulators feed exportable per-query summaries
                 (obs/span.py).

``AURON_TPU_OBS_KILL=1`` is the obscheck *baseline* switch: at import the
public facade in ``auron_tpu.obs`` is rebound to true no-ops, so a replay
under it measures the engine without instrumentation (tools/obscheck.py).

Threading: ``record()`` touches only the calling thread's ring (created
lazily); the registry of rings is locked ONLY at ring creation and at
snapshot — never on the event path. Ring memory is bounded two ways:
each ring holds at most ``ring_capacity`` events (a finished thread's
ring is cut down to the events it recorded), and the registry keeps at
most ``_MAX_RINGS`` rings, evicting the stalest dead-thread ring first (a
finished task's recent events stay readable until they age out).
"""

from __future__ import annotations

import itertools
import os
import threading
import time
import weakref

MODE_OFF, MODE_RECORDER, MODE_TRACE = 0, 1, 2
_MODE_NAMES = {"off": MODE_OFF, "recorder": MODE_RECORDER, "trace": MODE_TRACE}

#: hard baseline switch: no instrumentation at all (see module docstring)
KILLED = os.environ.get("AURON_TPU_OBS_KILL", "") == "1"


def _initial_mode() -> int:
    if KILLED:
        return MODE_OFF
    m = os.environ.get("AURON_TPU_OBS_MODE", "recorder").strip().lower()
    return _MODE_NAMES.get(m, MODE_RECORDER)


#: THE hot-path flag; instrumentation sites read it as ``core._mode``
_mode = _initial_mode()


def mode() -> int:
    return _mode


def mode_name() -> str:
    return {v: k for k, v in _MODE_NAMES.items()}[_mode]


def set_mode(m: int | str) -> None:
    """Switch the process-wide recording mode ("off"|"recorder"|"trace")."""
    global _mode
    if KILLED:
        return
    if isinstance(m, str):
        if m.strip().lower() not in _MODE_NAMES:
            raise ValueError(f"unknown obs mode {m!r}")
        m = _MODE_NAMES[m.strip().lower()]
    _mode = int(m)


# ---------------------------------------------------------------------------
# per-thread rings
# ---------------------------------------------------------------------------

#: every bridge task runs on a thread of its own, four a query: a window
#: of the benchmark holds 300 tasks at 0.55 s a query, and a reader of
#: the window (``window_summary``) is complete only while none of their
#: rings has been evicted. Finished threads' rings are trimmed to their
#: events (``_make_ring``), so the bound on memory is the events, not
#: ``_MAX_RINGS`` whole buffers.
_MAX_RINGS = 4096
#: dead-thread rings older than this are pruned at snapshot/creation
_RETENTION_NS = 300 * 1_000_000_000

# SAME env name the Configuration system derives for obs.recorder.events:
# one knob whether set via env or session conf (obs.apply_conf)
_ring_capacity = int(os.environ.get("AURON_TPU_OBS_RECORDER_EVENTS", "32768"))


def set_ring_capacity(cap: int) -> None:
    """Capacity for rings created AFTER this call (existing rings keep
    theirs — resizing a live ring would race its owner thread)."""
    global _ring_capacity
    _ring_capacity = max(256, int(cap))


class _Ring:
    __slots__ = ("buf", "idx", "cap", "tid", "ident", "tname", "last_ns",
                 "owner")

    def __init__(self, tid: int, cap: int):
        self.buf: list = [None] * cap
        self.idx = 0
        self.cap = cap
        self.tid = tid
        t = threading.current_thread()
        self.ident = t.ident
        # liveness is the thread OBJECT's: the OS hands a finished
        # thread's ident to the next one started
        self.owner = weakref.ref(t)
        self.tname = t.name
        self.last_ns = time.perf_counter_ns()


_tls = threading.local()
_reg_lock = threading.Lock()
_rings: list[_Ring] = []
_ring_seq = itertools.count(1)
#: newest event of any ring that left the registry (evicted or pruned):
#: a reader of a window that starts before it cannot claim completeness
_lost_until_ns = 0


def _dead(r: "_Ring") -> bool:
    t = r.owner()
    return t is None or not t.is_alive()


def _make_ring() -> _Ring:
    with _reg_lock:
        dead = [r for r in _rings if _dead(r)]
        for r in dead:
            # a finished thread records no more: keep its events, free
            # the unused rest of its buffer (a task's few hundred events
            # in 32,768 slots)
            if r.idx < len(r.buf):
                del r.buf[r.idx:]
        if len(_rings) >= _MAX_RINGS and dead:
            # evict the stalest DEAD-thread ring only. A live thread's
            # ring must never leave the registry — its owner would keep
            # recording into an orphan invisible to every export. With
            # no dead rings the registry simply grows: it is bounded by
            # the live thread count, which is a process-level bound
            # already (each thread's ring is just its buffer)
            _drop_locked(min(dead, key=lambda r: r.last_ns))
        r = _Ring(next(_ring_seq), _ring_capacity)
        _rings.append(r)
    _tls.ring = r  # auronlint: disable=R7 -- per-THREAD ring is the recorder's design: events buffer by executing thread; TASK attribution rides in the event's trace/span fields, never in this local
    return r


def record(kind: str, name: str, dur_ns: int, trace_id: int,
           span_id: int, parent_id: int, arg=None, layer: str = "",
           end_ns: int = 0) -> None:
    """Append one event to the calling thread's ring. Callers MUST have
    checked ``core._mode`` already — this function does not re-check.
    Event layout (a plain tuple, cheapest thing Python has):
    ``(ts_start_ns, dur_ns, kind, name, trace_id, span_id, parent_id, arg,
    layer)``. ``layer`` is set on REGIONS only — intervals that nest on
    their thread and are also on the profiler's clock as
    ``auron:<layer>:<name>`` (spans, host reads, compiles, spills); an
    ``op`` timer interval may stay open across a ``yield`` and has none.
    The event ends now, or at ``end_ns`` where the caller read the clock
    itself (a span: its interval then nests exactly inside its parent's).
    """
    r = getattr(_tls, "ring", None)  # auronlint: disable=R7 -- per-THREAD ring is the recorder's design: events buffer by executing thread; TASK attribution rides in the event's trace/span fields, never in this local
    if r is None:
        r = _make_ring()
    now = end_ns or time.perf_counter_ns()
    i = r.idx
    r.buf[i % r.cap] = (now - dur_ns, dur_ns, kind, name,
                        trace_id, span_id, parent_id, arg, layer)
    r.idx = i + 1
    r.last_ns = now


def _drop_locked(r: _Ring) -> None:
    global _lost_until_ns
    _rings.remove(r)
    if r.idx:
        _lost_until_ns = max(_lost_until_ns, r.last_ns)


def _prune_locked(now_ns: int) -> None:
    for r in [r for r in _rings
              if _dead(r) and now_ns - r.last_ns >= _RETENTION_NS]:
        _drop_locked(r)


def snapshot_events(last_s: float | None = None,
                    trace_id: int | None = None) -> list[tuple[dict, list]]:
    """Best-effort copy of every ring's events, oldest-first per ring,
    optionally limited to the last ``last_s`` seconds and/or one trace.
    Returns ``[(ring_info, [event, ...]), ...]``; ``ring_info["wrapped"]``
    says that the ring has overwritten events older than its first one.
    Concurrent writers may overwrite a slot mid-copy; the copy simply
    reflects whichever event won — the recorder trades a perfectly
    consistent snapshot for a lock-free hot path."""
    now = time.perf_counter_ns()
    cut = None if last_s is None else now - int(float(last_s) * 1e9)
    with _reg_lock:
        _prune_locked(now)
        rings = list(_rings)
    out = []
    for r in rings:
        idx, cap = r.idx, r.cap
        buf = list(r.buf)  # one GIL-atomic-ish copy, then filter
        if idx >= cap:
            start = idx % cap
            ordered = buf[start:] + buf[:start]
        else:
            ordered = buf[:idx]
        evs = [
            ev for ev in ordered
            if ev is not None
            and (cut is None or ev[0] + ev[1] >= cut)
            and (trace_id is None or ev[4] == trace_id)
        ]
        if evs:
            out.append(({"tid": r.tid, "name": r.tname,
                         "wrapped": idx > cap}, evs))
    return out


def lost_until_ns() -> int:
    """End of the newest event that left with an evicted or pruned ring."""
    return _lost_until_ns


def reset_for_tests() -> None:
    """Drop all rings (test isolation only — not part of the API)."""
    global _lost_until_ns
    with _reg_lock:
        _rings.clear()
        _lost_until_ns = 0
    # each thread's _tls.ring is dropped lazily: a stale thread-local ring
    # keeps recording but is no longer exported
    if getattr(_tls, "ring", None) is not None:
        _tls.ring = None
