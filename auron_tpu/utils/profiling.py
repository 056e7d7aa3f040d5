"""Engine-level counters: XLA compiles and host syncs.

The reference accounts where task time goes with ~20 named per-operator
metrics (native-engine/auron/src/metrics.rs:7-35); on the XLA substrate the
two engine-level costs that metric tree cannot see are (a) compilation of
new program shapes and (b) device->host syncs (every ``device_get`` /
``np.asarray`` of a live array blocks on the computation producing it).
``EngineCounters`` taps both, best-effort: the jaxlib internals it wraps are
version-dependent, so every hook degrades to "counter absent" rather than
failing the run.

Every observed compile/sync is also an ``obs.span`` (auron_tpu/obs): an
event of the flight recorder under the active span's trace — the
time-correlated record that turns "host_sync_s grew" into "the syncs
happened HERE, during THAT query" — and a region on the profiler's
clock: ``auron:sync:<file:line>`` (``auron:sync:async:<site>`` for a
window harvest) with the waiting operator's class and the bytes read as
its arguments, and ``auron:compile:<program>`` named by the XLA module.
The bytes read back are counted there and nowhere else
(``obs.window_summary``'s ``d2h_bytes``).

Thread safety: syncs arrive from task pumps, spill threads and transfer
harvests concurrently. All counter state is guarded by one lock — the
previous lock-free read-modify-write of ``sync_sites`` lost counts when
two spill threads raced, and ``snapshot()`` could observe a half-updated
``[n, secs]`` pair.
"""

from __future__ import annotations

import threading
import time
from contextlib import contextmanager

from auron_tpu import obs

# thread-local marker set by the async-transfer window while it harvests a
# read whose device->host copy was STARTED batches ago (runtime/transfer.py):
# the harvest is a copy completion, not a pipeline stall, so it is accounted
# as an async_read instead of a host sync. A harvest that still blocks
# (> _STALL_S) is attributed to its site like any sync — an "async" window
# that stalls must stay visible in the breakdown.
_async_ctx = threading.local()

_STALL_S = 0.001


@contextmanager
def async_read_scope():
    """Mark device->host reads on this thread as async-window harvests."""
    prev = getattr(_async_ctx, "on", False)
    _async_ctx.on = True
    try:
        yield
    finally:
        _async_ctx.on = prev


def _module_name(args, kwargs) -> str:
    """The XLA module's name (``jit__reduce_arrays_impl``) out of the
    compile entry's arguments: the MLIR module is the one with an
    ``operation`` that carries ``sym_name``. Best effort, like the hook
    itself: "?" where this jaxlib hands the module over otherwise."""
    for a in (*args, *kwargs.values()):
        attrs = getattr(getattr(a, "operation", None), "attributes", None)
        if attrs is not None and "sym_name" in attrs:
            return str(attrs["sym_name"]).strip('"')
    return "?"


class EngineCounters:
    """Process-wide compile/sync counters. install() is idempotent per
    process; read the totals from .snapshot()."""

    _installed: "EngineCounters | None" = None

    def __init__(self) -> None:
        # one lock for ALL mutable counter state: increments arrive from
        # any thread that syncs (pumps, spill dispatch, harvest drains)
        self._lock = threading.Lock()
        self.compiles = 0
        self.compile_s = 0.0
        self.syncs = 0
        self.sync_s = 0.0
        # async-window harvests (transfer started k batches earlier);
        # separated so host_syncs measures pipeline stalls, not reads
        self.async_reads = 0
        self.async_read_s = 0.0
        # batches pumped through task runtimes — the per-batch denominator
        # for sync-budget checks (tools/perfcheck.py)
        self.batches = 0
        # per-call-site sync attribution (engine frame nearest the sync):
        # one stack walk per read, BEFORE it (the site names the read's
        # region); the table keeps stalls, or every read on request
        self.sync_sites: dict[str, list] = {}
        # per-OPERATOR sync-wait attribution: the innermost live ExecOperator
        # frame at the moment of the stall. Generator suspension makes this
        # the honest attribution — a producer suspended at `yield` inside an
        # open timer is NOT on the stack, so a consumer's sync can never book
        # under the producer's operator (the q93 misattribution: 38s of
        # agg_exec.py:427 stalls rode BroadcastHashJoinExec's probe_time
        # because the timer's wall clock kept ticking across the yield)
        self.op_sync: dict[str, list] = {}
        # record every blocking sync's site regardless of duration (the
        # sync-budget gate counts multiplicities, not just stalls)
        self.record_all_sites = False

    def _find_site(self) -> tuple[str, str | None]:
        """(nearest engine frame, innermost ExecOperator class name) —
        one stack walk, outside the lock. The operator is found by the
        first live frame whose ``self`` (locals or closure) is an
        ExecOperator; suspended generator frames are not on the stack, so
        attribution follows the operator actually doing the waiting."""
        import sys as _sys

        try:
            from auron_tpu.exec.base import ExecOperator as _EO
        except Exception:  # pragma: no cover — partial-import windows
            _EO = None
        site = None
        op = None
        f = _sys._getframe(1)
        while f is not None:
            fn = f.f_code.co_filename
            if "auron_tpu" in fn and "utils/profiling" not in fn:
                if site is None:
                    site = f"{fn.rsplit('auron_tpu/', 1)[-1]}:{f.f_lineno}"
                if op is None and _EO is not None:
                    slf = f.f_locals.get("self")
                    if isinstance(slf, _EO):
                        op = type(slf).__name__
                if site is not None and op is not None:
                    break
            f = f.f_back
        return site or "?", op

    def _record_site(self, site: str, op: str | None, dt: float) -> None:
        with self._lock:
            ent = self.sync_sites.setdefault(site, [0, 0.0])
            ent[0] += 1
            ent[1] += dt
            if op is not None:
                oent = self.op_sync.setdefault(op, [0, 0.0])
                oent[0] += 1
                oent[1] += dt

    @classmethod
    def install(cls) -> "EngineCounters":
        if cls._installed is not None:
            return cls._installed
        self = cls()
        try:
            from jax._src import compiler as _jc

            # the module-level entry every compile goes through; renamed
            # across jax versions (0.4.x: backend_compile) — hook the
            # first one present, degrade to "counter absent" otherwise
            for fn_name in ("backend_compile_and_load", "backend_compile"):
                orig_compile = getattr(_jc, fn_name, None)
                if orig_compile is not None:
                    break
            if orig_compile is not None:
                def counted_compile(*a, **kw):
                    t0 = time.perf_counter()
                    try:
                        with obs.span(_module_name(a, kw), cat="compile"):
                            return orig_compile(*a, **kw)
                    finally:
                        dt = time.perf_counter() - t0
                        with self._lock:
                            self.compiles += 1
                            self.compile_s += dt
                        obs.note_compile(int(dt * 1e9))

                setattr(_jc, fn_name, counted_compile)
        except Exception:
            pass
        try:
            from jax._src import array as _ja

            orig_value = _ja.ArrayImpl._value

            @property
            def counted_value(arr):
                site, op = self._find_site()
                is_async = getattr(_async_ctx, "on", False)
                t0 = time.perf_counter()
                try:
                    with obs.span(f"async:{site}" if is_async else site,
                                  cat="sync") as sp:
                        if sp is not None:
                            sp.arg = {"op": op or "",
                                      "bytes": int(getattr(arr, "nbytes", 0))}
                        return orig_value.fget(arr)
                finally:
                    dt = time.perf_counter() - t0
                    with self._lock:
                        if is_async:
                            self.async_reads += 1
                            self.async_read_s += dt
                        else:
                            self.syncs += 1
                            self.sync_s += dt
                        all_sites = self.record_all_sites
                    # an async harvest that still blocked (the window was
                    # too shallow) stays visible in the site table
                    if dt > _STALL_S or (all_sites and not is_async):
                        self._record_site(site, op, dt)
                    obs.note_sync(int(dt * 1e9), is_async)

            _ja.ArrayImpl._value = counted_value
        except Exception:
            pass
        cls._installed = self
        return self

    def note_batch(self) -> None:
        with self._lock:
            self.batches += 1

    def reset(self) -> None:
        """Zero all counters (e.g. after an untimed warmup run)."""
        with self._lock:
            self.compiles = 0
            self.compile_s = 0.0
            self.syncs = 0
            self.sync_s = 0.0
            self.async_reads = 0
            self.async_read_s = 0.0
            self.batches = 0
            self.sync_sites.clear()
            self.op_sync.clear()

    def snapshot(self) -> dict:
        with self._lock:
            sites = {k: [v[0], v[1]] for k, v in self.sync_sites.items()}
            ops = {k: [v[0], v[1]] for k, v in self.op_sync.items()}
            out = {
                "compiles": self.compiles,
                "compile_s": round(self.compile_s, 3),
                "host_syncs": self.syncs,
                "host_sync_s": round(self.sync_s, 3),
                "async_reads": self.async_reads,
                "async_read_s": round(self.async_read_s, 3),
                "batches": self.batches,
            }
        top = sorted(sites.items(), key=lambda kv: -kv[1][1])[:10]
        out["sync_sites"] = {k: [v[0], round(v[1], 3)] for k, v in top}
        # per-operator stall seconds, ranked: the breakdown column that
        # keeps a downstream consumer's sync waits from being read as the
        # producer's compute (reported as top_ops_sync by bench/perf_gate)
        otop = sorted(ops.items(), key=lambda kv: -kv[1][1])[:10]
        out["op_sync"] = {k: [v[0], round(v[1], 3)] for k, v in otop}
        return out
