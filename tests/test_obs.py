"""Query-scoped structured tracing (auron_tpu/obs, docs/observability.md).

The acceptance teeth live in test_gate_class_trace_is_complete_and_agrees:
a gate-class replay under full tracing must export a Perfetto-loadable
trace whose per-operator op events agree with MetricNode.op_seconds
within 5%, whose event stream carries compile, host-sync, spill and
async-harvest regions — with a FORCED spill and a FORCED sync performed
by foreign threads still attributed to the owning task's trace — and
whose window_summary accounts for every pump thread's time by layer.
"""

import json
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from auron_tpu import obs
from auron_tpu import types as T
from auron_tpu.bridge import api
from auron_tpu.columnar import Batch
from auron_tpu.exec.metrics import MetricNode
from auron_tpu.exprs.ir import col
from auron_tpu.obs import core, export
from auron_tpu.plan import builders as B
from auron_tpu.utils.profiling import EngineCounters


@pytest.fixture(autouse=True)
def _restore_mode():
    prev = obs.mode()
    yield
    obs.set_mode(prev)


def _events(trace_id=None, kind=None, layer=None):
    out = []
    for _ring, evs in core.snapshot_events(trace_id=trace_id):
        for ev in evs:
            if (kind is None or ev[2] == kind) and \
                    (layer is None or ev[8] == layer):
                out.append(ev)
    return out


# ---------------------------------------------------------------------------
# span model
# ---------------------------------------------------------------------------


def test_mode_off_short_circuits_everything():
    obs.set_mode("off")
    with obs.query_trace("off_query") as qt:
        assert qt.trace is None
        with obs.span("x", cat="task") as sp:
            assert sp is None
        obs.note_op("Op", "elapsed_compute", 123)
    assert qt.summary is None


def test_mode_off_makes_no_span_and_a_hooked_read_leaves_no_event():
    """Mode off is tested first of all: a ``span`` makes neither a Span
    nor a profiler annotation nor a ring event, also where the
    EngineCounters hook opens it around a host read; the counters, which
    are not obs's, still count the read."""
    counters = EngineCounters.install()
    arr = jnp.arange(64, dtype=jnp.int32) + 1
    arr.block_until_ready()
    obs.set_mode("off")
    n_events = len(_events())
    syncs = counters.syncs
    opened = obs.span("never", cat="exchange", arg={"bytes": 1})
    assert opened.sp is None
    with opened as sp:
        assert sp is None and obs.current_span() is None
    assert int(jax.device_get(arr)[3]) == 4
    assert counters.syncs - syncs == 1
    assert len(_events()) == n_events


def test_span_nesting_and_contextvar():
    obs.set_mode("trace")
    with obs.query_trace("nest") as qt:
        root = obs.current_span()
        assert root is not None and root.trace is qt.trace
        with obs.span("child", cat="task") as c:
            assert c.parent_id == root.span_id
            assert obs.current_span() is c
        assert obs.current_span() is root
    assert obs.current_span() is None
    evs = _events(trace_id=qt.trace.id, kind="span")
    assert {e[3] for e in evs} >= {"child", "nest"}


def test_use_span_hands_off_across_threads_and_none_clears():
    obs.set_mode("trace")
    seen = {}
    with obs.query_trace("hop") as qt:
        sp = obs.current_span()

        def foreign():
            with obs.use_span(sp):
                seen["inside"] = obs.current_span()
                obs.note_op("ForeignOp", "elapsed_compute", 1000)
            seen["after"] = obs.current_span()

        t = threading.Thread(target=foreign)
        t.start()
        t.join()
    assert seen["inside"] is sp and seen["after"] is None
    ops = _events(trace_id=qt.trace.id, kind="op")
    assert [(e[1], e[5], e[7]) for e in ops] == [(1000, sp.span_id, "ForeignOp")]
    # use_span(None) CLEARS: an untraced producer must not inherit the
    # executing thread's foreign span
    with obs.span("ambient", cat="task"):
        with obs.use_span(None):
            assert obs.current_span() is None


def test_ring_is_bounded_and_wraps():
    obs.set_mode("recorder")
    core.set_ring_capacity(256)
    try:
        done = []

        def burst():
            for i in range(1000):
                core.record("t", f"e{i}", 0, 0, 0, 0, None)
            r = core._tls.ring
            done.append((r.idx, r.cap, sum(1 for x in r.buf if x)))

        t = threading.Thread(target=burst)  # fresh thread -> fresh ring
        t.start()
        t.join()
        idx, cap, filled = done[0]
        assert cap == 256 and idx == 1000 and filled == 256
    finally:
        core.set_ring_capacity(32768)


def test_a_window_of_three_hundred_task_threads_stays_complete():
    """Every bridge task runs on a thread of its own: a benchmark window
    of 75 queries is 300 of them. Their rings stay in the registry (the
    window's summary is complete and holds every thread's events), and a
    finished thread's ring is cut down to the events it recorded."""
    import time

    saved = obs.mode()
    obs.set_mode("recorder")
    try:
        t0 = time.perf_counter()

        def task():
            obs.note_join_take("compact", 128, 4194304)
            obs.note_agg_fold(256, 4194304)

        for i in range(300):
            t = threading.Thread(target=task, name=f"ring-task-{i}")
            t.start()
            t.join()
        ws = obs.window_summary(t0, time.perf_counter())
        with core._reg_lock:
            finished = [r for r in core._rings
                        if r.tname.startswith("ring-task-")]
    finally:
        obs.set_mode(saved)
    assert ws["complete"]
    assert ws["join_takes"] == {"compact": 300}
    assert ws["join_gather_rows"] == 300 * 128
    assert ws["agg_fold_rows"] == 300 * 256
    assert len(finished) == 300
    # all but the newest (trimmed when the next ring is made) hold their
    # events and no more
    assert sum(len(r.buf) > r.idx for r in finished) <= 1


def test_window_summary_sums_the_joins_lookups_by_kind():
    """``note_join_lookup`` is an event of no duration and no layer:
    ``window_summary`` sums its rows by kind as ``join_lookup_rows``
    (events that began in the window), books it under no layer, and a
    window with no such event reads an empty table: the benchmark's
    reader then reports 0 rows looked up by a gather, and None only on a
    program whose summary lacks the key."""
    import time

    saved = obs.mode()
    obs.set_mode("recorder")
    try:
        obs.note_join_lookup("lut", 4194304)              # before the window
        t0 = time.perf_counter()
        obs.note_join_lookup("compare", 1048576)
        obs.note_join_lookup("lut", 1048576)
        obs.note_join_lookup("compare", 4194304)
        obs.note_join_lookup("search", 8192)
        obs.note_join_lookup("lut", 1048576)
        obs.note_join_take("dense", 1048576, 1048576)     # another event
        t1 = time.perf_counter()
        obs.note_join_lookup("lut", 128)                  # after it
        ws = obs.window_summary(t0, t1)
        t2 = time.perf_counter()
        obs.note_join_take("dense", 128, 128)
        quiet = obs.window_summary(t2, time.perf_counter())
    finally:
        obs.set_mode(saved)
    assert ws["join_lookup_rows"] == {
        "compare": 1048576 + 4194304, "lut": 2 * 1048576, "search": 8192}
    assert ws["join_gather_rows"] == 1048576      # takes are not lookups
    assert "lookup" not in ws["layers"] and not ws["spans"]
    assert quiet["join_lookup_rows"] == {}
    obs.set_mode("off")
    try:
        obs.note_join_lookup("lut", 128)          # mode off: one flag test
    finally:
        obs.set_mode(saved)


def test_recorder_mode_rings_only_no_per_event_lock():
    """recorder vs trace distinction: recorder records ring events and
    publishes per-task summaries, but never takes the per-event Trace
    lock (sync counters stay at zero); trace accumulates."""
    obs.set_mode("recorder")
    with obs.query_trace("rec_mode") as qt:
        obs.note_op("SomeExec", "elapsed_compute", 5_000_000)
        with obs.span("x.py:1", cat="sync", arg={"op": "", "bytes": 8}):
            pass
        obs.note_sync(100_000, False)
    assert _events(trace_id=qt.trace.id, kind="op")      # rings: yes
    assert _events(trace_id=qt.trace.id, layer="sync")
    assert qt.summary["host_syncs"] == 0                 # accumulators: no
    assert qt.summary["trace_id"] == qt.trace.id         # /queries: yes
    obs.set_mode("trace")
    with obs.query_trace("trace_mode") as qt2:
        obs.note_sync(100_000, False)
    assert qt2.summary["host_syncs"] == 1
    assert qt2.summary["host_sync_s"] == pytest.approx(1e-4)


def test_apply_conf_ignores_env_only_mode(monkeypatch):
    """An env-set obs.mode must not be re-asserted per task: it already
    took effect at import, and re-applying would clobber a later
    programmatic set_mode (bench --trace-out under env off)."""
    from auron_tpu.utils.config import Configuration

    monkeypatch.setenv("AURON_TPU_OBS_MODE", "off")
    obs.set_mode("trace")
    obs.apply_conf(Configuration())          # env-only: no-op
    assert obs.mode() == obs.MODE_TRACE
    obs.apply_conf(Configuration().set(obs.OBS_MODE, "recorder"))
    assert obs.mode() == obs.MODE_RECORDER   # session-set: applies


def test_query_trace_summary_lands_in_recent_ring():
    obs.set_mode("trace")
    with obs.query_trace("ringed") as qt:
        obs.note_op("AggExec", "elapsed_compute", 2_000_000)
        obs.note_sync(500_000, False)
    recent = obs.recent_queries()
    assert recent and recent[0]["trace_id"] == qt.trace.id
    assert recent[0]["host_syncs"] == 1
    assert recent[0]["name"] == "ringed"


def test_sql_compile_emits_parse_bind_lower_spans():
    from auron_tpu.sql import compile_text

    obs.set_mode("trace")
    with obs.query_trace("sqlspans") as qt:
        compile_text(
            "select ss_item_sk, sum(ss_ext_sales_price) s from store_sales "
            "group by ss_item_sk"
        )
    names = {e[3] for e in _events(trace_id=qt.trace.id, kind="span")}
    assert {"sql.parse", "sql.bind", "sql.lower"} <= names


# ---------------------------------------------------------------------------
# the acceptance teeth
# ---------------------------------------------------------------------------


def test_gate_class_trace_is_complete_and_agrees(tmp_path):
    from auron_tpu.memory.memmgr import MemManager
    from auron_tpu.models import tpcds
    from auron_tpu.runtime.transfer import TransferWindow

    EngineCounters.install()
    obs.set_mode("trace")
    spilled = threading.Event()

    class _Consumer:
        name = "teeth_consumer"

        def mem_used(self):
            return 0 if spilled.is_set() else (4 << 20)

        def spill(self):
            spilled.set()
            return 4 << 20

    data = tpcds.generate(sf=0.1, seed=3)
    with obs.query_trace("gate.q3") as qt:
        # --- the gate-class replay itself
        tpcds.run_q3_class(data, n_map=2, n_reduce=2,
                           work_dir=str(tmp_path / "q3"))
        # --- forced spill: consumer registered under the OWNING trace,
        # spill dispatched by a FOREIGN thread with no span installed
        mm = MemManager(budget_bytes=0)
        mm.register(_Consumer())
        t = threading.Thread(
            target=lambda: mm.acquire(_Consumer(), 1 << 20)
        )
        t.start()
        t.join()
        assert spilled.is_set()
        # --- forced sync on a foreign thread, span threaded explicitly
        # (the R7 hand-off recipe, docs/observability.md)
        sp = obs.current_span()
        arr = jnp.arange(1 << 16)

        def foreign_sync():
            with obs.use_span(sp):
                jax.device_get(arr + 1)

        t = threading.Thread(target=foreign_sync)
        t.start()
        t.join()
        # --- a compile inside the trace (fresh persistent-cache dir so
        # the compile can't be served from the box's warm XLA cache)
        prev_cache = jax.config.jax_compilation_cache_dir
        jax.config.update("jax_compilation_cache_dir",
                          str(tmp_path / "xlacache"))
        try:
            jax.jit(lambda x: x * 3 + 1)(jnp.arange(12347))
        finally:
            jax.config.update("jax_compilation_cache_dir", prev_cache)
        # --- an async-transfer harvest inside the trace
        w = TransferWindow(1)
        for i in range(3):
            w.push((jnp.asarray([i]),), i)
        list(w.drain())

    out = str(tmp_path / "trace.json")
    export.write_chrome_trace(out, trace_id=qt.trace.id)
    with open(out) as f:
        ct = json.load(f)

    # Perfetto-loadable shape: X events with name/ts/dur/pid/tid
    xs = [e for e in ct["traceEvents"] if e["ph"] == "X"]
    assert xs
    for e in xs:
        assert {"name", "cat", "ts", "dur", "pid", "tid"} <= set(e)
        assert e["pid"] == qt.trace.id  # every event attributed

    # the full event stream is present, attributed to THIS trace even for
    # the foreign-thread spill and sync; a region renders under its layer
    kinds = {e["cat"] for e in xs}
    assert {"op", "task", "pump", "entry", "plan", "exchange", "wait",
            "sync", "compile", "spill"} <= kinds
    spill_evs = [e for e in xs if e["cat"] == "spill"
                 and e["args"].get("consumer") == "teeth_consumer"]
    assert spill_evs, "forced foreign-thread spill missing from the trace"
    assert spill_evs[0]["args"]["bytes"] == 4 << 20
    assert any(e["name"] == "harvest" for e in xs if e["cat"] == "wait")
    # every host read names its site (file:line; "?" for the read this
    # test makes from outside the engine) and carries its bytes; every
    # compile names its program
    syncs = [e for e in xs if e["cat"] == "sync"]
    assert syncs and all((":" in e["name"] or e["name"] == "?")
                         and "bytes" in e["args"] for e in syncs)
    assert any(e["name"].startswith("async:") for e in syncs)
    assert any(e["name"].startswith("jit_") for e in xs
               if e["cat"] == "compile")

    # per-operator op events FROM THE EXPORTED FILE agree with the
    # MetricNode.op_seconds rollup within 5%
    from_file: dict[str, float] = {}
    for e in xs:
        if e["cat"] != "op":
            continue
        metric = e["args"]["metric"]
        if metric in MetricNode.NESTED_TIMERS:
            continue
        op = e["args"]["op"]
        from_file[op] = from_file.get(op, 0.0) + e["dur"] / 1e6
    metric_ops = qt.trace.metric_op_seconds()
    assert metric_ops, "no finalize-time metric rollup reached the trace"
    for op, secs in metric_ops.items():
        if secs < 0.01:
            continue  # sub-10ms ops: rounding noise dominates percentages
        assert from_file.get(op, 0.0) == pytest.approx(secs, rel=0.05), (
            op, from_file.get(op), secs
        )

    # where the host's time went, by layer, over the same run: complete,
    # every layer of the batch path present, and the self times add up to
    # the thread-seconds of the outermost regions (nothing counted twice)
    ws = obs.window_summary(qt.trace.t0_ns / 1e9, time.perf_counter())
    assert ws["complete"]
    layers = ws["layers"]
    assert {"query", "entry", "plan", "task", "pump", "wait", "exchange",
            "sync", "compile", "spill"} <= set(layers)
    assert layers["task"]["n"] == 4 and layers["plan"]["n"] >= 4
    assert ws["spans"]["wait:queue_get"]["n"] >= 4
    assert layers["sync"]["self_s"] == pytest.approx(layers["sync"]["total_s"])
    assert ws["d2h_bytes"] >= arr.nbytes
    assert ws["sync_sites"][0][2] >= ws["sync_sites"][-1][2]
    for ent in layers.values():
        assert 0.0 <= ent["self_s"] <= ent["total_s"] + 1e-9
    # the pumps' host work is inside their task spans, and a pump's waits
    # and reads are not its operators' self time
    assert layers["pump"]["total_s"] <= layers["task"]["total_s"]
    assert layers["pump"]["self_s"] < layers["pump"]["total_s"]


def test_spill_container_attributes_via_conf_trace_id():
    """HostSpill carries the owning conf; a write on a foreign thread
    attributes through obs.trace.id with NO live span anywhere."""
    import pyarrow as pa

    from auron_tpu.memory.memmgr import make_spill
    from auron_tpu.utils.config import Configuration

    obs.set_mode("trace")
    with obs.query_trace("conf_attr") as qt:
        from auron_tpu.utils.config import active_conf

        conf = active_conf().copy()  # carries obs.trace.id
    # trace CLOSED; write from a plain thread with no span: the ring event
    # must still carry the owning trace id
    spill = make_spill(conf=conf)
    tbl = pa.table({"v": list(range(100))})

    def foreign_write():
        spill.write_table(tbl)

    t = threading.Thread(target=foreign_write)
    t.start()
    t.join()
    evs = _events(trace_id=qt.trace.id, layer="spill")
    assert any(e[3] == "write" and e[7]["consumer"] == "HostSpill"
               and e[7]["bytes"] > 0 for e in evs)
    spill.release()


def test_chrome_trace_last_window_filters_old_events():
    import time as _t

    obs.set_mode("recorder")
    core.record("t", "old_event_marker", 0, 0, 0, 0, None)
    _t.sleep(0.05)
    core.record("t", "new_event_marker", 0, 0, 0, 0, None)
    ct = export.chrome_trace(last_s=0.03)
    names = {e["name"] for e in ct["traceEvents"] if e["ph"] == "X"}
    assert "new_event_marker" in names and "old_event_marker" not in names


# ---------------------------------------------------------------------------
# regions on the profiler's clock, and the reader over the rings by layer
# ---------------------------------------------------------------------------


def _properly_nested(intervals) -> bool:
    """Intervals of one thread either nest or are disjoint."""
    open_ends = []
    for s, e in sorted(intervals, key=lambda x: (x[0], -x[1])):
        while open_ends and open_ends[-1] <= s:
            open_ends.pop()
        if open_ends and e > open_ends[-1]:
            return False
        open_ends.append(e)
    return True


def test_every_layer_is_a_region_on_the_profilers_clock(tmp_path):
    """The batch path at a tiny size under jax.profiler.trace: the host
    plane of the .xplane.pb holds an ``auron:<layer>:<name>`` region for
    every layer the path crosses, properly nested per thread, and each
    span's region agrees with its ring event to 1 ms."""
    from jax.profiler import ProfileData

    import glob

    import pyarrow as pa

    from auron_tpu.memory.memmgr import make_spill
    from auron_tpu.models import tpcds
    from auron_tpu.runtime.transfer import TransferWindow
    from auron_tpu.utils.config import active_conf

    EngineCounters.install()
    obs.set_mode("recorder")
    data = tpcds.generate(sf=0.05, seed=5)
    tpcds.run_q3_class(data, n_map=2, n_reduce=2,
                       work_dir=str(tmp_path / "warm"))   # compile outside
    jax.profiler.start_trace(str(tmp_path / "prof"))
    try:
        with obs.query_trace("profiled.q3") as qt:
            tpcds.run_q3_class(data, n_map=2, n_reduce=2,
                               work_dir=str(tmp_path / "q3"))
            spill = make_spill(conf=active_conf().copy())
            spill.write_table(pa.table({"v": list(range(64))}))
            spill.release()
            w = TransferWindow(1)
            w.push((jnp.asarray([1]),), 0)
            list(w.drain())
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "prof/plugins/profile/*/*.xplane.pb"))
    regions: dict[str, list] = {}         # line -> [(name, start, end, stats)]
    for plane in ProfileData.from_file(path).planes:
        for i, line in enumerate(plane.lines):    # one line per thread
            for ev in line.events:
                if ev.name.startswith("auron:"):
                    regions.setdefault(f"{plane.name}/{i}", []).append(
                        (ev.name.split("#", 1)[0], ev.start_ns,
                         ev.start_ns + ev.duration_ns, dict(ev.stats)))
    names = {n for evs in regions.values() for n, *_ in evs}
    layers = {n.split(":")[1] for n in names}
    assert {"query", "entry", "plan", "task", "pump", "wait", "exchange",
            "sync", "spill"} <= layers, layers
    assert {"auron:entry:call_native", "auron:entry:next_batch",
            "auron:entry:finalize_native", "auron:plan:task",
            "auron:plan:fusion", "auron:pump:batch", "auron:wait:queue_put",
            "auron:wait:queue_get", "auron:wait:harvest",
            "auron:exchange:write", "auron:exchange:read",
            "auron:spill:write"} <= names, names
    assert any(n.startswith("auron:sync:async:") for n in names)
    assert any(n.startswith("auron:sync:") and ".py:" in n for n in names)
    assert len(regions) >= 3      # the caller's thread and the map pumps
    for line, evs in regions.items():
        assert _properly_nested([(s, e) for _, s, e, _ in evs]), line
    # a region's arguments hold the span's ids; its duration is the ring's
    ring = {e[5]: e for e in _events(trace_id=qt.trace.id, kind="span")}
    skews = []
    for evs in regions.values():
        for name, s, e, stats in evs:
            if stats.get("trace") != qt.trace.id or "span" not in stats:
                continue
            ev = ring[stats["span"]]
            assert name == f"auron:{ev[8]}:{ev[3]}"
            assert stats["parent"] == ev[6]
            # the region lies inside the ring event's interval; a thread
            # switch between the two clock reads is the only thing that
            # can part them by more than microseconds
            skews.append(ev[1] - (e - s))
    assert len(skews) >= 20
    assert min(skews) > -1e5 and max(skews) < 1e8, (min(skews), max(skews))
    assert sorted(skews)[int(0.9 * len(skews))] < 1e6, sorted(skews)[-5:]
    # arguments that are known only at the region's end reach it too
    writes = [st for evs in regions.values() for n, _, _, st in evs
              if n == "auron:exchange:write" and st.get("phase") == "write"]
    assert writes and all(st["bytes"] > 0 for st in writes)


def _synthetic_ring(tid: int, name: str, events: list, cap: int = 16):
    """A ring put into the registry by hand: events are (start_ns, dur_ns,
    layer, name[, arg]), recorded in this order (more than ``cap`` of them
    wrap the ring)."""
    r = core._Ring(tid, cap)
    r.tname = name
    for i, (ts, dur, layer, nm, *arg) in enumerate(events):
        r.buf[i % cap] = (ts, dur, "span", nm, 0, i + 1, 0,
                          arg[0] if arg else None, layer)
    r.idx = len(events)
    r.last_ns = max(ts + dur for ts, dur, *_ in events)
    with core._reg_lock:
        core._rings.append(r)
    return r


@pytest.fixture
def empty_rings():
    with core._reg_lock:
        saved = list(core._rings)
        core._rings.clear()
    saved_lost = core._lost_until_ns
    core._lost_until_ns = 0
    yield
    with core._reg_lock:
        core._rings[:] = saved
    core._lost_until_ns = saved_lost


def test_window_summary_self_time_nested_sibling_and_cross_thread(empty_rings):
    s = 1_000_000_000  # one second in ns
    # thread 1: task [0,10) > pump [1,9) > {sync [2,4), exchange [5,8) >
    # sync [6,7)}; an op timer event (no layer) is never a region
    _synthetic_ring(1, "pump-1", [
        (2 * s, 2 * s, "sync", "a.py:1", {"op": "X", "bytes": 100}),
        (6 * s, 1 * s, "sync", "b.py:2", {"op": None, "bytes": 28}),
        (5 * s, 3 * s, "exchange", "write", {"phase": "repart"}),
        (1 * s, 8 * s, "pump", "batch"),
        (0 * s, 10 * s, "task", "task s1p0"),
    ])
    core._rings[-1].buf[5] = (0, 10 * s, "op", "elapsed_compute", 0, 0, 0,
                              "FilterExec", "")
    core._rings[-1].idx = 6
    # thread 2 overlaps thread 1 in time: a cross-thread region is never
    # taken out of another thread's self time
    _synthetic_ring(2, "main", [
        (3 * s, 4 * s, "wait", "queue_get"),
        (2 * s, 6 * s, "entry", "next_batch"),
    ])
    ws = obs.window_summary(0.0, 10.0)
    assert ws["complete"]
    lay = ws["layers"]
    assert lay["task"] == {"n": 1, "total_s": 10.0, "self_s": 2.0}
    assert lay["pump"] == {"n": 1, "total_s": 8.0, "self_s": 3.0}
    assert lay["exchange"] == {"n": 1, "total_s": 3.0, "self_s": 2.0}
    assert lay["sync"] == {"n": 2, "total_s": 3.0, "self_s": 3.0}
    assert lay["entry"] == {"n": 1, "total_s": 6.0, "self_s": 2.0}
    assert lay["wait"] == {"n": 1, "total_s": 4.0, "self_s": 4.0}
    assert "" not in lay and "op" not in lay
    assert ws["spans"]["exchange:write"]["self_s"] == 2.0
    assert ws["d2h_bytes"] == 128
    assert ws["sync_sites"] == [["a.py:1", 1, 2.0], ["b.py:2", 1, 1.0]]
    # thread-seconds: the self times of one thread add up to its outermost
    # region, the two threads together to more than the wall
    assert sum(e["self_s"] for e in lay.values()) == 16.0


def test_window_summary_counts_no_bytes_for_a_read_that_names_none(empty_rings):
    """A ``sync`` span is a span like any other: one opened without the
    hook's ``bytes`` argument is a read of the layer and of its site, and
    adds nothing to ``d2h_bytes``."""
    s = 1_000_000_000
    _synthetic_ring(1, "pump-1", [
        (0 * s, 1 * s, "sync", "a.py:1"),
        (1 * s, 1 * s, "sync", "a.py:1", {"op": "X"}),
        (2 * s, 1 * s, "sync", "b.py:2", {"op": "", "bytes": 12}),
    ])
    ws = obs.window_summary(0.0, 3.0)
    assert ws["layers"]["sync"] == {"n": 3, "total_s": 3.0, "self_s": 3.0}
    assert ws["d2h_bytes"] == 12
    assert ws["sync_sites"] == [["a.py:1", 2, 2.0], ["b.py:2", 1, 1.0]]


def test_window_summary_clips_at_the_windows_edges(empty_rings):
    s = 1_000_000_000
    _synthetic_ring(1, "pump-1", [
        (2 * s, 2 * s, "sync", "a.py:1", {"op": None, "bytes": 8}),
        (7 * s, 2 * s, "sync", "a.py:1", {"op": None, "bytes": 8}),
        (1 * s, 9 * s, "pump", "batch"),
        (11 * s, 1 * s, "pump", "batch"),        # after the window
    ])
    ws = obs.window_summary(3.0, 8.0)
    # pump [1,10) clipped to [3,8); the reads to [3,4) and [7,8)
    assert ws["layers"]["pump"] == {"n": 1, "total_s": 5.0, "self_s": 3.0}
    assert ws["layers"]["sync"] == {"n": 2, "total_s": 2.0, "self_s": 2.0}
    assert ws["d2h_bytes"] == 16
    assert obs.window_summary(20.0, 30.0)["layers"] == {}


def test_window_summary_is_incomplete_after_a_wrap_or_a_lost_ring(empty_rings):
    s = 1_000_000_000
    evs = [(i * s, s // 2, "pump", "batch") for i in range(2, 8)]
    # a ring of 4 that has taken 6 events: the two it lost ended before its
    # oldest one, [4, 4.5), did
    _synthetic_ring(1, "pump-1", evs, cap=4)
    assert not obs.window_summary(3.0, 9.0)["complete"]
    assert not obs.window_summary(4.2, 9.0)["complete"]
    assert obs.window_summary(4.5, 9.0)["complete"]     # lost ones ended before
    # a ring that left the registry with events newer than the window's start
    with core._reg_lock:
        core._drop_locked(core._rings[0])
    assert core.lost_until_ns() == 7 * s + s // 2
    assert not obs.window_summary(5.0, 9.0)["complete"]
    assert obs.window_summary(7.5, 9.0)["complete"]


def test_pump_threads_self_times_add_up_to_their_task_span(tmp_path):
    """Conservation, on a real tiny run: on each pump thread the self
    times of every region add up to the ``task`` span's duration (each
    region nests inside it, none is counted twice or lost)."""
    from auron_tpu.models import tpcds
    from auron_tpu.obs.export import self_ns

    EngineCounters.install()
    obs.set_mode("recorder")
    data = tpcds.generate(sf=0.05, seed=9)
    with obs.query_trace("conserve.q3") as qt:
        tpcds.run_q3_class(data, n_map=2, n_reduce=2,
                           work_dir=str(tmp_path / "q3"))
    pumps = 0
    for _ring, evs in core.snapshot_events(trace_id=qt.trace.id):
        regions = [(e[0], e[0] + e[1], e[8], e[3]) for e in evs if e[8]]
        tasks = [r for r in regions if r[2] == "task"]
        if not tasks:
            continue
        (task,) = tasks               # one pump thread, one task
        pumps += 1
        assert all(task[0] <= r[0] and r[1] <= task[1] for r in regions)
        selfs = self_ns(regions)
        assert all(ns >= 0 for _, ns in selfs)
        assert sum(ns for _, ns in selfs) == task[1] - task[0]
        assert {r[2] for r in regions} >= {"task", "pump"}
    assert pumps == 4


def test_sync_region_names_a_declared_sync_point_and_counts_its_bytes():
    import os

    import auron_tpu
    from auron_tpu.runtime import transfer

    counters = EngineCounters.install()
    obs.set_mode("recorder")
    arr = jnp.arange(1000, dtype=jnp.int32) * 2
    arr.block_until_ready()
    reads = counters.async_reads
    t0 = time.perf_counter()
    with obs.query_trace("one_read") as qt:
        (host,) = transfer.harvest(arr)
    t1 = time.perf_counter()
    assert host[7] == 14
    assert counters.async_reads - reads == 1 and arr.nbytes == 4000
    (ev,) = _events(trace_id=qt.trace.id, layer="sync")
    assert ev[2] == "span" and ev[7] == {"op": "", "bytes": 4000}
    # one region, one clock: the read is a child span of its harvest
    (hv,) = [e for e in _events(trace_id=qt.trace.id, layer="wait")
             if e[3] == "harvest"]
    assert ev[6] == hv[5] and hv[0] <= ev[0] and ev[0] + ev[1] <= hv[0] + hv[1]
    prefix, path, line = ev[3].rsplit(":", 2)
    assert prefix == "async" and path == "runtime/transfer.py"
    src = os.path.join(os.path.dirname(auron_tpu.__file__), path)
    with open(src) as f:
        assert "# auronlint: sync-point" in f.readlines()[int(line) - 1]
    ws = obs.window_summary(t0, t1)
    assert ws["d2h_bytes"] == 4000
    assert ws["sync_sites"][0][:2] == [ev[3], 1]
    # the read is inside its harvest: taken out of the harvest's self time
    h = ws["spans"]["wait:harvest"]
    assert h["n"] == 1 and h["self_s"] < h["total_s"]


def test_reduce_program_carries_its_scope_names():
    """The lowered text of the sort-segmented aggregate holds every
    ``auron.agg.*`` scope name (metadata only: a device trace names the
    program's operations by them)."""
    from auron_tpu.exec.agg_exec import AggExpr, _reduce_arrays_jit

    n = 64
    sel = jnp.ones(n, bool)
    key = (jnp.arange(n, dtype=jnp.int64) % 5,)
    ok = (jnp.ones(n, bool),)
    val = ((jnp.arange(n, dtype=jnp.int64),),)
    cfg = (1, (T.INT64,), ((AggExpr("sum", col(1)), T.INT64),),
           False, "lax", True, 64)       # device sort on fingerprints
    text = _reduce_arrays_jit.lower(
        sel, key, ok, val, (ok,), (None,), None, None, None,
        cfg=cfg, raw=True).as_text(debug_info=True)
    for scope in ("auron.agg.key_words", "auron.agg.fingerprint",
                  "auron.agg.sort", "auron.agg.boundaries",
                  "auron.agg.key_gather", "auron.agg.reduce.sum",
                  "auron.agg.group_fp"):
        assert scope in text, scope
