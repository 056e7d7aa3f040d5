"""Query-scoped structured tracing + always-on flight recorder.

The engine's aggregate halves (per-operator MetricNode trees, process
EngineCounters) say *how much* a cost was; this package records *when*
and *under which query* it occurred — the time-correlated view the PR 3
q5 misattribution (eager-dispatch blocking billed to FilterExec) needed
a manual A/B hunt to reconstruct. See docs/observability.md.

Public surface:

- ``span`` / ``use_span`` / ``current_span`` / ``query_trace`` — the
  span model (obs/span.py); spans cross thread hops EXPLICITLY, like
  conf (R7). A span's ``cat`` is its layer (``LAYERS``), and every span
  is also a region ``auron:<layer>:<name>`` on the profiler's clock.
- ``note_op`` / ``note_sync`` / ``note_compile`` / ``note_pump_batch`` /
  ``note_agg_fold`` / ``note_agg_reduce`` / ``note_agg_emit`` /
  ``note_decimal_host_cells`` / ``note_join_take`` / ``note_join_lookup``
  / ``note_join_hash_build`` / ``note_compaction_index`` / ``note_sort``
  / ``note_exchange_read`` — the instrumentation facade behind
  MetricNode.timer, the EngineCounters hooks, the task pump, the
  aggregate's folds, reduces and emissions, the DECIMAL cells the host
  handles one by one, the unique-build join's lookup, hash-map build and
  output boundary, every program launched with a compaction index, the
  sorts of plans, joins' builds and the host's lexsort, and the batches
  the shuffle reader seals.
  Each checks ``core._mode`` first; in mode off a call is one flag test.
- ``jit`` — ``jax.jit`` for every program of the engine: each call is a
  device launch, counted and timed beside the primitives JAX runs eagerly
  (obs/launch.py's hook); a thread's launches go to its ring as one
  ``launch`` event when its innermost region closes.
- ``window_summary(t0_s, t1_s)`` — where the host's time went between
  two readings of ``time.perf_counter()``, by layer, and the launches
  (obs/export.py).
- exporters in ``auron_tpu.obs.export`` (Chrome/Perfetto JSON,
  Prometheus text), served by utils/httpsvc at ``/trace``,
  ``/metrics.prom``, ``/queries``.

``AURON_TPU_OBS_KILL=1`` rebinds the whole facade to no-ops at import —
the no-obs baseline for the ``make obscheck`` overhead gate.
"""

from __future__ import annotations

from auron_tpu.obs import core
from auron_tpu.obs.core import (  # noqa: F401  (re-exported)
    MODE_OFF,
    MODE_RECORDER,
    MODE_TRACE,
    mode,
    mode_name,
    set_mode,
)
from auron_tpu.obs.export import window_summary  # noqa: F401  (re-exported)
from auron_tpu.obs.launch import jit  # noqa: F401  (re-exported)
from auron_tpu.obs.span import (  # noqa: F401  (re-exported)
    LAYERS,
    Span,
    Trace,
    _span_var,
    current_span,
    current_trace,
    get_trace,
    query_trace,
    recent_queries,
    span,
    use_span,
)
from auron_tpu.utils.config import int_conf, str_conf

OBS_MODE = str_conf(
    "obs.mode", "recorder", "observability",
    "recording mode: off (instrumentation short-circuits) | recorder "
    "(always-on bounded flight recorder, <=5% overhead by the obscheck "
    "gate) | trace (full tracing: per-query summaries with event "
    "counters). Applied process-wide when a task's conf sets it "
    "explicitly (bridge/api.py); AURON_TPU_OBS_MODE sets the start mode",
)
OBS_TRACE_ID = int_conf(
    "obs.trace.id", 0, "observability",
    "INTERNAL: id of the owning query trace, threaded through task/spill "
    "confs by obs.query_trace so work dispatched to foreign threads still "
    "attributes to its query (the conf-threading discipline, R7). 0 = "
    "untraced",
)
OBS_RING_EVENTS = int_conf(
    "obs.recorder.events", 32768, "observability",
    "flight-recorder ring capacity in events PER THREAD (bounded memory; "
    "oldest events overwrite first). The derived env twin "
    "AURON_TPU_OBS_RECORDER_EVENTS also applies at import, before any "
    "session conf reaches the bridge",
)
OBS_QUERIES_KEEP = int_conf(
    "obs.queries.keep", 64, "observability",
    "finished query-trace summaries retained in the /queries ring",
)


def apply_conf(conf) -> None:
    """Apply explicitly-set obs knobs from a session/task conf (called by
    the bridge on task entry, next to the httpsvc lazy start). Only keys
    the SESSION conf actually carries are applied — env values took
    effect at import, and re-asserting them per task would clobber a
    later programmatic set_mode (bench.py --trace-out under
    AURON_TPU_OBS_MODE=off, for instance)."""
    if conf.has(OBS_MODE, include_env=False):
        set_mode(conf.get(OBS_MODE))
    if conf.has(OBS_RING_EVENTS, include_env=False):
        core.set_ring_capacity(conf.get(OBS_RING_EVENTS))
    if conf.has(OBS_QUERIES_KEEP, include_env=False):
        from auron_tpu.obs.span import set_queries_keep

        set_queries_keep(conf.get(OBS_QUERIES_KEEP))


# ---------------------------------------------------------------------------
# instrumentation facade (the engine-side call sites)
# ---------------------------------------------------------------------------


def note_op(op: str, metric: str, dur_ns: int) -> None:
    """One MetricNode.timer interval (exec/metrics.py), as an ``op`` event
    of the flight recorder. The SAME dt lands in the metric tree. It is
    dispatch time, and a timer may stay open across a ``yield``: the
    event is NOT a region (no layer) and no reader sums it by layer."""
    if core._mode == MODE_OFF:
        return
    sp = _span_var.get()
    tid, sid = (sp.trace_id, sp.span_id) if sp is not None else (0, 0)
    core.record("op", metric, dur_ns, tid, sid, 0, op.partition(".")[0])


def _event(kind: str, name: str, arg: dict) -> None:
    """An event of no duration and no layer (NOT a region: it takes nothing
    out of ``pump:batch``'s self time), under the calling thread's span."""
    sp = _span_var.get()
    tid, sid = (sp.trace_id, sp.span_id) if sp is not None else (0, 0)
    core.record(kind, name, 0, tid, sid, 0, arg)


def note_agg_fold(rows: int, in_rows: int, path: str = "deferred",
                  mode: str = "partial", live: int | None = None,
                  take: str | None = None,
                  scatters: tuple[int, int] | None = None) -> None:
    """One fold of a batch into an aggregate (exec/agg_exec.py): ``path`` is
    ``dense`` (the direct-address table: one scatter, no sort), ``probe``
    (the sorted state probed and scatter-updated), ``sort`` (the blocking
    sort-segmented reduce: every merge-mode fold, and a partial fold outside
    the deferred arm) or ``deferred`` (the partial aggregate's sync-free
    arm, a mispredict's repair included); ``rows`` the capacity the fold
    runs at beside ``in_rows``, the capacity the batch came in with;
    ``mode`` the aggregate's; ``live`` the batch's live rows where the site
    has read them (None where reading them would cost a sync); ``take`` how
    the dense arm's compaction boundary took the batch: ``seed``,
    ``compact``, ``dense``, ``repair`` as a join's takes (exec/
    selectivity.py) or ``empty`` (the count came out 0: ``rows`` 0, no
    program issued), None off that arm and for a held batch folded again
    after a restart; ``scatters`` the (32-bit, 64-bit) planes the dense
    table's device fold scatters at ``rows`` (flags, limbs of the integer
    sums and counts, whole float sums and extrema: known from the types and
    the shape, no read; None off that fold and on its host substrate), the
    event's ``narrow`` and ``wide``. A ``fold`` event; ``window_summary``
    sums ``rows`` as ``agg_fold_rows`` and by path as ``agg_folds``, counts
    the dense arm's by ``take`` as ``agg_dense_folds`` and sums ``rows`` x
    planes as ``agg_dense_scatter_rows``."""
    if core._mode == MODE_OFF:
        return
    narrow, wide = scatters or (None, None)
    _event("fold", f"agg.{mode}",
           {"rows": rows, "in_rows": in_rows, "path": path, "live": live,
            "take": take, "narrow": narrow, "wide": wide})


def note_agg_reduce(rows: int, how: str) -> None:
    """One grouped reduce of the sort-segmented aggregate (``HashAggExec.
    _group_reduce``: folds, merges of staged state, collision repairs alike):
    ``how`` is ``sort`` (a device sort at ``rows`` of capacity), ``hostsort``
    (the host's lexsort, XLA:CPU's arm) or ``mergepath`` (two sorted runs
    merged by rank, no sort). A ``reduce`` event; ``window_summary`` sums the
    rows that were sorted as ``agg_sorted_rows`` and counts by ``how`` as
    ``agg_reduces``."""
    if core._mode == MODE_OFF:
        return
    _event("reduce", "agg.reduce", {"rows": rows, "how": how})


def note_agg_emit(groups: int | None, mode: str) -> None:
    """One emission of an aggregate's groups (its state at the end of its
    stream, an intermediate passed through while skipping): ``groups`` as
    the host holds them from the reads the aggregate makes anyway (None
    where no read has settled them). An ``emit`` event; ``window_summary``
    sums them as ``agg_groups``."""
    if core._mode == MODE_OFF:
        return
    _event("emit", f"agg.{mode}", {"groups": groups})


def note_decimal_host_cells(cells: int, site: str) -> None:
    """DECIMAL cells the host handled one by one in Python: ``site`` is
    ``final`` (a wide sum or average rebuilt from its limbs,
    ``HashAggExec._final_wide``) or ``arith`` / ``compare`` (wide-decimal
    arithmetic and comparison tables, one cell a dictionary entry or a
    distinct pair, exprs/eval.py). A ``decimal`` event;
    ``window_summary`` sums them as ``wide_decimal_host_cells``."""
    if core._mode == MODE_OFF:
        return
    _event("decimal", f"decimal.{site}", {"cells": cells})


def note_join_take(mode: str, rows: int, in_rows: int) -> None:
    """One take of the unique-build join's output boundary (the BHJ
    driver, its fused stage twin, the star-join chain): ``mode`` is
    ``compact`` (the predicted bucket), ``dense`` (the batch's capacity),
    ``seed`` (a stream's first batch, taken at the bucket of the live
    count just read) or ``repair`` (a mispredict's second take); ``rows``
    the rows of capacity the build columns were gathered at (the chain:
    times its levels), ``in_rows`` the capacity the batch came in with.
    A ``take`` event of no duration and no layer, like ``fold``;
    ``window_summary`` sums ``rows`` as ``join_gather_rows`` and counts
    the takes by mode as ``join_takes``."""
    if core._mode == MODE_OFF:
        return
    _event("take", "join.unique", {"mode": mode, "rows": rows, "in_rows": in_rows})


def note_join_lookup(kind: str, rows: int) -> None:
    """One lookup of the unique-build join's probe, noted once a probed
    batch a level where the takes are (the BHJ driver, for its fused stage
    twin too; the star-join chain): ``kind`` is how the key -> build row
    map was read, ``compare`` (against a small build's live key list),
    ``lut`` (one gather a row), ``hash`` (the hash map, ops/hashmap.py:
    two slots and a row gathered) or ``search`` (the sorted words); ``rows``
    the width the lookup ran at, the batch's capacity. A ``lookup`` event
    of no duration and no layer, like ``take``; ``window_summary`` sums
    ``rows`` by kind as ``join_lookup_rows``."""
    if core._mode == MODE_OFF:
        return
    _event("lookup", "join.unique", {"kind": kind, "rows": rows})


def note_join_hash_build(rows: int, buckets: int, rounds: int,
                         fallback: bool) -> None:
    """One build of a join's hash map (``exec/joins/core.py
    prepare_build``, ops/hashmap.py): ``rows`` the live keys it placed,
    ``buckets`` the table's slots, ``rounds`` the rounds of placement it
    ran, ``fallback`` True where it met a duplicate key or left a row
    unplaced and the build took its sorted path instead. A ``hashbuild``
    event of no duration and no layer, like ``take``; ``window_summary``
    sums them as ``join_hash_build`` ``{n, rows, rounds, fallbacks}``."""
    if core._mode == MODE_OFF:
        return
    _event("hashbuild", "join.unique",
           {"rows": rows, "buckets": buckets, "rounds": rounds,
            "fallback": fallback})


def note_sort(rows: int, where: str, substrate: str) -> None:
    """One sort of ``rows`` rows of capacity: ``where`` is ``sort_exec``
    (a plan's Sort, exec/sort_exec.py), ``join_build`` (a join's sorted
    build, ``exec/joins/core.py prepare_build``) or ``hostsort`` (an
    aggregate's order from the host's lexsort, ``ops/segments.py
    host_order``); ``substrate`` ``host`` or ``device``. A ``sort`` event
    of no duration and no layer, like ``take``; ``window_summary`` sums
    ``rows`` by ``where`` and substrate as ``sort_rows``."""
    if core._mode == MODE_OFF:
        return
    _event("sort", f"sort.{where}", {"rows": rows, "where": where,
                                     "substrate": substrate})


def note_compaction_index(form: str, in_rows: int, out_rows: int) -> None:
    """One program launched with a compaction index (``columnar/batch.py
    compaction_index``: the aggregate's drains and boundary, the joins'
    takes, a small build's key list), noted by the host that launches it:
    ``form`` the index's, ``search`` or ``scatter``
    (``compaction_index_form`` of the static shapes), ``in_rows`` the
    capacity it reads, ``out_rows`` the bucket it fills. An ``index`` event
    of no duration and no layer, like ``take``; ``window_summary`` counts
    them by form as ``compaction_index``, with ``in_rows`` summed as
    ``rows`` and ``out_rows`` as ``out_rows``."""
    if core._mode == MODE_OFF:
        return
    _event("index", "compaction", {"form": form, "in_rows": in_rows,
                                   "out_rows": out_rows})


def note_exchange_read(rows: int, capacity: int, carried: int) -> None:
    """One batch the shuffle reader sealed (``exec/shuffle/reader.py``, both
    decode paths): ``rows`` its live rows, ``capacity`` its bucket,
    ``carried`` the rows the seal rule (``seal_rows``) left for the next
    batch, 0 where it sealed all it held. A ``seal`` event of no duration
    and no layer, like ``take``; ``window_summary`` sums them as
    ``exchange_read`` ``{batches, rows, capacity, carried}``."""
    if core._mode == MODE_OFF:
        return
    _event("seal", "exchange.read", {"rows": rows, "capacity": capacity,
                                     "carried": carried})


def note_sync(dur_ns: int, is_async: bool) -> None:
    """One device->host read observed by EngineCounters (blocking sync or
    async-window harvest), into the trace counters of the calling
    thread's span. Its region and ring event are the hook's own
    ``span(<site>, cat="sync")``."""
    if core._mode != MODE_TRACE:
        return
    trace = current_trace()
    if trace is not None:
        trace.note_sync(dur_ns, is_async)


def note_compile(dur_ns: int) -> None:
    """One backend compile (or fetch from the persistent cache), into
    the trace counters; its region is the hook's ``span(<program>,
    cat="compile")``, named by the XLA module."""
    if core._mode != MODE_TRACE:
        return
    trace = current_trace()
    if trace is not None:
        trace.note_compile(dur_ns)


def note_pump_batch() -> None:
    """One batch through a task pump (runtime/task.py): the trace's
    batch count (its interval is the pump's ``pump:batch`` span)."""
    if core._mode != MODE_TRACE:
        return
    trace = current_trace()
    if trace is not None:
        trace.note_batch()


if core.KILLED:  # no-obs baseline (make obscheck): rebind facade to no-ops
    def _noop(*a, **k) -> None:
        return None

    note_op = note_sync = note_compile = note_pump_batch = _noop  # noqa: F811
    note_agg_fold = note_join_take = note_join_lookup = _noop  # noqa: F811
    note_join_hash_build = note_sort = _noop  # noqa: F811
    note_compaction_index = note_exchange_read = _noop  # noqa: F811
    note_agg_reduce = note_agg_emit = note_decimal_host_cells = _noop  # noqa: F811
    apply_conf = _noop  # noqa: F811
