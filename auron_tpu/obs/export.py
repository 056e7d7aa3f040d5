"""Exporters: Chrome/Perfetto trace JSON, Prometheus text, query ring,
and the one reader over the rings by layer (``window_summary``).

Views of the same recorded state (docs/observability.md):

- ``chrome_trace()``   — the flight recorder's rings as Chrome
  trace-event JSON (loadable in Perfetto / chrome://tracing): one
  "process" per trace id (pid = trace, so per-query attribution is the
  grouping), one "thread" row per recorder ring, complete ("X") events
  for spans/timers and the compile/sync/spill/harvest event stream.
- ``prometheus_text()`` — ``MetricNode.flat_totals`` of every LIVE task
  plus the process-wide ``EngineCounters`` rendered as Prometheus 0.0.4
  text exposition with task/stage/partition/operator labels
  (``/metrics.prom``).
- the recent-queries ring (obs/span.py) served at ``/queries``.
- ``window_summary()`` — where the host's time went between two readings
  of the clock, by layer: count, seconds and SELF seconds of the regions
  (the benchmark's per-layer metrics read it).
"""

from __future__ import annotations

import json

from auron_tpu.obs import core

# ---------------------------------------------------------------------------
# Chrome / Perfetto trace-event JSON
# ---------------------------------------------------------------------------


def chrome_trace(last_s: float | None = None,
                 trace_id: int | None = None) -> dict:
    """Trace-event JSON object for the recorder's current contents."""
    groups = core.snapshot_events(last_s=last_s, trace_id=trace_id)
    events: list[dict] = []
    named: set = set()
    for ring, evs in groups:
        tid = ring["tid"]
        for (ts, dur, kind, name, tr, sp, parent, arg, layer) in evs:
            if (tr, tid) not in named:
                named.add((tr, tid))
                events.append({
                    "ph": "M", "name": "thread_name", "pid": tr, "tid": tid,
                    "args": {"name": ring["name"]},
                })
            if isinstance(arg, dict):
                args = dict(arg)
            elif kind == "op":
                # carry op + raw metric name so consumers can re-derive
                # per-op totals under the MetricNode.op_seconds rules
                args = {"op": arg, "metric": name}
            elif arg is not None:
                args = {"arg": arg}
            else:
                args = {}
            if sp:
                args["span"] = sp
            if parent:
                args["parent"] = parent
            events.append({
                "ph": "X",
                "name": f"{arg}.{name}" if kind == "op" and arg else name,
                # a region renders under its layer, an op timer as "op"
                "cat": layer or kind,
                "ts": ts / 1e3,        # trace-event time unit is us
                "dur": max(dur / 1e3, 0.001),
                "pid": tr,
                "tid": tid,
                "args": args,
            })
    for tr_id, tr_name in _trace_names():
        events.append({
            "ph": "M", "name": "process_name", "pid": tr_id,
            "args": {"name": tr_name},
        })
    return {"traceEvents": events, "displayTimeUnit": "ms"}


# ---------------------------------------------------------------------------
# by layer: where the host's time went in a window
# ---------------------------------------------------------------------------


def self_ns(regions: list) -> list:
    """Self nanoseconds of one thread's regions ``(start, end, ...)``,
    already clipped: each region's duration minus what the regions nested
    directly inside it cover. Regions of one thread nest properly (they
    are ``with`` blocks), so one pass over them by start with a stack of
    the open ones is exact. Returns ``[(region, self_ns), ...]``. Also the
    one implementation behind ``benchmark/trace_scopes.py``'s tables."""
    out, stack = [], []        # stack of [region, covered_by_children_ns]
    def close(upto):
        while stack and stack[-1][0][1] <= upto:
            reg, covered = stack.pop()
            out.append((reg, reg[1] - reg[0] - covered))
    for reg in sorted(regions, key=lambda r: (r[0], -r[1])):
        close(reg[0])
        if stack:
            stack[-1][1] += min(reg[1], stack[-1][0][1]) - reg[0]
        stack.append([reg, 0])
    close(float("inf"))
    return out


def window_summary(t0_s: float, t1_s: float, top: int = 10) -> dict:
    """Where the host's time went between ``t0_s`` and ``t1_s`` (readings
    of ``time.perf_counter()``, the clock the rings are stamped with).

    From the REGION events (those with a layer: spans, host reads,
    compiles) whose interval meets the window, clipped to it: per layer
    the count ``n``, the summed duration ``total_s`` and the self time
    ``self_s`` (duration minus the part its same-thread child regions
    cover), under ``layers``; the same three per ``<layer>:<name>`` under
    ``spans`` (host reads excepted: their names are sites). Thread-seconds,
    not wall: two map pumps run at once. ``d2h_bytes`` sums the bytes of
    the host reads; ``sync_sites`` ranks them by seconds as ``[site, n,
    seconds]``. ``agg_fold_rows`` sums the capacities the aggregates' folds
    ran at (the ``fold`` events that began in the window,
    ``obs.note_agg_fold``) and ``agg_folds`` holds them by path (``dense``,
    ``probe``, ``sort``, ``deferred``) as ``{n, rows, live}``, with
    ``agg_dense_folds`` counting the dense arm's by how its compaction
    boundary took the batch (``seed``, ``compact``, ``dense``, ``repair``,
    ``empty``) and ``agg_dense_scatter_rows`` summing, over the dense
    table's device folds, ``rows`` x the planes each scattered, ``narrow``
    (32-bit: flags, limbs) and ``wide`` (64-bit) apart;
    ``agg_sorted_rows`` sums the capacities the grouped reduces sorted and
    ``agg_reduces`` counts them by ``how`` (the ``reduce`` events,
    ``obs.note_agg_reduce``); ``agg_groups`` sums the groups the aggregates
    emitted (``emit``, ``obs.note_agg_emit``); ``wide_decimal_host_cells``
    the DECIMAL cells the host handled one by one (``decimal``,
    ``obs.note_decimal_host_cells``); ``join_gather_rows`` sums the
    capacities the unique-build joins gathered their build columns at and
    ``join_takes`` counts those takes by mode (the ``take`` events,
    ``obs.note_join_take``); ``join_lookup_rows`` sums by kind the widths
    the unique-build probes looked their keys up at (the ``lookup``
    events, ``obs.note_join_lookup``) and ``join_hash_build`` the joins'
    hash-map builds as ``{n, rows, rounds, fallbacks}`` (``hashbuild``,
    ``obs.note_join_hash_build``); ``sort_rows`` sums by ``where`` and
    substrate, as ``{where: {substrate: rows}}``, the capacities the plans'
    sorts, the joins' sorted builds and the host's lexsorts ordered (the
    ``sort`` events, ``obs.note_sort``). ``compaction_index`` holds by form
    (``search``, ``scatter``) the programs launched with a compaction index
    as ``{n, rows, out_rows}``: their count, the capacities the indexes
    read and the buckets they filled (the ``index`` events,
    ``obs.note_compaction_index``). ``exchange_read`` sums the batches the
    shuffle reader sealed as ``{batches, rows, capacity, carried}``: their
    count, live rows, capacities and the rows each carried into the next
    (the ``seal`` events, ``obs.note_exchange_read``). ``plan_cache_hits`` and
    ``plan_cache_misses`` count the ``serve:plan`` spans that began in the
    window by their ``cache_hit`` argument. ``exchange_bytes`` sums by
    ``mode`` (``mesh``, ``file``) the ``bytes`` of the ``exchange:write``
    spans that began in the window, and ``exchange_devices_min`` holds by
    span name (``write``, ``read``) the least ``devices`` any of them
    carried: the devices the operands lay on before the send, those of what
    the read handed out. ``stage_devices_min`` is the least ``devices`` of
    the window's ``pump:stage`` spans (the distinct single devices a
    stage's partitions' inputs lay on; None where no stage began), and
    ``partition_pumps`` the ``pump:partition`` regions: their count ``n``,
    their ``thread_s``, the seconds ``open_s`` in which at least one was
    open, and ``width``, the most partitions a stage had: ``thread_s`` over
    ``width x open_s`` is 1 where a stage's partitions ran side by side
    and ``1 / width`` where they took turns. ``launches`` sums the device
    launches of the ``launch`` events that began in the window (obs/
    launch.py: engine programs and eager primitives, each call's host
    seconds): totals ``n`` and ``seconds``, the same ``by_kind``
    (``program``, ``eager``) and ``by_layer`` (the innermost open region's
    layer at the call, ``none`` outside every region), and ``families``
    ranked by seconds as ``[family, kind, n, seconds]``; the calling
    thread's launches not yet in its ring are put there first. ``complete`` is False where
    a ring that may hold events of the window has wrapped, or left the
    registry with events newer than the window's start: the sums are then
    a lower bound and a metric reader reports nothing."""
    lo, hi = int(t0_s * 1e9), int(t1_s * 1e9)
    core.flush_launches()
    complete = core.lost_until_ns() <= lo
    layers: dict[str, dict] = {}
    spans: dict[str, dict] = {}
    sites: dict[str, list] = {}
    d2h = fold_rows = gather_rows = sorted_rows = groups = dec_cells = 0
    folds: dict[str, dict] = {}
    dense_folds: dict[str, int] = {}
    scatter_rows = {"narrow": 0, "wide": 0}
    reduces: dict[str, dict] = {}
    takes: dict[str, int] = {}
    lookups: dict[str, int] = {}
    hash_builds = {"n": 0, "rows": 0, "rounds": 0, "fallbacks": 0}
    sorts: dict[str, dict] = {}
    indexes = {form: {"n": 0, "rows": 0, "out_rows": 0}
               for form in ("search", "scatter")}
    reads = {"batches": 0, "rows": 0, "capacity": 0, "carried": 0}
    plans = [0, 0]          # serve:plan spans: [misses, hits]
    ex_bytes: dict[str, int] = {}
    ex_devices: dict[str, int] = {}     # least devices by exchange:<name>
    stage_devices = None                # least devices over pump:stage spans
    pumps = {"n": 0, "thread_s": 0.0, "open_s": 0.0, "width": 0}
    pump_open: list = []                # pump:partition intervals, clipped
    launches: dict[tuple, list] = {}    # (family, kind, layer) -> [n, ns]

    def book(table: dict, key: str, dur_ns: int, own_ns: int) -> None:
        ent = table.setdefault(key, {"n": 0, "total_s": 0.0, "self_s": 0.0})
        ent["n"] += 1
        ent["total_s"] += dur_ns / 1e9
        ent["self_s"] += own_ns / 1e9

    for ring, evs in core.snapshot_events():
        # a wrapped ring has lost only events that ended before its
        # oldest one did
        if ring["wrapped"] and evs[0][0] + evs[0][1] > lo:
            complete = False
        for ev in evs:
            if not lo <= ev[0] < hi:
                continue
            if ev[8] == "serve" and ev[3] == "plan":
                plans[bool(ev[7]["cache_hit"])] += 1
            elif ev[8] == "exchange" and isinstance(ev[7], dict):
                if "mode" in ev[7]:
                    ex_bytes[ev[7]["mode"]] = (ex_bytes.get(ev[7]["mode"], 0)
                                               + ev[7].get("bytes", 0))
                if "devices" in ev[7]:
                    ex_devices[ev[3]] = min(ex_devices.get(ev[3], 1 << 30),
                                            ev[7]["devices"])
            elif ev[8] == "pump" and ev[3] == "stage":
                pumps["width"] = max(pumps["width"], ev[7]["parts"])
                stage_devices = (ev[7]["devices"] if stage_devices is None
                                 else min(stage_devices, ev[7]["devices"]))
            elif ev[2] == "fold":
                fold_rows += ev[7]["rows"]
                ent = folds.setdefault(ev[7]["path"],
                                       {"n": 0, "rows": 0, "live": 0})
                ent["n"] += 1
                ent["rows"] += ev[7]["rows"]
                ent["live"] += ev[7]["live"] or 0
                how = ev[7].get("take")
                if how is not None:
                    dense_folds[how] = dense_folds.get(how, 0) + 1
                for width in scatter_rows:
                    scatter_rows[width] += ev[7]["rows"] * (ev[7].get(width) or 0)
            elif ev[2] == "reduce":
                ent = reduces.setdefault(ev[7]["how"], {"n": 0, "rows": 0})
                ent["n"] += 1
                ent["rows"] += ev[7]["rows"]
                if ev[7]["how"] != "mergepath":
                    sorted_rows += ev[7]["rows"]
            elif ev[2] == "emit":
                groups += ev[7]["groups"] or 0
            elif ev[2] == "decimal":
                dec_cells += ev[7]["cells"]
            elif ev[2] == "take":
                gather_rows += ev[7]["rows"]
                takes[ev[7]["mode"]] = takes.get(ev[7]["mode"], 0) + 1
            elif ev[2] == "lookup":
                kind = ev[7]["kind"]
                lookups[kind] = lookups.get(kind, 0) + ev[7]["rows"]
            elif ev[2] == "hashbuild":
                hash_builds["n"] += 1
                hash_builds["rows"] += ev[7]["rows"]
                hash_builds["rounds"] += ev[7]["rounds"]
                hash_builds["fallbacks"] += bool(ev[7]["fallback"])
            elif ev[2] == "sort":
                ent = sorts.setdefault(ev[7]["where"], {})
                sub = ev[7]["substrate"]
                ent[sub] = ent.get(sub, 0) + ev[7]["rows"]
            elif ev[2] == "index":
                ent = indexes[ev[7]["form"]]
                ent["n"] += 1
                ent["rows"] += ev[7]["in_rows"]
                ent["out_rows"] += ev[7]["out_rows"]
            elif ev[2] == "seal":
                reads["batches"] += 1
                for key in ("rows", "capacity", "carried"):
                    reads[key] += ev[7][key]
            elif ev[2] == "launch":
                for kind, family, layer, n, ns in ev[7]:
                    ent = launches.setdefault((family, kind, layer), [0, 0])
                    ent[0] += n
                    ent[1] += ns
        regions = [
            (max(ts, lo), min(ts + dur, hi), layer, name, arg)
            for (ts, dur, _k, name, _t, _s, _p, arg, layer) in evs
            if layer and ts < hi and ts + dur > lo
        ]
        for (s, e, layer, name, arg), own in self_ns(regions):
            book(layers, layer, e - s, own)
            if layer == "pump" and name == "partition":
                pump_open.append((s, e))
            if layer == "sync":
                d2h += arg.get("bytes", 0) if isinstance(arg, dict) else 0
                site = sites.setdefault(name, [0, 0.0])
                site[0] += 1
                site[1] += (e - s) / 1e9
            else:
                book(spans, f"{layer}:{name}", e - s, own)
    pumps["n"] = len(pump_open)
    pumps["thread_s"] = sum(e - s for s, e in pump_open) / 1e9
    end = 0
    for s, e in sorted(pump_open):      # the union: seconds with one open
        pumps["open_s"] += max(e - max(s, end), 0) / 1e9
        end = max(end, e)
    ranked = sorted(sites.items(), key=lambda kv: -kv[1][1])[:top]
    by_kind: dict[str, dict] = {}
    by_layer: dict[str, dict] = {}
    families: dict[tuple, list] = {}
    for (family, kind, layer), (n, ns) in launches.items():
        for table, key in ((by_kind, kind), (by_layer, layer)):
            ent = table.setdefault(key, {"n": 0, "seconds": 0.0})
            ent["n"] += n
            ent["seconds"] += ns / 1e9
        ent = families.setdefault((family, kind), [0, 0])
        ent[0] += n
        ent[1] += ns
    launch_sum = {
        "n": sum(e["n"] for e in by_kind.values()),
        "seconds": sum(e["seconds"] for e in by_kind.values()),
        "by_kind": by_kind, "by_layer": by_layer,
        "families": [[f, k, n, ns / 1e9] for (f, k), (n, ns)
                     in sorted(families.items(), key=lambda kv: -kv[1][1])[:top]]}
    return {"t0_s": t0_s, "t1_s": t1_s, "complete": complete,
            "layers": layers, "spans": spans, "d2h_bytes": d2h,
            "agg_fold_rows": fold_rows, "agg_folds": folds,
            "agg_dense_folds": dense_folds,
            "agg_dense_scatter_rows": scatter_rows,
            "agg_reduces": reduces, "agg_sorted_rows": sorted_rows,
            "agg_groups": groups, "wide_decimal_host_cells": dec_cells,
            "join_gather_rows": gather_rows, "join_takes": takes,
            "join_lookup_rows": lookups, "join_hash_build": hash_builds,
            "sort_rows": sorts, "compaction_index": indexes,
            "exchange_read": reads,
            "plan_cache_misses": plans[0], "plan_cache_hits": plans[1],
            "exchange_bytes": ex_bytes, "exchange_devices_min": ex_devices,
            "stage_devices_min": stage_devices, "partition_pumps": pumps,
            "launches": launch_sum,
            "sync_sites": [[k, n, secs] for k, (n, secs) in ranked]}


def _trace_names() -> list[tuple[int, str]]:
    # NOTE: the module is fetched via sys.modules — ``from auron_tpu.obs
    # import span`` would resolve to the re-exported span CLASS
    import sys

    _span = sys.modules["auron_tpu.obs.span"]
    out = [(0, "untraced")]
    with _span._traces_lock:
        out += [(t.id, f"{t.kind}:{t.name}") for t in _span._traces.values()]
    with _span._recent_lock:
        out += [(s["trace_id"], f"{s['kind']}:{s['name']}")
                for s in _span._recent]
    return out


def write_chrome_trace(path: str, last_s: float | None = None,
                       trace_id: int | None = None) -> str:
    with open(path, "w") as f:
        json.dump(chrome_trace(last_s=last_s, trace_id=trace_id), f)
    return path


def trace_out_arg(argv, env_key: str) -> str | None:
    """THE ``--trace-out[=]PATH`` scanner shared by bench.py and
    perf_gate.py (env_key is each script's fallback variable)."""
    import os

    for i, a in enumerate(argv):
        if a.startswith("--trace-out="):
            return a.split("=", 1)[1]
        if a == "--trace-out" and i + 1 < len(argv):
            return argv[i + 1]
    return os.environ.get(env_key) or None


# ---------------------------------------------------------------------------
# Prometheus text exposition (0.0.4)
# ---------------------------------------------------------------------------


def _label_escape(v) -> str:
    return (str(v).replace("\\", "\\\\").replace("\n", "\\n")
            .replace('"', '\\"'))


def _labels(d: dict) -> str:
    return "{" + ",".join(
        f'{k}="{_label_escape(v)}"' for k, v in d.items()
    ) + "}"


def render_prometheus(tasks: dict, counters: dict | None,
                      memory: dict | None, queries: int) -> str:
    """Pure renderer (unit-testable with crafted label values). Each
    family is emitted exactly once with one HELP/TYPE block — the
    duplicate-family pitfall — and label values are escaped."""
    fams: list[tuple[str, str, str, list[str]]] = []

    def fam(name: str, typ: str, help_: str, lines: list[str]) -> None:
        if lines:
            fams.append((name, typ, help_, lines))

    if counters:
        for key, typ, help_ in (
            ("compiles", "counter", "XLA program compiles"),
            ("compile_s", "counter", "seconds spent compiling"),
            ("host_syncs", "counter", "blocking device->host syncs"),
            ("host_sync_s", "counter", "seconds blocked in host syncs"),
            ("async_reads", "counter", "async-window harvests"),
            ("async_read_s", "counter", "seconds harvesting async reads"),
            ("batches", "counter", "batches pumped through task runtimes"),
        ):
            if key in counters:
                fam(f"auron_engine_{key}_total", typ, help_,
                    [f"auron_engine_{key}_total {counters[key]}"])
    if memory:
        fam("auron_memory_budget_bytes", "gauge", "memory-manager budget",
            [f"auron_memory_budget_bytes {memory.get('budget_bytes', 0)}"])
        fam("auron_memory_spills_total", "counter", "spills dispatched",
            [f"auron_memory_spills_total {memory.get('num_spills', 0)}"])
        by_name: dict[str, int] = {}
        for c in memory.get("consumers", ()):  # same name may repeat: sum
            by_name[c["name"]] = by_name.get(c["name"], 0) + int(c["mem_used"])
        fam("auron_memory_consumer_bytes", "gauge",
            "registered consumer memory by name",
            [f"auron_memory_consumer_bytes{_labels({'consumer': n})} {v}"
             for n, v in sorted(by_name.items())])

    from auron_tpu.exec.metrics import MetricNode

    op_lines: list[str] = []
    sec_lines: list[str] = []
    for task, t in sorted(tasks.items()):
        base = {"task": task, "stage": t["stage"], "partition": t["partition"]}
        for op, tot in sorted(t["ops"].items()):
            for metric, val in sorted(tot.items()):
                op_lines.append(
                    "auron_op_metric"
                    + _labels({**base, "op": op, "metric": metric})
                    + f" {val}"
                )
            sec_lines.append(
                "auron_op_seconds" + _labels({**base, "op": op})
                + f" {round(MetricNode.op_seconds(tot), 6)}"
            )
    fam("auron_op_metric", "gauge",
        "per-operator MetricNode totals of live tasks (raw units)", op_lines)
    fam("auron_op_seconds", "gauge",
        "per-operator timer seconds of live tasks (MetricNode.op_seconds)",
        sec_lines)
    fam("auron_obs_recent_queries", "gauge",
        "finished query traces in the /queries ring",
        [f"auron_obs_recent_queries {queries}"])

    out = []
    for name, typ, help_, lines in fams:
        out.append(f"# HELP {name} {help_}")
        out.append(f"# TYPE {name} {typ}")
        out.extend(lines)
    return "\n".join(out) + "\n"


def gather_tasks() -> dict:
    """Live task runtimes -> per-operator metric rollups (snapshot()s are
    retry-tolerant against concurrent operator mutation; exec/metrics)."""
    from auron_tpu.bridge import api
    from auron_tpu.exec.metrics import MetricNode

    with api._lock:
        runtimes = dict(api._runtimes)
    tasks = {}
    for h, rt in runtimes.items():
        ops: dict[str, dict[str, int]] = {}
        MetricNode.accumulate_op_totals(rt.ctx.metrics.snapshot(), ops)
        tasks[str(h)] = {
            "stage": rt.ctx.stage_id,
            "partition": rt.ctx.partition_id,
            "ops": ops,
        }
    return tasks


def prometheus_text() -> str:
    from auron_tpu.memory.memmgr import MemManager
    from auron_tpu.obs.span import _recent, _recent_lock  # noqa: F401
    from auron_tpu.utils.profiling import EngineCounters

    counters = (EngineCounters._installed.snapshot()
                if EngineCounters._installed is not None else None)
    memory = MemManager.get().mem_snapshot()
    with _recent_lock:
        nq = len(_recent)
    return render_prometheus(gather_tasks(), counters, memory, nq)
