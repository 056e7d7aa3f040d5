"""Driver of the batch cells: one closed loop of query classes, back to back.

Set-up makes the tables from the seed, uploads them once through the query
files' ``ingest`` (queries that share one ``ingest`` share what it uploads)
and runs each query of the mix once untimed, which compiles or fetches every
program of this seed's own shapes. The window runs the mix's queries round
robin, each ended by its last ``next_batch``, until the window's seconds are
spent; a round of the mix that has started is finished and counted, so that
every window holds whole rounds and the seconds per query are those of the
mix whatever the window's length. Every answer of the window is compared with
the plain reference afterwards.

A query class is a file ``benchmark/queries/<name>.py`` with ``ingest``,
``run``, ``reference`` (the rows before ORDER BY and LIMIT), ``ORDER``,
``ASCENDING``, ``LIMIT``, ``SCAN_COLUMNS`` and ``IN_ORDER``; the traffic file
names the mix and gives its parameters.
"""

from __future__ import annotations

import gc
import tempfile
import time

from benchmark import compare, datagen
from benchmark.harness import load_module


def _queries(traffic: dict) -> dict:
    return {name: load_module("queries", name) for name in traffic["queries"]}


def setup(config: dict, traffic: dict, seed: int, span, say) -> dict:
    t0 = time.perf_counter()
    frames = datagen.make(config, seed)
    gen_s = time.perf_counter() - t0
    queries = _queries(traffic)
    params = {**config["sizes"], **traffic["params"]}
    t0 = time.perf_counter()
    uploaded, resident = {}, {}
    for name, q in queries.items():
        if q.ingest not in uploaded:
            uploaded[q.ingest] = q.ingest(frames, params)
        resident[name] = uploaded[q.ingest]
    ingest_s = time.perf_counter() - t0
    state = {"frames": frames, "queries": queries, "params": params,
             "resident": resident, "span": span, "traffic": traffic,
             "scan_bytes": {n: datagen.column_bytes(frames, q.SCAN_COLUMNS)
                            for n, q in queries.items()}}
    warm = {}
    for name, q in queries.items():
        t0 = time.perf_counter()
        with tempfile.TemporaryDirectory(prefix="bench_q_") as wd:
            q.run(resident[name], params, wd, span)
        warm[name] = time.perf_counter() - t0
    say(phase="setup", fact_rows=len(frames["store_sales"]), generate_s=gen_s,
        ingest_s=ingest_s, warmup_s=warm)
    return state


def window(state: dict, seconds: float, tracer) -> tuple:
    params, span = state["params"], state["span"]
    names = list(state["queries"])
    skip = state["traffic"]["trace"]["skip_queries"]
    traced = state["traffic"]["trace"]["queries"]
    records = []
    t_first = time.perf_counter()
    deadline = t_first + seconds
    while time.perf_counter() < deadline or len(records) % len(names):
        if len(records) == skip:
            tracer.start()
        if len(records) == skip + traced:
            tracer.stop()
        name = names[len(records) % len(names)]
        rec = {"name": name, "stream": 0, "ok": False, "t0": time.perf_counter()}
        try:
            with tempfile.TemporaryDirectory(prefix="bench_q_") as wd, \
                    span(f"bench:query:{name}"):
                rec["answer"], rec["shuffle_bytes"] = state["queries"][name].run(
                    state["resident"][name], params, wd, span)
            rec["ok"] = True
        except Exception as e:  # noqa: BLE001 -- a failed query counts in `failed`
            rec["error"] = f"{type(e).__name__}: {e}"
        rec["t1"] = time.perf_counter()
        records.append(rec)
    tracer.stop()
    return records, records[-1]["t1"] - t_first


def finish(state: dict) -> None:
    state["resident"] = None
    gc.collect()


def _want(query, frames: dict, params: dict):
    want = compare.head(query.reference(frames, params), query.ORDER,
                        query.ASCENDING, query.LIMIT)
    if len(want) == 0:
        raise AssertionError("the reference has no rows: nothing is compared")
    return want


def check(state: dict, records: list, limits: dict) -> dict:
    """Every answer of the window against its query's reference (computed
    once: the data does not change between queries)."""
    rows_wrong, gap = 0, 0.0
    for name, query in state["queries"].items():
        want = _want(query, state["frames"], state["params"])
        seen = []
        for r in records:
            if not r["ok"] or r["name"] != name:
                continue
            got = r["answer"]
            hit = next((g for a, g in seen if a.equals(got)), None)
            if hit is None:
                hit = compare.frame_gap(got, want, query.IN_ORDER)
                seen.append((got, hit))
            rows_wrong += hit["rows_wrong"]
            gap = max(gap, hit["float_gap"])
    out = {"rows_wrong": {"value": rows_wrong, "limit": limits["rows_wrong"]}}
    if "float_gap" in limits:
        out["float_gap"] = {"value": gap, "limit": limits["float_gap"]}
    return out


def control(config: dict, traffic: dict, seed: int) -> tuple:
    """The references put in the program's place with money in the lower
    precision that the traffic file names (``control_money``): ``(state,
    records)`` for ``check``, which has to find them not correct."""
    frames = datagen.make(config, seed)
    queries = _queries(traffic)
    params = {**config["sizes"], **traffic["params"]}
    low = compare.money_in(traffic["control_money"], frames, datagen.schemas())
    records = [{"ok": True, "name": n, "answer": _want(q, low, params)}
               for n, q in queries.items()]
    return {"frames": frames, "queries": queries, "params": params}, records
