"""Driver of the batch cells: one closed loop of a query class, back to back.

Set-up makes the star schema from the seed, uploads it once through the query
file's ``ingest`` and runs one untimed query, which compiles or fetches every
program of this seed's own shapes. The window runs the class's queries back to
back, each ended by its last ``next_batch``, until the window's seconds are
spent; a query that has started is finished and counted. Every answer of the
window is compared with the plain reference afterwards.

A query class is a file ``benchmark/queries/<name>.py`` with ``ingest``,
``run``, ``reference``, ``SCAN_COLUMNS`` and ``IN_ORDER``; the traffic file
names it and gives its parameters.
"""

from __future__ import annotations

import gc
import tempfile
import time

from benchmark import compare, datagen
from benchmark.harness import load_module


def setup(config: dict, traffic: dict, seed: int, span, say) -> dict:
    t0 = time.perf_counter()
    frames = datagen.make(config, seed)
    gen_s = time.perf_counter() - t0
    (name,) = traffic["queries"]
    query = load_module("queries", name)
    params = {**config["sizes"], **traffic["params"]}
    t0 = time.perf_counter()
    resident = query.ingest(frames, params)
    ingest_s = time.perf_counter() - t0
    state = {"frames": frames, "query": query, "name": name, "params": params,
             "resident": resident, "span": span, "traffic": traffic,
             "scan_bytes": {name: datagen.column_bytes(frames, query.SCAN_COLUMNS)}}
    t0 = time.perf_counter()
    with tempfile.TemporaryDirectory(prefix="bench_q_") as wd:
        query.run(resident, params, wd, span)
    say(phase="setup", fact_rows=len(frames["store_sales"]), generate_s=gen_s,
        ingest_s=ingest_s, warmup_s=time.perf_counter() - t0)
    return state


def window(state: dict, seconds: float, tracer) -> tuple:
    query, params, span = state["query"], state["params"], state["span"]
    skip = state["traffic"]["trace"]["skip_queries"]
    traced = state["traffic"]["trace"]["queries"]
    records = []
    t_first = time.perf_counter()
    deadline = t_first + seconds
    while time.perf_counter() < deadline:
        if len(records) == skip:
            tracer.start()
        if len(records) == skip + traced:
            tracer.stop()
        rec = {"name": state["name"], "stream": 0, "ok": False,
               "t0": time.perf_counter()}
        try:
            with tempfile.TemporaryDirectory(prefix="bench_q_") as wd, \
                    span("bench:query"):
                rec["answer"], rec["shuffle_bytes"] = query.run(
                    state["resident"], params, wd, span)
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


def check(state: dict, records: list, limits: dict) -> dict:
    """Every answer of the window against the reference (computed once: the
    data does not change between queries)."""
    want = state["query"].reference(state["frames"], state["params"])
    if len(want) == 0:
        raise AssertionError("the reference has no rows: nothing is compared")
    rows_wrong, gap, seen = 0, 0.0, []
    for r in records:
        if not r["ok"]:
            continue
        got = r["answer"]
        hit = next((g for a, g in seen if a.equals(got)), None)
        if hit is None:
            hit = compare.frame_gap(got, want, state["query"].IN_ORDER)
            seen.append((got, hit))
        rows_wrong += hit["rows_wrong"]
        gap = max(gap, hit["float_gap"])
    return {"rows_wrong": {"value": rows_wrong, "limit": limits["rows_wrong"]},
            "float_gap": {"value": gap, "limit": limits["float_gap"]}}


def control(config: dict, traffic: dict, seed: int) -> tuple:
    """The reference put in the program's place and computed in float32:
    ``(state, records)`` for ``check``, which has to find it not correct."""
    frames = datagen.make(config, seed)
    (name,) = traffic["queries"]
    query = load_module("queries", name)
    params = {**config["sizes"], **traffic["params"]}
    low = query.reference(compare.to_float32(frames), params)
    low = low.astype({c: "float64" for c in low.columns
                      if low[c].dtype == "float32"})
    state = {"frames": frames, "query": query, "params": params}
    return state, [{"ok": True, "answer": low}]
