"""Driver of the batch cells whose tables come from a generator module of
their own: ``batch_class``'s closed loop (its ``window``, ``finish`` and
``check``, imported), with the tables made by the module that the
configuration names.

``batch_class.setup`` asks ``datagen.make`` for the frames, and that looks a
generator up in ``datagen.py``'s own globals; a table that ``datagen.py`` does
not make comes from a module beside it (``data.module``, for example
``datagen_store``) that brings ``make(config, seed)`` and ``schemas()``. So a
further table is a generator module, a schema file and a configuration that
names them, and a further plan is a ``queries/<name>.py`` with a ``run`` of
its own: no file that is there is edited.
"""

from __future__ import annotations

import importlib
import tempfile
import time

from benchmark import compare, datagen
from benchmark.harness import load_module

_batch = load_module("drivers", "batch_class")
_queries = _batch._queries
window, finish = _batch.window, _batch.finish


def _generator(config: dict):
    return importlib.import_module("benchmark." + config["data"]["module"])


def setup(config: dict, traffic: dict, seed: int, span, say) -> dict:
    queries = _queries(traffic)
    for q in queries.values():
        # a program that cannot run the plan fails here, before the tables
        getattr(q, "require_program", lambda: None)()
    t0 = time.perf_counter()
    frames = _generator(config).make(config, seed)
    gen_s = time.perf_counter() - t0
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


def _want(query, frames: dict, params: dict):
    """The reference's rows under ORDER BY ... LIMIT, with the parts the
    reference hangs on its frame (``attrs``: query 65's ``sb``) carried over."""
    ref = query.reference(frames, params)
    want = compare.head(ref, query.ORDER, query.ASCENDING, query.LIMIT)
    want.attrs = dict(ref.attrs)
    if len(want) == 0 and not want.attrs:
        raise AssertionError("the reference has no rows: nothing is compared")
    return want


def _rows_wrong(got, want, in_order: bool) -> int:
    """Wrong rows of the answer and of each part the reference names: a
    part that the program's answer lacks counts as every row of it."""
    wrong = compare.frame_gap(got, want, in_order)["rows_wrong"]
    for part, w in want.attrs.items():
        g = got.attrs.get(part)
        wrong += (max(len(w), 1) if g is None
                  else compare.frame_gap(g, w, False)["rows_wrong"])
    return wrong


def check(state: dict, records: list, limits: dict) -> dict:
    """``batch_class.check`` with the parts: every answer of the window
    against its query's reference, and beside the top 100 each intermediate
    that the reference hangs on its frame. ``rows_wrong`` sums both: a query
    whose top 100 holds a handful of rows is still held to every group's sum
    through the stores' averages."""
    rows_wrong = 0
    for name, query in state["queries"].items():
        want = _want(query, state["frames"], state["params"])
        seen = []
        for r in records:
            if not r["ok"] or r["name"] != name:
                continue
            got = r["answer"]
            hit = next((h for a, h in seen if a.equals(got) and all(
                a.attrs[p].equals(got.attrs.get(p)) for p in a.attrs)), None)
            if hit is None:
                hit = _rows_wrong(got, want, query.IN_ORDER)
                seen.append((got, hit))
            rows_wrong += hit
    return {"rows_wrong": {"value": rows_wrong, "limit": limits["rows_wrong"]}}


def control(config: dict, traffic: dict, seed: int) -> tuple:
    """``batch_class.control`` over this configuration's generator: the
    references in the program's place with money in the lower precision that
    the traffic file names; ``check`` has to find them not correct."""
    gen = _generator(config)
    frames = gen.make(config, seed)
    queries = _queries(traffic)
    params = {**config["sizes"], **traffic["params"]}
    low = compare.money_in(traffic["control_money"], frames, gen.schemas())
    records = [{"ok": True, "name": n, "answer": _want(q, low, params)}
               for n, q in queries.items()]
    return {"frames": frames, "queries": queries, "params": params}, records
