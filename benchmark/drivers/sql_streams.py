"""Driver of the SQL serving cells: closed-loop query streams over ``POST /sql``.

The deployment is the program's own serving stack, nothing stubbed: a
``serve.SqlServer`` behind the ``utils/httpsvc`` service on 127.0.0.1, and
one client thread a stream, each a session on a kept-alive HTTP/1.1
connection that posts a text, reads and parses the whole answer, and posts
the next (TPC-DS's throughput test, clause 7.4, in small).

How the files fit (``benchmark/README.md`` has the rest):

- ``configs/<config>.json`` names this driver, the sizes (``batch_rows``, the
  rows of a resident batch; ``n_parts``, the mesh's width) and the limits;
- ``traffic/<traffic>.json`` gives the streams, their tenants, the texts and
  the traced sub-window (``skip_queries`` answers skipped, ``queries`` held);
- ``sql/<name>.sql`` is a text as it is posted; ``queries/<name>.py`` is the
  same query of the batch cells and lends its ``reference``, ``ORDER``,
  ``ASCENDING``, ``LIMIT``, ``IN_ORDER`` and ``SCAN_COLUMNS``, so that both
  configurations are held to one reference;
- ``schemas/tpcds.json``, ``datagen.py``, ``ingest.py``: the tables from the
  seed, handed to the server as columnar batches of the declared schemas
  (DECIMAL(7,2) money, nullable columns); the catalog is made from those
  schemas and the frames' own row counts.

Set-up posts each text once untimed (which compiles every program of this
seed's shapes and fills the plan cache) and aborts the run on the first answer
that is not 200. In the window stream ``i`` starts at text ``i`` and goes
round robin; no stream submits after the window's seconds, a query in flight
is finished and counted; ``window_s`` is first submit to last answer. Every
answer of the window, as the client parsed it from the body, is compared with
the reference after the window: DECIMAL cells arrive as decimal strings and
are compared exact to the cent.
"""

from __future__ import annotations

import decimal
import gc
import http.client
import json
import os
import re
import threading
import time

import pandas as pd

from benchmark import compare, datagen
from benchmark.harness import HERE, load_module

#: a DECIMAL cell on the wire: digits, a point, the scale's two digits
_MONEY = re.compile(r"-?\d+\.\d\d")


def _queries(traffic: dict) -> dict:
    return {name: load_module("queries", name) for name in traffic["queries"]}


def _texts(traffic: dict) -> dict:
    out = {}
    for name in traffic["queries"]:
        with open(os.path.join(HERE, "sql", name + ".sql")) as f:
            out[name] = f.read()
    return out


def post(conn, text: str, tenant: str, session: dict) -> tuple:
    """One request on a kept-alive connection: ``(status, body)``, the body
    read to its last byte."""
    body = {"sql": text, "tenant": tenant}
    if session:
        body["conf"] = session
    conn.request("POST", "/sql", body=json.dumps(body),
                 headers={"Content-Type": "application/json"})
    resp = conn.getresponse()
    return resp.status, resp.read()


def setup(config: dict, traffic: dict, seed: int, span, say) -> dict:
    import jax

    from auron_tpu.serve.server import SqlServer
    from auron_tpu.sql.catalog import Catalog
    from auron_tpu.utils import httpsvc
    from benchmark import ingest

    # a program without it (the parent commit) fails here, at once, before
    # any data is made
    declared = Catalog.declared
    t0 = time.perf_counter()
    frames = datagen.make(config, seed)
    gen_s = time.perf_counter() - t0
    queries, texts = _queries(traffic), _texts(traffic)
    sizes = config["sizes"]

    t0 = time.perf_counter()
    catalog = declared({t: ingest.schema_of(t) for t in frames},
                       {t: len(df) for t, df in frames.items()})
    tables = {t: ingest.to_batches(df, t, 1, sizes["batch_rows"])[0]
              for t, df in frames.items()}
    jax.block_until_ready([b.device for bs in tables.values() for b in bs])
    server = SqlServer(catalog, tables, n_parts=sizes["n_parts"])
    port = httpsvc.start(0)
    httpsvc.install_sql_server(server)
    ingest_s = time.perf_counter() - t0

    state = {"frames": frames, "queries": queries, "texts": texts,
             "params": {**sizes, **traffic["params"]}, "span": span,
             "traffic": traffic, "server": server, "port": port,
             "scan_bytes": {n: datagen.column_bytes(frames, q.SCAN_COLUMNS)
                            for n, q in queries.items()}}
    warm = {}
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=3600)
    try:
        for name, text in texts.items():
            t0 = time.perf_counter()
            status, body = post(conn, text, "warm", traffic["session"])
            warm[name] = time.perf_counter() - t0
            if status != 200:
                finish(state)
                raise RuntimeError(f"warm-up of {name}: HTTP {status}: "
                                   f"{body[:2000].decode(errors='replace')}")
    finally:
        conn.close()
    say(phase="setup", rows={t: len(df) for t, df in frames.items()},
        generate_s=gen_s, ingest_s=ingest_s, warmup_s=warm, serve=server.stats())
    return state


def _stream(state: dict, i: int, seconds: float, start, done) -> list:
    """Stream ``i``'s session: its texts round robin from text ``i``, none
    submitted later than ``seconds`` after the common start."""
    names = list(state["texts"])
    traffic, span = state["traffic"], state["span"]
    tenant = traffic["tenants"][i]
    conn = http.client.HTTPConnection("127.0.0.1", state["port"], timeout=3600)
    out = []
    start.wait()
    deadline = time.perf_counter() + seconds
    try:
        k = i
        while time.perf_counter() < deadline:
            name = names[k % len(names)]
            k += 1
            rec = {"name": name, "stream": i, "ok": False,
                   "t0": time.perf_counter()}
            try:
                with span(f"bench:query:{name}"):
                    status, body = post(conn, state["texts"][name], tenant,
                                        traffic["session"])
                    rec["status"] = status
                    if status == 200:
                        got = json.loads(body)
                        rec["answer"] = {"columns": got["columns"],
                                         "rows": got["rows"]}
                        rec["cache_hit"] = got.get("cache_hit")
                        rec["ok"] = True
                    else:
                        rec["error"] = body[:500].decode(errors="replace")
            except Exception as e:  # noqa: BLE001 -- a failed query counts in `failed`
                rec["error"] = f"{type(e).__name__}: {e}"
                conn.close()        # the next request reconnects
            rec["t1"] = time.perf_counter()
            out.append(rec)
            done(rec)
    finally:
        conn.close()
    return out


def window(state: dict, seconds: float, tracer) -> tuple:
    traffic = state["traffic"]
    n = traffic["streams"]
    skip, traced = traffic["trace"]["skip_queries"], traffic["trace"]["queries"]
    answered = [0]
    tick = threading.Condition()

    def done(_rec) -> None:
        with tick:
            answered[0] += 1
            tick.notify_all()

    start = threading.Barrier(n + 1)
    results: list = [None] * n

    def run(i: int) -> None:
        results[i] = _stream(state, i, seconds, start, done)

    threads = [threading.Thread(target=run, args=(i,), name=f"bench-stream{i}")
               for i in range(n)]
    for t in threads:
        t.start()
    start.wait()
    # the profiler's window opens and closes on this thread (a region has to
    # end on the thread it began on): after `skip` answers, for `traced` more
    for mark, act in ((skip, tracer.start), (skip + traced, tracer.stop)):
        with tick:
            while answered[0] < mark and any(t.is_alive() for t in threads):
                tick.wait(0.05)
        if answered[0] >= mark:
            act()
    for t in threads:
        t.join()
    tracer.stop()
    records = sorted((r for rs in results for r in rs or []),
                     key=lambda r: r["t1"])
    if not records:
        raise RuntimeError("no stream submitted a query")
    return records, records[-1]["t1"] - min(r["t0"] for r in records)


def finish(state: dict) -> None:
    from auron_tpu.utils import httpsvc

    httpsvc.stop()              # uninstalls the server too
    state["server"] = None
    gc.collect()


def _want(query, frames: dict, params: dict) -> pd.DataFrame:
    want = compare.head(query.reference(frames, params), query.ORDER,
                        query.ASCENDING, query.LIMIT)
    if len(want) == 0:
        raise AssertionError("the reference has no rows: nothing is compared")
    return want


def as_frame(answer: dict, want: pd.DataFrame) -> pd.DataFrame:
    """An answer's JSON rows as a frame under the reference's column names,
    by position (the text names its sum where the reference does, or not at
    all: query 42). A money cell (a column the reference holds as decimals)
    is a decimal string on the wire and is turned back into whole cents;
    anything else in such a cell stays as it came and compares as wrong."""
    if len(answer["columns"]) != len(want.columns):
        return pd.DataFrame()
    money = [any(isinstance(v, decimal.Decimal) for v in want[c])
             for c in want.columns]

    def cell(v, is_money: bool):
        if is_money and isinstance(v, str) and _MONEY.fullmatch(v):
            return decimal.Decimal(int(v.replace(".", ""))).scaleb(-2)
        return v

    rows = [[cell(v, m) for v, m in zip(row, money)] for row in answer["rows"]]
    return pd.DataFrame({c: pd.Series([r[j] for r in rows], dtype=object)
                         for j, c in enumerate(want.columns)})


def check(state: dict, records: list, limits: dict) -> dict:
    """Every answer of the window against its text's reference (computed
    once: the data does not change between queries)."""
    rows_wrong = 0
    for name, query in state["queries"].items():
        want = _want(query, state["frames"], state["params"])
        seen: dict = {}
        for r in records:
            if not r["ok"] or r["name"] != name:
                continue
            key = json.dumps(r["answer"], sort_keys=True)
            if key not in seen:
                seen[key] = compare.frame_gap(as_frame(r["answer"], want), want,
                                              query.IN_ORDER)["rows_wrong"]
            rows_wrong += seen[key]
    return {"rows_wrong": {"value": rows_wrong, "limit": limits["rows_wrong"]}}


def to_answer(df: pd.DataFrame) -> dict:
    """A reference's frame as the JSON a client would have parsed."""
    def cell(v):
        if compare.is_null(v):
            return None
        if isinstance(v, decimal.Decimal):
            return format(v, "f")
        return v.item() if hasattr(v, "item") else v

    return {"columns": list(df.columns),
            "rows": [[cell(v) for v in row]
                     for row in df.itertuples(index=False, name=None)]}


def control(config: dict, traffic: dict, seed: int) -> tuple:
    """The references put in the program's place over the control's data
    that the traffic file names (``compare.control_frames``), as answers a
    client would have parsed: ``(state, records)`` for ``check``, which has
    to find them not correct."""
    frames = datagen.make(config, seed)
    queries = _queries(traffic)
    params = {**config["sizes"], **traffic["params"]}
    low = compare.control_frames(traffic, queries, params, frames,
                                 datagen.schemas())
    records = [{"ok": True, "name": n, "answer": to_answer(_want(q, low, params))}
               for n, q in queries.items()]
    return {"frames": frames, "queries": queries, "params": params}, records
