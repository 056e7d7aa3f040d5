"""Driver of the SQL cells: closed-loop client streams over real HTTP.

Set-up makes the store-channel tables from the seed, starts ``SqlServer``
behind ``utils.httpsvc`` on a free port and POSTs every text once, which
uploads the tables and compiles or fetches every program of this seed's own
shapes. The window starts ``streams`` client threads; stream i replays the
texts round robin from text i mod n, sends its next request when the reply
has come, and stops sending when the window's seconds are spent; a request in
flight is waited for and counted. Latency is POST to last byte on the client's
side of the socket. Every answer of the window is compared with its text's
plain reference afterwards, and with the warm-up's answer to the same text.

A text is a file ``benchmark/queries/<name>.py`` with ``SQL``, ``ORDER``,
``ASCENDING``, ``LIMIT``, ``reference`` and ``SCAN_COLUMNS``.
"""

from __future__ import annotations

import json
import threading
import time
import urllib.error
import urllib.request

import pandas as pd

from benchmark import compare, datagen
from benchmark.harness import load_module


def post_sql(port: int, body: dict, timeout: float) -> tuple:
    """``(status, bytes)`` of one POST /sql, read to the last byte."""
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/sql", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"}, method="POST")
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, r.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


def setup(config: dict, traffic: dict, seed: int, span, say) -> dict:
    from auron_tpu.serve import SqlServer
    from auron_tpu.sql.catalog import TABLES, tpcds_catalog
    from auron_tpu.utils import httpsvc
    from auron_tpu.utils.config import Configuration

    t0 = time.perf_counter()
    frames = datagen.make(config, seed)
    for table, df in frames.items():
        want = [n for n, _, _ in TABLES[table]]
        if list(df.columns) != want:
            raise AssertionError(f"benchmark/datagen.py and the program's "
                                 f"catalog disagree on {table}")
    gen_s = time.perf_counter() - t0
    conf = Configuration()
    for k, v in config["deployment"]["conf"].items():
        conf = conf.set(k, str(v))
    server = SqlServer(tpcds_catalog(config["sizes"]["catalog_fact_rows"]),
                       frames, conf=conf, n_parts=config["sizes"]["n_parts"])
    port = httpsvc.start(0)
    httpsvc.install_sql_server(server)
    texts = {n: load_module("queries", n) for n in traffic["queries"]}
    unknown = set(texts) - set(config["texts"])
    if unknown:
        raise KeyError(f"the traffic names texts the configuration lacks: {unknown}")
    state = {"frames": frames, "server": server, "port": port, "texts": texts,
             "traffic": traffic, "span": span, "first": {},
             "scan_bytes": {n: datagen.column_bytes(frames, q.SCAN_COLUMNS)
                            for n, q in texts.items()}}
    warm = {}
    try:
        for name, q in texts.items():
            t0 = time.perf_counter()
            status, raw = post_sql(port, {"sql": q.SQL, "tenant": "warm"},
                                   timeout=1100.0)
            if status != 200:
                raise RuntimeError(f"warm-up of {name} answered {status}: "
                                   f"{raw.decode(errors='replace')[:2000]}")
            state["first"][name] = json.loads(raw)["rows"]
            warm[name] = time.perf_counter() - t0
    except BaseException:
        httpsvc.stop()
        raise
    say(phase="setup", fact_rows=len(frames["store_sales"]), generate_s=gen_s,
        warmup_s=warm)
    return state


def _stream(i: int, state: dict, deadline: float, out: list) -> None:
    names = state["traffic"]["queries"]
    tenant = state["traffic"]["tenant"].format(i=i)
    timeout = state["traffic"]["request_timeout_s"]
    k = i % len(names)
    while time.perf_counter() < deadline:
        name = names[k % len(names)]
        k += 1
        rec = {"name": name, "stream": i, "ok": False}
        body = {"sql": state["texts"][name].SQL, "tenant": tenant}
        with state["span"](f"bench:http:{name}"):
            rec["t0"] = time.perf_counter()
            try:
                status, raw = post_sql(state["port"], body, timeout)
            except Exception as e:  # noqa: BLE001 -- a failed request counts in `failed`
                status, raw = None, f"{type(e).__name__}: {e}".encode()
            rec["t1"] = time.perf_counter()
        if status == 200:
            rec["ok"] = True
            rec["answer"] = json.loads(raw)
        else:
            rec["error"] = f"{status}: {raw.decode(errors='replace')[:500]}"
        out.append(rec)


def window(state: dict, seconds: float, tracer) -> tuple:
    tr = state["traffic"]["trace"]
    outs = [[] for _ in range(state["traffic"]["streams"])]
    t_first = time.perf_counter()
    deadline = t_first + seconds
    threads = [threading.Thread(target=_stream, args=(i, state, deadline, out),
                                name=f"bench-stream{i}")
               for i, out in enumerate(outs)]
    for t in threads:
        t.start()
    if tracer.on:
        time.sleep(max(0.0, min(tr["start_s"], seconds / 4)))
        tracer.start()
        time.sleep(min(tr["seconds"], seconds / 2))
        tracer.stop()
    for t in threads:
        t.join()
    records = sorted((r for out in outs for r in out), key=lambda r: r["t0"])
    for r in records:
        if r["ok"]:
            r["cache_hit"] = r["answer"].get("cache_hit")
            r["server_wall_s"] = r["answer"].get("wall_s")
    return records, max(r["t1"] for r in records) - t_first


def finish(state: dict) -> None:
    from auron_tpu.utils import httpsvc

    state["stats"] = state["server"].stats()
    httpsvc.stop()
    state["server"] = None


def check(state: dict, records: list, limits: dict) -> dict:
    """Every answer of the window against its text's reference, each distinct
    answer compared once, and against the warm-up's answer to that text."""
    rows_wrong, gap, diverged = 0, 0.0, 0
    for name, q in state["texts"].items():
        want = compare.head(q.reference(state["frames"]), q.ORDER,
                            q.ASCENDING, q.LIMIT)
        if len(want) == 0:
            raise AssertionError(f"{name}: the reference has no rows")
        seen = {}
        for r in records:
            if not r["ok"] or r["name"] != name:
                continue
            ans = r["answer"]
            diverged += ans["rows"] != state["first"][name]
            key = json.dumps([ans["columns"], ans["rows"]])
            if key not in seen:
                got = pd.DataFrame(ans["rows"], columns=ans["columns"])
                seen[key] = compare.frame_gap(got, want, in_order=False)
            rows_wrong += seen[key]["rows_wrong"]
            gap = max(gap, seen[key]["float_gap"])
    return {"rows_wrong": {"value": rows_wrong, "limit": limits["rows_wrong"]},
            "replays_diverged": {"value": diverged,
                                 "limit": limits["replays_diverged"]},
            "float_gap": {"value": gap, "limit": limits["float_gap"]}}


def control(config: dict, traffic: dict, seed: int) -> tuple:
    """The references put in the program's place and computed in float32:
    ``(state, records)`` for ``check``, which has to find them not correct."""
    frames = datagen.make(config, seed)
    low_frames = compare.to_float32(frames)
    texts = {n: load_module("queries", n) for n in traffic["queries"]}
    state = {"frames": frames, "texts": texts, "first": {}}
    records = []
    for name, q in texts.items():
        low = compare.head(q.reference(low_frames), q.ORDER, q.ASCENDING, q.LIMIT)
        rows = [[v.item() if hasattr(v, "item") else v for v in row]
                for row in low.itertuples(index=False, name=None)]
        state["first"][name] = rows
        records.append({"ok": True, "name": name,
                        "answer": {"columns": list(low.columns), "rows": rows}})
    return state, records
