"""Driver of the SQL cells on a mesh of several chips: ``sql_streams``'s
closed loop over ``POST /sql`` (its ``post``, ``window`` and ``finish``,
imported), with tables from the generator module the configuration names and
a fact table that is handed to the server one split at a time.

What differs from ``sql_streams`` and why:

- the configuration's ``data.module`` (``datagen_store``) makes the frames and
  brings its own ``schemas()``, as in ``drivers/batch_agg.py``;
- ``store_sales`` at the configuration's scale does not fit one chip, so its
  batches are made as the server asks for them (a generator): the server
  has split ``i`` made on mesh device ``i mod n_parts``, and no chip ever
  holds more than its share;
- before any table is made the program is asked for what a mesh of several
  chips needs of it (``Batch.on_device``: a table's split placed on its chip),
  so a program without it exits non-zero within seconds instead of loading
  the whole table onto chip 0;
- the answers are compared by text: ``q65`` against the reference's top 100,
  ``q65_sb`` against the reference's ``attrs["sb"]`` (the stores' averages),
  both exact, a DECIMAL cell as the decimal string of its column's scale,
  the columns under the reference's own names.

What the comparison can and cannot hold at the configuration's scale: a pair
has about 60 sales in the year at sf=24, no revenue lies under a tenth of its
store's average, and ``q65``'s top 100 is EMPTY there. Of a ``q65`` answer the
run then holds its column names and that it has no rows; what the text runs
beyond ``q65_sb`` (the broadcast of the averages, the wide compare, the joins
with ``store`` and ``item``, the collect task's ORDER BY) is held to the
reference only where the top 100 has rows: in the rehearsals on the CPU's
virtual devices (``tests/test_mesh_deployment.py`` and
``benchmark/tests/test_q65_mesh_cell.py``, sf 0.03-0.05, 100 rows), not on the
chip. ``q65_sb``'s 13 averages are DECIMAL(21,6): a cent on one of a store's
18,000 pair sums moves its average by 5.6e-7, which shows in the sixth place
in about half the cases and always from two cents on; float32 money moves
every store's average (the control).
"""

from __future__ import annotations

import decimal
import http.client
import importlib
import json
import re
import time

import pandas as pd

from benchmark import compare, datagen
from benchmark.harness import load_module, say

_sql = load_module("drivers", "sql_streams")
post, finish = _sql.post, _sql.finish
_queries, _texts, to_answer = _sql._queries, _sql._texts, _sql.to_answer

_DECIMAL = re.compile(r"-?\d+\.(\d+)")


def _generator(config: dict):
    return importlib.import_module("benchmark." + config["data"]["module"])


def require_program() -> None:
    """What a mesh of several chips needs of the program, asked for before
    any table is made."""
    from auron_tpu.columnar.batch import Batch
    from auron_tpu.sql.catalog import Catalog

    if not hasattr(Batch, "on_device") or not hasattr(Catalog, "declared"):
        raise SystemExit(
            "sql_mesh: this program places no table split on a chip of its "
            "own (no Batch.on_device): a mesh of several chips would load "
            "every table onto chip 0")


def _schema_of(gen, table: str, physical: bool = False):
    from auron_tpu import types as T
    from benchmark import ingest

    return T.Schema(tuple(
        T.Field(c, T.INT64 if physical and t.startswith("decimal")
                else ingest.dtype_of(t), nullable)
        for c, t, nullable in gen.schemas()[table]))


def _splits(gen, df: pd.DataFrame, table: str, batch_rows: int):
    """The table's batches in row order, each made when it is asked for."""
    from auron_tpu.columnar.batch import Batch

    physical, declared = _schema_of(gen, table, True), _schema_of(gen, table)
    for lo in range(0, max(len(df), 1), batch_rows):
        b = Batch.from_pandas(df.iloc[lo:lo + batch_rows], schema=physical)
        yield Batch(declared, b.device, b.dicts)


def setup(config: dict, traffic: dict, seed: int, span, say) -> dict:
    require_program()
    import jax

    from auron_tpu.serve.server import SqlServer
    from auron_tpu.sql.catalog import Catalog
    from auron_tpu.utils import httpsvc

    gen = _generator(config)
    t0 = time.perf_counter()
    frames = gen.make(config, seed)
    gen_s = time.perf_counter() - t0
    queries, texts = _queries(traffic), _texts(traffic)
    sizes = config["sizes"]

    t0 = time.perf_counter()
    catalog = Catalog.declared({t: _schema_of(gen, t) for t in frames},
                               {t: len(df) for t, df in frames.items()})
    tables = {t: _splits(gen, df, t, sizes["batch_rows"])
              for t, df in frames.items()}
    server = SqlServer(catalog, tables, n_parts=sizes["n_parts"])
    jax.block_until_ready([b.device for bs in server.tables.values() for b in bs])
    port = httpsvc.start(0)
    httpsvc.install_sql_server(server)
    ingest_s = time.perf_counter() - t0
    resident = [sum(int(a.nbytes) for bs in server.tables.values() for b in bs
                    for a in jax.tree.leaves(b.device)
                    if a.devices() == {d})
                for d in server.mesh.devices.flat]

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
    say(phase="setup", fact_rows=len(frames["store_sales"]), generate_s=gen_s,
        ingest_s=ingest_s, warmup_s=warm, serve=server.stats(),
        resident_bytes_by_chip=resident,
        peak_bytes_by_chip=_peaks(server))
    return state


def _peaks(server) -> list:
    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in server.mesh.devices.flat]


def window(state: dict, seconds: float, tracer) -> tuple:
    """``sql_streams.window``, and as evidence every chip's peak bytes when
    it has closed (the result line holds the fullest chip's alone), each
    answer's latency by text and how many rows each text answered."""
    records, window_s = _sql.window(state, seconds, tracer)
    say(phase="window", peak_bytes_by_chip=_peaks(state["server"]),
        latency_s=[[r["name"], round(r["t1"] - r["t0"], 4)] for r in records],
        answer_rows={r["name"]: len(r["answer"]["rows"])
                     for r in records if r["ok"]})
    return records, window_s


def wants(queries: dict, frames: dict, params: dict) -> dict:
    """Each text's reference rows: query 65's under ORDER BY ... LIMIT, and
    the averages its reference hangs on its frame for ``q65_sb`` (one pass
    over the fact table serves both texts)."""
    out = {}
    if "q65" in queries:
        q = queries["q65"]
        ref = q.reference(frames, params)
        out["q65"] = compare.head(ref, q.ORDER, q.ASCENDING, q.LIMIT)
        if "q65_sb" in queries:
            out["q65_sb"] = ref.attrs["sb"].reset_index(drop=True)
    for name, q in queries.items():
        if name not in out:
            out[name] = compare.head(q.reference(frames, params), q.ORDER,
                                     q.ASCENDING, q.LIMIT)
    if not any(len(w) for w in out.values()):
        raise AssertionError("the references have no rows: nothing is compared")
    return out


def _scale(col: pd.Series):
    """The scale of a reference column of decimals, None of any other."""
    for v in col:
        if isinstance(v, decimal.Decimal):
            return -v.as_tuple().exponent
    return None


def as_frame(answer: dict, want: pd.DataFrame) -> pd.DataFrame:
    """An answer's JSON rows as a frame under the reference's column names,
    by position. A cell of a column the reference holds as decimals is a
    decimal string of that column's scale on the wire and becomes the
    ``Decimal`` it spells; anything else in such a cell stays as it came and
    compares as wrong."""
    if len(answer["columns"]) != len(want.columns):
        return pd.DataFrame()
    scales = [_scale(want[c]) for c in want.columns]

    def cell(v, scale):
        if scale is not None and isinstance(v, str):
            m = _DECIMAL.fullmatch(v)
            if m and len(m.group(1)) == scale:
                return decimal.Decimal(v)
        return v

    rows = [[cell(v, s) for v, s in zip(row, scales)] for row in answer["rows"]]
    return pd.DataFrame({c: pd.Series([r[j] for r in rows], dtype=object)
                         for j, c in enumerate(want.columns)})


def rows_wrong_of(answer: dict, want: pd.DataFrame, in_order: bool) -> int:
    """The wrong rows of one answer. An answer under other column names than
    the reference's is wrong in every row of the reference, and in one where
    that has none: the names are what an answer of no rows still says."""
    if list(answer["columns"]) != list(want.columns):
        return max(len(want), 1)
    return compare.frame_gap(as_frame(answer, want), want, in_order)["rows_wrong"]


def check(state: dict, records: list, limits: dict) -> dict:
    """Every answer of the window against its text's reference: the wrong rows
    of every ``q65`` answer's top 100 and of every ``q65_sb`` answer's
    averages, summed."""
    want = wants(state["queries"], state["frames"], state["params"])
    rows_wrong = 0
    seen: dict = {}
    for r in records:
        if not r["ok"]:
            continue
        key = (r["name"], json.dumps(r["answer"], sort_keys=True))
        if key not in seen:
            seen[key] = rows_wrong_of(r["answer"], want[r["name"]],
                                      state["queries"][r["name"]].IN_ORDER)
        rows_wrong += seen[key]
    return {"rows_wrong": {"value": rows_wrong, "limit": limits["rows_wrong"]}}


def control(config: dict, traffic: dict, seed: int) -> tuple:
    """The references put in the program's place with money in the lower
    precision that the traffic file names, as answers a client would have
    parsed; ``check`` has to find them not correct."""
    gen = _generator(config)
    frames = gen.make(config, seed)
    queries = _queries(traffic)
    params = {**config["sizes"], **traffic["params"]}
    low = compare.money_in(traffic["control_money"], frames, gen.schemas())
    records = [{"ok": True, "name": n, "answer": to_answer(w)}
               for n, w in wants(queries, low, params).items()]
    return {"frames": frames, "queries": queries, "params": params}, records
