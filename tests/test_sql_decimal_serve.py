"""The SQL serving path on TPC-DS's own types: DECIMAL(7,2) money, nullable
keys, a catalog of declared schemas and real row counts; POST /sql end to end.

Covers what PR 28 changed: the vectorised DECIMAL ingest, DECIMAL on the wire,
``Catalog.declared``, columnar tables in ``SqlServer``, the ``serve`` layer of
spans and the mesh driver's ``pump:batch`` / ``exchange:*`` regions.
"""

import decimal
import http.client
import json
import os
import threading
import time

import numpy as np
import pandas as pd
import pyarrow as pa
import pytest

from auron_tpu import obs
from auron_tpu import types as T
from auron_tpu.columnar import batch as cb
from auron_tpu.columnar.batch import Batch
from auron_tpu.serve.server import SqlServer
from auron_tpu.sql.catalog import Catalog
from auron_tpu.utils import httpsvc

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MONEY = T.decimal(7, 2)
D = decimal.Decimal


@pytest.fixture(scope="module", autouse=True)
def _suite_leak_canary(leak_canary):
    yield


# ---------------------------------------------------------------------------
# DECIMAL ingest: one pass over the Decimal128 buffer
# ---------------------------------------------------------------------------


def _cell_by_cell(arr: pa.Array, dtype: T.DataType):
    """The ingest this PR replaced, kept here as the oracle: one Python
    Decimal a cell."""
    n = len(arr)
    mask = np.array([x.is_valid for x in arr], dtype=bool).reshape(n)
    ints = np.zeros(n, dtype=np.int64)
    for j, x in enumerate(arr.cast(pa.decimal128(38, dtype.scale))):
        if not x.is_valid:
            continue
        u = int(x.as_py().scaleb(dtype.scale))
        if -(2**63) <= u < 2**63:
            ints[j] = u
        else:
            mask[j] = False
    return ints, mask


@pytest.mark.parametrize("prec,scale", [(7, 2), (17, 2), (18, 4), (18, 0)])
def test_vectorised_decimal_ingest_equals_cell_by_cell(prec, scale):
    rng = np.random.default_rng(prec * 100 + scale)
    dtype = T.decimal(prec, scale)
    n = 3000
    unscaled = rng.integers(-(10**prec) + 1, 10**prec, n)
    cells = [None if rng.random() < 0.2 else D(int(u)).scaleb(-scale)
             for u in unscaled]
    whole = pa.array(cells, type=pa.decimal128(prec, scale))
    for arr in (whole, whole.slice(17, 1000), whole.slice(0, 0)):
        vals, mask, d = cb._arrow_to_host(arr, dtype, 4096)
        want_vals, want_mask = _cell_by_cell(arr, dtype)
        k = len(arr)
        assert d is None
        np.testing.assert_array_equal(vals[:k], want_vals)
        np.testing.assert_array_equal(mask[:k], want_mask)
        assert not mask[k:].any() and not vals[k:].any()


def test_decimal_ingest_overflow_arm_unchanged():
    """An unscaled value past int64 becomes NULL (Spark's non-ANSI
    overflow), its lane 0; the edges of int64 themselves stay."""
    cells = [D(2**63), D(-(2**63)), D(2**63 - 1), None, D(-(2**63) - 1), D(5)]
    arr = pa.array(cells, type=pa.decimal128(38, 0))
    vals, mask, _ = cb._arrow_to_host(arr, T.decimal(18, 0), 8)
    want_vals, want_mask = _cell_by_cell(arr, T.decimal(18, 0))
    np.testing.assert_array_equal(vals[:6], want_vals)
    np.testing.assert_array_equal(mask[:6], want_mask)
    assert mask.tolist() == [False, True, True, False, False, True,
                             False, False]
    assert vals[1] == -(2**63) and vals[2] == 2**63 - 1


def test_from_pandas_decimal_column_round_trips():
    cells = [D("12.30"), None, D("-0.05"), D("99999.99"), D("0.00")]
    df = pd.DataFrame({"k": np.arange(5, dtype=np.int64),
                       "amount": pd.Series(cells, dtype=object)})
    schema = T.Schema((T.Field("k", T.INT64, False),
                       T.Field("amount", MONEY, True)))
    b = Batch.from_pandas(df, schema=schema)
    np.testing.assert_array_equal(
        np.asarray(b.col_values(1))[:5], [1230, 0, -5, 9999999, 0])
    assert b.to_pandas()["amount"].tolist() == cells


# ---------------------------------------------------------------------------
# the specification-typed star schema, tiny
# ---------------------------------------------------------------------------

SCHEMAS = {
    "store_sales": T.Schema((
        T.Field("ss_sold_date_sk", T.INT64, True),
        T.Field("ss_item_sk", T.INT64, False),
        T.Field("ss_ticket_number", T.INT64, False),
        T.Field("ss_quantity", T.INT32, True),
        T.Field("ss_ext_sales_price", MONEY, True),
        T.Field("ss_net_profit", MONEY, True))),
    "date_dim": T.Schema((
        T.Field("d_date_sk", T.INT64, False),
        T.Field("d_year", T.INT32, True),
        T.Field("d_moy", T.INT32, True))),
    "item": T.Schema((
        T.Field("i_item_sk", T.INT64, False),
        T.Field("i_brand_id", T.INT32, True),
        T.Field("i_brand", T.STRING, True),
        T.Field("i_category_id", T.INT32, True),
        T.Field("i_category", T.STRING, True),
        T.Field("i_manufact_id", T.INT32, True),
        T.Field("i_manager_id", T.INT32, True))),
}


def _money(cents: np.ndarray, null: np.ndarray) -> pd.Series:
    return pd.Series([None if z else D(int(c)).scaleb(-2)
                      for c, z in zip(cents, null)], dtype=object)


def make_frames(seed: int = 5, n_fact: int = 24_000) -> dict:
    rng = np.random.default_rng(seed)
    days = 730
    dd = pd.DataFrame({
        "d_date_sk": np.arange(2_450_000, 2_450_000 + days, dtype=np.int64),
        "d_year": pd.array(1999 + np.arange(days) // 365, dtype="Int32"),
        "d_moy": pd.array((np.arange(days) % 365) // 31 + 1, dtype="Int32"),
    })
    dd.loc[3, "d_year"] = pd.NA                # a NULL group key, in November
    dd.loc[3, "d_moy"] = 11
    n_item = 80
    brand_id = rng.integers(1, 12, n_item)
    cat_id = rng.integers(1, 6, n_item)
    it = pd.DataFrame({
        "i_item_sk": np.arange(1, n_item + 1, dtype=np.int64),
        "i_brand_id": pd.array(brand_id, dtype="Int32"),
        "i_brand": pd.Series([f"brand #{b}" for b in brand_id], dtype=object),
        "i_category_id": pd.array(cat_id, dtype="Int32"),
        "i_category": pd.Series([f"cat{c}" for c in cat_id], dtype=object),
        "i_manufact_id": pd.array(rng.choice([128, 7], n_item), dtype="Int32"),
        "i_manager_id": pd.array(rng.choice([1, 28, 3], n_item), dtype="Int32"),
    })
    it.loc[0, ["i_brand", "i_brand_id"]] = [None, pd.NA]      # a NULL group
    it.loc[0, ["i_manufact_id", "i_manager_id"]] = [128, 1]
    it.loc[1, "i_category"] = None
    it.loc[1, "i_manager_id"] = 1
    date_sk = rng.choice(dd.d_date_sk.to_numpy(), n_fact)
    ss = pd.DataFrame({
        "ss_sold_date_sk": pd.array(date_sk, dtype="Int64"),
        "ss_item_sk": rng.integers(1, n_item + 1, n_fact).astype(np.int64),
        "ss_ticket_number": np.arange(n_fact, dtype=np.int64),
        "ss_quantity": pd.array(rng.integers(1, 100, n_fact), dtype="Int32"),
        "ss_ext_sales_price": _money(rng.integers(0, 2_000_000, n_fact),
                                     rng.random(n_fact) < 0.05),
        "ss_net_profit": _money(rng.integers(-1_000_000, 1_000_000, n_fact),
                                rng.random(n_fact) < 0.05),
    })
    ss.loc[rng.random(n_fact) < 0.05, "ss_sold_date_sk"] = pd.NA  # never joins
    return {"store_sales": ss, "date_dim": dd, "item": it}


def make_catalog(frames: dict) -> Catalog:
    return Catalog.declared(SCHEMAS, {t: len(f) for t, f in frames.items()})


@pytest.fixture(scope="module")
def frames():
    return make_frames()


@pytest.fixture(scope="module")
def server(frames):
    return SqlServer(make_catalog(frames), frames, n_parts=2)


@pytest.fixture()
def service(server):
    port = httpsvc.start(0)
    httpsvc.install_sql_server(server)
    yield port
    httpsvc.stop()


def _text(name: str) -> str:
    with open(os.path.join(ROOT, "benchmark", "sql", name + ".sql")) as f:
        return f.read()


def _post(conn, sql: str, tenant: str = "t") -> tuple:
    conn.request("POST", "/sql", body=json.dumps({"sql": sql, "tenant": tenant}))
    r = conn.getresponse()
    return r.status, r.read()


#: per text: date filter, item filter, group keys (reference's names in the
#: text's output order), ORDER BY as (column, ascending)
STAR = {
    "q3": ({"d_moy": 11}, {"i_manufact_id": 128},
           ["d_year", "i_brand_id", "i_brand"],
           [("d_year", True), ("sum", False), ("i_brand_id", True)]),
    "q42": ({"d_moy": 11, "d_year": 2000}, {"i_manager_id": 1},
            ["d_year", "i_category_id", "i_category"],
            [("sum", False), ("d_year", True), ("i_category_id", True),
             ("i_category", True)]),
    "q52": ({"d_moy": 11, "d_year": 2000}, {"i_manager_id": 1},
            ["d_year", "i_brand_id", "i_brand"],
            [("d_year", True), ("sum", False), ("i_brand_id", True)]),
    "q55": ({"d_moy": 11, "d_year": 1999}, {"i_manager_id": 28},
            ["i_brand_id", "i_brand"],
            [("sum", False), ("i_brand_id", True)]),
}


def star_reference(frames: dict, name: str) -> list:
    """The text's answer by plain pandas over whole cents: NULL keys never
    join, NULL group keys form one group, SUM skips NULLs; ORDER BY with
    NULLs first where a key ascends and last where it descends; LIMIT 100."""
    date_f, item_f, keys, order = STAR[name]
    dd, it = frames["date_dim"], frames["item"]
    for c, v in date_f.items():
        dd = dd[(dd[c] == v).fillna(False)]
    for c, v in item_f.items():
        it = it[(it[c] == v).fillna(False)]
    ss = frames["store_sales"].dropna(subset=["ss_sold_date_sk"]).copy()
    ss["cents"] = [None if v is None else int(v.scaleb(2))
                   for v in ss.ss_ext_sales_price]
    m = ss.merge(dd, left_on="ss_sold_date_sk", right_on="d_date_sk")
    m = m.merge(it, left_on="ss_item_sk", right_on="i_item_sk")
    g = (m.groupby(keys, as_index=False, dropna=False)
          .agg(sum=("cents", lambda s: s.astype("float64").sum(min_count=1))))
    rows = []
    for rec in g.to_dict("records"):
        row = [None if pd.isna(rec[k]) else
               (rec[k] if isinstance(rec[k], str) else int(rec[k]))
               for k in keys]
        total = rec["sum"]
        row.append(None if pd.isna(total)
                   else format(D(int(round(total))).scaleb(-2), "f"))
        rows.append(row)
    cols = keys + ["sum"]

    def sort_key(row):
        out = []
        for c, asc in order:
            v = row[cols.index(c)]
            if c == "sum" and v is not None:
                v = D(v)
            null = v is None
            rank = (0 if null else 1) if asc else (1 if null else 0)
            if null:
                out.append((rank, 0))
            elif isinstance(v, str):
                out.append((rank, v))       # ascending only, in these texts
            else:
                out.append((rank, v if asc else -v))
        return tuple(out)

    return sorted(rows, key=sort_key)[:100]


@pytest.mark.parametrize("name", sorted(STAR))
def test_tpcds_text_on_declared_catalog_is_row_exact(server, frames, name):
    rec = server.execute_json({"sql": _text(name), "tenant": "exact"})
    want = star_reference(frames, name)
    assert len(want) > 3, "the reference must hold rows"
    assert rec["rows"] == want
    # a NULL group is there to be compared, and money is a decimal string
    if name in ("q3", "q52"):
        assert any(r[1] is None and r[2] is None for r in want)
    assert all(r[-1] is None or isinstance(r[-1], str) for r in rec["rows"])


def test_sum_of_decimal_derives_decimal_17_2(server):
    lq, _, _ = server.plan(_text("q3"), server.conf)
    assert lq.schema[-1].dtype == T.decimal(17, 2)
    assert [f.nullable for f in lq.schema] == [True] * 4


def test_catalog_declared_wants_every_row_count():
    with pytest.raises(ValueError, match="no row count"):
        Catalog.declared(SCHEMAS, {"store_sales": 1, "item": 1})
    cat = Catalog.declared(SCHEMAS, {t: 10 for t in SCHEMAS})
    assert cat.rows("ITEM") == 10
    with pytest.raises(KeyError):
        cat.rows("nowhere")
    assert cat.rows("nowhere", default=7) == 7


def test_server_refuses_batches_of_another_schema(frames):
    wrong = Batch.from_pandas(
        frames["date_dim"],
        schema=T.Schema(tuple(T.Field(f.name, T.INT64, f.nullable)
                              for f in SCHEMAS["date_dim"])))
    with pytest.raises(ValueError, match="not the catalog's"):
        SqlServer(make_catalog(frames), {**frames, "date_dim": [wrong]},
                  n_parts=1)


def test_columnar_tables_give_the_frames_answers(server, frames):
    """Tables handed over as batches of the declared schema (what a host
    engine's scan hands over) answer as the converted frames do, and every
    view shares the one upload."""
    tables = {t: [Batch.from_pandas(df.iloc[i:i + 5000], schema=SCHEMAS[t])
                  for i in range(0, len(df), 5000)]
              for t, df in frames.items()}
    srv = SqlServer(make_catalog(frames), tables, n_parts=1)
    assert srv.tables["store_sales"][0] is tables["store_sales"][0]
    for name in ("q3", "q55"):
        got = srv.execute_json({"sql": _text(name)})["rows"]
        assert got == server.execute_json({"sql": _text(name)})["rows"]
    lq, _, _ = srv.plan(_text("q3"), srv.conf)
    res = srv._build_resources(lq)
    assert res["sql:store_sales"] == [tables["store_sales"]]
    assert res["sql:item:all"] == [tables["item"]]


# ---------------------------------------------------------------------------
# DECIMAL on the wire
# ---------------------------------------------------------------------------


def test_decimal_round_trip_over_post_sql_exact_to_the_cent(service, frames):
    ss = frames["store_sales"]
    pick = ss[ss.ss_ticket_number < 400]
    assert pick.ss_net_profit.isna().any()
    assert any(v is not None and v < 0 for v in pick.ss_net_profit)
    conn = http.client.HTTPConnection("127.0.0.1", service, timeout=120)
    try:
        status, body = _post(
            conn, "select ss_ticket_number, ss_net_profit from store_sales "
                  "where ss_ticket_number < 400 order by ss_ticket_number")
        assert status == 200, body
        got = json.loads(body)
        assert got["columns"] == ["ss_ticket_number", "ss_net_profit"]
        want = [[int(k), None if v is None else format(v, "f")]
                for k, v in zip(pick.ss_ticket_number, pick.ss_net_profit)]
        assert got["rows"] == want
        assert all(v is None or (isinstance(v, str) and v[-3] == ".")
                   for _, v in got["rows"])
        # a sum: DECIMAL(17,2), exact where float32 would have rounded
        status, body = _post(conn, "select sum(ss_net_profit) total, "
                                   "count(*) n from store_sales")
        assert status == 200, body
        total = sum(v for v in ss.ss_net_profit if v is not None)
        assert json.loads(body)["rows"] == [[format(total, "f"), len(ss)]]
    finally:
        conn.close()


# ---------------------------------------------------------------------------
# four sessions at once
# ---------------------------------------------------------------------------


def test_four_clients_at_once_answer_as_one_alone(service, server):
    names = sorted(STAR)
    conn = http.client.HTTPConnection("127.0.0.1", service, timeout=120)
    alone = {}
    for name in names:
        status, body = _post(conn, _text(name), "alone")
        assert status == 200, body
        got = json.loads(body)
        alone[name] = json.dumps([got["columns"], got["rows"]])
    conn.close()
    before = json.loads(_get(service, "/serve"))
    answers: dict = {}
    errors: list = []

    def client(i: int) -> None:
        c = http.client.HTTPConnection("127.0.0.1", service, timeout=120)
        try:
            for k in range(len(names)):
                name = names[(i + k) % len(names)]
                status, body = _post(c, _text(name), f"s{i}")
                got = json.loads(body)
                assert status == 200 and got["cache_hit"], body
                answers[i, name] = json.dumps([got["columns"], got["rows"]])
        except Exception as e:  # noqa: BLE001 -- relayed to the test thread
            errors.append(e)
        finally:
            c.close()

    threads = [threading.Thread(target=client, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors
    assert len(answers) == 16
    for (_, name), got in answers.items():
        assert got == alone[name]
    after = json.loads(_get(service, "/serve"))
    assert after["queries_ok"] - before["queries_ok"] == 16
    assert after["queries_err"] == before["queries_err"]
    assert after["admission"]["admitted"] - before["admission"]["admitted"] == 16
    assert after["plan_cache"]["hits"] - before["plan_cache"]["hits"] == 16
    assert after["tables_resident"] == 3


def _get(port: int, path: str) -> bytes:
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    try:
        conn.request("GET", path)
        return conn.getresponse().read()
    finally:
        conn.close()


# ---------------------------------------------------------------------------
# the serve layer's spans
# ---------------------------------------------------------------------------

SERVE_SPANS = {"request", "admit", "plan", "execute", "collect", "encode"}


def _await_request_spans(n: int, since_ns: int) -> None:
    """Until ``n`` ``serve:request`` spans that began after ``since_ns`` have
    ENDED: the span closes after the body's last byte is written, so a client
    that has its answer may read the clock before the handler thread does (a
    busy machine: 22 ``serve`` regions for 21, seen in whole tier-1 runs)."""
    deadline = time.perf_counter() + 30.0
    while time.perf_counter() < deadline:
        done = sum(1 for _ring, evs in obs.core.snapshot_events() for ev in evs
                   if ev[8] == "serve" and ev[3] == "request" and ev[0] >= since_ns)
        if done >= n:
            return
        time.sleep(0.002)
    raise AssertionError(f"{n} serve:request spans did not end")


def test_serve_spans_nest_under_the_request_and_are_summed(service):
    obs.set_mode("recorder")
    conn = http.client.HTTPConnection("127.0.0.1", service, timeout=120)
    began = time.perf_counter_ns()
    _post(conn, _text("q55"), "spans")          # a miss or a hit, before t0
    # the window holds this test's three requests and nothing of the one
    # before them: its span has ended before t0, theirs before t1
    _await_request_spans(1, began)
    t0 = time.perf_counter()
    for _ in range(3):
        status, _body = _post(conn, _text("q55"), "spans")
        assert status == 200
    _await_request_spans(4, began)
    conn.close()
    t1 = time.perf_counter()
    lo, hi = int(t0 * 1e9), int(t1 * 1e9)
    per_ring = []
    for _ring, evs in obs.core.snapshot_events():
        serve = [(ts, ts + dur, name, arg) for
                 (ts, dur, kind, name, _t, _s, _p, arg, layer) in evs
                 if layer == "serve" and ts >= lo and ts + dur <= hi]
        if serve:
            per_ring.append(serve)
    assert len(per_ring) == 1, "one kept-alive connection, one handler thread"
    serve = per_ring[0]
    requests = [e for e in serve if e[2] == "request"]
    assert len(requests) == 3
    assert {e[2] for e in serve} == SERVE_SPANS
    for ts, te, name, arg in serve:
        if name != "request":
            assert any(r[0] <= ts and te <= r[1] for r in requests), name
    by_name = {n: [e for e in serve if e[2] == n] for n in SERVE_SPANS}
    assert all(e[3] == {"cache_hit": True} for e in by_name["plan"])
    assert all(e[3]["queue_wait_s"] >= 0 for e in by_name["admit"])
    # the second half of each encode carries the body's size (the record's
    # timings differ by a digit from answer to answer)
    sizes = [e[3]["bytes"] for e in by_name["encode"] if e[3]]
    assert len(sizes) == 3 and all(400 < b < 700 for b in sizes)

    s = obs.window_summary(t0, t1)
    assert s["complete"]
    assert s["layers"]["serve"]["n"] == 3 * 7      # encode has two halves
    assert s["spans"]["serve:request"]["n"] == 3
    assert s["spans"]["serve:encode"]["n"] == 6
    assert s["plan_cache_hits"] == 3 and s["plan_cache_misses"] == 0
    inner = sum(s["spans"][f"serve:{n}"]["total_s"]
                for n in SERVE_SPANS - {"request"})
    assert 0 < inner <= s["spans"]["serve:request"]["total_s"]
    # a request's self time is what is left of it outside its children
    # (the query's root span, of layer `query`, is one of them)
    assert 0 < s["spans"]["serve:request"]["self_s"] <= \
        s["spans"]["serve:request"]["total_s"] - inner + 1e-6


def test_plan_span_says_miss_on_a_new_text(service):
    obs.set_mode("recorder")
    conn = http.client.HTTPConnection("127.0.0.1", service, timeout=120)
    t0 = time.perf_counter()
    status, _ = _post(conn, "select count(*) n from item where i_item_sk > 77",
                      "miss")
    conn.close()
    assert status == 200
    s = obs.window_summary(t0, time.perf_counter())
    assert s["plan_cache_misses"] == 1 and s["plan_cache_hits"] == 0
    # parse, bind and lower ran inside serve:plan
    assert {"sql:sql.parse", "sql:sql.bind", "sql:sql.lower"} <= set(s["spans"])
    assert s["spans"]["serve:plan"]["total_s"] >= \
        s["spans"]["sql:sql.lower"]["total_s"]


# ---------------------------------------------------------------------------
# the mesh driver's regions
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", ["mesh", "file"])
def test_mesh_driver_run_has_pump_and_exchange_regions(server, mode):
    from auron_tpu.parallel.mesh_driver import MeshQueryDriver
    from auron_tpu.utils.config import EXCHANGE_MODE

    obs.set_mode("recorder")
    lq, _, _ = server.plan(_text("q52"), server.conf)
    conf = server.conf.set(EXCHANGE_MODE, mode)
    driver = MeshQueryDriver(server._mesh_for(2), conf=conf)
    t0 = time.perf_counter()
    outs = driver.run(lq.distributed, server._build_resources(lq))
    t1 = time.perf_counter()
    assert sum(b.num_rows() for part in outs for b in part) > 0
    assert driver.stats[0].mode == mode
    s = obs.window_summary(t0, t1)
    spans = s["spans"]
    # a pull per batch and one that ends the stream, per partition and stage
    fact_batches = len(server.tables["store_sales"])
    assert spans["pump:batch"]["n"] >= fact_batches + 4
    assert spans["exchange:write"]["n"] >= 1
    if mode == "mesh":
        # the wait for the collective and the received shards; the file
        # transport's reads are its IpcReaders' own
        assert spans["exchange:read"]["n"] == 1
    # the stages were planned under the spans a bridge task opens: once a
    # partition's task thread, the map stage's two and the residual stage's
    # one a reduce partition (AQE may coalesce the file transport's two
    # into one)
    assert spans["plan:task"]["n"] == spans["plan:fusion"]["n"]
    assert spans["plan:task"]["n"] in (3, 4)
    # each stage is a span, each of its partitions one under it
    assert spans["pump:stage"]["n"] == 2
    assert spans["pump:partition"]["n"] == spans["plan:task"]["n"]
    assert spans["exchange:write"]["self_s"] > 0
