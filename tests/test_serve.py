"""Concurrent multi-tenant serving (auron_tpu/serve, docs/serving.md).

Covers the ISSUE-12 satellite contract for the program cache — hit/miss
accounting, bounded-size eviction, invalidation when a session conf
changes a plan-affecting knob, replay-adds-no-compiles across fresh
server sessions — plus admission control (queueing, timeouts, memory
backpressure), the POST /sql front door, and a toy-scale run of the
concurrency differential gate (bit-identity + zero-compile legs; the
throughput floor is `make servegate`'s job at real scale).
"""

import json
import threading
import urllib.error
import urllib.request

import pytest

from auron_tpu.models import sqlgate, tpcds
from auron_tpu.serve import (
    AdmissionController,
    AdmissionTimeout,
    PlanCache,
    QueryError,
    SqlServer,
)
from auron_tpu.serve.cache import plan_cache_key
from auron_tpu.sql.catalog import build_tables
from auron_tpu.utils.config import (
    SERVE_MAX_CONCURRENT,
    SERVE_QUEUE_TIMEOUT_S,
    SQL_SHUFFLE_PARTITIONS,
    Configuration,
)

TOY_SF = 0.02


@pytest.fixture(scope="module", autouse=True)
def _suite_leak_canary(leak_canary):
    """Tier-1 leak canary (conftest): runtimes/resource-map/obs rings
    must return to their pre-suite baselines after this module."""
    yield


@pytest.fixture(scope="module")
def frames():
    data = tpcds.generate(sf=TOY_SF, seed=42)
    return build_tables(data, seed=42)


@pytest.fixture(scope="module")
def server(frames):
    srv = SqlServer(sqlgate.gate_catalog(), frames, n_parts=2)
    yield srv
    # the frames were converted once, at construction: every table is
    # resident as batches of the catalog's schema, and no query replaced
    # or re-uploaded them
    assert set(srv.tables) == set(frames)
    for name, batches in srv.tables.items():
        assert batches and all(
            b.schema == srv.catalog.schema(name) for b in batches), name


def _sql(name):
    return sqlgate.case_by_name(name).sql


# ---------------------------------------------------------------------------
# plan digests
# ---------------------------------------------------------------------------


def test_digest_normalizes_whitespace_comments_case():
    from auron_tpu.sql.digest import plan_digest

    a = plan_digest("select d_year from date_dim where d_moy = 11")
    b = plan_digest(
        "SELECT  d_year\n FROM date_dim -- comment\n WHERE D_MOY = 11")
    c = plan_digest("select d_year from date_dim where d_moy = 12")
    assert a == b        # whitespace/comments/identifier case fold away
    assert a != c        # literals are part of the plan


def test_digest_distinguishes_string_literals_from_bare_tokens():
    """Token kinds survive canonicalization: the lexer strips quotes, so
    a bare rendering would collide ``'1'`` with ``1`` and ``'NAME'``
    with an identifier — two different plans on one cache key (review
    finding, reproduced)."""
    from auron_tpu.sql.digest import plan_digest

    assert plan_digest("select '1' from t") != plan_digest("select 1 from t")
    assert (plan_digest("select a from t where s = 'NAME'")
            != plan_digest("select a from t where s = NAME"))
    # '' escaping round-trips into ONE canonical form
    assert (plan_digest("select 'o''k' from t")
            == plan_digest("select  'o''k'  from t"))


def test_json_rows_serializes_datetimes_and_nulls():
    import json as _json

    import numpy as np
    import pandas as pd

    from auron_tpu.serve.server import _json_rows

    df = pd.DataFrame({
        "d": pd.to_datetime(["2020-01-01", None]),
        "x": [np.int64(7), np.int64(8)],
        "f": [1.5, float("nan")],
    })
    rows = _json_rows(df)
    _json.dumps(rows)  # must be JSON-safe (Timestamp 500'd POST /sql)
    assert rows[0][0].startswith("2020-01-01") and rows[1][0] is None
    assert rows[0][1] == 7 and rows[1][2] is None


def test_digest_distinguishes_quoted_identifiers():
    """Quoted identifiers re-quote in the canonical form: rendered bare,
    ``"a b"`` (one column) collides with ``a b`` (implicit alias) — two
    different plans on one cache key (review finding)."""
    from auron_tpu.sql.digest import plan_digest

    assert (plan_digest('select "a b" from t')
            != plan_digest("select a b from t"))
    assert (plan_digest('select "from" from t')
            != plan_digest("select from from t"))
    # quoting is canonical regardless of surrounding whitespace
    assert (plan_digest('select  "a b"  from t')
            == plan_digest('select "a b" from t'))


def test_failing_query_does_not_leak_task_runtimes(server, monkeypatch):
    """A query whose collect-stage drain fails must still finalize its
    TaskRuntime: a persistent server leaking one handle + pump thread
    per failing request grows without bound (review finding)."""
    from auron_tpu.bridge import api

    before = set(api._runtimes)

    def boom(h):
        raise RuntimeError("injected drain failure")

    monkeypatch.setattr(api, "next_batch", boom)
    with pytest.raises(RuntimeError, match="injected"):
        server.submit(_sql("q3"), tenant="leak")  # q3 has a collect stage
    monkeypatch.undo()
    assert set(api._runtimes) == before
    from auron_tpu.sql.digest import plan_digest

    a = plan_digest("select X from t", fold_ident_case=False)
    b = plan_digest("select x from t", fold_ident_case=False)
    assert a != b


def test_plan_cache_key_includes_plan_knobs():
    conf2 = Configuration().set(SQL_SHUFFLE_PARTITIONS, 2)
    conf4 = Configuration().set(SQL_SHUFFLE_PARTITIONS, 4)
    sql = _sql("q96")
    assert plan_cache_key(sql, conf2) != plan_cache_key(sql, conf4)
    assert plan_cache_key(sql, conf2) == plan_cache_key(sql, conf2)


def test_plan_cache_key_splits_on_fusion_and_host_sort_knobs():
    """The cache-split bugs auronlint R14 found in this tree: the fuse
    family and exec.host.sort are read during lowering/fusion, so two
    sessions differing on them must land on DIFFERENT cache keys —
    before PLAN_KNOBS covered them, both tenants shared one compiled
    plan and the second silently ran under the first's settings."""
    from auron_tpu.utils.config import (
        FUSE_AGG_INPUTS,
        FUSE_ENABLE,
        FUSE_MIN_OPS,
        FUSE_PROBE,
        FUSE_SHUFFLE,
        HOST_SORT_MODE,
    )

    sql = _sql("q96")
    for knob, a, b in (
        (FUSE_ENABLE, "on", "off"),
        (FUSE_PROBE, "on", "off"),
        (FUSE_SHUFFLE, "on", "off"),
        (FUSE_MIN_OPS, 2, 9),
        (FUSE_AGG_INPUTS, True, False),
        (HOST_SORT_MODE, "on", "off"),
    ):
        ka = plan_cache_key(sql, Configuration().set(knob, a))
        kb = plan_cache_key(sql, Configuration().set(knob, b))
        assert ka != kb, f"{knob.key} does not split the plan cache"
    # defaults are stable: two fresh sessions share the compiled plan
    assert plan_cache_key(sql, Configuration()) == plan_cache_key(
        sql, Configuration())


def test_plan_knobs_single_source_of_truth():
    """PLAN_KNOBS lives in sql/digest.py (next to the digest it keys);
    serve/cache.py re-exports the SAME tuple — two copies would drift."""
    from auron_tpu.serve import cache
    from auron_tpu.sql import digest

    assert cache.PLAN_KNOBS is digest.PLAN_KNOBS
    assert {k.key for k in digest.PLAN_KNOBS} >= {
        "sql.shuffle.partitions",
        "exec.fuse.enable",
        "exec.host.sort",
    }


# ---------------------------------------------------------------------------
# program cache: accounting, eviction, invalidation, zero-compile replay
# ---------------------------------------------------------------------------


def test_plan_cache_hit_miss_accounting():
    c = PlanCache(capacity=8)
    assert c.lookup("k1") is None
    c.insert("k1", "plan1")
    assert c.lookup("k1") == "plan1"
    s = c.stats()
    assert (s["hits"], s["misses"], s["entries"]) == (1, 1, 1)


def test_plan_cache_eviction_is_lru_and_bounded():
    c = PlanCache(capacity=2)
    c.insert("a", 1)
    c.insert("b", 2)
    assert c.lookup("a") == 1       # touch a: b is now least-recent
    c.insert("c", 3)                # evicts b
    assert c.lookup("b") is None
    assert c.lookup("a") == 1 and c.lookup("c") == 3
    s = c.stats()
    assert s["evictions"] == 1 and s["entries"] == 2


def test_server_cache_hit_and_knob_invalidation(server):
    sql = _sql("q96")
    df1, r1 = server.submit(sql, tenant="a")
    df2, r2 = server.submit(sql, tenant="b")
    assert not r1["cache_hit"] and r2["cache_hit"]
    assert r1["digest"] == r2["digest"]
    assert df1.equals(df2)
    # a session conf changing a plan-affecting knob lands on a DIFFERENT
    # cache entry (invalidation by keying) and still computes the same
    # rows at the new mesh width
    df3, r3 = server.submit(sql, session={"sql.shuffle.partitions": 4},
                            tenant="c")
    assert not r3["cache_hit"]
    assert r3["digest"] != r1["digest"]
    assert df3.equals(df1)
    # and back on the default width: the original entry still hits
    _, r4 = server.submit(sql, tenant="d")
    assert r4["cache_hit"]


def test_replay_adds_no_compiles_across_fresh_server_sessions(frames):
    from auron_tpu.utils.profiling import EngineCounters

    counters = EngineCounters.install()
    sql = _sql("q3")
    warm = SqlServer(sqlgate.gate_catalog(), frames, n_parts=2)
    df1, _ = warm.submit(sql)            # compiles (first touch this test)
    before = counters.compiles
    fresh = SqlServer(sqlgate.gate_catalog(), frames, n_parts=2)
    df2, rec = fresh.submit(sql)         # fresh session: its OWN plan
    assert not rec["cache_hit"]          # cache is empty -> re-lowered...
    assert counters.compiles == before   # ...but ZERO new XLA compiles
    assert df1.equals(df2)


# ---------------------------------------------------------------------------
# session confs
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("key", [
    "no.such.key",
    # process-global: not a session's to set
    "obs.mode", "http.service.enable", "serve.admission.max.concurrent",
    # removed with their losing arms (PR 30): the compaction boundary has
    # no switches, so a session that still names one is refused like any
    # unknown key (HTTP 400)
    "join.compact.output", "exec.selectivity.predictor",
    "exec.agg.partial.defer", "exec.selectivity.ewma.alpha",
    "exec.selectivity.headroom", "exec.selectivity.shrink.patience",
    "batch.capacity.buckets",
])
def test_session_conf_rejects_unknown_and_process_global_keys(server, key):
    with pytest.raises(QueryError):
        server.session_conf({key: "1"})


def test_session_conf_accepts_an_engine_knob(server):
    conf = server.session_conf({"batch.size": 4096})
    from auron_tpu.utils.config import BATCH_SIZE

    assert conf.get(BATCH_SIZE) == 4096


def test_sql_diagnostics_surface_as_query_errors(server):
    err0 = server.stats()["queries_err"]
    with pytest.raises(QueryError):
        server.execute_json({"sql": "select definitely from"})
    with pytest.raises(QueryError):
        server.execute_json({"nope": 1})
    with pytest.raises(QueryError):
        server.submit(_sql("q96"), session={"obs.mode": "off"})
    with pytest.raises(QueryError):
        server.submit(_sql("q96"),
                      session={"sql.shuffle.partitions": 4096})
    # refused requests COUNT on /serve (review finding: conf refusals
    # and admission timeouts were raised before the stats try block).
    # The malformed-body refusal raises before submit, so 3 of the 4.
    assert server.stats()["queries_err"] >= err0 + 3


# ---------------------------------------------------------------------------
# admission control
# ---------------------------------------------------------------------------


def _adm(max_concurrent=1, timeout_s=0.3, mem_fraction=0.9):
    conf = (Configuration()
            .set(SERVE_MAX_CONCURRENT, max_concurrent)
            .set(SERVE_QUEUE_TIMEOUT_S, timeout_s))
    from auron_tpu.utils.config import SERVE_ADMIT_MEM_FRACTION

    conf = conf.set(SERVE_ADMIT_MEM_FRACTION, mem_fraction)
    return AdmissionController(conf)


def test_admission_queues_beyond_the_slot_bound():
    adm = _adm(max_concurrent=1, timeout_s=5.0)
    order = []
    gate = threading.Event()

    def worker(i):
        with adm.admit():
            order.append(i)
            if i == 0:
                gate.wait(2.0)

    t0 = threading.Thread(target=worker, args=(0,))
    t0.start()
    while not order:            # first worker holds the only slot
        pass
    t1 = threading.Thread(target=worker, args=(1,))
    t1.start()
    t1.join(0.2)
    assert t1.is_alive()        # queued behind the held slot
    gate.set()
    t0.join(3.0)
    t1.join(3.0)
    st = adm.stats()
    assert st["peak_running"] == 1 and st["queued"] >= 1
    assert order == [0, 1]


def test_admission_timeout_answers_instead_of_hanging():
    adm = _adm(max_concurrent=1, timeout_s=0.15)
    with adm.admit():
        with pytest.raises(AdmissionTimeout):
            with adm.admit():
                pass
    assert adm.stats()["timeouts"] == 1
    with adm.admit():           # slot released: admits again
        pass


def test_admission_memory_backpressure_queues_then_admits():
    """A consumer holding more than the admission fraction of the budget
    makes new queries WAIT; releasing it unblocks them (queue-don't-die)."""
    from auron_tpu.memory.memmgr import MemManager

    mgr = MemManager.get()

    class Hog:
        name = "test_admission_hog"

        def __init__(self, nbytes):
            self.nbytes = nbytes

        def mem_used(self):
            return self.nbytes

        def spill(self):
            return 0

    hog = Hog(int(mgr.budget * 1.5) + (1 << 20))
    adm = _adm(max_concurrent=4, timeout_s=0.2)
    mgr.register(hog, spillable=False)
    try:
        with pytest.raises(AdmissionTimeout):
            with adm.admit():
                pass
    finally:
        mgr.unregister(hog)
    with adm.admit():           # pressure gone: admits
        pass
    st = adm.stats()
    assert st["timeouts"] == 1 and st["admitted"] == 1


# ---------------------------------------------------------------------------
# POST /sql front door
# ---------------------------------------------------------------------------


def _post(port, body):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/sql", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"}, method="POST")
    try:
        with urllib.request.urlopen(req, timeout=60) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        body = e.read()
        try:
            return e.code, json.loads(body)
        except ValueError:
            return e.code, {"error": body.decode(errors="replace")}


def test_post_sql_endpoint(server):
    from auron_tpu.utils import httpsvc

    port = httpsvc.start(0)
    httpsvc.install_sql_server(server)
    try:
        code, resp = _post(port, {"sql": _sql("q1a"), "tenant": "http"})
        assert code == 200
        assert resp["columns"] == ["cnt", "total", "mean"]
        assert len(resp["rows"]) == 1 and resp["rows"][0][0] > 0
        assert resp["digest"] and "trace_id" in resp
        code, resp = _post(port, {"sql": "select broken from"})
        assert code == 400 and "error" in resp
        code, resp = _post(port, {"sql": _sql("q1a"),
                                  "conf": {"obs.mode": "off"}})
        assert code == 400
        # /serve reflects the traffic
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/serve", timeout=30
        ) as r:
            stats = json.loads(r.read())
        assert stats["plan_cache"]["misses"] >= 1
        assert stats["queries_err"] >= 2
    finally:
        httpsvc.stop()


def test_post_sql_404_without_server():
    from auron_tpu.utils import httpsvc

    port = httpsvc.start(0)
    try:
        code, _ = _post(port, {"sql": "select 1"})
        assert code == 404
    finally:
        httpsvc.stop()


def test_post_sql_500_names_the_cause():
    """A task failure reaches the handler as "task ... failed" FROM its
    cause: the 500 must carry the whole chain, or the reason is lost (the
    first SQL run on a chip answered a bare "task stage=0 partition=0
    failed")."""
    from auron_tpu.utils import httpsvc

    class Failing:
        def execute_json(self, body):
            try:
                raise ValueError("no such kernel")
            except ValueError as e:
                raise RuntimeError("task stage=0 partition=0 failed") from e

    port = httpsvc.start(0)
    httpsvc.install_sql_server(Failing())
    try:
        code, resp = _post(port, {"sql": "select 1"})
        assert code == 500
        assert "task stage=0 partition=0 failed" in resp["error"]
        assert "ValueError: no such kernel" in resp["error"]
    finally:
        httpsvc.stop()


# ---------------------------------------------------------------------------
# the concurrency differential gate, toy scale
# ---------------------------------------------------------------------------


def test_servegate_toy_bit_identity_and_zero_compiles(frames, monkeypatch):
    from auron_tpu.models import servegate

    monkeypatch.setenv("SERVEGATE_RATCHET", "0")
    rec = servegate.run_gate(sf=TOY_SF, clients=3, frames=frames,
                             names=["q3", "q96", "q5a"], min_speedup=0.0)
    assert rec["ok"], rec["failures"]
    assert rec["replay_compiles"] == 0
    assert rec["concurrent_compiles"] == 0
    assert rec["concurrent"]["p50_ms"] is not None


def test_servegate_detects_divergence(frames, monkeypatch):
    """Teeth: a server returning wrong rows must FAIL the gate."""
    from auron_tpu.models import servegate

    monkeypatch.setenv("SERVEGATE_RATCHET", "0")
    srv = SqlServer(sqlgate.gate_catalog(), frames, n_parts=2)
    real_submit = srv.submit
    calls = {"n": 0}

    def flaky(sql, session=None, tenant=None):
        df, rec = real_submit(sql, session=session, tenant=tenant)
        calls["n"] += 1
        if tenant == "client0" and len(df):
            df = df.iloc[::-1].reset_index(drop=True)  # reordered rows
        return df, rec

    srv.submit = flaky
    rec = servegate.run_gate(sf=TOY_SF, clients=2, names=["q3"],
                             min_speedup=0.0, server=srv)
    assert not rec["ok"]
    assert any("diverged" in f for f in rec["failures"])
