"""The SQL serving cell's own files: the driver end to end on the CPU's
devices, its control, its faults, and the four readers it brings.

The faults are those a serving cell can have (the bridge-level ones of
``test_control_and_faults.py`` inject nothing on this path: the tables are
handed to the server, not to ``api.put_resource``; the exchange is one wide):
a cent in one sum, one session's answers swapped between texts, a 500.
"""

import copy
import time

import pytest

from auron_tpu import obs
from benchmark import compare, control, harness

CELL = "sql_streams4_sf8"
SEED = 2147483659


def tiny(sf: float = 0.05) -> dict:
    cell = copy.deepcopy(harness.load_cell(CELL))
    cell["config_file"]["data"]["sf"] = sf
    cell["config_file"]["sizes"]["batch_rows"] = 1 << 14
    return cell


def run(cell: dict, seconds: float = 2.0, trace: bool = False) -> dict:
    import jax

    return harness.run_cell(cell, SEED, seconds, trace, jax.devices()[:1],
                            time.perf_counter())


# ---- the driver, end to end -------------------------------------------------


def test_cell_runs_through_the_harness_and_is_correct():
    out = run(tiny())
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 4
    assert set(out["metrics"]) == {"batch_query_s", "setup_s"}
    assert out["compared"]["rows_wrong"] == {"value": 0, "limit": 0}


def test_window_is_four_sessions_round_robin_from_different_texts():
    cell = tiny()
    config, traffic = cell["config_file"], cell["traffic_file"]
    driver = harness.load_module("drivers", config["driver"])
    state = driver.setup(config, traffic, SEED, span=harness.span,
                         say=lambda **kw: None)
    try:
        served = state["server"].stats()
        assert served["plan_cache"]["misses"] == 4      # the warm-up's
        assert served["tables_resident"] == 3
        records, window_s = driver.window(state, 2.0, harness.Tracer(False))
    finally:
        driver.finish(state)
    assert state["server"] is None
    names = traffic["queries"]
    by_stream = {i: [r for r in records if r["stream"] == i] for i in range(4)}
    for i, recs in by_stream.items():
        assert recs, f"stream {i} posted nothing"
        recs.sort(key=lambda r: r["t0"])
        assert [r["name"] for r in recs] == \
            [names[(i + k) % 4] for k in range(len(recs))]
        # closed loop: the next text is posted when the last is answered
        assert all(a["t1"] <= b["t0"] for a, b in zip(recs, recs[1:]))
    assert all(r["ok"] and r["status"] == 200 and r["cache_hit"]
               for r in records)
    assert [r["t1"] for r in records] == sorted(r["t1"] for r in records)
    assert window_s == pytest.approx(
        records[-1]["t1"] - min(r["t0"] for r in records))
    # no stream submitted after the window's seconds
    first = min(r["t0"] for r in records)
    assert max(r["t0"] for r in records) < first + 2.0 + 0.05
    compared = driver.check(state, records, config["limits"])
    assert compared["rows_wrong"]["value"] == 0
    # q3's rows are those the batch cell's own reference gives
    q3 = harness.load_module("queries", "q3")
    want = compare.head(q3.reference(state["frames"]), q3.ORDER, q3.ASCENDING,
                        q3.LIMIT)
    got = next(r["answer"] for r in records if r["name"] == "q3")
    assert got["columns"] == ["d_year", "brand_id", "brand", "sum_agg"]
    assert [row[-1] for row in got["rows"]] == \
        [None if v is None else format(v, "f") for v in want["sum_agg"]]


def test_traced_run_reports_every_listed_metric_the_cpu_can_read():
    out = run(tiny(), seconds=3.0, trace=True)
    assert out["correct"] is True
    listed = {m["name"] for m in harness.load_cell(CELL)["per_layer"]}
    # the three that read the device's trace or memory need the chip
    device = {"scan_roofline.sql", "device_idle_share.sql", "peak_hbm_bytes.sql"}
    assert set(out["metrics"]) == listed - device
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert m["plan_cache_hit_share.sql"] == 100.0
    assert m["compiles_in_window.sql"] == 0
    assert m["host_syncs_per_query.sql"] > 0
    assert m["agg_fold_rows_per_query.sql"] > 0
    assert 0 < m["sql_front_s_per_query.sql"] < m["serve_answer_s_per_query.sql"] + 1
    assert m["admission_wait_s_per_query.sql"] >= 0
    assert m["op_host_s_per_query.sql"] > 0 and m["exchange_s_per_query.sql"] > 0
    assert m["plan_s_per_query.sql"] > 0 and m["sync_wait_s_per_query.sql"] > 0


# ---- the control ------------------------------------------------------------


def test_float32_control_is_not_correct_where_sums_outgrow_24_bits():
    out = control.run_control(tiny(sf=2.0), SEED)
    assert out["correct"] is False
    assert out["compared"]["rows_wrong"]["value"] > 0


def test_exact_reference_as_a_parsed_answer_is_correct(monkeypatch):
    monkeypatch.setattr(compare, "money_in", lambda precision, frames, schemas: frames)
    assert control.run_control(tiny(sf=0.2), SEED)["correct"] is True


# ---- faults: the serving path broken underneath -----------------------------


def _patched_answers(monkeypatch, alter):
    """Every answer of the server goes through ``alter(body, rec)``."""
    from auron_tpu.serve.server import SqlServer

    real = SqlServer.execute_json

    def execute_json(self, body):
        return alter(body, real(self, body))

    monkeypatch.setattr(SqlServer, "execute_json", execute_json)


def test_one_cent_in_one_sum_is_not_correct(monkeypatch):
    def alter(body, rec):
        # session s1's first text is query 42
        if body.get("tenant") == "s1" and "i_category" in body["sql"]:
            row = next(r for r in rec["rows"] if r[-1] is not None)
            cents = int(row[-1].replace(".", "")) + 1
            row[-1] = f"{cents // 100}.{cents % 100:02d}"
        return rec

    _patched_answers(monkeypatch, alter)
    out = run(tiny(sf=0.2))
    assert out["failed"] == 0 and out["correct"] is False
    assert out["compared"]["rows_wrong"]["value"] >= 1


def test_money_as_a_float_on_the_wire_is_not_correct(monkeypatch):
    def alter(body, rec):
        rec["rows"] = [r[:-1] + [None if r[-1] is None else float(r[-1])]
                       for r in rec["rows"]]
        return rec

    _patched_answers(monkeypatch, alter)
    assert run(tiny(sf=0.2))["correct"] is False


def test_one_sessions_answers_swapped_between_texts_is_not_correct(monkeypatch):
    from auron_tpu.serve.server import SqlServer

    real = SqlServer.execute_json
    texts = harness.load_module("drivers", "sql_streams")._texts(
        harness.load_cell(CELL)["traffic_file"])

    def execute_json(self, body):
        if body.get("tenant") == "s2" and body["sql"] == texts["q52"]:
            body = dict(body, sql=texts["q55"])      # another text's answer
        return real(self, body)

    monkeypatch.setattr(SqlServer, "execute_json", execute_json)
    out = run(tiny(sf=0.2))
    assert out["failed"] == 0 and out["correct"] is False


def test_a_500_counts_as_failed_and_not_correct(monkeypatch):
    calls = []

    def alter(body, rec):
        calls.append(1)
        if len(calls) > 6:                  # after the warm-up's four
            raise RuntimeError("refused by the test")
        return rec

    _patched_answers(monkeypatch, alter)
    out = run(tiny(), seconds=1.5)
    assert out["failed"] > 0 and out["correct"] is False
    assert out["compared"]["failed"]["value"] == out["failed"]


def test_warm_up_that_is_not_200_aborts_the_run(monkeypatch):
    def alter(body, rec):
        raise RuntimeError("no such kernel")

    _patched_answers(monkeypatch, alter)
    with pytest.raises(RuntimeError, match="warm-up of q3: HTTP 500.*no such kernel"):
        run(tiny())
    from auron_tpu.utils import httpsvc

    assert httpsvc._server is None and httpsvc._sql_server is None


# ---- the four readers this cell brings, on hand-made facts ------------------

SUMMARY = {
    "complete": True,
    "layers": {"serve": {"n": 28, "total_s": 9.0, "self_s": 0.4}},
    "spans": {"serve:request": {"n": 4, "total_s": 8.0, "self_s": 0.010},
              "serve:admit": {"n": 4, "total_s": 0.002, "self_s": 0.002},
              "serve:plan": {"n": 4, "total_s": 0.004, "self_s": 0.003},
              "serve:execute": {"n": 4, "total_s": 7.0, "self_s": 0.1},
              "serve:collect": {"n": 4, "total_s": 0.8, "self_s": 0.020},
              "serve:encode": {"n": 8, "total_s": 0.006, "self_s": 0.006},
              "sql:sql.parse": {"n": 1, "total_s": 0.001, "self_s": 0.001}},
    "d2h_bytes": 0, "agg_fold_rows": 0, "sync_sites": [],
    "plan_cache_hits": 3, "plan_cache_misses": 1,
}
WANT = {   # four queries completed
    "admission_wait_s_per_query.sql": 0.0005,
    "sql_front_s_per_query.sql": 0.001,
    "serve_answer_s_per_query.sql": 0.009,
    "plan_cache_hit_share.sql": 75.0,
}
FACTS = {"records": [{"ok": True, "t0": 10.0 + i, "t1": 12.0 + i}
                     for i in range(4)]}


@pytest.fixture
def summary(monkeypatch):
    monkeypatch.setattr(obs, "window_summary", lambda t0, t1: dict(SUMMARY))
    monkeypatch.setattr(obs, "mode", lambda: obs.MODE_RECORDER)


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_picks_its_number(name, summary):
    read = harness.load_module("metrics", name).read
    assert read(FACTS) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_reports_nothing_on_a_program_without_the_serve_layer(
        name, summary, monkeypatch):
    """The parent commit: no ``serve`` spans, no plan-cache counts. The
    reader returns None and does not raise, so the line leaves the metric
    out."""
    read = harness.load_module("metrics", name).read
    older = {k: v for k, v in SUMMARY.items() if not k.startswith("plan_cache")}
    older = dict(older, layers={}, spans={"pump:batch": {
        "n": 1, "total_s": 1.0, "self_s": 1.0}})
    monkeypatch.setattr(obs, "window_summary", lambda t0, t1: older)
    assert read(FACTS) is None
    assert read({"records": []}) is None
    monkeypatch.setattr(obs, "window_summary",
                        lambda t0, t1: dict(SUMMARY, complete=False))
    assert read(FACTS) is None
    monkeypatch.setattr(obs, "mode", lambda: obs.MODE_OFF)
    assert read(FACTS) is None


def test_hit_share_with_no_lookups_is_nothing(summary, monkeypatch):
    monkeypatch.setattr(obs, "window_summary", lambda t0, t1: dict(
        SUMMARY, plan_cache_hits=0, plan_cache_misses=0))
    assert harness.load_module(
        "metrics", "plan_cache_hit_share.sql").read(FACTS) is None


def test_new_entries_are_the_cells_alone():
    cell = harness.load_cell(CELL)
    assert [m["name"] for m in cell["end_to_end"]] == ["batch_query_s", "setup_s"]
    for m in cell["per_layer"]:
        assert m["name"].endswith(".sql") and m["workloads"] == [CELL]
        assert m["moves"] == "batch_query_s"
    old = harness.load_cell("batch_q3_sf8")
    assert not any(m["name"].endswith(".sql") for m in old["per_layer"])
    assert cell["config_file"]["sizes"]["batch_rows"] == 1 << 20
    assert cell["traffic_file"]["streams"] == 4 == len(cell["traffic_file"]["tenants"])
