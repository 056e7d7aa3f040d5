"""The cell ``batch_q65_mesh4_sf24``: its files are found by name, the three
readers it brings read the program's rings (and read nothing where there is
nothing), the driver's comparison holds each text to its own reference, the
control fails it, and the cell's own faults each turn ``correct`` false.

The runs are whole ``run_cell`` runs at a tiny size on four of the CPU's
virtual devices (by hand: ``python -m pytest benchmark/tests -q -p
no:cacheprovider``).
"""

import copy
import decimal
import json
import os
import time

import pandas as pd
import pytest

from auron_tpu import obs
from benchmark import control, harness

SEED = 2147483659
CELL = "batch_q65_mesh4_sf24"
NEW_READERS = ("mesh_exchange_bytes_per_query.mesh", "stage_devices_min.mesh",
               "partition_overlap_share.mesh")


# ---- the cell's files, found by name ----------------------------------------


def test_cell_resolves_to_its_files_by_name():
    cell = harness.load_cell(CELL)
    assert cell["config_file"]["name"] == cell["config"] == "tpcds_sql_mesh4_sf24"
    assert cell["traffic_file"]["name"] == cell["traffic"] == "closed1_q65_sql"
    assert cell["chips"] == 4 and cell["config_file"]["deployment"]["chips"] == 4
    assert cell["config_file"]["sizes"]["n_parts"] == 4
    driver = harness.load_module("drivers", cell["config_file"]["driver"])
    for fn in ("setup", "window", "finish", "check", "control", "require_program"):
        assert callable(getattr(driver, fn))
    assert cell["traffic_file"]["queries"] == ["q65", "q65_sb"]
    for q in cell["traffic_file"]["queries"]:
        mod = harness.load_module("queries", q)
        for name in ("reference", "ORDER", "ASCENDING", "LIMIT", "SCAN_COLUMNS",
                     "IN_ORDER"):
            assert hasattr(mod, name), name
        assert os.path.exists(os.path.join(harness.HERE, "sql", q + ".sql"))
    assert {m["name"] for m in cell["end_to_end"]} == {"batch_query_s", "setup_s"}
    names = [m["name"] for m in cell["per_layer"]]
    assert len(names) == 20 and all(n.endswith(".mesh") for n in names)
    assert set(NEW_READERS) <= set(names)
    for n in names:
        assert callable(harness.load_module("metrics", n).read)
    assert cell["config_file"]["limits"] == {"failed": 0, "rows_wrong": 0}


def test_benchmark_json_gained_one_configuration_and_one_four_chip_cell():
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    entry = spec["configs"][-1]
    cfg = harness.load_cell(CELL)["config_file"]
    assert entry["name"] == "tpcds_sql_mesh4_sf24" and entry["source"] == cfg["source"]
    assert len(entry["source"]) <= 200
    assert cfg["reduced"] == entry["reduced"] == ["sf", "tables", "queries"]
    assert set(cfg["reduced_why"]) == set(cfg["reduced"])
    assert cfg["data"] == {"module": "datagen_store", "generator": "tpcds_store",
                           "sf": 24}
    assert cfg["sizes"]["fact_rows"] == 24 * 2880404
    for key in ("generator", "dimensions", "splits", "tie_break", "second_text",
                "types", "dms"):
        assert key in cfg["assumed"], key
    assert spec["workloads"][-1]["name"] == CELL
    assert [w["name"] for w in spec["workloads"] if w["chips"] == 4] == [CELL]
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    assert e2e["batch_query_s"]["workloads"][-1] == CELL
    assert e2e["batch_query_s"]["bound"] == 0.08 and spec["run_seconds"] == 40


def test_the_posted_text_spells_the_tie_break_and_sb_is_the_texts_own_block():
    def text(name: str) -> str:
        with open(os.path.join(harness.HERE, "sql", name + ".sql")) as f:
            return " ".join(f.read().split())

    q65, sb = text("q65"), text("q65_sb")
    assert "d_month_seq between 1176 and 1176 + 11" in q65
    assert q65.endswith("order by s_store_name, i_item_desc, revenue, "
                        "i_current_price, i_wholesale_cost, i_brand limit 100")
    # the statement is the derived table, letter for letter
    assert "(" + sb + ") sb" in q65


# ---- the readers this cell brings ----------------------------------------------


@pytest.fixture
def recorder():
    prev = obs.mode()
    obs.set_mode("recorder")
    yield
    obs.set_mode(prev)


def _facts(t0: float, t1: float) -> dict:
    return {"records": [{"ok": True, "t0": t0, "t1": (t0 + t1) / 2},
                        {"ok": True, "t0": (t0 + t1) / 2, "t1": t1}]}


def _read(name: str, facts: dict):
    return harness.load_module("metrics", name).read(facts)


def test_readers_read_the_spans_that_began_in_the_window(recorder):
    t0 = time.perf_counter()
    with obs.span("stage", cat="pump", arg={"parts": 4, "devices": 4}):
        for p in range(4):
            with obs.span("partition", cat="pump",
                          arg={"partition": p, "device": p}):
                time.sleep(0.01)
    with obs.span("write", cat="exchange",
                  arg={"mode": "mesh", "rows": 10, "bytes": 280, "devices": 4}):
        pass
    with obs.span("write", cat="exchange",
                  arg={"mode": "file", "rows": 10, "bytes": 999, "devices": 4}):
        pass
    with obs.span("write", cat="exchange",
                  arg={"mode": "mesh", "rows": 5, "bytes": 120, "devices": 4}):
        pass
    with obs.span("stage", cat="pump", arg={"parts": 4, "devices": 3}):
        pass
    t1 = time.perf_counter()
    facts = _facts(t0, t1)
    # per completed query (two): the mesh transport's bytes alone
    assert _read("mesh_exchange_bytes_per_query.mesh", facts) == (280 + 120) / 2
    assert _read("stage_devices_min.mesh", facts) == 3
    # four partitions one after the other on one thread: a quarter
    assert _read("partition_overlap_share.mesh", facts) == pytest.approx(0.25,
                                                                        abs=0.02)


@pytest.mark.parametrize("name", NEW_READERS)
def test_reader_reports_nothing_where_there_is_nothing_to_read(name, monkeypatch,
                                                               recorder):
    t0 = time.perf_counter()
    with obs.span("batch", cat="pump"):
        pass
    facts = _facts(t0, time.perf_counter())
    if name == "mesh_exchange_bytes_per_query.mesh":
        assert _read(name, facts) == 0          # no mesh exchange: none moved
    else:
        assert _read(name, facts) is None       # no stage, no partition pumped
    # a program whose summary lacks the sums (the parent commit's)
    real = obs.window_summary
    monkeypatch.setattr(obs, "window_summary", lambda *a, **k: {
        k_: v for k_, v in real(*a, **k).items()
        if k_ not in ("exchange_bytes", "stage_devices_min", "partition_pumps")})
    assert _read(name, facts) is None
    # nothing completed, or the recorder off
    assert _read(name, {"records": [{"ok": False, "t0": t0, "t1": t0 + 1}]}) is None
    obs.set_mode("off")
    assert _read(name, facts) is None


# ---- the driver's comparison -------------------------------------------------------


def _tiny(sf: float = 0.05) -> dict:
    cell = copy.deepcopy(harness.load_cell(CELL))
    cell["config_file"]["data"]["sf"] = sf
    cell["config_file"]["sizes"]["batch_rows"] = 1 << 13
    return cell


def _run(cell: dict, seconds: float = 8.0, trace: bool = False) -> dict:
    import jax

    return harness.run_cell(cell, SEED, seconds, trace, jax.devices()[:4],
                            time.perf_counter())


def test_check_holds_each_text_to_its_own_reference():
    cell = _tiny()
    config, traffic = cell["config_file"], cell["traffic_file"]
    driver = harness.load_module("drivers", config["driver"])
    gen = driver._generator(config)
    frames = gen.make(config, SEED)
    queries = driver._queries(traffic)
    want = driver.wants(queries, frames, {})
    assert len(want["q65"]) == 100 and len(want["q65_sb"]) == 13
    state = {"frames": frames, "queries": queries, "params": {}}
    good = {n: driver.to_answer(w) for n, w in want.items()}

    def wrong(answers: dict) -> int:
        records = [{"ok": True, "name": n, "answer": a}
                   for n, a in answers.items()]
        return driver.check(state, records, config["limits"])["rows_wrong"]["value"]

    assert wrong(good) == 0
    # a cent on one revenue; a cent... a millionth on one store's average
    cent = copy.deepcopy(good)
    j = cent["q65"]["columns"].index("revenue")
    cent["q65"]["rows"][3][j] = format(
        decimal.Decimal(cent["q65"]["rows"][3][j]) + decimal.Decimal("0.01"), "f")
    assert wrong(cent) == 1
    ave = copy.deepcopy(good)
    ave["q65_sb"]["rows"][5][1] = format(
        decimal.Decimal(ave["q65_sb"]["rows"][5][1]) + decimal.Decimal("0.000001"),
        "f")
    assert wrong(ave) == 1
    # money that is not a decimal string of the column's scale is wrong
    as_float = copy.deepcopy(good)
    as_float["q65_sb"]["rows"][0][1] = float(as_float["q65_sb"]["rows"][0][1])
    short = copy.deepcopy(good)
    short["q65_sb"]["rows"][0][1] = short["q65_sb"]["rows"][0][1][:-1]
    assert wrong(as_float) == 1 and wrong(short) == 1
    # the texts' answers swapped; a text's answer missing rows
    swapped = {"q65": good["q65_sb"], "q65_sb": good["q65"]}
    assert wrong(swapped) == 100 + 13
    fewer = copy.deepcopy(good)
    fewer["q65_sb"]["rows"] = fewer["q65_sb"]["rows"][:-1]
    assert wrong(fewer) == 13
    # every answer of the window counts, not each text once
    records = [{"ok": True, "name": "q65", "answer": cent["q65"]}] * 3
    assert driver.check(state, records, config["limits"])["rows_wrong"]["value"] == 3
    # the configuration's own scale: the top 100 has NO rows. What is held of
    # an answer then is that it has none, under the reference's column names
    none = want["q65"].iloc[:0]
    empty = driver.to_answer(none)
    assert driver.rows_wrong_of(empty, none, True) == 0
    assert driver.rows_wrong_of(good["q65"], none, True) == 100  # rows where none are due
    renamed = {**empty, "columns": ["s_store_name", "i_item_desc", "sum",
                                    "i_current_price", "i_wholesale_cost",
                                    "i_brand"]}
    assert driver.rows_wrong_of(renamed, none, True) == 1
    assert driver.rows_wrong_of({**empty, "columns": empty["columns"][:5]},
                                none, True) == 1


def test_control_in_float32_is_found_not_correct():
    out = control.run_control(_tiny(sf=0.2), SEED)
    assert out["correct"] is False
    assert out["compared"]["rows_wrong"]["value"] > 0


def test_a_program_without_the_placement_is_refused_before_any_table(monkeypatch):
    from auron_tpu.columnar.batch import Batch

    cell = _tiny()
    driver = harness.load_module("drivers", cell["config_file"]["driver"])
    made = []
    monkeypatch.setattr(driver._generator(cell["config_file"]), "make",
                        lambda *a: made.append(1))
    monkeypatch.delattr(Batch, "on_device")
    with pytest.raises(SystemExit, match="chip 0"):
        driver.setup(cell["config_file"], cell["traffic_file"], SEED,
                     span=harness.span, say=lambda **kw: None)
    assert not made


# ---- the cell, end to end, and its own faults ---------------------------------------


def test_cell_runs_through_the_harness_and_is_correct():
    out = _run(_tiny())
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 2
    assert set(out["metrics"]) == {"batch_query_s", "setup_s"}
    assert out["compared"]["rows_wrong"] == {"value": 0, "limit": 0}
    assert out["device"]["count"] == 4


def test_traced_run_reports_the_mesh_metrics():
    cell = _tiny()
    # the chip's transport (on the CPU the server's default is ``file``,
    # whose stages AQE may coalesce to fewer partitions than the mesh has)
    cell["traffic_file"]["session"] = {"exchange.mode": "mesh"}
    out = _run(cell, trace=True)
    assert out["correct"] is True
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert m["stage_devices_min.mesh"] == 4
    assert 0.25 < m["partition_overlap_share.mesh"] <= 1.0
    assert m["mesh_exchange_bytes_per_query.mesh"] > 0
    assert m["compiles_in_window.mesh"] == 0
    assert m["plan_cache_hit_share.mesh"] == 100.0
    assert m["agg_groups_per_query.mesh"] > 0


def test_fault_a_cent_on_revenue(monkeypatch):
    """One cent on the first ``revenue`` of every answer that has one."""
    from auron_tpu.serve import server

    real, altered = server._json_rows, []

    def cent(df: pd.DataFrame):
        if "revenue" in df.columns and len(df):
            df = df.copy()
            df.loc[df.index[0], "revenue"] += decimal.Decimal("0.01")
            altered.append(1)
        return real(df)

    monkeypatch.setattr(server, "_json_rows", cent)
    out = _run(_tiny())
    assert altered and out["correct"] is False
    assert out["compared"]["rows_wrong"]["value"] > 0


def test_fault_a_millionth_on_one_stores_average(monkeypatch):
    from auron_tpu.serve import server

    real, altered = server._json_rows, []

    def nudge(df: pd.DataFrame):
        if list(df.columns) == ["ss_store_sk", "ave"]:
            df = df.copy()
            df.loc[df.index[2], "ave"] += decimal.Decimal("0.000001")
            altered.append(1)
        return real(df)

    monkeypatch.setattr(server, "_json_rows", nudge)
    out = _run(_tiny())
    assert altered and out["correct"] is False
    # one row of each q65_sb answer; query 65's own rows stay right
    assert 0 < out["compared"]["rows_wrong"]["value"] <= out["attempted"]


def test_fault_one_chips_splits_left_out(monkeypatch):
    """Chip 1's splits never reach its partition: the year's rows that were
    dealt to it are in no sum."""
    from auron_tpu.serve.server import SqlServer

    real = SqlServer._view

    def view(self, table, n_parts, replicated):
        got = real(self, table, n_parts, replicated)
        if table == "store_sales" and not replicated:
            got = [part if p != 1 else [] for p, part in enumerate(got)]
        return got

    monkeypatch.setattr(SqlServer, "_view", view)
    out = _run(_tiny())
    assert out["correct"] is False
    assert out["compared"]["rows_wrong"]["value"] > 0


def test_fault_one_exchanges_received_shard_zeroed(monkeypatch):
    """Partition 2 of every mesh exchange receives no rows: its groups'
    partial sums are lost on the way."""
    from auron_tpu.parallel import mesh_driver

    real = mesh_driver._local_shard

    def zeroed(arr, p):
        out = real(arr, p)
        return out & False if p == 2 and out.dtype == bool else out

    monkeypatch.setattr(mesh_driver, "_local_shard", zeroed)
    cell = _tiny()
    cell["traffic_file"]["session"] = {"exchange.mode": "mesh"}
    out = _run(cell)
    assert out["correct"] is False
    assert out["compared"]["rows_wrong"]["value"] > 0
