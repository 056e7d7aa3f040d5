"""The cell ``batch_q65_sf8``: its files are found by name, the ``store``
generator keeps to ``schemas/tpcds_store.json`` and to a history-keeping
dimension's shape, the three readers this cell brings read the program's rings,
and the driver's comparison holds the stores' averages beside the top 100.
The bridge's faults run over this cell as cases of the parametrised tests of
``test_control_and_faults.py``."""

import decimal
import json
import os
import time

import pandas as pd
import pytest

from auron_tpu import obs
from benchmark import datagen, datagen_store, harness

SEED = 2147483659
CELL = "batch_q65_sf8"
NEW_READERS = {"agg_groups_per_query.agg": "agg_groups",
               "agg_sorted_rows_per_query.agg": "agg_sorted_rows",
               "wide_decimal_host_cells_per_query.agg": "wide_decimal_host_cells"}


# ---- the cell's files, found by name ----------------------------------------


def test_cell_resolves_to_its_files_by_name():
    cell = harness.load_cell(CELL)
    assert cell["config_file"]["name"] == cell["config"] == "tpcds_batch_agg_sf8"
    assert cell["traffic_file"]["name"] == cell["traffic"] == "closed1_q65"
    assert cell["chips"] == 1 and cell["config_file"]["deployment"]["chips"] == 1
    driver = harness.load_module("drivers", cell["config_file"]["driver"])
    for fn in ("setup", "window", "finish", "check", "control"):
        assert callable(getattr(driver, fn))
    for q in cell["traffic_file"]["queries"]:
        mod = harness.load_module("queries", q)
        for name in ("ingest", "run", "reference", "ORDER", "ASCENDING", "LIMIT",
                     "SCAN_COLUMNS", "IN_ORDER"):
            assert hasattr(mod, name), name
    assert {m["name"] for m in cell["end_to_end"]} == {"batch_query_s", "setup_s"}
    names = [m["name"] for m in cell["per_layer"]]
    assert len(names) == 20 and all(n.endswith(".agg") for n in names)
    assert set(NEW_READERS) <= set(names)
    for n in names:
        assert callable(harness.load_module("metrics", n).read)
    assert cell["config_file"]["limits"] == {"failed": 0, "rows_wrong": 0}


def test_the_configuration_names_its_generator_module_and_what_was_cut():
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    entry = next(c for c in spec["configs"] if c["name"] == "tpcds_batch_agg_sf8")
    cfg = harness.load_cell(CELL)["config_file"]
    assert cfg["reduced"] == entry["reduced"] == ["sf", "tables", "queries"]
    assert set(cfg["reduced_why"]) == set(cfg["reduced"])
    assert cfg["data"] == {"module": "datagen_store", "generator": "tpcds_store",
                           "sf": 8}
    for key in ("generator", "plan", "types", "tie_break", "dms", "batch_rows",
                "n_map", "n_reduce"):
        assert key in cfg["assumed"], key
    assert cfg["sizes"]["fact_rows"] == round(datagen.SF1["store_sales"] * 8)


# ---- the generator against its schema file ----------------------------------


def test_store_keeps_to_its_schema_file():
    cols = datagen_store.schemas()["store"]
    assert len(cols) == 29
    assert [c for c, _, nullable in cols if not nullable] == ["s_store_sk",
                                                              "s_store_id"]
    assert [c for c, t, _ in cols if t == "decimal(5,2)"] == ["s_gmt_offset",
                                                               "s_tax_precentage"]
    st = datagen_store.store(SEED)
    assert list(st.columns) == [c for c, _, _ in cols]
    assert len(st) == 12 and st.s_store_sk.tolist() == list(range(1, 13))
    for c, t, nullable in cols:
        if t in ("int64", "int32") or t.startswith("decimal"):
            assert pd.api.types.is_integer_dtype(st[c].dtype), c
        if not nullable:
            assert not st[c].isna().any(), c
    # the schema files of both generators, the old tables untouched
    assert set(datagen_store.schemas()) == set(datagen.schemas()) | {"store"}


def test_store_is_history_keeping_and_names_repeat():
    st = datagen_store.store(SEED)
    runs = st.groupby("s_store_id", sort=False)
    assert runs.size().between(1, 3).all() and runs.ngroups < len(st)
    assert (runs.s_store_name.nunique() <= 1).all()         # a name a store
    assert st.s_store_name.nunique() < len(st)              # so names repeat
    assert st.s_rec_end_date.isna().sum() == runs.ngroups   # last revision open
    assert (runs.s_street_name.nunique() <= 1).all()        # the address stays
    first = st.groupby("s_store_id", sort=False).s_store_sk.min()
    assert st.s_store_name.dropna().isin(
        [datagen_store.store_name(k) for k in first]).all()
    assert datagen_store.store_name(1) == "ought"
    assert datagen_store.store_name(10) == "barought"


def test_same_seed_same_tables_and_the_other_tables_are_datagens_own():
    a, b = datagen_store.tpcds_store(0.01, SEED), datagen_store.tpcds_store(0.01, SEED)
    assert set(a) == {"store_sales", "date_dim", "item", "store"}
    assert all(a[t].equals(b[t]) for t in a)
    base = datagen.tpcds(0.01, SEED)
    assert all(a[t].equals(base[t]) for t in base)
    assert not a["store"].equals(datagen_store.store(SEED + 1))
    assert a["store_sales"].ss_store_sk.dropna().between(1, 12).all()


# ---- the three readers on the program's rings -------------------------------


@pytest.fixture
def recorder():
    saved = obs.mode()
    obs.set_mode("recorder")
    yield
    obs.set_mode(saved)


def _facts(t0: float, t1: float) -> dict:
    mid = (t0 + t1) / 2
    return {"records": [{"ok": True, "t0": t0, "t1": mid},
                        {"ok": True, "t0": mid, "t1": t1}]}


def test_readers_sum_the_events_that_began_in_the_window(recorder):
    obs.note_agg_emit(1000, "partial")                  # before the window
    obs.note_agg_reduce(4096, "sort")
    obs.note_decimal_host_cells(50, "final")
    t0 = time.perf_counter()
    obs.note_agg_fold(4194304, 4194304, path="dense")
    obs.note_agg_fold(131072, 131072, path="sort", mode="final", live=117000)
    obs.note_agg_reduce(131072, "sort")
    obs.note_agg_reduce(2048, "hostsort")
    obs.note_agg_reduce(262144, "mergepath")            # sorts nothing
    obs.note_agg_emit(233940, "partial")
    obs.note_agg_emit(13, "final")
    obs.note_agg_emit(None, "partial")                  # no read settled it
    obs.note_decimal_host_cells(16, "final")
    obs.note_decimal_host_cells(13, "compare")
    t1 = time.perf_counter()
    obs.note_agg_emit(7, "final")                       # after it
    ws = obs.window_summary(t0, t1)
    assert ws["agg_groups"] == 233953
    assert ws["agg_sorted_rows"] == 133120
    assert ws["wide_decimal_host_cells"] == 29
    assert ws["agg_fold_rows"] == 4194304 + 131072
    assert ws["agg_folds"] == {
        "dense": {"n": 1, "rows": 4194304, "live": 0},
        "sort": {"n": 1, "rows": 131072, "live": 117000}}
    assert ws["agg_reduces"]["mergepath"] == {"n": 1, "rows": 262144}
    assert not {"fold", "reduce", "emit", "decimal"} & set(ws["layers"])
    facts = _facts(t0, t1)
    want = {"agg_groups_per_query.agg": 233953 / 2,
            "agg_sorted_rows_per_query.agg": 133120 / 2,
            "wide_decimal_host_cells_per_query.agg": 29 / 2}
    for name, value in want.items():
        assert harness.load_module("metrics", name).read(facts) == value


@pytest.mark.parametrize("name", sorted(NEW_READERS))
def test_reader_reports_nothing_where_there_is_nothing_to_read(name, monkeypatch):
    read = harness.load_module("metrics", name).read
    summary = {"complete": True, "layers": {}, "spans": {}, NEW_READERS[name]: 12}
    facts = _facts(100.0, 104.0)
    monkeypatch.setattr(obs, "mode", lambda: obs.MODE_RECORDER)
    monkeypatch.setattr(obs, "window_summary", lambda t0, t1: dict(summary))
    assert read(facts) == 6.0
    monkeypatch.setattr(obs, "window_summary", lambda t0, t1: {
        k: v for k, v in summary.items() if k != NEW_READERS[name]})
    assert read(facts) is None                # the parent's program: no such sum
    monkeypatch.setattr(obs, "window_summary",
                        lambda t0, t1: dict(summary, complete=False))
    assert read(facts) is None                # a ring wrapped
    assert read({"records": []}) is None
    monkeypatch.setattr(obs, "mode", lambda: obs.MODE_OFF)
    assert read(facts) is None                # the recorder is off


# ---- the driver's comparison ------------------------------------------------


def _answer(rows: list, sb: pd.DataFrame) -> pd.DataFrame:
    q = harness.load_module("queries", "q65")
    out = pd.DataFrame(rows, columns=q.OUTPUT)
    out.attrs["sb"] = sb
    return out


def test_check_holds_the_averages_beside_the_top_100(monkeypatch):
    driver = harness.load_module("drivers", "batch_agg")
    q = harness.load_module("queries", "q65")
    sb = pd.DataFrame({"ss_store_sk": pd.array([1, 2, None], dtype="Int64"),
                       "ave": [decimal.Decimal("10.000005"),
                               decimal.Decimal("9.999995"),
                               decimal.Decimal("3.500000")]})
    row = ["ought", "item description 000001 of revision 0", decimal.Decimal("1.00"),
           decimal.Decimal("2.50"), decimal.Decimal("1.25"), "brandbrand #1"]
    monkeypatch.setattr(q, "reference", lambda frames, params=None: _answer([row], sb))
    state = {"queries": {"q65": q}, "frames": {}, "params": {}}

    def wrong(answer) -> int:
        rec = {"ok": True, "name": "q65", "answer": answer}
        return driver.check(state, [rec], {"rows_wrong": 0})["rows_wrong"]["value"]

    assert wrong(_answer([row], sb.copy())) == 0
    off = sb.copy()
    off.loc[1, "ave"] = decimal.Decimal("9.999996")     # the sixth place
    assert wrong(_answer([row], off)) == 1
    assert wrong(_answer([row], sb.iloc[:2])) == 3      # the NULL store's group lost
    assert wrong(pd.DataFrame([row], columns=q.OUTPUT)) == 3     # no averages at all
    assert wrong(_answer([], sb.copy())) == 1           # the row lost
    # an answer of no rows is still compared, through the averages
    monkeypatch.setattr(q, "reference", lambda frames, params=None: _answer([], sb))
    assert wrong(_answer([], sb.copy())) == 0
    assert wrong(_answer([], off)) == 1
