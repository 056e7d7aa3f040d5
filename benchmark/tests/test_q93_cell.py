"""The cell ``batch_q93_sf8``: its files are found by name, the ``store_returns``
and ``reason`` generator keeps to ``schemas/tpcds_returns.json`` and to the
specification's shapes (a return is a sold line and never more than it), the
three readers this cell brings read the program's rings and the trace, the
driver's comparison holds every customer's sum beside the top 100, and the
cell's own fault comes out not correct (the bridge's faults run over this cell
as cases of the parametrised tests of ``test_control_and_faults.py``)."""

import copy
import decimal
import json
import os
import time

import pandas as pd
import pytest

from auron_tpu import obs
from benchmark import datagen, datagen_returns, harness

SEED = 2147483659
CELL = "batch_q93_sf8"
NEW_READERS = ("join_hash_rows_per_query.ret", "sort_rows_per_query.ret",
               "hash_probe_roofline.ret")


# ---- the cell's files, found by name ----------------------------------------


def test_cell_resolves_to_its_files_by_name():
    cell = harness.load_cell(CELL)
    assert cell["config_file"]["name"] == cell["config"] == "tpcds_batch_returns_sf8"
    assert cell["traffic_file"]["name"] == cell["traffic"] == "closed1_q93"
    assert cell["chips"] == 1 and cell["config_file"]["deployment"]["chips"] == 1
    driver = harness.load_module("drivers", cell["config_file"]["driver"])
    for fn in ("setup", "window", "finish", "check", "control"):
        assert callable(getattr(driver, fn))
    mod = harness.load_module("queries", "q93")
    for name in ("ingest", "run", "reference", "ORDER", "ASCENDING", "LIMIT",
                 "SCAN_COLUMNS", "IN_ORDER", "require_program"):
        assert hasattr(mod, name), name
    assert {m["name"] for m in cell["end_to_end"]} == {"batch_query_s", "setup_s"}
    names = [m["name"] for m in cell["per_layer"]]
    assert len(names) == 20 and all(n.endswith(".ret") for n in names)
    assert set(NEW_READERS) <= set(names)
    for n in names:
        assert callable(harness.load_module("metrics", n).read)
    assert cell["config_file"]["limits"] == {"failed": 0, "rows_wrong": 0}


def test_the_configuration_names_its_generator_module_and_what_was_cut():
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    entry = next(c for c in spec["configs"] if c["name"] == "tpcds_batch_returns_sf8")
    cfg = harness.load_cell(CELL)["config_file"]
    assert cfg["reduced"] == entry["reduced"] == ["sf", "tables", "queries"]
    assert set(cfg["reduced_why"]) == set(cfg["reduced"])
    assert cfg["data"] == {"module": "datagen_returns", "generator": "tpcds_returns",
                           "sf": 8}
    for key in ("generator", "reasons", "plan", "types", "comparison",
                "batch_rows", "n_map", "n_reduce"):
        assert key in cfg["assumed"], key
    assert cfg["sizes"]["fact_rows"] == round(datagen.SF1["store_sales"] * 8)
    traffic = harness.load_cell(CELL)["traffic_file"]
    assert traffic["control_money"] in ("float32", "bfloat16")


# ---- the generator against its schema file ----------------------------------


@pytest.fixture(scope="module")
def frames():
    return datagen_returns.tpcds_returns(0.05, SEED)


def test_returns_and_reason_keep_to_their_schema_file(frames):
    schemas = datagen_returns.schemas()
    cols = schemas["store_returns"]
    assert len(cols) == 20 and len(schemas["reason"]) == 3
    assert [c for c, _, nullable in cols if not nullable] == ["sr_item_sk",
                                                              "sr_ticket_number"]
    assert sum(t == "decimal(7,2)" for _, t, _ in cols) == 9
    for table in ("store_returns", "reason"):
        df = frames[table]
        assert list(df.columns) == [c for c, _, _ in schemas[table]]
        for c, t, nullable in schemas[table]:
            if t in ("int64", "int32") or t.startswith("decimal"):
                assert pd.api.types.is_integer_dtype(df[c].dtype), c
            if not nullable:
                assert not df[c].isna().any(), c
    assert set(schemas) == set(datagen.schemas()) | {"store_returns", "reason"}


def test_a_return_is_a_sold_line_and_never_more_than_it(frames):
    ss, sr = frames["store_sales"], frames["store_returns"]
    assert 0.08 < len(sr) / len(ss) < 0.12
    assert not sr.duplicated(["sr_item_sk", "sr_ticket_number"]).any()
    m = sr.merge(ss, left_on=["sr_item_sk", "sr_ticket_number"],
                 right_on=["ss_item_sk", "ss_ticket_number"], how="left")
    assert len(m) == len(sr) and m.ss_item_sk.notna().all()
    both = m.dropna(subset=["sr_return_quantity", "ss_quantity"])
    assert len(both) > 0.8 * len(sr)
    assert (both.sr_return_quantity >= 1).all()
    assert (both.sr_return_quantity <= both.ss_quantity).all()
    assert sr.sr_reason_sk.dropna().between(1, 35).all()
    assert sr.sr_reason_sk.isna().mean() < 0.1


def test_reason_28_names_exactly_one_row_and_the_seed_fixes_the_tables(frames):
    r = frames["reason"]
    assert len(r) == 35 and r.r_reason_sk.tolist() == list(range(1, 36))
    assert (r.r_reason_desc == "reason 28").sum() == 1
    assert r.loc[r.r_reason_desc == "reason 28", "r_reason_sk"].item() == 28
    again = datagen_returns.tpcds_returns(0.05, SEED)
    assert all(frames[t].equals(again[t]) for t in frames)
    base = datagen.tpcds(0.05, SEED)
    assert all(frames[t].equals(base[t]) for t in base)
    other = datagen_returns.tpcds_returns(0.05, SEED + 1)["store_returns"]
    assert not frames["store_returns"].equals(other)


# ---- the three readers ------------------------------------------------------


@pytest.fixture
def recorder():
    saved = obs.mode()
    obs.set_mode("recorder")
    yield
    obs.set_mode(saved)


def _facts(t0: float, t1: float, trace=None) -> dict:
    mid = (t0 + t1) / 2
    return {"records": [{"ok": True, "t0": t0, "t1": mid},
                        {"ok": True, "t0": mid, "t1": t1}],
            "trace": trace, "traced": [t0, t1],
            "peaks": harness.chip_peaks("TPU v5 lite")}


def test_readers_sum_the_events_that_began_in_the_window(recorder):
    obs.note_join_lookup("hash", 999)                   # before the window
    obs.note_sort(4096, "sort_exec", "host")
    t0 = time.perf_counter()
    obs.note_join_hash_build(1_150_000, 1 << 23, 8, False)
    obs.note_join_lookup("hash", 4194304)
    obs.note_join_lookup("hash", 4194304)
    obs.note_join_lookup("compare", 2097152)
    obs.note_sort(131072, "hostsort", "host")
    obs.note_sort(16384, "join_build", "device")
    t1 = time.perf_counter()
    obs.note_join_lookup("hash", 7)                     # after it
    ws = obs.window_summary(t0, t1)
    assert ws["join_lookup_rows"] == {"hash": 8388608, "compare": 2097152}
    assert ws["join_hash_build"] == {"n": 1, "rows": 1_150_000, "rounds": 8,
                                     "fallbacks": 0}
    assert ws["sort_rows"] == {"hostsort": {"host": 131072},
                               "join_build": {"device": 16384}}
    trace = {"device_ops": [["jit__unique_probe_jit/_fusion.3", 0.020],
                            ["jit__unique_probe_jit/_while.4", 7.0],
                            ["jit__gather_build_jit/_fusion", 1.0]]}
    facts = _facts(t0, t1, trace)
    read = {n: harness.load_module("metrics", n).read(facts) for n in NEW_READERS}
    assert read["join_hash_rows_per_query.ret"] == 8388608 / 2
    assert read["sort_rows_per_query.ret"] == (131072 + 16384) / 2
    roof = harness.load_module("metrics", "hash_probe_roofline.ret")
    assert roof.probe_bytes(8388608) == 8388608 * 30
    want = 100 * 8388608 * 30 / 819e9 / 0.020       # the _while op left out
    assert read["hash_probe_roofline.ret"] == pytest.approx(want)


@pytest.mark.parametrize("name", NEW_READERS)
def test_reader_reports_nothing_where_there_is_nothing_to_read(name, monkeypatch):
    read = harness.load_module("metrics", name).read
    summary = {"complete": True, "layers": {}, "spans": {},
               "join_lookup_rows": {"hash": 12}, "sort_rows": {"a": {"host": 12}},
               "join_hash_build": {"n": 1, "rows": 1, "rounds": 1, "fallbacks": 0}}
    trace = {"device_ops": [["jit__unique_probe_jit/_fusion", 1.0]]}
    facts = _facts(100.0, 104.0, trace)
    monkeypatch.setattr(obs, "mode", lambda: obs.MODE_RECORDER)
    monkeypatch.setattr(obs, "window_summary", lambda t0, t1: dict(summary))
    assert read(facts) is not None and read(facts) > 0
    # the parent's program: no hash map, no sort events
    lacking = {k: v for k, v in summary.items()
               if k not in ("join_hash_build", "sort_rows")}
    monkeypatch.setattr(obs, "window_summary", lambda t0, t1: dict(lacking))
    assert read(facts) is None
    monkeypatch.setattr(obs, "window_summary",
                        lambda t0, t1: dict(summary, complete=False))
    assert read(facts) is None                # a ring wrapped
    monkeypatch.setattr(obs, "mode", lambda: obs.MODE_OFF)
    assert read(facts) is None                # the recorder is off


def test_roofline_reads_nothing_where_the_probe_program_is_not_in_the_trace(
        monkeypatch):
    read = harness.load_module("metrics", "hash_probe_roofline.ret").read
    monkeypatch.setattr(obs, "mode", lambda: obs.MODE_RECORDER)
    monkeypatch.setattr(obs, "window_summary", lambda t0, t1: {
        "complete": True, "join_lookup_rows": {"hash": 12},
        "join_hash_build": {"n": 1}})
    assert read(_facts(1.0, 2.0, {"device_ops": [["jit_other/_fusion", 1.0]]})) is None
    assert read(_facts(1.0, 2.0, None)) is None
    monkeypatch.setattr(obs, "window_summary", lambda t0, t1: {
        "complete": True, "join_lookup_rows": {"search": 12},
        "join_hash_build": {"n": 0}})
    trace = {"device_ops": [["jit__unique_probe_jit/_fusion", 1.0]]}
    assert read(_facts(1.0, 2.0, trace)) is None     # no hash lookup ran


# ---- the driver's comparison ------------------------------------------------


def _frame(rows: list) -> pd.DataFrame:
    q = harness.load_module("queries", "q93")
    out = pd.DataFrame(rows, columns=q.OUTPUT)
    out["ss_customer_sk"] = out.ss_customer_sk.astype("Int64")
    return out


def _answer(rows: list, sums: pd.DataFrame) -> pd.DataFrame:
    out = _frame(rows)
    out.attrs["sums"] = sums
    return out


def test_check_holds_every_customers_sum_beside_the_top_100(monkeypatch):
    driver = harness.load_module("drivers", "batch_agg")
    q = harness.load_module("queries", "q93")
    rows = [[7, None], [None, decimal.Decimal("0.00")], [3, decimal.Decimal("12.50")]]
    sums = _frame(rows)
    monkeypatch.setattr(q, "reference",
                        lambda frames, params=None: _answer(rows, sums.copy()))
    state = {"queries": {"q93": q}, "frames": {}, "params": {}}

    def wrong(answer) -> int:
        rec = {"ok": True, "name": "q93", "answer": answer}
        return driver.check(state, [rec], {"rows_wrong": 0})["rows_wrong"]["value"]

    assert wrong(_answer(rows, sums.copy())) == 0
    off = sums.copy()
    off.loc[2, "sumsales"] = decimal.Decimal("12.51")      # a cent, outside the top
    assert wrong(_answer(rows, off)) == 1
    assert wrong(_answer(rows, sums.iloc[:2])) == 3        # a customer lost
    assert wrong(pd.DataFrame(rows, columns=q.OUTPUT)) == 3    # no sums at all
    assert wrong(_answer(rows[:2], sums.copy())) == 3      # the top loses a row


# ---- the cell's own faults, planted where they bite query 93 ----------------
#
# A whole run at a tiny size on the CPU (everything of ``run_cell``);
# ``correct`` has to come out false.


def _tiny(sf: float = 0.1) -> dict:
    cell = copy.deepcopy(harness.load_cell(CELL))
    cell["config_file"]["data"]["sf"] = sf
    cell["config_file"]["sizes"]["batch_rows"] = 1 << 14
    return cell


def _run(cell: dict, seconds: float = 1.0) -> dict:
    import jax

    return harness.run_cell(cell, SEED, seconds, False, jax.devices()[:1],
                            time.perf_counter())


def test_sound_run_is_correct():
    out = _run(_tiny())
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] >= 1


def test_fault_a_map_tasks_returns_left_out(monkeypatch):
    """Map task 1 of stage 2 scans none of its returns: the sales they were
    made from join nothing, and their customers' sums move."""
    from auron_tpu.bridge import api

    real = api.put_resource

    def drop(rid, value, *a, **kw):
        if rid.endswith("_returns"):
            value = [value[0], [b.with_device(b.device._replace(
                sel=b.device.sel & False)) for b in value[1]]]
        return real(rid, value, *a, **kw)

    monkeypatch.setattr(api, "put_resource", drop)
    out = _run(_tiny())
    assert out["failed"] == 0
    assert out["correct"] is False and out["compared"]["rows_wrong"]["value"] > 0


def test_control_fails_in_almost_every_customers_sum():
    """The traffic file's bfloat16 money in the references' place, at a
    small size: the prices themselves are rounded, so the sums move."""
    from benchmark import control

    assert harness.load_cell(CELL)["traffic_file"]["control_money"] == "bfloat16"
    out = control.run_control(_tiny(sf=0.2), SEED)
    assert out["correct"] is False and out["compared"]["rows_wrong"]["value"] > 100
