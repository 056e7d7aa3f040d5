"""BENCHMARK.json against the files under benchmark/: every name resolves to a
file of its own, and names, units and bounds keep to the contract's shapes."""

import json
import os
import re

import pytest

from benchmark import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def spec():
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level_keys(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert spec["paths"] == ["benchmark"]
    assert 1 <= spec["run_seconds"] <= 51
    assert os.path.exists(os.path.join(harness.ROOT, spec["command"][1]))


def test_every_cell_resolves_to_its_files(spec):
    for w in spec["workloads"]:
        cell = harness.load_cell(w["name"], spec)
        assert cell["config_file"]["name"] == w["config"]
        assert cell["traffic_file"]["name"] == w["traffic"]
        assert cell["chips"] in (1, 4)
        harness.load_module("drivers", cell["config_file"]["driver"])
        for q in cell["traffic_file"]["queries"]:
            harness.load_module("queries", q)
        names = {m["name"] for m in cell["end_to_end"]}
        assert "setup_s" in names and len(names) >= 2
        assert cell["per_layer"], "a cell reports at least one per-layer metric"
        for limit in ("failed", "rows_wrong"):
            assert limit in cell["config_file"]["limits"]


def test_the_bound_and_the_window_are_those_pr_33_sized(spec):
    """PERF.md section 2 has the rule and the spreads that set them; only a
    ``benchmark`` PR that measures every cell again may move them."""
    bounds = {m["name"]: m["bound"] for m in spec["end_to_end"]}
    assert bounds == {"batch_query_s": 0.08, "setup_s": 0.25}
    assert spec["run_seconds"] == 40


def test_every_config_has_a_file_of_its_own_and_is_used(spec):
    files = [c["file"] for c in spec["configs"]]
    assert len(set(files)) == len(files)
    used = {w["config"] for w in spec["workloads"]}
    for c in spec["configs"]:
        assert c["file"].startswith("benchmark/configs/")
        assert c["name"] in used
        with open(os.path.join(harness.ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"]
        assert cfg["reduced"] == c["reduced"]
        assert cfg["source"] == c["source"]


def test_every_per_layer_metric_has_a_reader(spec):
    e2e = {m["name"] for m in spec["end_to_end"]}
    cells = {w["name"] for w in spec["workloads"]}
    for m in spec["per_layer"]:
        assert callable(harness.load_module("metrics", m["name"]).read)
        assert m["moves"] in e2e
        assert set(m["workloads"]) <= cells


def test_every_end_to_end_metric_has_a_reader(spec):
    facts = {"records": [{"ok": True, "t0": 0.0, "t1": 2.0}], "window_s": 4.0,
             "setup_s": 9.0}
    want = {"setup_s": 9.0, "batch_query_s": 4.0}
    for m in spec["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        value = harness.load_module("metrics", m["name"]).read(facts)
        assert value == want.get(m["name"], value) and value > 0
        empty = dict(facts, records=[])
        if m["name"] != "setup_s":
            assert harness.load_module("metrics", m["name"]).read(empty) is None


def test_schema_file_has_the_specifications_column_counts():
    from benchmark import datagen

    tables = datagen.schemas()
    assert {t: len(c) for t, c in tables.items()} == {
        "store_sales": 23, "date_dim": 28, "item": 22}
    money = [c for c, t, _ in tables["store_sales"] if t == "decimal(7,2)"]
    assert len(money) == 12
    not_null = [c for c, _, nullable in tables["store_sales"] if not nullable]
    assert not_null == ["ss_item_sk", "ss_ticket_number"]


def test_names_units_and_lengths(spec):
    metrics = spec["end_to_end"] + spec["per_layer"]
    for group in (metrics, spec["workloads"], spec["configs"]):
        names = [x["name"] for x in group]
        assert len(set(names)) == len(names)
        assert all(NAME.match(n) for n in names), names
    for m in metrics:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for w in spec["workloads"]:
        assert NAME.match(w["traffic"]) and NAME.match(w["config"])
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    for c in spec["configs"]:
        assert 1 <= len(c["source"]) <= 200
        assert all(NAME.match(k) for k in c["reduced"]) and len(c["reduced"]) <= 16
    pairs = [(w["config"], w["traffic"]) for w in spec["workloads"]]
    assert len(set(pairs)) == len(pairs)
    four = sum(w["chips"] == 4 for w in spec["workloads"])
    assert four <= max(1, len(spec["workloads"]) // 2)


def test_file_names_use_only_the_allowed_characters():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    here = os.path.join(harness.ROOT, "benchmark")
    for d, dirs, files in os.walk(here):
        dirs[:] = [x for x in dirs if x != "__pycache__"]
        for f in files:
            rel = os.path.relpath(os.path.join(d, f), harness.ROOT)
            assert ok.match(rel), rel


def test_an_unknown_device_kind_is_an_error():
    assert harness.chip_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        harness.chip_peaks("TPU v99")
