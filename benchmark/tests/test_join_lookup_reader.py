"""The reader of ``join_lookup_gather_rows_per_query.*`` on hand-made
``facts``: the rows of the summary's ``join_lookup_rows`` that a gather looked
up (``lut`` and ``search``; ``compare`` is what it leaves out) over the queries
completed; None where the summary has no such table (a program from before the
counter), where a ring of the window wrapped, where the recorder is off or
nothing completed."""

import json
import os

import pytest

from auron_tpu import obs
from benchmark import harness

NAMES = ("join_lookup_gather_rows_per_query.batch",
         "join_lookup_gather_rows_per_query.sql")
SUMMARY = {"complete": True, "layers": {}, "spans": {}, "d2h_bytes": 26000,
           "sync_sites": [], "agg_fold_rows": 14336,
           "join_gather_rows": 42_000_000, "join_takes": {"compact": 20},
           "join_lookup_rows": {"compare": 50_331_648, "lut": 50_331_648,
                                "search": 16_384}}
FACTS = {"records": [{"ok": True, "t0": 100.0, "t1": 112.0},
                     {"ok": False, "t0": 112.0, "t1": 113.0},
                     {"ok": True, "t0": 113.0, "t1": 125.0}]}


@pytest.fixture(params=NAMES)
def read(request, monkeypatch):
    monkeypatch.setattr(obs, "mode", lambda: obs.MODE_RECORDER)
    return harness.load_module("metrics", request.param).read


@pytest.mark.parametrize("summary, want", [
    (SUMMARY, 25_174_016.0),                             # two queries completed
    (dict(SUMMARY, join_lookup_rows={"compare": 9_000_000}), 0.0),
    (dict(SUMMARY, join_lookup_rows={}), 0.0),           # no probe ran: a count
    ({k: v for k, v in SUMMARY.items()
      if k != "join_lookup_rows"}, None),                # the parent's program
    (dict(SUMMARY, complete=False), None),               # a ring wrapped
], ids=["sum", "all_compared", "zero", "no_table", "incomplete"])
def test_reader_divides_the_gathered_lookups_by_the_queries_completed(
        read, monkeypatch, summary, want):
    seen = []
    monkeypatch.setattr(obs, "window_summary",
                        lambda t0, t1: seen.append((t0, t1)) or dict(summary))
    assert read(FACTS) == want
    assert seen == [(100.0, 125.0)]           # first submit to last result


def test_reader_reports_nothing_where_there_is_nothing_sound(read, monkeypatch):
    monkeypatch.setattr(obs, "window_summary", lambda t0, t1: dict(SUMMARY))
    assert read({"records": []}) is None
    assert read({"records": [{"ok": False, "t0": 1.0, "t1": 2.0}]}) is None
    monkeypatch.setattr(obs, "mode", lambda: obs.MODE_OFF)
    assert read(FACTS) is None                # the recorder is off
    monkeypatch.setattr(obs, "mode", lambda: obs.MODE_RECORDER)
    monkeypatch.delattr(obs, "window_summary")
    assert read(FACTS) is None                # a program without the summary


@pytest.mark.parametrize("cell, name, workloads", [
    ("batch_q3_sf8", NAMES[0], ["batch_q3_sf8", "batch_mix4_sf8"]),
    ("batch_mix4_sf8", NAMES[0], ["batch_q3_sf8", "batch_mix4_sf8"]),
    ("sql_streams4_sf8", NAMES[1], ["sql_streams4_sf8"]),
])
def test_the_metric_is_declared_for_its_cells(cell, name, workloads):
    """Membership, not position: later PRs append after it."""
    entry = {"name": name, "unit": "rows/query", "better": "lower",
             "source": "program_span", "layer": "operators",
             "moves": "batch_query_s", "workloads": workloads}
    declared = [m for m in harness.load_cell(cell)["per_layer"]
                if m["name"].startswith("join_lookup_gather_rows_per_query")]
    assert declared == [entry]
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        assert entry in json.load(f)["per_layer"]


def test_the_program_sums_the_lookup_events_that_began_in_the_window():
    """On the real rings: ``note_join_lookup`` is an event of no duration and
    no layer, so it adds to the table by kind and to no layer's seconds."""
    import time

    saved = obs.mode()
    obs.set_mode("recorder")
    try:
        obs.note_join_lookup("lut", 4194304)              # before the window
        t0 = time.perf_counter()
        obs.note_join_lookup("lut", 4194304)
        obs.note_join_lookup("compare", 4194304)
        obs.note_join_lookup("compare", 1048576)
        obs.note_join_lookup("search", 1024)
        t1 = time.perf_counter()
        obs.note_join_lookup("lut", 1048576)              # after it
        ws = obs.window_summary(t0, t1)
    finally:
        obs.set_mode(saved)
    assert ws["join_lookup_rows"] == {"lut": 4194304, "search": 1024,
                                      "compare": 4194304 + 1048576}
    assert "lookup" not in ws["layers"]
    module = harness.load_module("metrics", NAMES[0])
    assert module is harness.load_module("metrics", NAMES[1])
