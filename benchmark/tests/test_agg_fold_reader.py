"""The reader of ``agg_fold_rows_per_query.batch`` on hand-made ``facts``: the
summary's ``agg_fold_rows`` over the queries completed; None where the summary
has no such sum (a program from before the counter), where a ring of the window
wrapped, where the recorder is off or nothing completed."""

import pytest

from auron_tpu import obs
from benchmark import harness

NAME = "agg_fold_rows_per_query.batch"
SUMMARY = {"complete": True, "layers": {}, "spans": {}, "d2h_bytes": 26000,
           "sync_sites": [], "agg_fold_rows": 14336}
FACTS = {"records": [{"ok": True, "t0": 100.0, "t1": 112.0},
                     {"ok": False, "t0": 112.0, "t1": 113.0},
                     {"ok": True, "t0": 113.0, "t1": 125.0}]}


@pytest.fixture
def read(monkeypatch):
    monkeypatch.setattr(obs, "mode", lambda: obs.MODE_RECORDER)
    return harness.load_module("metrics", NAME).read


@pytest.mark.parametrize("summary, want", [
    (SUMMARY, 7168.0),                                   # two queries completed
    (dict(SUMMARY, agg_fold_rows=0), 0.0),               # no fold ran: a count
    ({k: v for k, v in SUMMARY.items()
      if k != "agg_fold_rows"}, None),                   # the parent's program
    (dict(SUMMARY, complete=False), None),               # a ring wrapped
], ids=["sum", "zero", "no_sum", "incomplete"])
def test_reader_divides_the_sum_by_the_queries_completed(read, monkeypatch,
                                                         summary, want):
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


@pytest.mark.parametrize("cell", ["batch_q3_sf8", "batch_mix4_sf8"])
def test_the_metric_is_declared_for_both_cells(cell):
    """Membership, not position: later PRs append after it."""
    entry = {"name": NAME, "unit": "rows/query", "better": "lower",
             "source": "program_span", "layer": "operators",
             "moves": "batch_query_s",
             "workloads": ["batch_q3_sf8", "batch_mix4_sf8"]}
    declared = [m for m in harness.load_cell(cell)["per_layer"]
                if m["name"] == NAME]
    assert declared == [entry]


def test_the_program_sums_the_fold_events_that_began_in_the_window():
    """On the real rings: ``note_agg_fold`` is an event of no duration and no
    layer, so it adds to the sum and to no layer's seconds."""
    import time

    saved = obs.mode()
    obs.set_mode("recorder")
    try:
        obs.note_agg_fold(1024, 4194304)          # before the window
        t0 = time.perf_counter()
        obs.note_agg_fold(1024, 4194304)
        obs.note_agg_fold(2048, 4194304)
        t1 = time.perf_counter()
        obs.note_agg_fold(128, 4194304)           # after it
        ws = obs.window_summary(t0, t1)
    finally:
        obs.set_mode(saved)
    assert ws["agg_fold_rows"] == 3072
    assert "fold" not in ws["layers"]
