"""The reader of ``join_gather_rows_per_query.*`` on hand-made ``facts``: the
summary's ``join_gather_rows`` over the queries completed; None where the
summary has no such sum (a program from before the counter), where a ring of
the window wrapped, where the recorder is off or nothing completed."""

import pytest

from auron_tpu import obs
from benchmark import harness

NAMES = ("join_gather_rows_per_query.batch", "join_gather_rows_per_query.sql")
SUMMARY = {"complete": True, "layers": {}, "spans": {}, "d2h_bytes": 26000,
           "sync_sites": [], "agg_fold_rows": 14336,
           "join_gather_rows": 50_343_936, "join_takes": {"compact": 20,
                                                           "dense": 4}}
FACTS = {"records": [{"ok": True, "t0": 100.0, "t1": 112.0},
                     {"ok": False, "t0": 112.0, "t1": 113.0},
                     {"ok": True, "t0": 113.0, "t1": 125.0}]}


@pytest.fixture(params=NAMES)
def read(request, monkeypatch):
    monkeypatch.setattr(obs, "mode", lambda: obs.MODE_RECORDER)
    return harness.load_module("metrics", request.param).read


@pytest.mark.parametrize("summary, want", [
    (SUMMARY, 25_171_968.0),                             # two queries completed
    (dict(SUMMARY, join_gather_rows=0), 0.0),            # no take ran: a count
    ({k: v for k, v in SUMMARY.items()
      if k != "join_gather_rows"}, None),                # the parent's program
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


@pytest.mark.parametrize("cell, name, workloads", [
    ("batch_q3_sf8", NAMES[0], ["batch_q3_sf8", "batch_mix4_sf8"]),
    ("batch_mix4_sf8", NAMES[0], ["batch_q3_sf8", "batch_mix4_sf8"]),
    ("sql_streams4_sf8", NAMES[1], ["sql_streams4_sf8"]),
])
def test_the_metric_is_declared_for_its_cells(cell, name, workloads):
    declared = [m for m in harness.load_cell(cell)["per_layer"]
                if m["name"].startswith("join_gather_rows_per_query")]
    assert declared == [{"name": name, "unit": "rows/query", "better": "lower",
                         "source": "program_span", "layer": "operators",
                         "moves": "batch_query_s", "workloads": workloads}]


def test_the_program_sums_the_take_events_that_began_in_the_window():
    """On the real rings: ``note_join_take`` is an event of no duration and no
    layer, so it adds to the sum and the counts and to no layer's seconds."""
    import time

    saved = obs.mode()
    obs.set_mode("recorder")
    try:
        obs.note_join_take("dense", 4194304, 4194304)     # before the window
        t0 = time.perf_counter()
        obs.note_join_take("seed", 1024, 4194304)
        obs.note_join_take("compact", 1024, 4194304)
        obs.note_join_take("compact", 128, 4194304)
        obs.note_join_take("repair", 262144, 4194304)
        obs.note_join_take("dense", 4194304, 4194304)
        t1 = time.perf_counter()
        obs.note_join_take("compact", 128, 4194304)       # after it
        ws = obs.window_summary(t0, t1)
    finally:
        obs.set_mode(saved)
    assert ws["join_gather_rows"] == 1024 + 1024 + 128 + 262144 + 4194304
    assert ws["join_takes"] == {"seed": 1, "compact": 2, "repair": 1,
                                "dense": 1}
    assert "take" not in ws["layers"]
