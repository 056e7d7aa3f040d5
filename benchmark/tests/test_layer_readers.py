"""The seven readers of the program's rings, on hand-made ``facts``: each
picks its number out of ``obs.window_summary`` over the window of the records
and divides by the queries completed; each returns None where the summary is
not complete, the recorder is off, nothing completed, or the program is one
that has no ``window_summary`` yet."""

import pytest

from auron_tpu import obs
from benchmark import harness

SUMMARY = {
    "complete": True,
    "layers": {"entry": {"n": 12, "total_s": 50.0, "self_s": 0.04},
               "sync": {"n": 72, "total_s": 14.0, "self_s": 14.0},
               "wait": {"n": 30, "total_s": 26.0, "self_s": 25.0}},
    "spans": {"plan:task": {"n": 8, "total_s": 0.02, "self_s": 0.015},
              "plan:fusion": {"n": 8, "total_s": 0.005, "self_s": 0.005},
              "pump:batch": {"n": 20, "total_s": 48.0, "self_s": 34.0},
              "wait:harvest": {"n": 10, "total_s": 1.5, "self_s": 0.5},
              "wait:queue_get": {"n": 12, "total_s": 24.0, "self_s": 24.0},
              "wait:queue_put": {"n": 4, "total_s": 0.5, "self_s": 0.5},
              "exchange:write": {"n": 16, "total_s": 1.2, "self_s": 1.0},
              "exchange:read": {"n": 4, "total_s": 0.1, "self_s": 0.1}},
    "d2h_bytes": 26000,
    "sync_sites": [],
}
WANT = {   # two queries completed
    "entry_self_s_per_query.batch": 0.02,
    "plan_s_per_query.batch": 0.01,
    "op_host_s_per_query.batch": 17.0,
    "sync_wait_s_per_query.batch": 7.25,
    "queue_wait_s_per_query.batch": 12.25,
    "exchange_s_per_query.batch": 0.55,
    "d2h_bytes_per_query.batch": 13000,
}
FACTS = {"records": [{"ok": True, "t0": 100.0, "t1": 112.0},
                     {"ok": False, "t0": 112.0, "t1": 113.0},
                     {"ok": True, "t0": 113.0, "t1": 125.0}]}


@pytest.fixture
def summary(monkeypatch):
    seen = []

    def fake(t0, t1):
        seen.append((t0, t1))
        return dict(SUMMARY)

    monkeypatch.setattr(obs, "window_summary", fake)
    monkeypatch.setattr(obs, "mode", lambda: obs.MODE_RECORDER)
    return seen


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_picks_its_number_per_completed_query(name, summary):
    read = harness.load_module("metrics", name).read
    assert read(FACTS) == pytest.approx(WANT[name])
    assert summary == [(100.0, 125.0)]       # first submit to last result


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_reports_nothing_where_there_is_nothing_sound(name, summary,
                                                             monkeypatch):
    read = harness.load_module("metrics", name).read
    assert read({"records": []}) is None
    assert read({"records": [{"ok": False, "t0": 1.0, "t1": 2.0}]}) is None
    monkeypatch.setattr(obs, "window_summary",
                        lambda t0, t1: dict(SUMMARY, complete=False))
    assert read(FACTS) is None                # a ring of the window wrapped
    monkeypatch.setattr(obs, "mode", lambda: obs.MODE_OFF)
    assert read(FACTS) is None                # the recorder is off
    monkeypatch.setattr(obs, "mode", lambda: obs.MODE_RECORDER)
    monkeypatch.delattr(obs, "window_summary")
    assert read(FACTS) is None                # the parent commit's program


def test_a_span_that_never_ran_counts_as_zero_not_as_nothing(summary, monkeypatch):
    bare = dict(SUMMARY, layers={}, spans={})
    monkeypatch.setattr(obs, "window_summary", lambda t0, t1: bare)
    for name in WANT:
        if name != "d2h_bytes_per_query.batch":
            assert harness.load_module("metrics", name).read(FACTS) == 0.0


def test_every_new_metric_is_declared_for_both_cells():
    cell = harness.load_cell("batch_q3_sf8")
    declared = {m["name"]: m for m in cell["per_layer"]}
    for name in WANT:
        m = declared[name]
        assert m["better"] == "lower" and m["moves"] == "batch_query_s"
        assert m["workloads"] == ["batch_q3_sf8", "batch_mix4_sf8"]
