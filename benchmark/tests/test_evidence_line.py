"""The evidence line that carries every run's own noise record (the quartiles
of the window's latencies, the collector's generation-2 passes inside the
window, the process's hash seed), whatever the cell and whatever the driver;
and what the harness leaves alone: it sets nothing of the collector's aside
and the window still holds whole rounds (PERF.md section 2 has the chip
readings that dropped a fixed hash seed and ``gc.freeze``)."""

import copy
import gc
import json
import os
import time
import types

import pytest

from benchmark import harness


def fake_driver(events: list) -> types.ModuleType:
    """A driver the harness has never seen: its four functions and nothing
    else."""
    mod = types.ModuleType("benchmark_drivers_fake")

    def setup(config, traffic, seed, span, say):
        events.append("setup")
        return {}

    def window(state, seconds, tracer):
        events.append("window")
        t0 = time.perf_counter()
        gc.collect()                      # a full collection inside the window
        recs = [{"name": "q", "stream": 0, "ok": True, "t0": t0 + 0.1 * k,
                 "t1": t0 + 0.1 * k + 0.05 + 0.01 * k} for k in range(8)]
        return recs, 0.8

    mod.setup, mod.window = setup, window
    mod.finish = lambda state: events.append("finish")
    mod.check = lambda state, records, limits: {}
    return mod


def test_every_run_carries_its_noise_record_whatever_the_driver(monkeypatch,
                                                                capsys):
    import jax

    events: list = []
    driver = fake_driver(events)
    real = harness.load_module
    monkeypatch.setattr(harness, "load_module", lambda kind, name:
                        driver if kind == "drivers" else real(kind, name))
    for name in ("collect", "freeze", "disable"):
        monkeypatch.setattr(gc, name, lambda *a, _n=name, _f=getattr(gc, name):
                            (events.append(_n), _f(*a))[1])
    cell = {"config_file": {"driver": "fake", "limits": {"failed": 0}},
            "traffic_file": {}, "per_layer": [],
            "end_to_end": [{"name": "batch_query_s", "unit": "s/query"},
                           {"name": "setup_s", "unit": "s"}]}
    callbacks = list(gc.callbacks)
    out = harness.run_cell(cell, 7, 1.0, False, jax.devices()[:1],
                           time.perf_counter())
    assert out["correct"] is True and out["attempted"] == 8
    # the harness itself collects nothing, freezes nothing and leaves the
    # collector on: the one collection is the driver's own, inside the window
    assert events == ["setup", "window", "collect", "finish"]
    assert gc.isenabled() and gc.callbacks == callbacks
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    (evidence,) = [x for x in lines if "latency_s" in x]
    lat = evidence["latency_s"]
    assert lat["n"] == 8 and lat["q1"] < lat["median"] < lat["q3"] < lat["max"]
    assert lat["max"] == pytest.approx(0.12)
    assert evidence["gc2"]["count"] == 1 and evidence["gc2"]["seconds"] > 0
    assert evidence["hash_seed"] == os.environ.get("PYTHONHASHSEED", "random")


@pytest.mark.parametrize("records, want", [
    ([], {"n": 0, "median": None, "max": None}),
    ([{"t0": 1.0, "t1": 1.5}], {"n": 1, "median": 0.5, "max": 0.5}),
    ([{"t0": 0.0, "t1": x} for x in (4.0, 1.0, 3.0, 2.0, 5.0)],
     {"n": 5, "q1": 1.5, "median": 3.0, "q3": 4.5, "max": 5.0}),
], ids=["none", "one", "five"])
def test_latency_summary_takes_quartiles_as_the_bounds_spread_does(records, want):
    assert harness.latency_summary(records) == want


def test_the_window_still_holds_whole_rounds(capsys):
    """The mix's window ends on a whole round, the result line keeps its
    keys, and the evidence line has what a run's noise record needs."""
    import jax

    cell = copy.deepcopy(harness.load_cell("batch_mix4_sf8"))
    cell["config_file"]["data"]["sf"] = 0.05
    cell["config_file"]["sizes"]["batch_rows"] = 1 << 14
    out = harness.run_cell(cell, 2147483659, 1.0, False, jax.devices()[:1],
                           time.perf_counter())
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 4 and out["attempted"] % 4 == 0
    assert list(out) == ["correct", "attempted", "failed", "metrics", "device",
                         "compared"]
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()
             if x.startswith("{")]
    (evidence,) = [x for x in lines if "latency_s" in x]
    assert set(evidence) == {"setup", "window", "latency_s", "gc2", "hash_seed"}
    assert evidence["window"]["requests"] == out["attempted"]
