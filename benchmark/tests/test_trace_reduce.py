"""The reduction from a trace to busy, idle and labelled gaps, on a hand-built
trace of the shape the v5e writes (device plane with a modules line and an
ops line, host plane with the benchmark's spans)."""

import pytest

from benchmark import trace_reduce as tr

# device ops: [1000, 3000) with [2000, 2500) nested in it, and [7000, 10000);
# the module event spans all of them and must not count; window [500, 11500)
XSPACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 9000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 2 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 3 offset_ps: 1000000 duration_ps: 500000 }
    events { metadata_id: 2 offset_ps: 6000000 duration_ps: 3000000 } }
  event_metadata { key: 1 value { id: 1 name: "jit_stage" } }
  event_metadata { key: 2 value { id: 2 name: "fusion.1" } }
  event_metadata { key: 3 value { id: 3 name: "copy.2" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 7 name: "main" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 500000 duration_ps: 11000000 }
    events { metadata_id: 2 offset_ps: 3500000 duration_ps: 2000000 }
    events { metadata_id: 3 offset_ps: 100000 duration_ps: 100000 } }
  event_metadata { key: 1 value { id: 1 name: "bench:window" } }
  event_metadata { key: 2 value { id: 2 name: "bench:drain_map" } }
  event_metadata { key: 3 value { id: 3 name: "not_ours" } }
}
"""


@pytest.fixture(scope="module")
def extracted():
    from jax.profiler import ProfileData

    return tr.extract(ProfileData.from_text_proto(XSPACE))


def test_extract_takes_the_ops_line_and_our_spans_only(extracted):
    assert list(extracted["devices"]) == ["/device:TPU:0"]
    assert [n for n, _, _ in extracted["devices"]["/device:TPU:0"]] == \
        ["jit_stage/fusion.1", "jit_stage/copy.2", "jit_stage/fusion.1"]
    assert sorted(n for n, _, _ in extracted["spans"]) == \
        ["bench:drain_map", "bench:window"]


def test_busy_is_the_union_inside_the_window(extracted):
    out = tr.reduce_events(extracted["devices"], extracted["spans"])
    assert out["window_s"] == pytest.approx(11000e-9)
    assert out["busy_s"] == pytest.approx(5000e-9)   # nested op not twice
    assert out["n_devices"] == 1
    ops = dict(out["device_ops"])
    assert ops["jit_stage/fusion.1"] == pytest.approx(5000e-9)
    assert ops["jit_stage/copy.2"] == pytest.approx(500e-9)


def test_gaps_are_labelled_by_the_span_that_covers_most(extracted):
    out = tr.reduce_events(extracted["devices"], extracted["spans"])
    gaps = dict(out["idle_gaps"])
    # [3000, 7000) holds bench:drain_map; [500, 1000) and [10000, 11500) nothing
    assert gaps["bench:drain_map"] == pytest.approx(4000e-9)
    assert gaps["unlabelled"] == pytest.approx(2000e-9)
    assert out["longest_gap_s"] == pytest.approx(4000e-9)
    assert out["busy_s"] + sum(gaps.values()) == pytest.approx(out["window_s"])


def test_window_clips_ops_that_start_before_it():
    devices = {"d": [("a", 0, 100), ("b", 150, 400)]}
    out = tr.reduce_events(devices, [("bench:window", 50, 300)])
    assert out["busy_s"] == pytest.approx((50 + 150) * 1e-9)
    assert dict(out["device_ops"]) == {"b": pytest.approx(150e-9),
                                       "a": pytest.approx(50e-9)}


def test_two_devices_average():
    devices = {"d0": [("a", 0, 100)], "d1": [("a", 0, 50)]}
    out = tr.reduce_events(devices, [("bench:window", 0, 100)])
    assert out["busy_s"] == pytest.approx(75e-9)
    assert out["n_devices"] == 2


@pytest.mark.parametrize("devices,spans", [
    ({}, [("bench:window", 0, 10)]),                 # no device plane
    ({"d": []}, [("bench:window", 0, 10)]),          # a device with no ops
    ({"d": [("a", 0, 5)]}, [("bench:query", 0, 10)]),  # no window span
])
def test_nothing_to_read_returns_nothing(devices, spans):
    assert tr.reduce_events(devices, spans) is None


def test_ops_are_named_by_their_program():
    mods = [("jit_a(123)", 0, 10), ("jit_b(9)", 20, 30)]
    ops = [("%fusion.3 = f32[8]{0} fusion(...)", 2, 4), ("%copy = ...", 25, 26),
           ("%lost = ...", 12, 13)]
    assert [n for n, _, _ in tr.name_ops(ops, mods)] == \
        ["jit_a/%fusion.3", "jit_b/%copy", "?/%lost"]


def test_union_and_gaps_arithmetic():
    assert tr.union([[5, 7], [1, 3], [2, 4], [7, 8], [9, 9]]) == [[1, 4], [5, 8]]
    assert tr.gaps([[1, 4], [5, 8]], 0, 10) == [[0, 1], [4, 5], [8, 10]]
    assert tr.gaps([], 0, 10) == [[0, 10]]
    assert tr.label_gap([4, 8], [("outer", 0, 10), ("inner", 4, 8)]) == "inner"
