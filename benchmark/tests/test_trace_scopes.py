"""The by-hand reader of the program's own names in a kept trace, on
hand-made events and on a hand-made XSpace of the shape the v5e writes (the
``op_name`` path is a stat of the event's metadata, not of the event)."""

import pytest

from benchmark import trace_scopes as ts

XSPACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 9000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 2 offset_ps: 0 duration_ps: 4000000 }
    events { metadata_id: 3 offset_ps: 1000000 duration_ps: 1000000 }
    events { metadata_id: 4 offset_ps: 6000000 duration_ps: 3000000 } }
  event_metadata { key: 1 value { id: 1 name: "jit__reduce_arrays_impl(7)" } }
  event_metadata { key: 2 value { id: 2 name: "%while.1 = while(...)"
    stats { metadata_id: 1 str_value: "jit(_reduce_arrays_impl)/jit(main)/auron.agg.sort/while" } } }
  event_metadata { key: 3 value { id: 3 name: "%fusion.2 = fusion(...)"
    stats { metadata_id: 1 ref_value: 2 } } }
  event_metadata { key: 4 value { id: 4 name: "%copy.3 = copy(...)"
    stats { metadata_id: 3 str_value: "not the op name" } } }
  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }
  stat_metadata { key: 2 value { id: 2 name: "jit(_reduce_arrays_impl)/auron.agg.sort/jit(f)/auron.agg.boundaries/cumsum" } }
  stat_metadata { key: 3 value { id: 3 name: "source" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 7 name: "main" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 500000 duration_ps: 11000000 }
    events { metadata_id: 2 offset_ps: 5000000 duration_ps: 2500000 }
    events { metadata_id: 4 offset_ps: 5200000 duration_ps: 600000 } }
  lines { id: 8 name: "pump" timestamp_ns: 0
    events { metadata_id: 3 offset_ps: 5500000 duration_ps: 700000 }
    events { metadata_id: 5 offset_ps: 2000000 duration_ps: 4000000 }
    events { metadata_id: 6 offset_ps: 2500000 duration_ps: 1000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench:window" } }
  event_metadata { key: 2 value { id: 2 name: "auron:wait:queue_get#span=4,parent=2#" } }
  event_metadata { key: 3 value { id: 3 name: "auron:sync:exec/agg_exec.py:900" } }
  event_metadata { key: 4 value { id: 4 name: "not_ours" } }
  event_metadata { key: 5 value { id: 5 name: "PjitFunction(dynamic_slice)" } }
  event_metadata { key: 6 value { id: 6 name: "PjitFunction(convert_element_type)" } }
}
"""


@pytest.fixture(scope="module")
def extracted():
    from jax.profiler import ProfileData

    data = ProfileData.text_proto_to_serialized_xspace(XSPACE)
    names = ts.metadata_stats(data)
    return ts.extract(ProfileData.from_serialized_xspace(data), names), names


def test_scope_is_the_innermost_auron_component():
    assert ts.scope_of("jit(f)/jit(main)/auron.agg.sort/sort") == "auron.agg.sort"
    assert ts.scope_of("jit(f)/auron.agg.sort/jit(g)/auron.agg.boundaries/add") \
        == "auron.agg.boundaries"
    assert ts.scope_of("jit(f)/jit(main)/reduce_window_sum") is None
    assert ts.scope_of("") is None


def test_op_names_come_from_the_events_metadata(extracted):
    ex, names = extracted
    assert names == {"/device:TPU:0": {
        "%while.1 = while(...)":
            "jit(_reduce_arrays_impl)/jit(main)/auron.agg.sort/while",
        "%fusion.2 = fusion(...)":      # a ref_value: the stat-metadata's name
            "jit(_reduce_arrays_impl)/auron.agg.sort/jit(f)/auron.agg.boundaries/cumsum",
    }}
    assert [(n, sc) for n, _, _, sc in ex["devices"]["/device:TPU:0"]] == [
        ("jit__reduce_arrays_impl/%while.1", "auron.agg.sort"),
        ("jit__reduce_arrays_impl/%fusion.2", "auron.agg.boundaries"),
        ("jit__reduce_arrays_impl/%copy.3", None)]
    assert sorted(n for n, _, _ in ex["spans"]) == [
        "auron:sync:exec/agg_exec.py:900", "auron:wait:queue_get", "bench:window"]


def test_device_seconds_are_self_time_by_scope(extracted):
    ex, _ = extracted
    t = ts.tables(ex["devices"], ex["spans"], dispatch=ex["dispatch"])
    prog = t["programs"]["jit__reduce_arrays_impl"]
    # the while [1000, 5000) holds the fusion [2000, 3000): 3 + 1 us, and
    # the copy [7000, 10000) has no scope
    assert prog["scopes"] == {"auron.agg.sort": pytest.approx(3000e-9),
                              "auron.agg.boundaries": pytest.approx(1000e-9),
                              "(no scope)": pytest.approx(3000e-9)}
    assert prog["total_s"] == pytest.approx(7000e-9)
    assert prog["scoped_s"] == pytest.approx(4000e-9)
    assert t["window_s"] == pytest.approx(11000e-9)


def test_idle_is_cut_at_span_edges_and_named_by_the_innermost(extracted):
    ex, _ = extracted
    t = ts.tables(ex["devices"], ex["spans"])
    # gaps [500, 1000), [5000, 7000), [10000, 11500); over [5000, 7000):
    # queue_get [5000, 7500) and the read [5500, 6200) of another thread
    assert t["idle_s"] == {
        "unlabelled": pytest.approx(2000e-9),
        "auron:wait:queue_get": pytest.approx(1300e-9),
        "auron:sync:exec/agg_exec.py:900": pytest.approx(700e-9)}
    assert t["idle_under_auron_share"] == pytest.approx(0.5)
    assert t["longest_gaps"][0] == [pytest.approx(2000e-9),
                                    "auron:wait:queue_get",
                                    "auron:sync:exec/agg_exec.py:900"]
    assert sum(t["idle_s"].values()) + 7000e-9 == pytest.approx(t["window_s"])


def test_dispatch_seconds_are_self_time_per_thread(extracted):
    ex, _ = extracted
    t = ts.tables(ex["devices"], ex["spans"], dispatch=ex["dispatch"])
    assert t["dispatch_s"] == [
        ("PjitFunction(dynamic_slice)", pytest.approx(3000e-9)),
        ("PjitFunction(convert_element_type)", pytest.approx(1000e-9))]
    assert "PjitFunction(dynamic_slice)" in ts.render(t)


def test_scope_map_from_compiled_text_marks_fusions_that_cross_scopes():
    text = """
HloModule jit_f

%fused_computation.1 (p: s32[8]) -> s32[8] {
  %p = s32[8]{0} parameter(0)
  %a = s32[8]{0} add(%p, %p), metadata={op_name="jit(f)/auron.agg.sort/add"}
  ROOT %m = s32[8]{0} multiply(%a, %a), metadata={op_name="jit(f)/auron.agg.boundaries/mul"}
}

ENTRY %main.3 (x: s32[8]) -> s32[8] {
  %x = s32[8]{0} parameter(0)
  %fusion.1 = s32[8]{0} fusion(%x), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(f)/auron.agg.boundaries/mul"}
  ROOT %neg = s32[8]{0} negate(%fusion.1), metadata={op_name="jit(f)/neg"}
}
"""
    m = ts.scope_map(text, "jit_f")
    assert m["jit_f/fusion.1"] == {"scope": "auron.agg.boundaries",
                                   "crosses": ["auron.agg.sort"]}
    assert m["jit_f/neg"] == {"scope": None, "crosses": []}
    devices = {"d": [("jit_f/fusion.1", 0, 100, None), ("jit_f/neg", 100, 150, None)]}
    t = ts.tables(devices, [("bench:window", 0, 200)], m)
    assert t["programs"]["jit_f"]["scopes"] == {
        "auron.agg.boundaries": pytest.approx(100e-9),
        "(no scope)": pytest.approx(50e-9)}
    assert t["crossing"] == [["jit_f/fusion.1", "auron.agg.boundaries",
                              ["auron.agg.sort"], pytest.approx(100e-9)]]


@pytest.mark.parametrize("devices,spans", [
    ({}, [("bench:window", 0, 10)]),
    ({"d": []}, [("bench:window", 0, 10)]),
    ({"d": [("p/a", 0, 5, None)]}, [("auron:pump:batch", 0, 10)]),
])
def test_nothing_to_read_returns_nothing(devices, spans):
    assert ts.tables(devices, spans) is None
