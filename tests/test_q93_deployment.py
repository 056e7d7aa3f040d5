"""TPC-DS query 93 as ``benchmark/returns_plan.py`` plans it (four stages
through ``bridge.api``: both fact tables through a file shuffle by (item,
ticket), a sort-merge join of the two, a BHJ with ``reason``, partial and final
DECIMAL(28,2) sums by customer) against the plain reference of
``benchmark/queries/q93.py``, at a small size on the CPU, on
specification-typed tables from ``benchmark/datagen_returns.py``."""

import tempfile
import time

import pytest

from auron_tpu import obs
from auron_tpu.columnar import batch as batch_mod
from benchmark import compare, datagen_returns, harness

SF = 0.02
SEEDS = (2147483659, 7, 2147484001)
PARAMS = {"batch_rows": 1 << 13, "n_map": 2, "n_reduce": 2}


@pytest.fixture(scope="module")
def q93():
    return harness.load_module("queries", "q93")


@pytest.fixture()
def rings():
    before = obs.mode()
    obs.set_mode(obs.MODE_RECORDER)
    yield time.perf_counter()
    obs.set_mode(before)


def run(q93, frames: dict):
    resident = q93.ingest(frames, PARAMS)
    with tempfile.TemporaryDirectory(prefix="q93_") as wd:
        return q93.run(resident, PARAMS, wd, harness.span)


@pytest.mark.parametrize("seed, chip", [
    (SEEDS[0], False), (SEEDS[1], False),
    (SEEDS[0], True), (SEEDS[1], True), (SEEDS[2], True),
], ids=lambda v: {True: "chip_rules", False: "cpu_rules"}.get(v, str(v)))
def test_plan_equals_reference_every_customer_included(q93, seed, chip, rings,
                                                       monkeypatch):
    """The top 100 and every customer's sum, exact, under XLA:CPU's rules
    (the sorted build, searched) and the chip's (the build's hash map): the
    probe side's Sort is gone either way, as the plan proves its order
    dies, and the build side's always was."""
    monkeypatch.setattr(batch_mod, "_gather_bound", lambda: chip)
    frames = datagen_returns.tpcds_returns(SF, seed)
    got, nbytes = run(q93, frames)
    s = obs.window_summary(rings, time.perf_counter())
    ref = q93.reference(frames)
    want = compare.head(ref, q93.ORDER, q93.ASCENDING, q93.LIMIT)
    assert compare.frame_gap(got, want, q93.IN_ORDER)["rows_wrong"] == 0
    assert compare.frame_gap(got.attrs["sums"], ref.attrs["sums"],
                             False)["rows_wrong"] == 0
    assert len(want) == min(len(ref), 100) and len(ref) > 100
    assert want.sumsales.isna().any() and want.ss_customer_sk.isna().sum() <= 1
    assert nbytes > 0
    assert "sort_exec" not in s["sort_rows"]
    if chip:
        assert s["join_lookup_rows"]["hash"] >= len(frames["store_sales"])
        assert s["join_hash_build"]["n"] == 2
        assert s["join_hash_build"]["fallbacks"] == 0
        assert "join_build" not in s["sort_rows"]
    else:
        assert "hash" not in s["join_lookup_rows"]
        assert s["join_hash_build"]["n"] == 0


def test_four_stages_through_the_bridge_with_a_sort_merge_join(q93, monkeypatch):
    """Eight tasks a query, stages 1 to 4, two tasks each; stage 3's plan
    holds the sort-merge join over two shuffle reads, both under a Sort,
    and ends in a hash-partitioning shuffle writer."""
    from auron_tpu.bridge import api
    from auron_tpu.proto import plan_pb2 as pb

    seen = []
    real = api.call_native

    def spy(blob):
        task = pb.TaskDefinition()
        task.ParseFromString(blob)
        seen.append(task)
        return real(blob)

    monkeypatch.setattr(api, "call_native", spy)
    run(q93, datagen_returns.tpcds_returns(0.01, SEEDS[2]))
    stages = [t.stage_id for t in seen]
    assert sorted(stages) == [1, 1, 2, 2, 3, 3, 4, 4]
    text = str(next(t for t in seen if t.stage_id == 3).plan)
    assert "sort_merge_join" in text and text.count("sort {") == 2
    assert text.count("ipc_reader") == 2
    assert text.startswith("shuffle_writer") and "kind: HASH" in text
