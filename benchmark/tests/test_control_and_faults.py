"""The comparison that decides ``correct`` is shown to fail.

- The control: the references put in the program's place with money summed in
  float32 have to come out not correct where the sums outgrow float32's 24
  bits (the four-query mix at a quarter of the cell's rows), and the
  exact references in the same place correct.
- The faults: a whole run is driven at a tiny size on the CPU (everything of
  ``run_cell``; only the look for a chip is skipped) with the timed path
  broken underneath, once for each fault a cell can have, and ``correct`` has
  to come out false. The cells hold no state that a step returns and, on one
  chip, no exchange between chips, so those two faults do not apply; the file
  shuffle stands in for the exchange. The faults planted in what only the
  bridge's driver calls (``put_resource``, the block provider, ``call_native``)
  run over the cells whose configuration's ``driver`` is ``batch_class``.
"""

import copy
import decimal
import json
import os
import time

import pandas as pd
import pyarrow as pa
import pytest

from benchmark import compare, control, harness

with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as _f:
    CELLS = [w["name"] for w in json.load(_f)["workloads"]]
#: the cells driven through ``bridge.api``: the faults injected there reach
#: these alone (a serving cell's own faults: ``test_sql_streams.py``)
BRIDGE_CELLS = [c for c in CELLS
                if harness.load_cell(c)["config_file"]["driver"] == "batch_class"]
SEED = 2147483659


def tiny(name: str, sf: float = 0.05) -> dict:
    cell = copy.deepcopy(harness.load_cell(name))
    cell["config_file"]["data"]["sf"] = sf
    cell["config_file"]["sizes"]["batch_rows"] = 1 << 14
    return cell


def run(cell: dict, seconds: float = 1.0) -> dict:
    import jax

    return harness.run_cell(cell, SEED, seconds, False, jax.devices()[:1],
                            time.perf_counter())


def test_float32_control_is_not_correct_where_sums_outgrow_24_bits():
    out = control.run_control(tiny("batch_mix4_sf8", sf=2.0), SEED)
    assert out["correct"] is False
    assert out["compared"]["rows_wrong"]["value"] > 0


def test_bfloat16_control_of_query_3_is_not_correct():
    out = control.run_control(tiny("batch_q3_sf8", sf=0.5), SEED)
    assert out["correct"] is False
    assert out["compared"]["rows_wrong"]["value"] > 0


@pytest.mark.parametrize("name", CELLS)
def test_exact_reference_in_the_programs_place_is_correct(name, monkeypatch):
    monkeypatch.setattr(compare, "money_in", lambda precision, frames, schemas: frames)
    assert control.run_control(tiny(name, sf=0.2), SEED)["correct"] is True


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    out = run(tiny(name))
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 1
    assert list(out)[-1] == "compared"


# ---- the timed path broken under bridge.api --------------------------------


@pytest.mark.parametrize("name", CELLS)     # the serving path collects through it too
def test_answer_altered_where_it_is_produced(name, monkeypatch):
    from auron_tpu.bridge import api

    real = api.next_batch
    sums = {"sum_agg", "sum_sales", "ext_price"}

    def altered(h):
        rb = real(h)
        col = sums & set(rb.schema.names) if rb is not None else None
        if not col or rb.num_rows == 0:
            return rb
        (c,) = col
        df = rb.to_pandas()
        k = df[c].first_valid_index()
        if k is None:
            return rb
        df.loc[k, c] = df.loc[k, c] + decimal.Decimal("0.01")   # one cent, one row
        return pa.RecordBatch.from_pandas(df, schema=rb.schema,
                                          preserve_index=False)

    monkeypatch.setattr(api, "next_batch", altered)
    out = run(tiny(name, sf=0.2))
    assert out["correct"] is False
    assert out["compared"]["rows_wrong"]["value"] > 0


@pytest.mark.parametrize("name", BRIDGE_CELLS)
def test_half_of_the_rows_left_out(name, monkeypatch):
    from auron_tpu.bridge import api

    real = api.put_resource

    def half(rid, value, *a, **kw):
        if rid.endswith("_fact"):
            value = [value[0]] + [[] for _ in value[1:]]
        return real(rid, value, *a, **kw)

    monkeypatch.setattr(api, "put_resource", half)
    assert run(tiny(name, sf=0.2))["correct"] is False


@pytest.mark.parametrize("name", BRIDGE_CELLS)
def test_shuffle_of_one_map_task_left_out(name, monkeypatch):
    from auron_tpu.exec.shuffle import reader

    real = reader.MultiMapBlockProvider
    monkeypatch.setattr(reader, "MultiMapBlockProvider",
                        lambda pairs: real(pairs[:1]))
    assert run(tiny(name, sf=0.2))["correct"] is False


@pytest.mark.parametrize("name", BRIDGE_CELLS)
def test_failing_query_counts_as_failed_and_not_correct(name, monkeypatch):
    from auron_tpu.bridge import api

    real, calls = api.call_native, []

    def refuse(task, *a, **kw):
        calls.append(1)
        if len(calls) > 40:             # after the warm-up's tasks
            raise RuntimeError("refused by the test")
        return real(task, *a, **kw)

    monkeypatch.setattr(api, "call_native", refuse)
    out = run(tiny(name), seconds=3.0)
    assert out["failed"] > 0 and out["correct"] is False


# ---- the comparison itself ---------------------------------------------------


def test_frame_gap_counts_rows_and_measures_floats():
    want = pd.DataFrame({"k": [1, 2, 3], "v": [10.0, 20.0, 30.0]})
    same = compare.frame_gap(want.iloc[::-1].reset_index(drop=True), want,
                             in_order=False)
    assert same == {"rows_wrong": 0, "float_gap": 0.0}
    got = pd.DataFrame({"k": [1, 2, 4], "v": [10.0, 20.0 * (1 + 1e-7), 30.0]})
    out = compare.frame_gap(got, want, in_order=True)
    assert out["rows_wrong"] == 1
    assert out["float_gap"] == pytest.approx(1e-7, rel=1e-3)
    assert compare.frame_gap(got.iloc[:2], want, in_order=True)["rows_wrong"] == 3
    assert compare.frame_gap(got.drop(columns="v"), want, True)["rows_wrong"] == 3


def test_frame_gap_holds_decimals_and_nulls_exactly():
    d = decimal.Decimal
    want = pd.DataFrame({"k": [1, None], "s": [d("10.10"), None]})
    assert compare.frame_gap(want.copy(), want, True)["rows_wrong"] == 0
    cent = pd.DataFrame({"k": [1, None], "s": [d("10.11"), None]})
    assert compare.frame_gap(cent, want, True)["rows_wrong"] == 1
    filled = pd.DataFrame({"k": [1, None], "s": [d("10.10"), d("0.00")]})
    assert compare.frame_gap(filled, want, True)["rows_wrong"] == 1


def test_head_orders_nulls_as_spark_and_refuses_a_tie_at_the_limit():
    d = decimal.Decimal
    df = pd.DataFrame({"y": [1999, 1998, 1998, 1998],
                       "s": [d("5"), None, d("7"), d("9")],
                       "b": [1, 2, None, 4]})
    out = compare.head(df, ("y", "s", "b"), (True, False, True), 3)
    assert out.s.tolist()[:2] == [d("9"), d("7")] and pd.isna(out.s[2])
    tie = pd.DataFrame({"s": [d("1"), d("1"), d("2")], "b": ["x", "y", "z"]})
    with pytest.raises(compare.TieError):
        compare.head(tie, ("s",), (True,), 1)
