"""The comparison that decides ``correct`` is shown to fail.

- The control: the reference put in the program's place and computed in
  float32 has to come out not correct, and the float64 reference in the same
  place correct.
- The faults: a whole run is driven at a tiny size on the CPU (everything of
  ``run_cell``; only the look for a chip is skipped) with the timed path
  broken underneath, once for each fault a cell can have, and ``correct`` has
  to come out false. The cells hold no state that a step returns and, on one
  chip, no exchange between chips, so those two faults do not apply; the batch
  cell's file shuffle stands in for the exchange.
"""

import copy
import time

import pandas as pd
import pyarrow as pa
import pytest

from benchmark import control, harness

CELLS = ["batch_q3_sf8", "sql_streams4_sf1"]
SEED = 2147483659


def tiny(name: str, sf: float = 0.02) -> dict:
    cell = copy.deepcopy(harness.load_cell(name))
    cell["config_file"]["data"]["sf"] = sf
    if "batch_rows" in cell["config_file"]["sizes"]:
        cell["config_file"]["sizes"]["batch_rows"] = 1 << 14
    return cell


def run(cell: dict, seconds: float = 1.0) -> dict:
    import jax

    return harness.run_cell(cell, SEED, seconds, False, jax.devices()[:1],
                            time.perf_counter())


@pytest.mark.parametrize("name", CELLS)
def test_float32_control_is_not_correct(name):
    out = control.run_control(tiny(name, sf=0.2), SEED)
    assert out["correct"] is False
    gap = out["compared"]["float_gap"]
    assert gap["value"] > 3 * gap["limit"]


@pytest.mark.parametrize("name", CELLS)
def test_float64_reference_in_the_programs_place_is_correct(name, monkeypatch):
    from benchmark import compare

    monkeypatch.setattr(compare, "to_float32", lambda frames: frames)
    assert control.run_control(tiny(name, sf=0.2), SEED)["correct"] is True


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    out = run(tiny(name))
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 1
    assert list(out)[-1] == "compared"


# ---- batch cell: the path broken under bridge.api ---------------------------


def test_batch_answer_altered_where_it_is_produced(monkeypatch):
    from auron_tpu.bridge import api

    real = api.next_batch

    def altered(h):
        rb = real(h)
        if rb is None or "s" not in rb.schema.names or rb.num_rows == 0:
            return rb
        df = rb.to_pandas()
        df["s"] = df["s"] * (1 + 1e-6)      # every sum of this batch
        return pa.RecordBatch.from_pandas(df, schema=rb.schema,
                                          preserve_index=False)

    monkeypatch.setattr(api, "next_batch", altered)
    out = run(tiny("batch_q3_sf8"))
    assert out["correct"] is False
    assert out["compared"]["float_gap"]["value"] > 1e-9


def test_batch_half_of_the_rows_left_out(monkeypatch):
    from auron_tpu.bridge import api

    real = api.put_resource

    def half(rid, value, *a, **kw):
        if rid == "q3_fact":
            value = [value[0]] + [[] for _ in value[1:]]
        return real(rid, value, *a, **kw)

    monkeypatch.setattr(api, "put_resource", half)
    assert run(tiny("batch_q3_sf8"))["correct"] is False


def test_batch_shuffle_of_one_map_task_left_out(monkeypatch):
    from auron_tpu.exec.shuffle import reader

    real = reader.MultiMapBlockProvider
    monkeypatch.setattr(reader, "MultiMapBlockProvider",
                        lambda pairs: real(pairs[:1]))
    assert run(tiny("batch_q3_sf8"))["correct"] is False


# ---- SQL cell: the path broken under POST /sql ------------------------------


def _alter(monkeypatch, change, only_after_warmup: bool):
    from auron_tpu.serve.server import SqlServer

    real = SqlServer.execute_json

    def altered(self, body):
        rec = real(self, body)
        if not (only_after_warmup and body.get("tenant") == "warm"):
            change(rec)
        return rec

    monkeypatch.setattr(SqlServer, "execute_json", altered)


def test_sql_float_altered_where_it_is_produced(monkeypatch):
    def change(rec):
        row = rec["rows"][0]
        k = max(i for i, v in enumerate(row) if isinstance(v, float))
        row[k] = row[k] * (1 + 1e-6)

    _alter(monkeypatch, change, only_after_warmup=False)
    out = run(tiny("sql_streams4_sf1"))
    assert out["correct"] is False
    assert out["compared"]["float_gap"]["value"] > 1e-9
    assert out["compared"]["replays_diverged"]["value"] == 0


def test_sql_row_dropped_in_replays_only(monkeypatch):
    _alter(monkeypatch, lambda rec: rec["rows"].pop() if len(rec["rows"]) > 1
           else None, only_after_warmup=True)
    out = run(tiny("sql_streams4_sf1"))
    assert out["correct"] is False
    assert out["compared"]["rows_wrong"]["value"] > 0
    assert out["compared"]["replays_diverged"]["value"] > 0


def test_sql_half_of_the_fact_rows_left_out(monkeypatch):
    from auron_tpu.models import tpcds

    real = tpcds.to_batches

    def half(df, n_partitions, *a, **kw):
        if "ss_ext_sales_price" in df.columns:
            df = df.iloc[:len(df) // 2]
        return real(df, n_partitions, *a, **kw)

    monkeypatch.setattr(tpcds, "to_batches", half)
    assert run(tiny("sql_streams4_sf1"))["correct"] is False


def test_sql_refused_request_counts_as_failed_and_not_correct(monkeypatch):
    from auron_tpu.serve.server import QueryError

    def refuse(rec):
        raise QueryError("refused by the test")

    _alter(monkeypatch, refuse, only_after_warmup=True)
    out = run(tiny("sql_streams4_sf1"), seconds=0.5)
    assert out["failed"] == out["attempted"] > 0
    assert out["correct"] is False


def test_frame_gap_counts_rows_and_measures_floats():
    from benchmark.compare import frame_gap

    want = pd.DataFrame({"k": [1, 2, 3], "v": [10.0, 20.0, 30.0]})
    same = frame_gap(want.iloc[::-1].reset_index(drop=True), want, in_order=False)
    assert same == {"rows_wrong": 0, "float_gap": 0.0}
    got = pd.DataFrame({"k": [1, 2, 4], "v": [10.0, 20.0 * (1 + 1e-7), 30.0]})
    out = frame_gap(got, want, in_order=True)
    assert out["rows_wrong"] == 1
    assert out["float_gap"] == pytest.approx(1e-7, rel=1e-3)
    assert frame_gap(got.iloc[:2], want, in_order=True)["rows_wrong"] == 3
    assert frame_gap(got.drop(columns="v"), want, True)["rows_wrong"] == 3
