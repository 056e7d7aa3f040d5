"""The comparison that decides ``correct`` is shown to fail.

- The control: the references put in the program's place with money summed in
  float32 have to come out not correct where the sums outgrow float32's 24
  bits (the four-query mix at a quarter of the cell's rows), and the
  exact references in the same place correct. A traffic file may state that
  no lower precision moves its answers (``control_money: null``); its control
  then drops every other row of the table that ``control_halved`` names, and
  has to come out not correct all the same. Beside a DECIMAL or float column
  of an answer, or where money in bfloat16 moves an answer, the null is an
  error, in every driver.
- The faults: a whole run is driven at a tiny size on the CPU (everything of
  ``run_cell``; only the look for a chip is skipped) with the timed path
  broken underneath, once for each fault a cell can have, and ``correct`` has
  to come out false. The cells hold no state that a step returns and, on one
  chip, no exchange between chips, so those two faults do not apply; the file
  shuffle stands in for the exchange. The faults planted in what only the
  bridge calls (``put_resource``, the block provider, ``call_native``) run
  over every cell that enters through ``bridge.api`` (drivers ``batch_class``
  and ``batch_agg``), and over the fixture deployment ``fixture_dupjoin``
  (``tests/fixture_dupjoin/``: two tables, neither ``store_sales``, a
  duplicate-key sort-merge join with a residual, an answer of a string and a
  count, no precision control), which is no cell of ``BENCHMARK.json``.
"""

import copy
import decimal
import json
import numbers
import os
import time
from types import SimpleNamespace

import pandas as pd
import pyarrow as pa
import pytest

from benchmark import compare, control, harness

with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as _f:
    CELLS = [w["name"] for w in json.load(_f)["workloads"]]
#: the fixture deployment: found under a root of its own, no cell
FIXTURE = "fixture_dupjoin"
FIXTURE_ROOT = os.path.join(harness.HERE, "tests", FIXTURE)
#: where each entry's answer is produced: the batches of ``bridge.api``'s
#: ``next_batch`` (the bridge's tasks; the SQL server's collect task), or the
#: SQL server's JSON rows, where the mesh cell's text ``q65_sb``, which takes
#: no collect task, is answered too
ANSWER_AT = {"batch_class": "next_batch", "batch_agg": "next_batch",
             "sql_streams": "next_batch", "sql_mesh": "json_rows"}
SEED = 2147483659


def load(name: str) -> dict:
    if name == FIXTURE:
        with open(os.path.join(FIXTURE_ROOT, "spec.json")) as f:
            return harness.load_cell(name, json.load(f), root=FIXTURE_ROOT)
    return harness.load_cell(name)


#: the cells driven through ``bridge.api``, and the fixture: the faults
#: injected there reach these alone (a serving cell's own faults:
#: ``test_sql_streams.py``, ``test_q65_mesh_cell.py``)
BRIDGE_CELLS = [c for c in CELLS + [FIXTURE]
                if load(c)["config_file"]["driver"] in ("batch_class", "batch_agg")]


def tiny(name: str, sf: float = 0.05) -> dict:
    cell = copy.deepcopy(load(name))
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


@pytest.mark.parametrize("name", CELLS + [FIXTURE])
def test_exact_reference_in_the_programs_place_is_correct(name, monkeypatch):
    monkeypatch.setattr(compare, "money_in", lambda precision, frames, schemas: frames)
    monkeypatch.setattr(compare, "halved", lambda table, frames: frames)
    assert control.run_control(tiny(name, sf=0.2), SEED)["correct"] is True


@pytest.mark.parametrize("name", CELLS)
def test_a_null_control_beside_money_is_an_error(name):
    """Every driver's ``control`` refuses ``control_money: null`` where a
    reference's answer, or a part it hangs on its frame, holds money."""
    cell = tiny(name)
    cell["traffic_file"]["control_money"] = None
    cell["traffic_file"]["control_why"] = "said by the test"
    cell["traffic_file"]["control_halved"] = "store_sales"
    with pytest.raises(ValueError, match="holds money"):
        control.run_control(cell, SEED)


def test_a_cell_without_money_names_its_reason_and_its_control_fails(
        monkeypatch, capsys):
    """The fixture's control drops every other order line: at two seeds it
    comes out not correct, and ``control.py`` prints the reason and exits 0;
    the same control with no row dropped comes out correct, and
    ``control.py`` exits non-zero."""
    cell = tiny(FIXTURE)
    why = cell["traffic_file"]["control_why"]
    assert cell["traffic_file"]["control_money"] is None and why
    monkeypatch.setattr(harness, "load_cell", lambda workload: cell)
    monkeypatch.setattr("sys.argv", ["control.py", "--workload", FIXTURE,
                                     "--seeds", str(SEED), str(SEED + 1)])
    control.main()                          # exits 0: both not correct
    out, err = capsys.readouterr()
    read = [json.loads(line) for line in out.splitlines()]
    assert [r["control"] for r in read] == ["every other row of line dropped"] * 2
    assert all(r["correct"] is False and r["compared"]["rows_wrong"]["value"] > 0
               for r in read)
    assert f"no precision control for {FIXTURE}: {why}" in err
    monkeypatch.setattr(compare, "halved", lambda table, frames: frames)
    with pytest.raises(SystemExit, match="a control came out correct"):
        control.main()


def _counted(where):
    """A query whose answer is a count of the rows of ``t`` that ``where``
    keeps: integers alone."""
    return SimpleNamespace(reference=lambda frames, params: pd.DataFrame(
        {"n": [int(where(frames["t"]).sum())]}))


def test_a_null_control_where_money_moves_a_count_is_an_error():
    """A count filtered on a price holds no money, but bfloat16 money moves
    it (50.01 rounds to 50.00): the null is refused. A count filtered on an
    integer is not moved, and its control drops every other row."""
    frames = {"t": pd.DataFrame({"price": pd.array([5001, 4999, 7000], dtype="Int64"),
                                 "qty": pd.array([3, 7, 9], dtype="Int32")})}
    schemas = {"t": [["price", "decimal(7,2)", True], ["qty", "int32", True]]}
    traffic = {"control_money": None, "control_why": "w", "control_halved": "t"}
    by_price = {"q": _counted(lambda t: t.price > 5000)}
    with pytest.raises(ValueError, match="bfloat16 moves the answer of q"):
        compare.control_frames(traffic, by_price, {}, frames, schemas)
    by_qty = {"q": _counted(lambda t: t.qty > 5)}
    low = compare.control_frames(traffic, by_qty, {}, frames, schemas)
    assert low["t"].price.tolist() == [5001, 7000]
    with pytest.raises(ValueError, match="no reason"):
        compare.control_frames({**traffic, "control_why": ""}, by_qty, {},
                               frames, schemas)
    with pytest.raises(ValueError, match="no table 'u'"):
        compare.control_frames({**traffic, "control_halved": "u"}, by_qty, {},
                               frames, schemas)


def test_the_fixture_reaches_the_duplicate_key_joins_ragged_expansion(monkeypatch):
    """No ``store_sales`` frame is made, and the sort-merge join expands the
    pairs of keys that repeat on both sides (``expand_pairs``)."""
    from auron_tpu.exec.joins import driver

    real, calls = driver.expand_pairs, []
    monkeypatch.setattr(driver, "expand_pairs",
                        lambda *a, **kw: (calls.append(1), real(*a, **kw))[1])
    cell = tiny(FIXTURE)
    gen = harness.load_module("drivers", "batch_agg")._generator(cell["config_file"])
    assert set(gen.make(cell["config_file"], SEED)) == {"line", "stock"}
    out = run(cell)
    assert out["correct"] is True and calls


def test_money_is_found_in_the_answer_and_in_its_parts():
    d = decimal.Decimal
    plain = pd.DataFrame({"w": ["a", "b"], "cnt": [3, 4],
                          "k": pd.array([1, None], dtype="Int64")})
    assert compare.money_columns(plain) == []
    assert compare.money_columns(plain.assign(f=[0.5, 1.0])) == ["f"]
    assert compare.money_columns(plain.assign(s=[d("1.00"), None])) == ["s"]
    assert compare.money_columns(plain.assign(e=[None, None])) == ["e"]
    part = plain.copy()
    part.attrs["sums"] = pd.DataFrame({"k": [1], "sumsales": [d("0.01")]})
    assert compare.money_columns(part) == ["sums.sumsales"]


@pytest.mark.parametrize("name", CELLS + [FIXTURE])
def test_sound_run_is_correct(name):
    out = run(tiny(name))
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 1
    assert list(out)[-1] == "compared"


# ---- the timed path broken where the answer is produced ---------------------


def one_unit_on(df: pd.DataFrame, queries: list):
    """``df`` with one value moved by one unit of its type, in the row that
    its query's ORDER BY puts first, so that the answer keeps it: one unit of
    a DECIMAL's scale (a cent at DECIMAL(p,2)), else 1 of an integer (a
    count). None where ``df`` carries no query's ORDER BY columns (no answer)
    or no such value."""
    q = next((q for q in queries if set(q.ORDER) <= set(df.columns)), None)
    if q is None or len(df) == 0:
        return None
    first = (df.sort_values(list(q.ORDER), ascending=list(q.ASCENDING),
                            kind="stable") if q.ORDER else df)
    for kind in (decimal.Decimal, numbers.Integral):
        for c in df.columns:
            k = first[c].first_valid_index()
            v = None if k is None else df.at[k, c]
            if not isinstance(v, kind) or isinstance(v, bool):
                continue
            out = df.copy()
            out.at[k, c] = v + (decimal.Decimal(1).scaleb(v.as_tuple().exponent)
                                if kind is decimal.Decimal else 1)
            return out
    return None


@pytest.mark.parametrize("name", CELLS + [FIXTURE])
def test_answer_altered_where_it_is_produced(name, monkeypatch):
    cell = tiny(name, sf=0.2)
    queries = list(harness.load_module("drivers", "batch_class")
                   ._queries(cell["traffic_file"]).values())
    altered = []

    def nudged(df: pd.DataFrame):
        out = one_unit_on(df, queries)
        if out is not None:
            altered.append(1)
        return out

    if ANSWER_AT[cell["config_file"]["driver"]] == "next_batch":
        from auron_tpu.bridge import api

        real = api.next_batch

        def produced(h):
            rb = real(h)
            df = None if rb is None or rb.num_rows == 0 else nudged(rb.to_pandas())
            return rb if df is None else pa.RecordBatch.from_pandas(
                df, schema=rb.schema, preserve_index=False)

        monkeypatch.setattr(api, "next_batch", produced)
    else:
        from auron_tpu.serve import server

        real = server._json_rows
        monkeypatch.setattr(server, "_json_rows",
                            lambda df: real(d if (d := nudged(df)) is not None else df))
    out = run(cell)
    assert altered
    assert out["correct"] is False
    assert out["compared"]["rows_wrong"]["value"] > 0


@pytest.mark.parametrize("name", BRIDGE_CELLS)
def test_half_of_the_rows_left_out(name, monkeypatch):
    """Every other row of every batch of the fact table is deselected, in
    every map task's range."""
    import jax.numpy as jnp

    from auron_tpu.bridge import api

    real = api.put_resource

    def every_other_row(b):
        keep = jnp.arange(b.device.capacity) % 2 == 0
        return b.with_device(b.device._replace(sel=b.device.sel & keep))

    def half(rid, value, *a, **kw):
        if rid.endswith("_fact"):
            value = [[every_other_row(b) for b in part] for part in value]
        return real(rid, value, *a, **kw)

    monkeypatch.setattr(api, "put_resource", half)
    out = run(tiny(name, sf=0.2))
    assert out["correct"] is False
    assert out["compared"]["rows_wrong"]["value"] > 0


@pytest.mark.parametrize("name", BRIDGE_CELLS)
def test_shuffle_of_one_map_task_left_out(name, monkeypatch):
    from auron_tpu.exec.shuffle import reader

    real = reader.MultiMapBlockProvider
    monkeypatch.setattr(reader, "MultiMapBlockProvider",
                        lambda pairs: real(pairs[:1]))
    assert run(tiny(name, sf=0.2))["correct"] is False


@pytest.mark.parametrize("name", BRIDGE_CELLS)
def test_failing_query_counts_as_failed_and_not_correct(name, monkeypatch):
    """``call_native`` refused from the 17th call on: the warm-up's calls go
    through (4 to 16 of them), and at least one of the window's queries is
    refused."""
    from auron_tpu.bridge import api

    real, calls = api.call_native, []

    def refuse(task, *a, **kw):
        calls.append(1)
        if len(calls) > 16:
            raise RuntimeError("refused by the test")
        return real(task, *a, **kw)

    monkeypatch.setattr(api, "call_native", refuse)
    out = run(tiny(name), seconds=3.0)
    assert len(calls) > 16
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
