"""TPC-DS query 65 as ``benchmark/agg_plan.py`` plans it (four stages through
``bridge.api``: partial and final sums by (store, item), a DECIMAL(21,6)
average by store broadcast back, three BHJs after an aggregate) against the
plain reference of ``benchmark/queries/q65.py``, at a small size on the CPU,
on specification-typed tables from ``benchmark/datagen_store.py``."""

import collections
import decimal
import tempfile

import pytest

from benchmark import compare, datagen_store, harness

SF = 0.01
SEEDS = (2147483659, 7, 2147484001)
PARAMS = {"batch_rows": 1 << 13, "n_map": 2, "n_reduce": 2}


@pytest.fixture(scope="module")
def q65():
    return harness.load_module("queries", "q65")


def run(q65, frames: dict):
    resident = q65.ingest(frames, PARAMS)
    with tempfile.TemporaryDirectory(prefix="q65_") as wd:
        answer, nbytes = q65.run(resident, PARAMS, wd, harness.span)
    return answer, nbytes


def wrong_rows(q65, got, frames: dict) -> dict:
    ref = q65.reference(frames)
    want = compare.head(ref, q65.ORDER, q65.ASCENDING, q65.LIMIT)
    return {"answer": compare.frame_gap(got, want, q65.IN_ORDER)["rows_wrong"],
            "sb": compare.frame_gap(got.attrs["sb"], ref.attrs["sb"],
                                    False)["rows_wrong"],
            "rows": len(want), "stores": len(ref.attrs["sb"])}


#: the arms an accelerator takes where XLA:CPU takes the host's: device
#: sorts, fingerprint segmentation, the sorted-state probe, merge-path, the
#: dense table's device scatter, the device-clustered shuffle write
CHIP_ARMS = {"exec.host.sort": "off", "exec.agg.dense.host.scatter": "off",
             "exec.agg.incremental.fingerprint": "on",
             "exec.agg.incremental.probe": "on",
             "exec.agg.incremental.mergepath": "on"}


@pytest.mark.parametrize("seed, dense, arms", [
    (SEEDS[0], True, "cpu"), (SEEDS[0], False, "cpu"),
    (SEEDS[1], True, "cpu"), (SEEDS[1], False, "cpu"),
    (SEEDS[2], True, "cpu"), (SEEDS[2], False, "cpu"),
    (SEEDS[0], True, "chip"), (SEEDS[1], False, "chip"),
    (SEEDS[2], True, "chip_wide"), (SEEDS[0], False, "chip_wide"),
], ids=lambda v: {True: "dense", False: "no_dense"}.get(v, str(v)))
def test_plan_equals_reference_average_included(q65, seed, dense, arms,
                                                monkeypatch):
    """Every cell of the top 100 and of the 13 stores' averages (the NULL
    store's own group among them, which never joins), with the dense table
    taking the integer-keyed sums and with every fold on the sorting paths,
    on XLA:CPU's arms and on the arms the chip takes."""
    from auron_tpu.exec.agg_exec import HashAggExec

    if not dense:
        monkeypatch.setattr(HashAggExec, "_dense_eligible", lambda self: False)
    if arms != "cpu":
        # "chip_wide": a batch over hostsort.DEVICE_SORT_MAX_ROWS takes its
        # order from the host on the chip too, under the same fingerprint,
        # probe and merge-path arms
        for key, value in {**CHIP_ARMS, **({"exec.host.sort": "on"}
                                           if arms == "chip_wide" else {})}.items():
            monkeypatch.setenv("AURON_TPU_" + key.upper().replace(".", "_"), value)
    frames = datagen_store.tpcds_store(SF, seed)
    year = frames["store_sales"].ss_sold_date_sk.between(2450815, 2451179)
    assert frames["store_sales"][year].ss_store_sk.isna().any()
    got, nbytes = run(q65, frames)
    assert wrong_rows(q65, got, frames) == {"answer": 0, "sb": 0, "rows": 100,
                                            "stores": 13}
    assert got.attrs["sb"].ss_store_sk.isna().sum() == 1
    assert nbytes > 0


def _pinned_frames(seed: int) -> dict:
    """Two stores of 2,000 items, one sale a pair: store 1's revenues sum to
    2,000,001 cents (ave 10.000005, a tenth of it 1.0000005), store 2's to
    1,999,999 (ave 9.999995, a tenth 0.9999995); item 1 sold for 1.00 in both."""
    frames = datagen_store.tpcds_store(SF, seed)
    ss = frames["store_sales"].iloc[:4000].copy()
    ss["ss_sold_date_sk"] = 2450900                      # 1998-03-27
    ss["ss_store_sk"] = [1] * 2000 + [2] * 2000
    ss["ss_item_sk"] = list(range(1, 2001)) * 2
    ss["ss_sales_price"] = ([100] + [1000] * 1998 + [1901]
                            + [100] + [1000] * 1998 + [1899])
    return {**frames, "store_sales": ss.reset_index(drop=True)}


def test_threshold_that_differs_in_the_seventh_digit(q65):
    """revenue DECIMAL(17,2) <= 0.1 * ave DECIMAL(23,7), exactly: 1.00 passes
    against 1.0000005 and fails against 0.9999995; a threshold rounded to six
    places (1.000000) or a float compare would let the second through."""
    frames = _pinned_frames(SEEDS[0])
    got, _ = run(q65, frames)
    sb = got.attrs["sb"].sort_values("ss_store_sk").reset_index(drop=True)
    assert sb.ave.tolist() == [decimal.Decimal("10.000005"),
                               decimal.Decimal("9.999995")]
    assert len(got) == 1
    assert got.revenue.tolist() == [decimal.Decimal("1.00")]
    name = frames["store"].set_index("s_store_sk").s_store_name[1]
    assert got.s_store_name.tolist() == [name]
    assert wrong_rows(q65, got, frames) == {"answer": 0, "sb": 0, "rows": 1,
                                            "stores": 2}


def test_first_exchange_is_written_once_and_read_twice(q65, monkeypatch):
    """Spark's ReusedExchange: stage 2 and stage 4 each read every partition
    of every map task's output; the second exchange is read once."""
    from auron_tpu.exec.shuffle import reader

    reads = collections.Counter()
    real = reader.LocalFileBlockProvider._region

    def counted(self, partition):
        reads[(self.data_file.rsplit("/", 1)[-1], partition)] += 1
        return real(self, partition)

    monkeypatch.setattr(reader.LocalFileBlockProvider, "_region", counted)
    frames = datagen_store.tpcds_store(SF, SEEDS[1])
    got, _ = run(q65, frames)
    want = {(f"map{m}.data", p): 2 for m in range(2) for p in range(2)}
    want.update({(f"ave{m}.data", p): 1 for m in range(2) for p in range(2)})
    assert dict(reads) == want
    assert wrong_rows(q65, got, frames)["answer"] == 0


def test_aggregates_leave_their_folds_and_groups_in_the_rings(q65, monkeypatch):
    """Under the chip's arms: the integer-keyed sums fold through the dense
    table and sort nothing, the wide average folds on the sorting path, the
    groups the aggregates emit are what the reference counts (three times the
    pairs, the partial averages of both reduce tasks, the stores), and the
    host handles DECIMAL cells one by one only for the stores' averages."""
    import time

    from auron_tpu import obs

    for key, value in CHIP_ARMS.items():
        monkeypatch.setenv("AURON_TPU_" + key.upper().replace(".", "_"), value)
    frames = datagen_store.tpcds_store(SF, SEEDS[0])
    saved = obs.mode()
    obs.set_mode("recorder")
    try:
        t0 = time.perf_counter()
        got, _ = run(q65, frames)
        ws = obs.window_summary(t0, time.perf_counter())
    finally:
        obs.set_mode(saved)
    pairs, stores = len(q65.pair_revenue(frames)), len(got.attrs["sb"])
    year = frames["store_sales"].ss_sold_date_sk.between(2450815, 2451179)
    assert ws["complete"]
    assert ws["agg_groups"] == 3 * pairs + PARAMS["n_reduce"] * stores + stores
    assert set(ws["agg_folds"]) == {"dense", "sort"}
    # the dense arm's compaction boundary (PR 35): of the map side's four
    # batches the head of the date-ordered fact table holds the year and is
    # folded as it came, the three behind it are empty and fold nothing
    # (rows 0); each reduce task's final sum folds its one all-live batch
    assert ws["agg_dense_folds"] == {"seed": 1 + 2 * PARAMS["n_reduce"],
                                     "empty": 3}
    dense = ws["agg_folds"]["dense"]
    assert dense["n"] == 8 and dense["rows"] == 3 * PARAMS["batch_rows"]
    assert dense["live"] == int(year.sum()) + 2 * pairs
    assert ws["agg_folds"]["sort"]["n"] == 2 * PARAMS["n_reduce"]   # the averages
    assert ws["agg_reduces"]["sort"]["rows"] == ws["agg_sorted_rows"]
    assert ws["agg_sorted_rows"] == ws["agg_folds"]["sort"]["rows"]
    assert 0 < ws["wide_decimal_host_cells"] < 1000
    assert wrong_rows(q65, got, frames)["sb"] == 0


def test_scatter_built_index_answers_as_the_search(q65, monkeypatch):
    """Under the TPU's rules (``_gather_bound``), query 65's drains and takes
    build their compaction index by one scatter where the shapes say it pays:
    the answer, the stores' averages included, is the one the search gives
    everywhere, and the rings say which form each launch took."""
    import time

    import jax

    from auron_tpu import obs
    from auron_tpu.columnar import batch as batch_mod

    monkeypatch.setattr(batch_mod, "_gather_bound", lambda: True)
    frames = datagen_store.tpcds_store(SF, SEEDS[2])
    rule = batch_mod.compaction_index_form
    saved = obs.mode()
    obs.set_mode("recorder")
    forms = {}
    try:
        for name, form in (("search", lambda cap, out_cap: "search"), ("shape", rule)):
            monkeypatch.setattr(batch_mod, "compaction_index_form", form)
            jax.clear_caches()      # a program traced under the other form
            t0 = time.perf_counter()
            got, _ = run(q65, frames)
            forms[name] = got, obs.window_summary(t0, time.perf_counter())
    finally:
        obs.set_mode(saved)
        jax.clear_caches()
    (searched, ws_search), (shaped, ws_shape) = forms["search"], forms["shape"]
    assert ws_search["compaction_index"]["scatter"]["n"] == 0
    assert ws_shape["compaction_index"]["scatter"]["n"] > 0
    assert (ws_shape["compaction_index"]["scatter"]["n"]
            + ws_shape["compaction_index"]["search"]["n"]
            == ws_search["compaction_index"]["search"]["n"])
    assert shaped.equals(searched)
    by_store = lambda sb: sb.sort_values("ss_store_sk").reset_index(drop=True)
    assert by_store(shaped.attrs["sb"]).equals(by_store(searched.attrs["sb"]))
    assert wrong_rows(q65, shaped, frames) == {"answer": 0, "sb": 0, "rows": 100,
                                               "stores": 13}


def test_decimal128_writer_equals_the_cell_by_cell_loop():
    """``_decimal_from_unscaled`` writes the Decimal128 buffer itself; the
    loop it replaced (a ``decimal.Decimal`` a cell) is the reference."""
    import numpy as np
    import pyarrow as pa

    from auron_tpu import types as T
    from auron_tpu.columnar.batch import _decimal_from_unscaled, _decimal_unscaled

    rng = np.random.default_rng(7)
    vals = np.concatenate([
        rng.integers(-10**17, 10**17, 500), rng.integers(-999, 999, 500),
        np.array([0, 1, -1, 10**17 - 1, -(10**17) + 1])]).astype(np.int64)
    mask = rng.random(len(vals)) < 0.8
    for dtype in (T.decimal(17, 2), T.decimal(18, 6), T.decimal(18, 0)):
        q = decimal.Decimal(1).scaleb(-dtype.scale)
        want = pa.array(
            [decimal.Decimal(int(v)).scaleb(-dtype.scale).quantize(q) if m else None
             for v, m in zip(vals, mask)],
            type=pa.decimal128(dtype.precision, dtype.scale))
        got = _decimal_from_unscaled(vals, mask, dtype)
        got.validate(full=True)
        assert got.equals(want)
        back, fits = _decimal_unscaled(got, dtype.scale)
        assert fits.all() and (back == np.where(mask, vals, 0)).all()
    empty = _decimal_from_unscaled(vals[:0], mask[:0], T.decimal(17, 2))
    assert len(empty) == 0


@pytest.mark.parametrize("mode, backend, rows, want", [
    ("auto", "cpu", 128, True), ("auto", "tpu", None, False),
    ("auto", "tpu", 1 << 14, False), ("auto", "tpu", (1 << 14) + 1, True),
    ("auto", "tpu", 1 << 22, True), ("off", "tpu", 1 << 22, False),
    ("on", "tpu", 128, True),
])
def test_a_sort_too_wide_for_the_device_is_ordered_on_the_host(monkeypatch, mode,
                                                               backend, rows, want):
    """XLA:TPU compiles a sort of 32,768 rows for minutes (ops/hostsort.py):
    under ``auto`` an accelerator orders a wider batch on the host, and the
    shuffle writer clusters it there, by the one rule."""
    import jax

    from auron_tpu.exec.shuffle.writer import repartition_substrate
    from auron_tpu.ops import hostsort
    from auron_tpu.utils.config import Configuration

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    conf = Configuration({"exec.host.sort": mode})
    assert hostsort.use_host_sort(conf, rows=rows) is want
    assert repartition_substrate(conf, rows) == ("host" if want else "device")
