"""A mesh of width N over N devices as a deployment: ``POST /sql`` ->
``SqlServer`` -> ``MeshQueryDriver`` on four of the CPU's virtual devices,
TPC-DS query 65's text and its derived table ``sb`` on specification-typed
tables (``benchmark/datagen_store.py``), held to the plain reference of
``benchmark/queries/q65.py``.

What is shown: the 4-wide answers equal the reference exactly and equal the
1-wide answers; the ``mesh`` and ``file`` transports agree; after registration
split ``i`` lies on device ``i mod N`` and partition ``p``'s view on device
``p``; after an exchange every later stage's inputs lie on one device each
(the case that fails where a received partition is ``a[p]`` of the exchanged
array: ROADMAP R-a3); a stage's four pumps overlap in time; the new spans'
fields and their sums in ``obs.window_summary``; the memory manager's ledger a
chip; integers beside a NULL on the wire.
"""

import decimal
import re
import threading
import time

import jax
import numpy as np
import pandas as pd
import pyarrow as pa
import pytest

from auron_tpu import obs
from auron_tpu.columnar.batch import Batch
from auron_tpu.memory.memmgr import MemManager
from auron_tpu.obs import core
from auron_tpu.parallel.mesh_driver import MeshQueryDriver
from auron_tpu.serve.server import SqlServer, _frame, _json_rows
from auron_tpu.sql.catalog import Catalog
from auron_tpu.utils.config import EXCHANGE_MODE, Configuration
from benchmark import compare, datagen_store, harness

#: the fact table has to outgrow date_dim's 73,049 rows, or the planner
#: replicates IT and deals the calendar out
SF = 0.03
SEED = 2147483659
N = 4
BATCH_ROWS = 1 << 12
TEXTS = ("q65", "q65_sb")
#: (mesh width, exchange transport) of each deployment that answers
DEPLOYMENTS = ((1, "file"), (N, "file"), (N, "mesh"))


@pytest.fixture(scope="module")
def driver():
    return harness.load_module("drivers", "sql_mesh")


@pytest.fixture(scope="module")
def world(driver):
    """The tables, each text's reference rows, and a server a deployment
    with the answers it gave to both texts (the ``POST /sql`` contract,
    ``execute_json``: cells as a client parses them from the body)."""
    frames = datagen_store.tpcds_store(SF, SEED)
    queries = {n: harness.load_module("queries", n) for n in TEXTS}
    texts = driver._texts({"queries": list(TEXTS)})
    want = driver.wants(queries, frames, {})
    catalog = Catalog.declared(
        {t: driver._schema_of(datagen_store, t) for t in frames},
        {t: len(df) for t, df in frames.items()})
    servers, answers = {}, {}
    for width, mode in DEPLOYMENTS:
        tables = {t: driver._splits(datagen_store, df, t, BATCH_ROWS)
                  for t, df in frames.items()}
        srv = SqlServer(catalog, tables, n_parts=width,
                        conf=Configuration().set(EXCHANGE_MODE, mode))
        servers[width, mode] = srv
        for name in TEXTS:
            answers[width, mode, name] = srv.execute_json({"sql": texts[name]})
    return {"frames": frames, "queries": queries, "texts": texts,
            "want": want, "servers": servers, "answers": answers}


# ---- the answers -------------------------------------------------------------


@pytest.mark.parametrize("width, mode", DEPLOYMENTS,
                         ids=[f"{w}wide-{m}" for w, m in DEPLOYMENTS])
@pytest.mark.parametrize("name", TEXTS)
def test_answer_equals_the_plain_reference_exactly(world, driver, name,
                                                   width, mode):
    want, got = world["want"][name], world["answers"][width, mode, name]
    assert len(want) == (100 if name == "q65" else 13)
    frame = driver.as_frame(got, want)
    gap = compare.frame_gap(frame, want, world["queries"][name].IN_ORDER)
    assert gap == {"rows_wrong": 0, "float_gap": 0.0}


@pytest.mark.parametrize("name", TEXTS)
def test_widths_and_transports_agree(world, name):
    """One answer whatever the mesh's width and the exchange's transport:
    query 65's rows in their order (the ORDER BY's tie-break is spelled in
    the text), the averages as a set."""
    rows = [world["answers"][w, m, name]["rows"] for w, m in DEPLOYMENTS]
    if name == "q65_sb":
        rows = [sorted(r, key=lambda row: (row[0] is not None, row[0] or 0))
                for r in rows]
    assert rows[0] == rows[1] == rows[2]
    assert all(world["answers"][w, m, name]["columns"]
               == world["answers"][1, "file", name]["columns"]
               for w, m in DEPLOYMENTS)


def test_money_answers_as_decimal_strings_and_keys_as_integers(world):
    q65 = world["answers"][N, "mesh", "q65"]
    revenue = q65["columns"].index("revenue")
    assert all(re.fullmatch(r"-?\d+\.\d\d", r[revenue]) for r in q65["rows"])
    sb = world["answers"][N, "mesh", "q65_sb"]
    assert sb["columns"] == ["ss_store_sk", "ave"]
    # DECIMAL(21,6): six places; the NULL store's group is a row of its own
    assert all(re.fullmatch(r"\d+\.\d{6}", r[1]) for r in sb["rows"])
    keys = [r[0] for r in sb["rows"]]
    assert keys.count(None) == 1
    assert sorted(k for k in keys if k is not None) == list(range(1, 13))
    assert all(type(k) is int for k in keys if k is not None)


def test_a_cent_on_one_pair_moves_its_stores_average(world, driver):
    """What the second text is in the mix for: every pair's sum is held."""
    frames = {**world["frames"]}
    ss = frames["store_sales"].copy()
    year = ss.ss_sold_date_sk.between(2450815, 2451179) & ss.ss_sales_price.notna() \
        & ss.ss_store_sk.notna()
    i = ss.index[year][0]
    ss.loc[i, "ss_sales_price"] += 1
    frames["store_sales"] = ss
    moved = world["queries"]["q65_sb"].reference(frames)
    gap = compare.frame_gap(moved, world["want"]["q65_sb"], False)
    assert gap["rows_wrong"] == 1


# ---- placement ----------------------------------------------------------------


def _device_ids(batch) -> set:
    return {d.id for a in jax.tree.leaves(batch.device) for d in a.devices()}


def test_registration_deals_the_splits_round_robin_to_the_chips(world):
    srv = world["servers"][N, "mesh"]
    devs = [d.id for d in srv.mesh.devices.flat]
    splits = srv.tables["store_sales"]
    assert len(splits) == -(-len(world["frames"]["store_sales"]) // BATCH_ROWS)
    assert len(splits) > 2 * N
    for i, b in enumerate(splits):
        assert _device_ids(b) == {devs[i % N]}
    assert sum(b.num_rows() for b in splits) == len(world["frames"]["store_sales"])
    # the partitioned view: partition p scans splits p, p + N, ... where
    # they lie; nothing is uploaded again
    view = srv._view("store_sales", N, False)
    for p, part in enumerate(view):
        assert [id(b) for b in part] == [id(b) for b in splits[p::N]]
        assert all(_device_ids(b) == {devs[p]} for b in part)
    # a replicated (build-side) view: the whole table on every chip
    for table in ("date_dim", "item", "store"):
        view = srv._view(table, N, True)
        assert len(view) == N
        for p, part in enumerate(view):
            assert sum(b.num_rows() for b in part) == len(world["frames"][table])
            assert all(_device_ids(b) == {devs[p]} for b in part)
        again = srv._view(table, N, True)                 # made once a chip
        assert all(a is b for a, b in zip(again, view))
    assert {t for t, _ in srv._replicas} <= {"date_dim", "item", "store"}


def test_another_width_copies_for_its_query_alone(world, driver):
    """A session two wide on the four-wide server: the splits that lie on
    chips 2 and 3 are copied to chips 0 and 1 for that query (the answer is
    the reference's), and the server pins none of it: only build sides'
    copies are kept, a chip each."""
    srv = world["servers"][N, "mesh"]
    devs = [d.id for d in srv.mesh.devices.flat]
    got = srv.execute_json({"sql": world["texts"]["q65_sb"],
                            "conf": {"sql.shuffle.partitions": 2}})
    want = world["want"]["q65_sb"]
    gap = compare.frame_gap(driver.as_frame(got, want), want, False)
    assert gap == {"rows_wrong": 0, "float_gap": 0.0}
    splits = srv.tables["store_sales"]
    view = srv._view("store_sales", 2, False)
    for p, part in enumerate(view):
        assert len(part) == len(splits[p::2])
        assert all(_device_ids(b) == {devs[p]} for b in part)
        # a split that lay there already is the resident one, the rest copies
        assert [b is r for b, r in zip(part, splits[p::2])] == [
            _device_ids(r) == {devs[p]} for r in splits[p::2]]
    assert not any(t == "store_sales" for t, _ in srv._replicas)
    assert {d.id for _, d in srv._replicas} <= set(devs)


def test_a_table_handed_over_as_a_generator_is_made_on_its_chips(driver):
    """Split ``i`` is asked for with device ``i mod N`` as JAX's default
    device, so a table larger than one chip never lies on one."""
    frames = datagen_store.tpcds_store(0.005, 7)
    drv = driver
    catalog = Catalog.declared(
        {"store_sales": drv._schema_of(datagen_store, "store_sales")},
        {"store_sales": len(frames["store_sales"])})
    asked = []

    def splits():
        for b in drv._splits(datagen_store, frames["store_sales"],
                             "store_sales", 2048):
            asked.append(jax.config.jax_default_device)
            yield b

    srv = SqlServer(catalog, {"store_sales": splits()}, n_parts=N)
    devs = list(srv.mesh.devices.flat)
    assert len(asked) == len(srv.tables["store_sales"]) >= N
    assert asked == [devs[i % N] for i in range(len(asked))]
    for i, b in enumerate(srv.tables["store_sales"]):
        assert _device_ids(b) == {devs[i % N].id}


def test_one_wide_mesh_keeps_the_handed_over_batches(driver):
    """On one device a view is a regrouping of the very batches handed
    over: the path of the one-chip serving cell uploads nothing twice."""
    frames = datagen_store.tpcds_store(0.005, 7)
    drv = driver
    catalog = Catalog.declared(
        {"store_sales": drv._schema_of(datagen_store, "store_sales")},
        {"store_sales": len(frames["store_sales"])})
    handed = list(drv._splits(datagen_store, frames["store_sales"],
                              "store_sales", 2048))
    srv = SqlServer(catalog, {"store_sales": handed}, n_parts=1)
    assert all(a is b for a, b in zip(srv.tables["store_sales"], handed))
    assert srv._view("store_sales", 1, False) == [handed]
    assert srv._view("store_sales", 1, True) == [handed]


# ---- the stages: spans, devices, overlap ---------------------------------------


@pytest.fixture()
def recorder():
    prev = obs.mode()
    obs.set_mode("recorder")
    yield
    obs.set_mode(prev)


def _run(world, mode: str, name: str = "q65"):
    """One query through a driver of its own; the window, the driver and the
    ring events that began in it."""
    srv = world["servers"][N, mode]
    lq, _, _ = srv.plan(world["texts"][name], srv.conf)
    driver = MeshQueryDriver(srv.mesh, conf=srv.conf)
    t0 = time.perf_counter()
    outs = driver.run(lq.distributed, srv._build_resources(lq))
    jax.block_until_ready([b.device for part in outs for b in part])
    t1 = time.perf_counter()
    lo, hi = int(t0 * 1e9), int(t1 * 1e9)
    events = [ev for _, evs in core.snapshot_events() for ev in evs
              if lo <= ev[0] < hi]
    return t0, t1, driver, outs, events


def _spans(events, layer: str, name: str) -> list:
    return [ev for ev in events if ev[8] == layer and ev[3] == name]


def test_every_stage_reads_one_device_a_partition_after_an_exchange(
        world, recorder):
    """Partition ``p``'s input lies on mesh device ``p`` in EVERY stage:
    the scans of the resident splits and the stages that read what an
    exchange handed out. With a received partition taken as ``a[p]`` of the
    exchanged array its planes lay on every device (or on one for all
    partitions) and this fails."""
    _, _, driver, outs, events = _run(world, "mesh")
    devs = [d.id for d in driver.mesh.devices.flat]
    stages = _spans(events, "pump", "stage")
    parts = _spans(events, "pump", "partition")
    # the pairs' exchange twice (sc, and under sb), the averages', and sb's
    # broadcast to every partition
    assert [s.mode for s in driver.stats] == ["mesh", "mesh", "mesh", "broadcast"]
    assert len(stages) == len(driver.stats) + 1        # map sides + the last
    assert len(parts) == N * len(stages)
    for st in stages:
        assert st[7] == {"parts": N, "devices": N}
        mine = [p for p in parts if p[6] == st[5]]      # parent is the stage
        assert sorted(p[7]["partition"] for p in mine) == list(range(N))
        for p in mine:
            assert p[7]["device"] == devs[p[7]["partition"]]
    # what the exchange handed out, and what the last stage gave back
    for st in driver.stats:
        assert st.n_devices == N
    for p, part in enumerate(outs):
        assert all(_device_ids(b) == {devs[p]} for b in part)


def test_exchange_spans_carry_the_devices_of_their_operands(world, recorder):
    _, _, driver, _, events = _run(world, "mesh")
    writes = _spans(events, "exchange", "write")
    reads = _spans(events, "exchange", "read")
    assert len(writes) == len(reads) == len(driver.stats) == 4
    for w, st in zip(sorted(writes), driver.stats):
        assert w[7]["mode"] == st.mode and w[7]["devices"] == N
        assert w[7]["rows"] == int(st.rows.sum()) > 0
        assert w[7]["bytes"] > 0
    assert all(r[7] == {"devices": N} for r in reads)
    # the broadcast hands every partition the 13 averages
    assert driver.stats[-1].rows.sum(axis=0).tolist() == [13] * N
    # the pairs' exchange carries a chip's groups to their owners: every
    # chip sends to every chip
    big = max(driver.stats, key=lambda s: int(s.rows.sum()))
    assert big.rows.shape == (N, N) and (big.rows > 0).all()


def test_file_transport_on_four_devices_reads_back_on_the_partitions_chip(
        world, recorder):
    _, _, driver, outs, events = _run(world, "file")
    devs = [d.id for d in driver.mesh.devices.flat]
    assert [s.mode for s in driver.stats] == ["file", "file", "file", "broadcast"]
    writes = [w for w in _spans(events, "exchange", "write")
              if isinstance(w[7], dict) and "devices" in w[7]]
    assert [w[7]["mode"] for w in sorted(writes)] == [s.mode for s in driver.stats]
    # a map stage that AQE coalesced to fewer tasks writes from fewer chips
    assert all(1 <= w[7]["devices"] <= N for w in writes)
    assert sorted(writes)[0][7]["devices"] == N         # the scan's stage
    # a stage that reads blocks from disk has no resident input: its
    # partition's device is that of what it made, its own chip's
    for p in _spans(events, "pump", "partition"):
        assert p[7]["device"] == devs[p[7]["partition"]]
    for p, part in enumerate(outs):
        assert all(_device_ids(b) == {devs[p]} for b in part)


def test_a_stages_four_pumps_overlap_in_time(world, recorder):
    """The first partition on the driver's thread, a task thread each for
    the others, started together: in the stage that does the most work (the
    scan of the fact table) the four partitions' spans are all open at one
    moment, each on a thread of its own."""
    _, _, _, _, events = _run(world, "mesh")
    stages = _spans(events, "pump", "stage")
    longest = max(stages, key=lambda ev: ev[1])
    mine = [p for p in _spans(events, "pump", "partition")
            if p[6] == longest[5]]
    assert len(mine) == N
    assert max(p[0] for p in mine) < min(p[0] + p[1] for p in mine)
    rings = {id(evs) for _, evs in core.snapshot_events()
             for ev in evs if ev in mine}
    assert len(rings) == N                              # four threads
    first = min(mine, key=lambda p: p[7]["partition"])
    assert any(longest in evs and first in evs          # the driver's own
               for _, evs in core.snapshot_events())
    # and inside the stage's own span
    assert all(longest[0] <= p[0] and p[0] + p[1] <= longest[0] + longest[1]
               for p in mine)


def test_a_stage_of_one_partition_is_pumped_on_the_drivers_thread(
        world, recorder):
    """The same lines at width one: the first partition is the only one, so
    no thread is started and nothing is handed over (a hand-over and back
    costs two waits for the interpreter's lock, what the one-wide serving
    cell paid with four queries in flight): same spans, one thread."""
    srv = world["servers"][1, "file"]
    lq, _, _ = srv.plan(world["texts"]["q65_sb"], srv.conf)
    t0 = time.perf_counter()
    MeshQueryDriver(srv.mesh, conf=srv.conf).run(
        lq.distributed, srv._build_resources(lq))
    lo, hi = int(t0 * 1e9), int(time.perf_counter() * 1e9)
    rings = [[ev for ev in evs if lo <= ev[0] < hi and ev[8] == "pump"
              and ev[3] in ("stage", "partition")]
             for _, evs in core.snapshot_events()]
    rings = [r for r in rings if r]
    assert len(rings) == 1                              # the caller's own
    stages = _spans(rings[0], "pump", "stage")
    parts = _spans(rings[0], "pump", "partition")
    assert len(stages) == len(parts) == 3
    assert all(st[7] == {"parts": 1, "devices": 1} for st in stages)
    assert sorted(p[6] for p in parts) == sorted(st[5] for st in stages)


def test_window_summary_sums_the_new_spans(world, recorder):
    t0, t1, driver, _, events = _run(world, "mesh")
    s = obs.window_summary(t0, t1)
    writes = _spans(events, "exchange", "write")
    assert s["exchange_bytes"] == {
        m: sum(w[7]["bytes"] for w in writes if w[7]["mode"] == m)
        for m in ("mesh", "broadcast")}
    assert s["exchange_bytes"]["broadcast"] < s["exchange_bytes"]["mesh"]
    assert s["exchange_devices_min"] == {"write": N, "read": N}
    assert s["stage_devices_min"] == N
    pumps = s["partition_pumps"]
    assert pumps["n"] == N * (len(driver.stats) + 1) and pumps["width"] == N
    assert 0 < pumps["open_s"] <= t1 - t0
    assert pumps["open_s"] <= pumps["thread_s"] <= N * pumps["open_s"] + 1e-6
    assert s["spans"]["pump:partition"]["n"] == pumps["n"]
    assert s["spans"]["pump:stage"]["n"] == len(driver.stats) + 1
    # the benchmark's readers of these sums
    facts = {"records": [{"ok": True, "t0": t0, "t1": t1}]}
    read = {n: harness.load_module("metrics", n).read(facts)
            for n in ("mesh_exchange_bytes_per_query", "stage_devices_min",
                      "partition_overlap_share")}
    assert read["mesh_exchange_bytes_per_query"] == s["exchange_bytes"]["mesh"]
    assert read["stage_devices_min"] == N
    assert read["partition_overlap_share"] == pytest.approx(
        pumps["thread_s"] / (N * pumps["open_s"]))
    assert 1 / N <= read["partition_overlap_share"] <= 1.0 + 1e-9


def test_a_partitions_error_is_raised_on_the_drivers_thread(world):
    srv = world["servers"][N, "file"]
    lq, _, _ = srv.plan(world["texts"]["q65_sb"], srv.conf)
    resources = srv._build_resources(lq)
    rid = next(u.rid for u in lq.tables if u.table == "store_sales")
    resources[rid] = [part if p != 2 else None
                      for p, part in enumerate(resources[rid])]
    before = threading.active_count()
    with pytest.raises(TypeError):
        MeshQueryDriver(srv.mesh, conf=srv.conf).run(lq.distributed, resources)
    assert threading.active_count() <= before           # every pump joined


# ---- beside the driver ------------------------------------------------------------


class _Consumer:
    def __init__(self, name: str, used: int):
        self.name, self.used, self.spills = name, used, 0

    def mem_used(self) -> int:
        return self.used

    def spill(self) -> int:
        freed, self.used = self.used, 0
        self.spills += 1
        return freed


def test_memory_manager_keeps_a_ledger_a_chip():
    """A consumer belongs to the chip its owner's work lands on; a
    partition that fills its chip spills what lies there, never a sibling's
    state on a chip with room, and the budget is one chip's."""
    d0, d1 = jax.devices()[:2]
    mm = MemManager(budget_bytes=100 << 20)
    budget = mm.budget
    with jax.default_device(d0):
        a, b = _Consumer("a", budget // 2), _Consumer("b", budget // 4)
        mm.register(a)
        mm.register(b)
    with jax.default_device(d1):
        c = _Consumer("c", budget * 3 // 4)
        mm.register(c)
    assert mm.total_used(a) == budget * 3 // 4 and mm.total_used(c) == c.used
    assert mm.total_used() == budget * 3 // 4          # the fullest chip's
    snap = {x["name"]: x["device"] for x in mm.mem_snapshot()["consumers"]}
    assert snap == {"a": d0.id, "b": d0.id, "c": d1.id}
    # chip 0 is asked for more than it has left: its largest other consumer
    # goes, chip 1's larger one stays
    mm.acquire(b, budget // 2)
    assert (a.spills, b.spills, c.spills) == (1, 0, 0)
    assert mm.mem_used_percent(c) == pytest.approx(0.75)
    # both chips together hold more than one budget, and nobody waits
    a.used = budget * 3 // 4
    mm.update_mem_used(c, 0, c.used)
    assert c.spills == 0
    for x in (a, b, c):
        mm.unregister(x)
    assert mm.total_used() == 0


def test_integers_beside_a_null_stay_integers_on_the_wire():
    big = 2**53 + 1
    rb = pa.record_batch({
        "k": pa.array([7, None, big], type=pa.int64()),
        "n": pa.array([1, 2, 3], type=pa.int32()),
        "m": pa.array([decimal.Decimal("1.50"), None, decimal.Decimal("0.05")],
                      type=pa.decimal128(7, 2))})
    rows = _json_rows(_frame(rb))
    assert rows == [[7, 1, "1.50"], [None, 2, None], [big, 3, "0.05"]]
    assert all(type(r[0]) is int for r in rows if r[0] is not None)


def test_on_device_moves_only_what_lies_elsewhere():
    d0, d1 = jax.devices()[:2]
    b = Batch.from_pandas(pd.DataFrame({"x": np.arange(5, dtype=np.int64)}))
    here = next(iter(b.device.sel.devices()))
    assert b.on_device(here) is b
    other = d1 if here == d0 else d0
    moved = b.on_device(other)
    assert moved is not b and _device_ids(moved) == {other.id}
    assert moved.on_device(other) is moved
    assert moved.to_pandas().x.tolist() == [0, 1, 2, 3, 4]


# ---- the host allocator's freed pages ------------------------------------------


def test_freed_heap_is_released_after_the_hand_over_and_after_a_plan_miss(
        world, monkeypatch, recorder):
    """glibc gives freed heap back lazily, in whatever thread frees next and
    under the interpreter's lock (1.1 GB in the middle of the 23rd query on
    the four-chip host: PERF.md section 6, PR 36). The server does it itself
    at the moments that are slow anyway: once its tables are in, and after a
    query that missed the plan cache; a hit releases nothing."""
    from auron_tpu.memory import hostheap

    assert hostheap.release_freed_heap() >= 0           # callable, and again
    assert hostheap.release_freed_heap() >= 0
    calls = []
    monkeypatch.setattr(hostheap, "release_freed_heap",
                        lambda: calls.append(1) or 4096)
    frames = world["frames"]
    drv = harness.load_module("drivers", "sql_mesh")
    catalog = Catalog.declared(
        {t: drv._schema_of(datagen_store, t) for t in frames},
        {t: len(df) for t, df in frames.items()})
    srv = SqlServer(catalog, {t: drv._splits(datagen_store, df, t, BATCH_ROWS)
                              for t, df in frames.items()}, n_parts=N)
    assert len(calls) == 1                              # the hand-over
    t0 = time.perf_counter()
    first = srv.execute_json({"sql": world["texts"]["q65_sb"]})
    assert first["cache_hit"] is False and len(calls) == 2
    again = srv.execute_json({"sql": world["texts"]["q65_sb"]})
    assert again["cache_hit"] is True and len(calls) == 2
    lo, hi = int(t0 * 1e9), int(time.perf_counter() * 1e9)
    spans = [ev for _, evs in core.snapshot_events() for ev in evs
             if lo <= ev[0] < hi and ev[8] == "serve" and ev[3] == "release"]
    assert [ev[7] for ev in spans] == [{"bytes": 4096}]
