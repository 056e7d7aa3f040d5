"""Shuffle write/read round-trip tests."""

import numpy as np
import pandas as pd
import pyarrow as pa
import pytest

from auron_tpu import types as T
from auron_tpu.columnar import Batch
from auron_tpu.exec.base import ExecutionContext
from auron_tpu.exec.basic import MemoryScanExec
from auron_tpu.exec.shuffle import (
    HashPartitioning,
    IpcReaderExec,
    RangePartitioning,
    RoundRobinPartitioning,
    ShuffleWriterExec,
    SinglePartitioning,
)
from auron_tpu.exec.shuffle.partitioning import make_range_bounds
from auron_tpu.exec.shuffle.reader import LocalFileBlockProvider, MultiMapBlockProvider
from auron_tpu.exprs.ir import col
from auron_tpu.ops.sortkeys import SortSpec


def _write(tmp_path, batches, partitioning, map_id=0):
    scan = MemoryScanExec.single(batches)
    data = str(tmp_path / f"map{map_id}.data")
    index = str(tmp_path / f"map{map_id}.index")
    w = ShuffleWriterExec(scan, partitioning, data, index)
    ctx = ExecutionContext(partition_id=map_id)
    assert list(w.execute(0, ctx)) == []
    return data, index


def _read_all(schema, provider, n_partitions):
    out = {}
    for p in range(n_partitions):
        r = IpcReaderExec(schema, "blocks")
        ctx = ExecutionContext()
        ctx.resources["blocks"] = provider
        parts = [b.to_pandas() for b in r.execute(p, ctx)]
        out[p] = pd.concat(parts).reset_index(drop=True) if parts else pd.DataFrame()
    return out


def test_hash_partitioning_roundtrip(tmp_path):
    df = pd.DataFrame({"k": np.arange(1000) % 37, "v": np.arange(1000.0)})
    b = Batch.from_arrow(pa.RecordBatch.from_pandas(df, preserve_index=False))
    part = HashPartitioning([col(0)], 4)
    data, index = _write(tmp_path, [b], part)
    out = _read_all(b.schema, LocalFileBlockProvider(data, index), 4)
    # all rows preserved
    total = pd.concat(out.values())
    assert len(total) == 1000
    assert sorted(total["v"].tolist()) == sorted(df["v"].tolist())
    # co-location: every key appears in exactly one partition
    seen = {}
    for p, d in out.items():
        for k in set(d["k"].tolist()):
            assert k not in seen, f"key {k} in partitions {seen[k]} and {p}"
            seen[k] = p
    # bit-exactness: partition of k must equal pmod(murmur3(k))
    from auron_tpu.ops.hash_dispatch import hash_batch
    from auron_tpu.ops.hashing import pmod

    kb = Batch.from_pydict({"k": list(seen.keys())},
                           schema=T.Schema.of(T.Field("k", T.INT64)))
    expected_pids = np.asarray(pmod(hash_batch(kb, [0], "murmur3"), 4))[: len(seen)]
    for (k, p), ep in zip(seen.items(), expected_pids):
        assert p == ep


def test_round_robin_and_single(tmp_path):
    df = pd.DataFrame({"x": np.arange(10)})
    b = Batch.from_arrow(pa.RecordBatch.from_pandas(df, preserve_index=False))
    data, index = _write(tmp_path, [b], RoundRobinPartitioning(3))
    out = _read_all(b.schema, LocalFileBlockProvider(data, index), 3)
    sizes = sorted(len(d) for d in out.values())
    assert sum(sizes) == 10 and max(sizes) - min(sizes) <= 1
    data2, index2 = _write(tmp_path, [b], SinglePartitioning(), map_id=1)
    out2 = _read_all(b.schema, LocalFileBlockProvider(data2, index2), 1)
    assert len(out2[0]) == 10


def test_range_partitioning(tmp_path):
    rng = np.random.default_rng(5)
    df = pd.DataFrame({"x": rng.integers(0, 1000, 500)})
    b = Batch.from_arrow(pa.RecordBatch.from_pandas(df, preserve_index=False))
    specs = [SortSpec()]
    bounds = make_range_bounds(b, [col(0)], specs, 4)
    part = RangePartitioning([col(0)], specs, 4, bounds)
    data, index = _write(tmp_path, [b], part)
    out = _read_all(b.schema, LocalFileBlockProvider(data, index), 4)
    total = pd.concat(out.values())
    assert len(total) == 500
    # ranges are disjoint and ordered
    for p in range(3):
        if len(out[p]) and len(out[p + 1]):
            assert out[p]["x"].max() <= out[p + 1]["x"].min()


def test_multi_map_exchange_with_strings(tmp_path):
    dfs = [
        pd.DataFrame({"k": ["a", "b", "c", "a"], "v": [1, 2, 3, 4]}),
        pd.DataFrame({"k": ["b", "c", "d"], "v": [5, 6, 7]}),
    ]
    pairs = []
    part = HashPartitioning([col(0)], 3)
    schema = None
    for mid, df in enumerate(dfs):
        b = Batch.from_arrow(pa.RecordBatch.from_pandas(df, preserve_index=False))
        schema = b.schema
        pairs.append(_write(tmp_path, [b], part, map_id=mid))
    out = _read_all(schema, MultiMapBlockProvider(pairs), 3)
    total = pd.concat(out.values())
    assert len(total) == 7
    assert sorted(total["v"].tolist()) == [1, 2, 3, 4, 5, 6, 7]
    # same key from different maps lands in the same partition
    where = {}
    for p, d in out.items():
        if len(d) == 0:
            continue
        for k in set(d["k"]):
            where.setdefault(k, set()).add(p)
    assert all(len(v) == 1 for v in where.values())


def test_empty_partition_regions(tmp_path):
    df = pd.DataFrame({"k": [5, 5, 5], "v": [1.0, 2.0, 3.0]})
    b = Batch.from_arrow(pa.RecordBatch.from_pandas(df, preserve_index=False))
    data, index = _write(tmp_path, [b], HashPartitioning([col(0)], 8))
    out = _read_all(b.schema, LocalFileBlockProvider(data, index), 8)
    nonempty = [p for p, d in out.items() if len(d)]
    assert len(nonempty) == 1
    assert len(out[nonempty[0]]) == 3


def test_rss_push_writer():
    """RSS-style push shuffle: blocks pushed per partition to a registered
    writer callable; reading them back reproduces the dataset."""
    from auron_tpu.exec.shuffle.format import decode_blocks
    from auron_tpu.exec.shuffle.writer import RssShuffleWriterExec

    df = pd.DataFrame({"k": np.arange(200) % 7, "v": np.arange(200.0)})
    b = Batch.from_arrow(pa.RecordBatch.from_pandas(df, preserve_index=False))
    scan = MemoryScanExec.single([b])

    pushed: dict[int, list[bytes]] = {}
    flushed = []

    class FakeRssClient:
        def write(self, pid, blk):
            pushed.setdefault(pid, []).append(blk)

        def flush(self):
            flushed.append(True)

    w = RssShuffleWriterExec(scan, HashPartitioning([col(0)], 5), "rss")
    ctx = ExecutionContext(resources={"rss": FakeRssClient()})
    assert list(w.execute(0, ctx)) == []
    assert flushed == [True]
    rows = 0
    for pid, blocks in pushed.items():
        for blk in blocks:
            for rb in decode_blocks(blk):
                rows += rb.num_rows
                ks = set(rb.column("k").to_pylist())
                from auron_tpu.ops.hash_dispatch import hash_batch
                from auron_tpu.ops.hashing import pmod
                kb = Batch.from_pydict({"k": sorted(ks)},
                                       schema=T.Schema.of(T.Field("k", T.INT64)))
                pids = np.asarray(pmod(hash_batch(kb, [0], "murmur3"), 5))[: len(ks)]
                assert (pids == pid).all()
    assert rows == 200


def test_corrupted_file_tolerance(tmp_path):
    import pyarrow.parquet as pq

    from auron_tpu.exec.scan import ParquetScanExec
    from auron_tpu.utils.config import Configuration, IGNORE_CORRUPTED_FILES

    good = str(tmp_path / "good.parquet")
    bad = str(tmp_path / "bad.parquet")
    pq.write_table(pa.table({"x": [1, 2, 3]}), good)
    with open(bad, "wb") as f:
        f.write(b"not a parquet file")
    schema = T.Schema.of(T.Field("x", T.INT64))
    scan = ParquetScanExec(schema, [bad, good])
    # default: corrupted file raises
    with pytest.raises(Exception):
        scan.collect()
    # tolerant mode: skipped, good file still read
    ctx = ExecutionContext(conf=Configuration().set(IGNORE_CORRUPTED_FILES, True))
    out = [b.to_pydict()["x"] for b in scan.execute(0, ctx)]
    assert out == [[1, 2, 3]]
    assert ctx.metrics.total("corrupted_files_skipped") == 1


# ---------------------------------------------------------------------------
# RSS service/client analog (thirdparty/auron-celeborn / auron-uniffle)
# ---------------------------------------------------------------------------


def test_rss_end_to_end_matches_file_shuffle(tmp_path):
    import pandas as pd

    from auron_tpu.bridge import api
    from auron_tpu.exec.shuffle.rss import (
        LocalRssService, RssBlockProvider, RssPartitionWriterClient,
    )
    from auron_tpu.plan import builders as B
    from auron_tpu.exprs.ir import col

    rng = np.random.default_rng(3)
    df = pd.DataFrame({"k": rng.integers(0, 50, 3000).astype(np.int64),
                       "v": rng.integers(0, 100, 3000).astype(np.int64)})
    schema = T.Schema.of(T.Field("k", T.INT64), T.Field("v", T.INT64))
    n_map, n_reduce = 3, 4
    per = 1000
    parts = [[Batch.from_pydict(
        {"k": df.k[p * per:(p + 1) * per].tolist(),
         "v": df.v[p * per:(p + 1) * per].tolist()}, schema=schema)]
        for p in range(n_map)]

    svc = LocalRssService(num_replicas=2)
    api.put_resource("rss_src", parts)
    try:
        part = B.hash_partitioning([col(0)], n_reduce)
        for m in range(n_map):
            api.put_resource("rss_w", RssPartitionWriterClient(svc, "shuf1", m))
            w = B.rss_shuffle_writer(
                B.memory_scan(schema, "rss_src"), part, "rss_w"
            )
            h = api.call_native(B.task(w, partition_id=m).SerializeToString())
            while api.next_batch(h) is not None:
                pass
            api.finalize_native(h)

        # reduce through the normal IPC reader over the RSS fetch path
        api.put_resource("rss_blocks", RssBlockProvider(svc, "shuf1"))
        got_rows = []
        for p in range(n_reduce):
            h = api.call_native(
                B.task(B.ipc_reader(schema, "rss_blocks"),
                       partition_id=p).SerializeToString())
            while (rb := api.next_batch(h)) is not None:
                got_rows += rb.to_pylist()
            api.finalize_native(h)
        got = sorted((r["k"], r["v"]) for r in got_rows)
        assert got == sorted(zip(df.k.tolist(), df.v.tolist()))
        # replica 1 serves the same data (replication fan-out)
        rep1 = RssBlockProvider(svc, "shuf1", replica=1)
        assert sum(rb.num_rows for p in range(n_reduce) for rb in rep1(p)) == 3000
    finally:
        for k in ("rss_src", "rss_w", "rss_blocks"):
            api.remove_resource(k)


def test_rss_commit_and_retry_semantics():
    from auron_tpu.exec.shuffle.format import encode_block
    from auron_tpu.exec.shuffle.rss import LocalRssService, RssPartitionWriterClient

    svc = LocalRssService()
    blk = encode_block(pa.table({"x": pa.array([1, 2, 3], pa.int64())}))

    w = RssPartitionWriterClient(svc, "s", map_id=0)
    w.write(0, blk)
    assert svc.fetch("s", 0) == []  # uncommitted: invisible to readers

    # task retry: a fresh writer for the same map drops stale pushes
    w2 = RssPartitionWriterClient(svc, "s", map_id=0)
    w2.write(0, blk)
    w2.flush()
    assert len(svc.fetch("s", 0)) == 1  # exactly one committed copy


def test_rss_speculative_attempt_cannot_destroy_committed():
    from auron_tpu.exec.shuffle.format import encode_block
    from auron_tpu.exec.shuffle.rss import LocalRssService, RssPartitionWriterClient

    svc = LocalRssService()
    blk = encode_block(pa.table({"x": pa.array([1], pa.int64())}))
    w = RssPartitionWriterClient(svc, "s2", map_id=0)
    w.write(0, blk)
    w.flush()
    assert len(svc.fetch("s2", 0)) == 1

    # speculative duplicate attempt: pushes + commits, but first wins
    spec = RssPartitionWriterClient(svc, "s2", map_id=0)
    assert len(svc.fetch("s2", 0)) == 1  # construction didn't wipe anything
    spec.write(0, blk)
    spec.write(0, blk)
    spec.flush()
    assert len(svc.fetch("s2", 0)) == 1  # still exactly one committed copy


def test_align_dict_batches_mixed_schema():
    """Dictionary-preserving and materialized blocks for the same column
    must merge (the preserve decision is per-batch dict size, so one
    stream can produce both)."""
    import pyarrow as pa

    from auron_tpu.exec.shuffle.format import align_dict_batches

    d = pa.RecordBatch.from_arrays(
        [pa.array(["a", "b", "a"]).dictionary_encode()], names=["s"])
    m = pa.RecordBatch.from_arrays([pa.array(["c", "a"])], names=["s"])
    tbl = pa.Table.from_batches(align_dict_batches([d, m]))
    assert tbl.column("s").to_pylist() == ["a", "b", "a", "c", "a"]


def test_cluster_rows_device_host_bit_identity():
    """ONE clustering policy (writer.cluster_rows / cluster_rows_host):
    the device lax.sort path and the host numpy-argsort fallback produce
    the same per-partition counts AND the same row order (stable sort by
    pid, dead rows last) — the fused repartition can never diverge from
    the host fallback."""
    import jax
    import jax.numpy as jnp

    from auron_tpu.exec.shuffle.writer import (
        _cluster_by_pid, cluster_rows_host,
    )

    rng = np.random.default_rng(23)
    for trial in range(5):
        cap = int(rng.integers(64, 1024))
        n_out = int(rng.integers(1, 9))
        sel = rng.random(cap) < 0.8
        pids = rng.integers(0, n_out, cap).astype(np.int32)
        vals = rng.integers(0, 1 << 40, cap).astype(np.int64)
        from auron_tpu.columnar.batch import DeviceBatch

        dev = DeviceBatch(
            jnp.asarray(sel), (jnp.asarray(vals),),
            (jnp.ones(cap, bool),),
        )
        out_dev, counts_dev = _cluster_by_pid(dev, jnp.asarray(pids), n_out)
        counts_np = np.asarray(jax.device_get(counts_dev))[:n_out]
        order_host, counts_host = cluster_rows_host(pids, sel, n_out)
        assert counts_np.tolist() == counts_host.tolist(), trial
        live = int(counts_host.sum())
        dev_vals = np.asarray(jax.device_get(out_dev.values[0]))[:live]
        host_vals = vals[order_host]
        assert dev_vals.tolist() == host_vals.tolist(), trial


def test_op_sync_attribution_follows_the_waiting_operator():
    """profiling.EngineCounters.op_sync books a blocking sync under the
    operator actually waiting (innermost LIVE ExecOperator frame) — a
    producer suspended at yield inside an open timer can no longer absorb
    a consumer's stall (the q93 probe_time misattribution)."""
    from auron_tpu.exec.agg_exec import AggExpr, HashAggExec
    from auron_tpu.utils.profiling import EngineCounters

    counters = EngineCounters.install()
    saved_all = counters.record_all_sites
    counters.record_all_sites = True
    try:
        rng = np.random.default_rng(3)
        frames = [
            Batch.from_pydict({
                "k": (rng.integers(0, 50, 800) * 1_000_003).tolist(),
                "c": [1] * 800,
            })
            for _ in range(6)
        ]
        # a merge-mode aggregate reads its counts once a batch, blocking
        agg = HashAggExec(
            MemoryScanExec.single(frames), [(col(0), "k")],
            [(AggExpr("count_star", None), "c")], "final")
        counters.reset()
        agg.collect()
        snap = counters.snapshot()
        assert "HashAggExec" in snap["op_sync"], snap["op_sync"]
        assert snap["op_sync"]["HashAggExec"][0] > 0
    finally:
        counters.record_all_sites = saved_all


def test_rss_fetch_rides_iter_payloads_raw_bytes(tmp_path):
    """ISSUE-12 satellite: the RSS fetch provider exposes iter_payloads,
    so format-v2 blocks cross into the reader as RAW BYTES (bucketed
    decode) instead of round-tripping through the RecordBatch view —
    and both paths emit identical rows."""
    import pandas as pd

    from auron_tpu.bridge import api
    from auron_tpu.exec.base import ExecutionContext
    from auron_tpu.exec.shuffle.format import is_v2_payload
    from auron_tpu.exec.shuffle.reader import IpcReaderExec
    from auron_tpu.exec.shuffle.rss import (
        LocalRssService, RssBlockProvider, RssPartitionWriterClient,
    )
    from auron_tpu.exprs.ir import col
    from auron_tpu.plan import builders as B
    from auron_tpu.utils.config import SHUFFLE_ENCODING, Configuration

    rng = np.random.default_rng(7)
    schema = T.Schema.of(T.Field("k", T.INT64), T.Field("v", T.INT64))
    batch = Batch.from_pydict(
        {"k": rng.integers(0, 40, 2000).astype(np.int64).tolist(),
         "v": rng.integers(0, 9, 2000).astype(np.int64).tolist()},
        schema=schema)
    n_reduce = 3
    svc = LocalRssService()
    api.put_resource("rssp_src", [[batch]])
    try:
        api.put_resource("rssp_w", RssPartitionWriterClient(svc, "shufp", 0))
        w = B.rss_shuffle_writer(
            B.memory_scan(schema, "rssp_src"),
            B.hash_partitioning([col(0)], n_reduce), "rssp_w")
        h = api.call_native(B.task(w, partition_id=0).SerializeToString())
        while api.next_batch(h) is not None:
            pass
        api.finalize_native(h)
    finally:
        api.remove_resource("rssp_src")
        api.remove_resource("rssp_w")

    prov = RssBlockProvider(svc, "shufp")
    # vacuity: the fetch path actually yields v2 payloads as raw bytes
    payloads = [p for part in range(n_reduce)
                for p in prov.iter_payloads(part)]
    assert payloads and any(is_v2_payload(p) for p in payloads)

    def read_all(encoding: str):
        rows = []
        for p in range(n_reduce):
            ctx = ExecutionContext(
                partition_id=p,
                conf=Configuration().set(SHUFFLE_ENCODING, encoding))
            ctx.resources["rssp_blocks"] = prov
            r = IpcReaderExec(schema, "rssp_blocks")
            for out in r.execute(p, ctx):
                rows.extend(out.to_arrow().to_pylist())
        return sorted((r["k"], r["v"]) for r in rows)

    bucketed = read_all("on")    # iter_payloads -> bucketed decode
    legacy = read_all("off")     # RecordBatch view path
    assert bucketed == legacy and len(bucketed) == 2000


def test_rss_push_rides_iter_payloads_raw_bytes(tmp_path):
    """ISSUE-20 satellite: the PUSH half of the raw-bytes pair — a
    finished local map output migrates into the RSS service via
    push_payloads as raw block payloads, never through the RecordBatch
    view, and the pushed bytes are byte-identical to the source file's
    payloads (no decode -> re-encode)."""
    from auron_tpu.exec.shuffle.format import is_v2_payload
    from auron_tpu.exec.shuffle.rss import (
        LocalRssService, RssBlockProvider, RssPartitionWriterClient,
        push_payloads,
    )

    rng = np.random.default_rng(13)
    df = pd.DataFrame({"k": rng.integers(0, 40, 2500).astype(np.int64),
                       "v": np.round(rng.random(2500) * 100, 2)})
    b = Batch.from_arrow(pa.RecordBatch.from_pandas(df, preserve_index=False))
    n_reduce = 4
    data, index = _write(tmp_path, [b], HashPartitioning([col(0)], n_reduce))

    class NoDecodeProvider(LocalFileBlockProvider):
        """The relay must never materialize the RecordBatch view."""

        def __call__(self, partition):
            raise AssertionError("push relay touched the RecordBatch view")

    src = NoDecodeProvider(data, index)
    src_payloads = [p for part in range(n_reduce)
                    for p in src.iter_payloads(part)]
    # vacuity: the source actually holds v2 payloads to relay
    assert src_payloads and any(is_v2_payload(p) for p in src_payloads)

    svc = LocalRssService()
    w = RssPartitionWriterClient(svc, "mig", 0)
    pushed = push_payloads(src, w, n_reduce)
    assert pushed == len(src_payloads)

    # byte identity: what the service serves back IS the source payloads
    dst = RssBlockProvider(svc, "mig")
    dst_payloads = [p for part in range(n_reduce)
                    for p in dst.iter_payloads(part)]
    assert dst_payloads == src_payloads

    # and the migrated output reads back as the original rows
    out = _read_all(b.schema, dst, n_reduce)
    total = pd.concat(out.values())
    assert sorted(total["v"].tolist()) == sorted(df["v"].tolist())


def test_rss_push_relay_aborts_on_failure():
    """A failing relay aborts the attempt (service drops staged blocks)."""
    from auron_tpu.exec.shuffle.rss import push_payloads

    class ExplodingProvider:
        def iter_payloads(self, partition):
            yield b"AUB2xxxx"
            raise RuntimeError("fetch died")

    events = []

    class Writer:
        def write(self, pid, blk):
            events.append(("write", pid))

        def abort(self):
            events.append(("abort",))

        def flush(self):
            events.append(("flush",))

    with pytest.raises(RuntimeError, match="fetch died"):
        push_payloads(ExplodingProvider(), Writer(), 2)
    assert ("abort",) in events and ("flush",) not in events
