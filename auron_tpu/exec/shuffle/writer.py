"""Shuffle writer exec.

Analog of the reference's sort-based shuffle writer
(shuffle_writer_exec.rs + shuffle/sort_repartitioner.rs + buffered_data.rs):
rows are partitioned on device (murmur3-exact ids), clustered per partition
by one device sort (the reference radix-sorts by partition id,
buffered_data.rs:285-340 — on TPU a lax.sort by pid is the vectorized
equivalent), then sliced into per-partition Arrow buffers host-side and
written as compacted compressed-IPC runs: ``.data`` + ``.index``
(format.py). An RSS-style writer (push to a remote partition writer object
instead of local files) plugs in through the same buffer interface
(reference: shuffle/rss.rs, RssPartitionWriterBase).
"""

from __future__ import annotations

from typing import Iterator

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa
from jax import lax

from auron_tpu import obs
from auron_tpu import types as T
from auron_tpu.columnar.batch import Batch, DeviceBatch
from auron_tpu.exec.base import ExecOperator, ExecutionContext
from auron_tpu.exec.shuffle.format import (
    align_dict_batches,
    encode_block,
    encode_block_v2,
    shuffle_encoding_enabled,
    write_index,
)

from auron_tpu.exec.shuffle.partitioning import Partitioning
from auron_tpu.utils.config import SHUFFLE_COMPRESSION_TARGET_BUF_SIZE


def encode_shuffle_block(batches: list, conf, metrics=None) -> bytes:
    """THE writer-side block encoder: format v2 light-weight columnar
    encodings under exec.shuffle.encoding (auto = on), the legacy
    compressed-IPC v1 block with =off — bit-identical file bytes to the
    pre-v2 writer (run align_dict_batches first; both flush paths and the
    spill flush share this single decision point)."""
    if shuffle_encoding_enabled(conf):
        return encode_block_v2(batches, conf=conf, metrics=metrics)
    return encode_block(pa.Table.from_batches(batches), conf=conf)



class ShuffleWriterExec(ExecOperator):
    """Writes the child's partition stream to (data_file, index_file); yields
    nothing (the exchange layer reports map status to the host engine)."""

    def __init__(
        self,
        child: ExecOperator,
        partitioning: Partitioning,
        data_file: str,
        index_file: str,
    ):
        super().__init__([child], child.schema)
        self.partitioning = partitioning
        self.data_file = data_file
        self.index_file = index_file

    def _execute(self, partition: int, ctx: ExecutionContext) -> Iterator[Batch]:
        from auron_tpu.memory.memmgr import MemManager

        n_out = self.partitioning.num_partitions
        mm = MemManager.get()
        staging = _ShuffleStaging(n_out, ctx)
        try:
            # staging (raw arrow buffers + compressed runs awaiting the
            # final write) is spill-managed: under pressure it compresses
            # and parks runs on disk, merged back per partition at write
            # time — the reference's spill-merge path
            # (sort_repartitioner.rs:98-151). Registered INSIDE the try:
            # the finally's unregister+release must cover every path out,
            # including a failure of register itself (R11)
            mm.register(staging)
            for parts in partitioned_stream(
                self.child_stream(0, partition, ctx), self.partitioning, ctx
            ):
                nbytes = sum(rb.nbytes for _, rb in parts)
                mm.acquire(staging, nbytes)
                staging.add_all(parts)

            offsets = [0]
            with ctx.metrics.timer("write_time"), \
                    obs.span("write", cat="exchange") as sp:
                # task-attempt isolation: a speculative duplicate or a
                # zombie attempt surviving an executor-loss retry may run
                # CONCURRENTLY with this one against the same deterministic
                # output paths (the staged-segment scheduler commits
                # whatever bytes land there). Each attempt writes its own
                # temp files and commits with atomic os.replace — attempts
                # are deterministic over the same input partition, so
                # whichever attempt's pair lands last is byte-identical.
                import os as _os
                import uuid as _uuid

                from auron_tpu.exec.shuffle.format import data_trailer

                attempt = _uuid.uuid4()
                suffix = f".attempt-{attempt.hex[:8]}"
                pair_tag = attempt.int & ((1 << 64) - 1)
                tmp_data = self.data_file + suffix
                tmp_index = self.index_file + suffix
                committed = False
                try:
                    with open(tmp_data, "wb") as f:
                        for pid in range(n_out):
                            for blk in staging.blocks_of(pid):
                                f.write(blk)
                            offsets.append(f.tell())
                        # pair tag past the last offset: invisible to
                        # offset-sliced reads, checked by the reader
                        f.write(data_trailer(pair_tag))
                    write_index(tmp_index, offsets, pair_tag=pair_tag)
                    _os.replace(tmp_data, self.data_file)
                    _os.replace(tmp_index, self.index_file)
                    committed = True
                    if sp is not None:
                        sp.arg = {"phase": "write", "bytes": offsets[-1]}
                finally:
                    if not committed:  # don't leak .attempt-* temps
                        for p in (tmp_data, tmp_index):
                            try:
                                _os.unlink(p)
                            except OSError:
                                pass
        finally:
            mm.unregister(staging)
            staging.release()
        ctx.metrics.add("data_size", offsets[-1])
        return
        yield  # pragma: no cover — generator with no items


class _ShuffleStaging:
    """Per-task shuffle staging buffers as a spillable MemConsumer.

    Layout per reduce partition: ``staged`` raw RecordBatches (uncompressed,
    awaiting a compression flush once they reach the target buffer size),
    ``regions`` compressed blocks in RAM, and ``spilled`` (file, [spans])
    compressed blocks parked on disk by a spill. blocks_of() streams a
    partition's blocks spill-order-first so the .data file keeps every
    partition's bytes contiguous."""

    def __init__(self, n_out: int, ctx: ExecutionContext):
        import threading

        self.name = f"shuffle-staging-{id(self):x}"
        self.n_out = n_out
        self.ctx = ctx
        self.target = ctx.conf.get(SHUFFLE_COMPRESSION_TARGET_BUF_SIZE)
        self.staged: list[list[pa.RecordBatch]] = [[] for _ in range(n_out)]
        self.staged_bytes = [0] * n_out
        self.regions: list[list[bytes]] = [[] for _ in range(n_out)]
        self._region_bytes = 0
        self._closed = False
        self._spill_files: list[tuple[str, list[list[tuple[int, int]]]]] = []
        # concurrent tasks: MemManager may spill this consumer from another
        # thread (lock order manager -> consumer, like agg/sort consumers)
        self._lock = threading.RLock()

    def add_all(self, parts) -> None:
        with self._lock:
            for pid, rb in parts:
                self.staged[pid].append(rb)
                self.staged_bytes[pid] += rb.nbytes
                if self.staged_bytes[pid] >= self.target:
                    self._flush(pid)

    def _flush(self, pid: int) -> None:
        if not self.staged[pid]:
            return
        with self.ctx.metrics.timer("compress_time"), \
                obs.span("write", cat="exchange") as sp:
            # conf threaded: spill() runs on the requesting task's thread
            blk = encode_shuffle_block(
                align_dict_batches(self.staged[pid]),
                conf=self.ctx.conf, metrics=self.ctx.metrics,
            )
            if sp is not None:
                sp.arg = {"phase": "compress", "bytes": len(blk)}
        self.ctx.metrics.add("shuffle_bytes_raw",
                             self.staged_bytes[pid])
        self.ctx.metrics.add("shuffle_bytes_written", len(blk))
        self.regions[pid].append(blk)
        self._region_bytes += len(blk)  # auronlint: guarded-by(self._lock) -- every _flush caller (add_all, spill, blocks_of) holds the staging lock
        self.staged[pid], self.staged_bytes[pid] = [], 0

    def mem_used(self) -> int:
        with self._lock:
            return sum(self.staged_bytes) + self._region_bytes

    def spill(self) -> int:  # auronlint: thread-root(foreign) -- MemManager dispatches spills on the requesting task's thread, not ours
        """Compress all staged buffers, park every in-RAM region on disk."""
        import tempfile

        with self._lock:
            # a release()d staging must never spill again: the race window
            # between the manager's victim snapshot and this call would
            # otherwise write a fresh .shuffle.spill temp file AFTER the
            # task already cleaned up — leaked file per race (ADVICE r4)
            if self._closed:
                return 0
            freed = self.mem_used()
            if freed == 0:
                return 0
            with self.ctx.metrics.timer("spill_time"):
                for pid in range(self.n_out):
                    self._flush(pid)
                fd, path = tempfile.mkstemp(suffix=".shuffle.spill")
                import os

                spans: list[list[tuple[int, int]]] = []
                with os.fdopen(fd, "wb") as f:
                    for pid in range(self.n_out):
                        pid_spans = []
                        for blk in self.regions[pid]:
                            pid_spans.append((f.tell(), len(blk)))
                            f.write(blk)
                        spans.append(pid_spans)
                self._spill_files.append((path, spans))
                self.regions = [[] for _ in range(self.n_out)]
                self._region_bytes = 0
            self.ctx.metrics.add("spilled_shuffle_runs", 1)
            return freed

    def blocks_of(self, pid: int) -> list[bytes]:
        """All of a partition's blocks: spilled runs first (oldest first),
        then resident regions, then a final flush of leftovers. Materialized
        under the lock so a concurrent spill can't move a region to disk
        mid-iteration (one partition's compressed bytes at a time)."""
        with self._lock:
            self._flush(pid)
            out: list[bytes] = []
            for path, spans in self._spill_files:
                with open(path, "rb") as f:
                    for off, ln in spans[pid]:
                        f.seek(off)
                        out.append(f.read(ln))
            out.extend(self.regions[pid])
            return out

    def release(self) -> None:
        import os

        with self._lock:
            files, self._spill_files = self._spill_files, []
            self._closed = True
        for path, _ in files:
            try:
                os.unlink(path)
            except OSError:
                pass


from functools import partial

# ---------------------------------------------------------------------------
# THE pid-clustering policy: stable sort by partition id, dead rows (pid ==
# n_out) last. ONE policy, three consumers — the eager device path
# (_cluster_by_pid), the fused stage program (plan/fusion.py
# _stage_program_shuffle via cluster_rows) and the host numpy fallback
# (cluster_rows_host) — with a bit-identity test (tests/test_shuffle.py)
# pinning that fused repartition can never diverge from the fallback.
# ---------------------------------------------------------------------------


def cluster_rows(dev: DeviceBatch, pids: jnp.ndarray, n_out: int):
    """Traceable clustering body shared by the eager jit wrapper and the
    fused stage program: (pid-clustered DeviceBatch, counts[n_out+1])."""
    sel = dev.sel
    cap = sel.shape[0]
    sort_pid = jnp.where(sel, pids, n_out).astype(jnp.int32)
    iota = jnp.arange(cap, dtype=jnp.int32)
    s_pid, order = lax.sort((sort_pid, iota), num_keys=1)
    counts = jnp.bincount(s_pid, length=n_out + 1)
    out = DeviceBatch(
        sel=dev.sel[order],
        values=tuple(v[order] for v in dev.values),
        validity=tuple(m[order] for m in dev.validity),
    )
    return out, counts


def cluster_rows_host(pids_np: np.ndarray, sel_np: np.ndarray, n_out: int):
    """Host twin of ``cluster_rows``: (live-row order, per-partition
    counts[n_out]) via the same stable-sort-by-pid policy (numpy's stable
    argsort == lax.sort's (pid, iota) tiebreak), dead rows sorted last and
    excluded from the returned order. The ids are sorted as the narrowest
    unsigned type that holds ``n_out``: numpy's stable sort of 8- and 16-bit
    integers is a radix sort, of wider ones a merge sort, and the order is
    the same (76 against 246 ms at 4,194,304 rows of two partitions, numpy
    on one x86 core)."""
    key = np.uint8 if n_out < 0xFF else np.uint16 if n_out < 0xFFFF else np.int32
    sort_pid = np.where(sel_np, pids_np, n_out).astype(key)
    counts = np.bincount(sort_pid, minlength=n_out + 1)[:n_out]
    order_live = np.argsort(sort_pid, kind="stable")[: int(counts.sum())]
    return order_live, counts


def repartition_substrate(conf, rows: int | None = None) -> str:
    """"host" (numpy argsort + host arrow slicing) or "device" (lax.sort
    clustering) — THE substrate decision shared by the eager writer and
    the fused stage so the two repartition paths cannot diverge. ``rows``
    is the batch's capacity: a batch too wide for a device sort
    (``hostsort.DEVICE_SORT_MAX_ROWS``) is clustered on the host, where
    its rows are headed anyway."""
    from auron_tpu.ops import hostsort

    return "host" if hostsort.use_host_sort(conf, rows=rows) else "device"


@partial(obs.jit, static_argnames=("n_out",))
def _cluster_by_pid(dev: DeviceBatch, pids: jnp.ndarray, n_out: int):
    return cluster_rows(dev, pids, n_out)




class RssShuffleWriterExec(ExecOperator):
    """Push-style shuffle writer for remote shuffle services.

    Analog of the reference's RSS writer (rss_shuffle_writer_exec.rs +
    shuffle/rss.rs + AuronRssShuffleWriterBase.scala:40-62): instead of
    local .data/.index files, compacted compressed-IPC blocks are pushed to
    a partition-writer object the engine integration registers in the task
    resource map (Celeborn/Uniffle clients implement the same callable:
    ``writer(partition_id, block_bytes)``; ``writer.flush()`` optional)."""

    def __init__(
        self,
        child: ExecOperator,
        partitioning: Partitioning,
        rss_resource_id: str,
    ):
        super().__init__([child], child.schema)
        self.partitioning = partitioning
        self.rss_resource_id = rss_resource_id

    def _execute(self, partition: int, ctx: ExecutionContext):
        writer = ctx.resources[self.rss_resource_id]
        push = writer if callable(writer) else writer.write
        n_out = self.partitioning.num_partitions
        staged: list[list[pa.RecordBatch]] = [[] for _ in range(n_out)]
        staged_bytes = [0] * n_out
        target = ctx.conf.get(SHUFFLE_COMPRESSION_TARGET_BUF_SIZE)

        def flush(pid: int):
            if staged[pid]:
                with ctx.metrics.timer("compress_time"), \
                        obs.span("write", cat="exchange") as sp:
                    blk = encode_shuffle_block(
                        align_dict_batches(staged[pid]),
                        conf=ctx.conf, metrics=ctx.metrics,
                    )
                    if sp is not None:
                        sp.arg = {"phase": "compress", "bytes": len(blk)}
                ctx.metrics.add("shuffle_bytes_raw", staged_bytes[pid])
                ctx.metrics.add("shuffle_bytes_written", len(blk))
                with ctx.metrics.timer("push_time"), \
                        obs.span("write", cat="exchange") as sp:
                    if sp is not None:
                        sp.arg = {"phase": "push", "bytes": len(blk)}
                    push(pid, blk)
                ctx.metrics.add("data_size", len(blk))
                staged[pid].clear()
                staged_bytes[pid] = 0

        try:
            for parts in partitioned_stream(
                self.child_stream(0, partition, ctx), self.partitioning, ctx
            ):
                for pid, rb in parts:
                    staged[pid].append(rb)
                    staged_bytes[pid] += rb.nbytes
                    if staged_bytes[pid] >= target:
                        flush(pid)
            for pid in range(n_out):
                flush(pid)
        except BaseException:
            # a failing map attempt must ABORT so the service drops its
            # staged blocks — an uncommitted attempt otherwise holds its
            # pushed bytes forever (local RAM or the remote daemon; the
            # first-commit-wins retry then runs against a clean slate)
            if hasattr(writer, "abort"):
                try:
                    writer.abort()
                except Exception:  # noqa: BLE001  # auronlint: disable=R12 -- unwind: the propagating stream error is primary; a failed abort just leaves the attempt for service GC
                    pass
            raise
        if hasattr(writer, "flush"):
            writer.flush()
        return
        yield  # pragma: no cover


def stage_partition_batch(
    b: Batch, partitioning: Partitioning, ctx: ExecutionContext
):
    """Dispatch half of the repartition: compute partition ids (and, on
    accelerators, the pid-clustered gather) on device and START the
    device->host copies — the writer loops finish one batch behind, so
    the transfer overlaps the child's next batch of compute
    (docs/pipeline.md; this is the spill/shuffle-count member of the
    async transfer window).

    A batch arriving from a fused writer stage carries a ``_shuffle_prep``
    payload (plan/fusion.py): pids — and on the device substrate the
    clustered batch + counts — already rode the stage program. The payload
    is consumed only when its n_out and substrate match what the eager
    path would compute (repartition_substrate), else ignored."""
    from auron_tpu.runtime.transfer import start_host_transfer

    n_out = partitioning.num_partitions
    substrate = repartition_substrate(ctx.conf, b.capacity)
    sp = getattr(b, "_shuffle_prep", None)
    if sp is not None and (sp.n_out != n_out or sp.mode != substrate):
        sp = None  # stale/foreign payload: recompute eagerly
    if substrate == "host":
        pids = sp.pids if sp is not None else partitioning.partition_ids(b, ctx)
        dev = b.device
        start_host_transfer(pids, dev.sel, *dev.values, *dev.validity)
        return (b, pids, None, None)
    if sp is not None:
        clustered_dev, counts = sp.clustered_dev, sp.counts
    else:
        pids = partitioning.partition_ids(b, ctx)
        clustered_dev, counts = _cluster_by_pid(b.device, pids, n_out)
    start_host_transfer(counts)
    return (b, None, clustered_dev, counts)


def finish_partition_batch(
    staged, partitioning: Partitioning, ctx: ExecutionContext
) -> list[tuple[int, pa.RecordBatch]]:
    """Harvest half: resolve the staged transfers and slice per-partition
    arrow blocks. Dead rows are excluded."""
    from auron_tpu.columnar.batch import bucket_capacity, prefix_slice
    from auron_tpu.utils.profiling import async_read_scope

    b, pids, clustered_dev, counts = staged
    n_out = partitioning.num_partitions
    if pids is not None:
        # CPU host: the clustered rows are headed to HOST Arrow blocks
        # anyway, so pull the WHOLE batch once and do everything — stable
        # integer argsort (numpy radix), live-prefix slicing, per-column
        # gathers — in numpy (cluster_rows_host: the SAME clustering
        # policy as the device path). The previous split (host argsort,
        # device gather, second full transfer via to_arrow) paid two round
        # trips and a capacity-sized gather program per batch; this is one
        # transfer and live-row-count work. The device path below stays
        # for accelerators, where the gather belongs on-device.
        from auron_tpu.columnar.batch import host_rows_to_arrow

        with async_read_scope():  # copies started at stage time
            pids_np, dev = jax.device_get((pids, b.device))  # numpy leaves
        order_live, counts_np = cluster_rows_host(pids_np, dev.sel, n_out)
        rb = host_rows_to_arrow(b.schema, b.dicts, dev.values, dev.validity,
                                order_live, preserve_dicts=True)
        out = []
        start = 0
        for pid in range(n_out):
            c = int(counts_np[pid])
            if c:
                out.append((pid, rb.slice(start, c)))
            start += c
        return out
    with async_read_scope():  # count copy started at stage time
        counts_np = np.asarray(jax.device_get(counts))[:n_out]
    clustered = Batch(b.schema, clustered_dev, b.dicts)
    total_live = int(counts_np.sum())
    # live rows sort to the front (dead rows got pid=n_out): pull only the
    # live prefix — sparse batches don't pay device->host bytes for padding
    clustered = prefix_slice(clustered, bucket_capacity(max(total_live, 1)))
    rb = clustered.to_arrow(compact=False, preserve_dicts=True)  # one transfer; rows already clustered
    out = []
    start = 0
    for pid in range(n_out):
        c = int(counts_np[pid])
        if c:
            out.append((pid, rb.slice(start, c)))
        start += c
    return out


def _repart_arg(parts) -> dict:
    """A repartition span's argument: the Arrow bytes it handed on."""
    return {"phase": "repart",
            "bytes": sum(rb.nbytes for _, rb in parts or ())}


def partitioned_stream(child_iter, partitioning: Partitioning, ctx):
    """One-deep stage/finish pipeline over a batch stream: batch i's
    device->host transfer rides behind batch i+1's dispatch, so the
    writer never blocks on the child's compute tail."""
    pending = None
    for b in child_iter:
        ctx.check_cancelled()
        with ctx.metrics.timer("repart_time", count=True), \
                obs.span("write", cat="exchange") as sp:
            cur = stage_partition_batch(b, partitioning, ctx)
            parts = None
            if pending is not None:
                parts = finish_partition_batch(pending, partitioning, ctx)
            if sp is not None:
                sp.arg = _repart_arg(parts)
        pending = cur
        if parts is not None:
            yield parts
    if pending is not None:
        with ctx.metrics.timer("repart_time"), \
                obs.span("write", cat="exchange") as sp:
            parts = finish_partition_batch(pending, partitioning, ctx)
            if sp is not None:
                sp.arg = _repart_arg(parts)
        yield parts
