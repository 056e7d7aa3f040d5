"""Shuffle reader exec.

Analog of the reference's IpcReaderExec (ipc_reader_exec.rs:50-56,120-240):
the engine-integration layer registers a *block provider* in the task
resource map (the JVM hands fetched shuffle blocks the same way through
JniBridge.putResource); the exec pulls length-prefixed compressed-IPC
blocks, decodes, and re-buckets rows into device batches.

Two decode paths (docs/shuffle.md):

- legacy: provider yields Arrow RecordBatches; pending batches combine
  into one Arrow table, dictionaries unify, and ``Batch.from_arrow``
  re-ingests — two Arrow materializations per emitted batch.
- bucketed (``exec.shuffle.encoding``, providers exposing
  ``iter_payloads``): raw block payloads decode into host column planes
  (format v2 decodes straight to numpy; v1 IPC blocks degrade per
  column) which assemble DIRECTLY into 64-byte-aligned capacity-bucket
  buffers — one fill pass per column, one aliased device transfer, no
  intermediate Arrow table.
"""

from __future__ import annotations

from typing import Iterator

import numpy as np
import pyarrow as pa

from auron_tpu import obs
from auron_tpu import types as T
from auron_tpu.columnar.batch import (
    Batch,
    _arrow_to_host,
    aligned_empty,
    bucket_capacity,
    merge_vocab,
)
from auron_tpu.exec.base import ExecOperator, ExecutionContext
from auron_tpu.exec.shuffle.format import (
    BlockColumns,
    align_dict_batches,
    decode_block_v2,
    decode_blocks,
    is_v2_payload,
    iter_block_payloads,
    shuffle_encoding_enabled,
)


#: the argument of every ``exchange:read`` span around an emit (shared:
#: nothing writes to it)
_EMIT = {"phase": "emit"}


class IpcReaderExec(ExecOperator):
    """Reads shuffle blocks for the task's reduce partition."""

    def __init__(self, schema: T.Schema, resource_id: str):
        super().__init__([], schema)
        self.resource_id = resource_id

    def _execute(self, partition: int, ctx: ExecutionContext) -> Iterator[Batch]:
        provider = ctx.resources[self.resource_id]
        target = ctx.batch_size()
        payloads = getattr(provider, "iter_payloads", None)
        if payloads is not None and shuffle_encoding_enabled(ctx.conf):
            yield from self._execute_bucketed(payloads(partition), ctx, target)
            return
        pending: list[pa.RecordBatch] = []
        pending_rows = 0
        for rb in provider(partition):
            ctx.check_cancelled()
            if rb.num_rows == 0:
                continue
            pending.append(rb)
            pending_rows += rb.num_rows
            if pending_rows >= target:
                yield _combine(pending, self.schema)
                pending, pending_rows = [], 0
        if pending:
            yield _combine(pending, self.schema)

    def _execute_bucketed(
        self, payload_iter, ctx: ExecutionContext, target: int
    ) -> Iterator[Batch]:
        """Decode raw block payloads straight into capacity-bucket device
        buffers (no intermediate Arrow table)."""
        asm = _BucketAssembler()
        for payload in payload_iter:
            ctx.check_cancelled()
            ctx.metrics.add("shuffle_bytes_read", len(payload))
            with ctx.metrics.timer("decode_time"), \
                    obs.span("read", cat="exchange") as sp:
                if sp is not None:
                    sp.arg = {"phase": "decode", "bytes": len(payload)}
                if is_v2_payload(payload):
                    asm.add_v2(decode_block_v2(payload))
                else:
                    # a mixed region (old files, v1 spill merges): degrade
                    # this block to per-column Arrow chunks
                    with pa.ipc.open_stream(payload) as r:
                        for rb in r:
                            asm.add_arrow(rb)
            if asm.rows >= target:
                with ctx.metrics.timer("decode_time"), \
                        obs.span("read", cat="exchange", arg=_EMIT):
                    b = asm.emit()
                if b is not None:
                    yield b
        if asm.rows:
            with ctx.metrics.timer("decode_time"), \
                    obs.span("read", cat="exchange", arg=_EMIT):
                b = asm.emit()
            if b is not None:
                yield b


class _BucketAssembler:
    """Accumulates decoded column chunks and seals them into one Batch.

    Chunks per column are (vals np[n], valid np[n] | None, dict | None)
    in the ENGINE's physical plane layout (the _arrow_to_host contract);
    emit() concatenates them into aligned capacity-bucket host buffers and
    ships the whole pytree in one (aliasing) device transfer."""

    def __init__(self):
        self.schema: T.Schema | None = None
        self.rows = 0
        self.chunks: list[list] = []  # per column

    def _bind_schema(self, arrow_schema: pa.Schema) -> None:
        if self.schema is None:
            self.schema = T.Schema.from_arrow(arrow_schema)
            self.chunks = [[] for _ in self.schema]

    def add_v2(self, bc: BlockColumns) -> None:
        from auron_tpu.exec.shuffle.format import _column_to_arrow

        self._bind_schema(bc.schema)
        if bc.nrows == 0:
            return
        n = bc.nrows
        for i, (f, col) in enumerate(zip(self.schema, bc.cols)):
            tag = col[0]
            if not f.dtype.is_dict_encoded and tag == "plane":
                _, vals, valid = col
                phys = np.dtype(f.dtype.physical_dtype().name)
                self.chunks[i].append(
                    (vals.astype(phys, copy=False), valid, None))
            elif (not f.dtype.is_dict_encoded and tag == "dec128"
                  and f.dtype.kind == T.TypeKind.DECIMAL):
                # decimal64 plane from the lo/hi limbs: values that fit
                # int64 pass through, overflow lanes go NULL — the exact
                # semantics of the legacy per-value ingest loop
                _, lo, hi, valid = col
                fits = hi == (lo >> 63)
                vals = np.where(fits, lo, np.int64(0))
                valid = fits if valid is None else (valid & fits)
                self.chunks[i].append((vals, valid, None))
            elif (f.dtype.is_dict_encoded and tag == "dict"
                  and f.dtype.kind not in (T.TypeKind.LIST, T.TypeKind.MAP,
                                           T.TypeKind.STRUCT)):
                _, codes, valid, dict_vals = col
                d = dict_vals
                if pa.types.is_large_string(d.type):
                    d = d.cast(pa.string())
                elif pa.types.is_large_binary(d.type):
                    d = d.cast(pa.binary())
                self.chunks[i].append(
                    (codes.astype(np.int32, copy=False), valid, d))
            else:
                # chunk shape doesn't match the engine plane (materialized
                # strings, wide decimals, nested): one Arrow hop per chunk
                arr = _column_to_arrow(bc.schema.field(i).type, n, col)
                v, m, d = _arrow_to_host(arr, f.dtype, n)
                self.chunks[i].append((v, m[:n], d))
        self.rows += n

    def add_arrow(self, rb: pa.RecordBatch) -> None:
        self._bind_schema(rb.schema)
        n = rb.num_rows
        if n == 0:
            return
        for i, f in enumerate(self.schema):
            v, m, d = _arrow_to_host(rb.column(i), f.dtype, n)
            self.chunks[i].append((v, m[:n], d))
        self.rows += n

    def emit(self) -> Batch | None:
        import jax

        from auron_tpu.columnar.batch import _seal_batch

        if self.schema is None or self.rows == 0:
            return None
        rows = self.rows
        cap = bucket_capacity(rows)
        values, validity, dicts = [], [], []
        for i, f in enumerate(self.schema):
            phys = np.dtype(f.dtype.physical_dtype().name)
            out = aligned_empty(cap, phys)
            out_m = aligned_empty(cap, bool)
            d = None
            if f.dtype.is_dict_encoded:
                entry_lists = [
                    (dct.to_pylist() if dct is not None else [])
                    for _, _, dct in self.chunks[i]
                ]
                d, remaps = merge_vocab(entry_lists, f.dtype)
                pos = 0
                for (codes, valid, _), r in zip(self.chunks[i], remaps):
                    k = len(codes)
                    remap = r if len(r) else np.zeros(1, np.int32)
                    out[pos : pos + k] = remap[np.clip(codes, 0, len(remap) - 1)]
                    if valid is None:
                        out_m[pos : pos + k] = True
                    else:
                        out_m[pos : pos + k] = valid
                    pos += k
            else:
                pos = 0
                for vals, valid, _ in self.chunks[i]:
                    k = len(vals)
                    out[pos : pos + k] = vals
                    if valid is None:
                        out_m[pos : pos + k] = True
                    else:
                        out_m[pos : pos + k] = valid
                    pos += k
            out[rows:] = phys.type(0)
            out_m[rows:] = False
            values.append(out)
            validity.append(out_m)
            dicts.append(d)
        batch = _seal_batch(self.schema, values, validity, dicts, rows, cap,
                            zc=True)
        self.rows = 0
        self.chunks = [[] for _ in self.schema]
        return batch


def _combine(batches: list[pa.RecordBatch], schema: T.Schema) -> Batch:
    tbl = pa.Table.from_batches(align_dict_batches(batches))
    if any(pa.types.is_dictionary(f.type) for f in tbl.schema):
        # dictionary-preserving blocks: each block carries its own dict;
        # unify so combine_chunks can merge codes into one array
        tbl = tbl.unify_dictionaries()
    tbl = tbl.combine_chunks()
    rb = tbl.to_batches()[0] if tbl.num_rows else pa.RecordBatch.from_pylist([], schema=tbl.schema)
    return Batch.from_arrow(rb)


class LocalFileBlockProvider:
    """Reads a (data, index) pair written by ShuffleWriterExec — the
    single-node stand-in for the engine's fetched-block channel."""

    def __init__(self, data_file: str, index_file: str):
        self.data_file = data_file
        self.index_file = index_file

    def _region(self, partition: int) -> bytes:
        from auron_tpu.exec.shuffle.format import read_data_tag, read_index_tagged

        offsets, pair_tag = read_index_tagged(self.index_file)
        if pair_tag is not None:
            # pair-integrity check: concurrent task attempts commit data
            # and index with separate atomic replaces; a mixed pair (rare
            # interleaving) must fail LOUDLY here so the task retries,
            # never decode blocks with the wrong offsets
            dtag = read_data_tag(self.data_file, offsets[-1])
            if dtag != pair_tag:
                raise RuntimeError(
                    f"shuffle pair mismatch: {self.data_file} tag={dtag} vs "
                    f"{self.index_file} tag={pair_tag} (concurrent attempt "
                    "commit interleaving); retry the task"
                )
        start, stop = offsets[partition], offsets[partition + 1]
        if start == stop:
            return b""
        with open(self.data_file, "rb") as f:
            f.seek(start)
            return f.read(stop - start)

    def __call__(self, partition: int) -> Iterator[pa.RecordBatch]:
        data = self._region(partition)
        if data:
            yield from decode_blocks(data)

    def iter_payloads(self, partition: int) -> Iterator[bytes]:
        """Raw block payloads (the bucketed decode path's input)."""
        from auron_tpu.exec.shuffle.format import iter_block_payloads

        data = self._region(partition)
        if data:
            yield from iter_block_payloads(data)


class BroadcastBlockProvider:
    """A broadcast relation as the host hands it to every task: the
    length-prefixed blocks an ``IpcWriterExec`` pushed into its channel
    (Spark's BroadcastExchange collects a small child as IPC bytes and ships
    them to each executor). Every partition reads all of them."""

    def __init__(self, blocks: list[bytes]):
        self.blocks = blocks

    def __call__(self, partition: int) -> Iterator[pa.RecordBatch]:
        for blk in self.blocks:
            yield from decode_blocks(blk)

    def iter_payloads(self, partition: int) -> Iterator[bytes]:
        for blk in self.blocks:
            yield from iter_block_payloads(blk)


class MultiMapBlockProvider:
    """Aggregates the outputs of several map tasks (one (data,index) pair per
    map task) for a reduce partition — single-process exchange used by tests
    and the local TPC-DS harness."""

    def __init__(self, pairs: list[tuple[str, str]]):
        self.pairs = pairs  # kept for AQE introspection (skew splitting)
        self.providers = [LocalFileBlockProvider(d, i) for d, i in pairs]

    def __call__(self, partition: int) -> Iterator[pa.RecordBatch]:
        for p in self.providers:
            yield from p(partition)

    def iter_payloads(self, partition: int) -> Iterator[bytes]:
        for p in self.providers:
            yield from p.iter_payloads(partition)

    def read_slice(
        self, partition: int, map_lo: int, map_hi: int
    ) -> Iterator[pa.RecordBatch]:
        """One partition's blocks from map outputs [map_lo, map_hi) —
        the skew-split unit (a slice of the skewed side joins the full
        other side)."""
        for p in self.providers[map_lo:map_hi]:
            yield from p(partition)
