"""External sort + TakeOrdered.

Analog of the reference's external sorter (datafusion-ext-plans/src/
sort_exec.rs: key-prefix compare, in-memory sorted runs, loser-tree k-way
merged output, TakeOrdered via fetch limit). TPU-native strategy:

- accumulate input batches (device_concat), encode sort keys as orderable
  uint64 words (ops/sortkeys.py) and run ONE multi-operand lax.sort with a
  row-index payload — the gather by the resulting permutation reorders all
  columns on device;
- dead rows (sel=0) sort to the end via a leading liveness word and are
  trimmed by capacity slicing;
- ``fetch`` (TakeOrdered / PartialTakeOrdered, auron.proto:664-674 analog)
  keeps only the first N sorted rows;
- when the accumulated size exceeds the spill threshold the run is sorted
  and parked on host RAM (device->host tier; disk tier arrives with the
  memory manager), and output k-way merges the parked runs with a numpy
  merge driven by the same key words.
"""

from __future__ import annotations

from typing import Iterator

import threading

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from auron_tpu import obs
from auron_tpu import types as T
from auron_tpu.columnar.batch import (
    Batch,
    DeviceBatch,
    bucket_capacity,
    device_concat,
    prefix_slice,
)
from auron_tpu.exec.base import ExecOperator, ExecutionContext
from auron_tpu.exprs import Evaluator, ir
from auron_tpu.obs.launch import ejnp
from auron_tpu.ops.sortkeys import SortSpec, sort_operands


class SortExec(ExecOperator):
    def __init__(
        self,
        child: ExecOperator,
        sort_exprs: list[ir.Expr],
        specs: list[SortSpec],
        fetch: int | None = None,
        spill_threshold_rows: int = 1 << 23,
    ):
        super().__init__([child], child.schema)
        self.sort_exprs = sort_exprs
        self.specs = specs
        self.fetch = fetch
        self.spill_threshold_rows = spill_threshold_rows
        # per-run dictionary ranks are not comparable across runs, so
        # dict-encoded sort keys force a global re-sort at merge time
        self._dict_keys = any(
            e.dtype_of(child.schema).is_dict_encoded for e in sort_exprs
        )

    def _execute(self, partition: int, ctx: ExecutionContext) -> Iterator[Batch]:
        from auron_tpu.memory.memmgr import MemManager

        mm = MemManager.get()
        sorter = _SorterConsumer(self, ctx)
        mm.register(sorter)
        try:
            for b in self.child_stream(0, partition, ctx):
                ctx.check_cancelled()
                n = b.num_rows()
                if n == 0:
                    continue
                mm.acquire(sorter, batch_nbytes(b))
                sorter.add(b, n)
                if sorter.pending_rows >= self.spill_threshold_rows:
                    sorter.spill()
        finally:
            mm.unregister(sorter)
        pending, runs = sorter.pending, sorter.runs

        if not runs:
            if not pending:
                return
            sorted_batch = self._sort_run(pending, ctx)
            yield from self._emit(sorted_batch.batch, ctx)
            return

        if self._dict_keys:
            # string/list sort keys: run ranks are per-run-dictionary local;
            # rebuild batches and re-sort globally (device_concat unifies the
            # dictionaries). Costs one device round-trip of the spilled data
            # — correctness over memory until global-rank dictionaries land.
            batches = pending + [_run_to_batch(r, self.schema) for r in runs]
            with ctx.metrics.timer("merge_time"):
                merged = self._sort_run(batches, ctx).batch
            yield from self._emit(merged, ctx)
            return
        if pending:
            runs.append(self._sort_run(pending, ctx).to_host())
        with ctx.metrics.timer("merge_time"):
            merged = _merge_runs(runs, self.schema)
        yield from self._emit(merged, ctx)

    # ------------------------------------------------------------------

    def _sort_run(self, batches: list[Batch], ctx: ExecutionContext) -> "_SortedRun":
        big = device_concat(batches)
        # context threaded explicitly: a cross-thread spill runs this on
        # the requesting task's thread, where current_context() (the
        # Evaluator default) would resolve a FOREIGN task's partition id
        # and resource map (R7)
        ev = Evaluator(
            self.schema, partition_id=ctx.partition_id, resources=ctx.resources
        )
        keys = ev.evaluate(big, self.sort_exprs)
        ops = sort_operands(keys, self.specs)
        cap = big.capacity
        live = ejnp.where(big.device.sel, jnp.uint64(0), jnp.uint64(1))
        iota = jnp.arange(cap, dtype=jnp.int32)
        from auron_tpu.ops import hostsort

        with ctx.metrics.timer("sort_time"):
            host = hostsort.use_host_sort(ctx.conf, rows=cap)
            obs.note_sort(cap, "sort_exec", "host" if host else "device")
            if host:
                order = hostsort.order_by_words((live, *ops))
                sorted_ops = (None, *(o[order] for o in ops), order)
            else:
                from auron_tpu.ops import bitonic, sortkeys

                sorted_ops = bitonic.ordered_sort(
                    tuple([live, *ops, iota]),
                    word_narrow=sortkeys.narrow_flags(len(self.specs)),
                    conf=ctx.conf,
                )
                order = sorted_ops[-1]
        dev = big.device
        n = big.num_rows()
        new_cap = bucket_capacity(max(n, 1))
        out, key_words = _gather_run(
            dev, order, tuple(sorted_ops[1:-1]), new_cap=new_cap
        )
        sorted_batch = Batch(self.schema, out, big.dicts)
        return _SortedRun(sorted_batch, key_words)

    def _emit(self, sorted_batch: Batch, ctx: ExecutionContext) -> Iterator[Batch]:
        n = sorted_batch.num_rows()
        if self.fetch is not None and self.fetch < n:
            keep = jnp.arange(sorted_batch.capacity) < self.fetch
            dev = sorted_batch.device
            sorted_batch = sorted_batch.with_device(
                DeviceBatch(dev.sel & keep, dev.values, dev.validity)
            )
            sorted_batch = prefix_slice(sorted_batch, bucket_capacity(max(self.fetch, 1)))
            n = self.fetch
        chunk = bucket_capacity(ctx.batch_size())
        if n <= chunk:
            yield sorted_batch
            return
        dev = sorted_batch.device
        for start in range(0, n, chunk):
            # one fused dynamic-slice program per chunk (bounds-clamped, so
            # the tail reads the zero-padded capacity region — those slots
            # carry sel=0 and are dead by construction)
            yield Batch(
                self.schema,
                _slice_chunk(dev, jnp.int32(start), chunk=chunk),
                sorted_batch.dicts,
            )


from functools import partial as _partial


@_partial(obs.jit, static_argnames=("new_cap",))
def _gather_run(dev: DeviceBatch, order, sorted_words, *, new_cap: int):
    """Fused run finalization: permute every column to sorted order and
    trim to the live-prefix bucket in ONE program."""
    from auron_tpu.columnar.batch import device_take

    out = device_take(dev, order[:new_cap])
    return out, tuple(o[:new_cap] for o in sorted_words)


@_partial(obs.jit, static_argnames=("chunk",))
def _slice_chunk(dev: DeviceBatch, start, *, chunk: int) -> DeviceBatch:
    """One fused dynamic-slice of every column. Capacities and chunks are
    both power-of-two buckets, so start+chunk never exceeds capacity and
    the clamp in dynamic_slice never rewinds (no duplicate rows)."""
    from jax import lax

    def sl(a):
        return lax.dynamic_slice_in_dim(a, start, chunk)

    return DeviceBatch(
        sel=sl(dev.sel),
        values=tuple(sl(v) for v in dev.values),
        validity=tuple(sl(m) for m in dev.validity),
    )


def batch_nbytes(b: Batch) -> int:
    """Device-memory estimate of a batch (values + validity + sel)."""
    total = b.capacity  # sel bool
    for v in b.device.values:
        total += v.size * v.dtype.itemsize
    for m in b.device.validity:
        total += m.size
    return total


class _SorterConsumer:
    """MemConsumer facade over the sorter's in-device pending batches
    (reference: ExternalSorter: MemConsumer, sort_exec.rs:375-390)."""

    def __init__(self, exec_: "SortExec", ctx: ExecutionContext):
        self.name = f"sort-{id(exec_):x}"
        self.exec = exec_
        self.ctx = ctx
        self.pending: list[Batch] = []
        self.runs: list["_HostRun"] = []
        self.pending_rows = 0
        self._bytes = 0
        # tasks run concurrently; MemManager.acquire may spill this consumer
        # from ANOTHER task's thread. Lock order is manager -> consumer (the
        # owner never holds this lock while calling acquire), so no deadlock.
        self._lock = threading.RLock()

    def add(self, b: Batch, n: int) -> None:
        with self._lock:
            self.pending.append(b)
            self.pending_rows += n
            self._bytes += batch_nbytes(b)

    def mem_used(self) -> int:
        with self._lock:
            return self._bytes

    def spill(self) -> int:  # auronlint: thread-root(foreign) -- MemManager dispatches spills on the requesting task's thread, not ours
        with self._lock:
            if not self.pending:
                return 0
            freed = self._bytes
            with self.ctx.metrics.timer("spill_time"):
                self.runs.append(self.exec._sort_run(self.pending, self.ctx).to_host())
            self.ctx.metrics.add("spilled_runs", 1)
            self.pending = []
            self.pending_rows = 0
            self._bytes = 0
            return freed


class _SortedRun:
    def __init__(self, batch: Batch, key_words: tuple):
        self.batch = batch
        self.key_words = key_words

    def to_host(self) -> "_HostRun":
        # auronlint: sync-point(call) -- spill tier: device->host is the operation itself; one batched transfer
        # auronlint: disable=R9 -- spill-tier boundary: rate owned by memory pressure (once per spilled run), amortized far below per-batch
        dev, words = jax.device_get((self.batch.device, self.key_words))
        n = int(np.sum(np.asarray(dev.sel)))
        return _HostRun(
            sel=np.asarray(dev.sel),
            values=[np.asarray(v) for v in dev.values],
            validity=[np.asarray(m) for m in dev.validity],
            key_words=[np.asarray(w) for w in words],
            dicts=self.batch.dicts,
            n=n,
        )


class _HostRun:
    """A sorted run parked in host RAM (the device->host spill tier)."""

    def __init__(self, sel, values, validity, key_words, dicts, n):
        self.sel = sel
        self.values = values
        self.validity = validity
        self.key_words = key_words
        self.dicts = dicts
        self.n = n


def _run_to_batch(r: "_HostRun", schema: T.Schema) -> Batch:
    """Rehydrate a host-parked run as a device batch."""
    return Batch(
        schema,
        DeviceBatch(
            jnp.asarray(r.sel),
            tuple(jnp.asarray(v) for v in r.values),
            tuple(jnp.asarray(m) for m in r.validity),
        ),
        r.dicts,
    )


def _merge_runs(runs: list[_HostRun], schema: T.Schema) -> Batch:
    """K-way merge of sorted host runs by their uint64 key words.

    Uses the native loser-tree (native/auron_native.cpp loser_tree_merge —
    the C++ analog of ext-commons/src/algorithm/loser_tree.rs) when built,
    falling back to a stable numpy lexsort."""
    from auron_tpu import native

    live_idx = [np.nonzero(r.sel)[0] for r in runs]
    n_words = len(runs[0].key_words)
    if native.available():
        run_words = [
            [r.key_words[w][i] for w in range(n_words)]
            for r, i in zip(runs, live_idx)
        ]
        out_run, out_idx = native.loser_tree_merge_host(run_words)
        run_base = np.zeros(len(runs) + 1, dtype=np.int64)
        np.cumsum([len(i) for i in live_idx], out=run_base[1:])
        order = run_base[out_run] + out_idx
    else:
        words = [
            np.concatenate([r.key_words[k][i] for r, i in zip(runs, live_idx)])
            for k in range(n_words)
        ]
        order = np.lexsort(list(reversed(words)))  # last key primary
    import pyarrow as pa

    total = order.shape[0]
    cap = bucket_capacity(max(total, 1))
    out_vals = []
    out_mask = []
    dicts: list = []

    for ci, f in enumerate(schema):
        vs = [r.values[ci][i] for r, i in zip(runs, live_idx)]
        ms = [r.validity[ci][i] for r, i in zip(runs, live_idx)]
        if f.dtype.is_dict_encoded:
            vocab: dict = {}
            remapped = []
            for r, v in zip(runs, vs):
                pl = r.dicts[ci].to_pylist()
                rm = np.empty(len(pl), dtype=np.int32)
                for j, s in enumerate(pl):
                    rm[j] = vocab.setdefault(s, len(vocab))
                remapped.append(rm[np.clip(v, 0, len(rm) - 1)])
            uni = pa.array(list(vocab.keys()) or [""], type=pa.string())
            merged_v = np.concatenate(remapped)[order]
            dicts.append(uni)
        else:
            merged_v = np.concatenate(vs)[order]
            dicts.append(None)
        merged_m = np.concatenate(ms)[order]
        pad = cap - total
        out_vals.append(jnp.asarray(np.pad(merged_v, (0, pad))))
        out_mask.append(jnp.asarray(np.pad(merged_m, (0, pad))))
    sel = np.zeros(cap, bool)
    sel[:total] = True
    dev = DeviceBatch(jnp.asarray(sel), tuple(out_vals), tuple(out_mask))
    return Batch(schema, dev, tuple(dicts))
