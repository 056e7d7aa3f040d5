"""Hash-aggregate exec (TPU sort-segmented design).

Semantics mirror the reference's aggregation operator
(datafusion-ext-plans/src/agg_exec.rs + agg/: modes Partial / PartialMerge /
Final, grouping keys + agg functions sum/count/avg/min/max/first/
first_ignores_null, partial-aggregation skipping at high cardinality
(agg/agg_table.rs:448, confs conf.rs:38-41)) — but the execution strategy is
TPU-first: instead of a row hash table, every (micro-)aggregation is a
multi-key ``lax.sort`` + segment reduction with static shapes
(ops/segments.py), and state accumulation is merge-regroup over prefix-packed
group batches:

- Partial: each input batch is grouped & reduced to an *intermediate* batch
  (keys + accumulator columns); intermediates accumulate and are re-merged
  when the staged row count crosses a threshold, keeping state compact;
- PartialMerge / Final: inputs are already intermediate batches (post
  shuffle); the same merge-regroup runs, and Final applies finalizers
  (avg = sum/count with Spark decimal typing, etc.).

Aggregate type rules follow Spark: sum(int*)->long (wrapping, non-ANSI),
sum(float*)->double, sum(decimal(p,s))->decimal(p+10,s),
avg(decimal(p,s))->decimal(p+4,s+4), avg(numeric)->double,
count->long (never null).
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Iterator

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa
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
from auron_tpu.exec.basic import batch_from_columns
from auron_tpu.exprs import Evaluator, ir
from auron_tpu.exprs import decimal_math as D
from auron_tpu.exprs.eval import ColumnVal
from auron_tpu.ops import hostsort
# top-level on purpose: binsearch/hashing hold module-level jnp constants or
# feed jitted programs — lazy in-trace imports would leak tracers (see
# ops/segments.py import note)
from auron_tpu.ops import binsearch, hashing
from auron_tpu.ops import segments as S
from auron_tpu.utils.config import (
    AGG_INCREMENTAL_ENABLE,
    AGG_INCREMENTAL_FINGERPRINT,
    AGG_INCREMENTAL_FP_BITS,
    AGG_INCREMENTAL_MERGEPATH,
    AGG_INCREMENTAL_PROBE,
    PARTIAL_AGG_SKIPPING_ENABLE,
    PARTIAL_AGG_SKIPPING_MIN_ROWS,
    PARTIAL_AGG_SKIPPING_RATIO,
    TRANSFER_WINDOW_DEPTH,
    active_conf,
    resolve_tri,
)

PARTIAL = "partial"
PARTIAL_MERGE = "partial_merge"
FINAL = "final"


@dataclass(frozen=True)
class AggExpr:
    func: str  # sum|count|count_star|avg|min|max|first|first_ignores_null|collect_list|collect_set|host_udaf
    expr: ir.Expr | None = None  # None only for count_star
    udaf: str | None = None  # host_udaf: name registered with bridge.udf


def sum_type(t: T.DataType) -> T.DataType:
    if t.kind == T.TypeKind.DECIMAL:
        return T.decimal(min(t.precision + 10, 38), t.scale)
    if t.is_float:
        return T.FLOAT64
    if t.is_integer:
        return T.INT64
    raise TypeError(f"sum over {t}")


def avg_type(t: T.DataType) -> T.DataType:
    if t.kind == T.TypeKind.DECIMAL:
        return T.decimal(min(t.precision + 4, 38), min(t.scale + 4, 37))
    return T.FLOAT64


def final_type(a: AggExpr, in_t: T.DataType | None) -> T.DataType:
    if a.func in ("count", "count_star"):
        return T.INT64
    if a.func == "sum":
        return sum_type(in_t)
    if a.func == "avg":
        return avg_type(in_t)
    if a.func in ("collect_list", "collect_set"):
        return T.DataType(T.TypeKind.LIST, inner=(in_t,))
    if a.func == "host_udaf":
        from auron_tpu.bridge.udf import lookup_udaf

        return lookup_udaf(a.udaf).out_dtype
    return in_t  # min/max/first


def is_wide_sum(in_t: T.DataType | None) -> bool:
    """Wide decimal sums (result precision > 18) would silently wrap int64
    during accumulation; they accumulate as base-1e6 limbs instead (linear,
    so per-limb segment sums stay exact; carries only at reconstruction)."""
    if in_t is None or in_t.kind != T.TypeKind.DECIMAL:
        return False
    return sum_type(in_t).precision > 18


def _n_limbs(sum_precision: int) -> int:
    """Base-1e9 limbs covering the sum's digit budget (<= 5 for p38)."""
    return -(-sum_precision // 9)


def _wide_sum_fields(in_t: T.DataType, prefix: str) -> list[T.Field]:
    st = sum_type(in_t)
    k = _n_limbs(st.precision)
    # limb0 carries the scale plus (via its name) the exact input
    # precision, so merge/final modes reconstruct the layout and output
    # type from the shuffled schema alone
    fields = [
        T.Field(f"{prefix}#sum0p{in_t.precision}", T.decimal(18, in_t.scale), True)
    ]
    fields += [T.Field(f"{prefix}#sum{i}", T.INT64, True) for i in range(1, k)]
    return fields


def _is_64bit_plane(t: T.DataType) -> bool:
    """Does a column of this type hold 8-byte values on the device? Such a
    plane costs twice a 32-bit one to gather and eight to nineteen times one
    to scatter (PERF.md section 5, "unit costs"), which is why the dense
    fold sums an integer plane as ``k`` int32 limbs (``k`` from
    ``ops/segments.py limb_plan``: the type's bits and the batch's rows)
    and prices it ``k`` narrow scatters; a float sum, a minimum or a
    maximum of such a plane keeps its one wide scatter."""
    return np.dtype(t.physical_dtype().name).itemsize == 8


def intermediate_fields(a: AggExpr, in_t: T.DataType | None, prefix: str) -> list[T.Field]:
    if a.func in ("count", "count_star"):
        return [T.Field(f"{prefix}#count", T.INT64, False)]
    if a.func == "sum":
        if is_wide_sum(in_t):
            return _wide_sum_fields(in_t, prefix)
        return [T.Field(f"{prefix}#sum", sum_type(in_t), True)]
    if a.func == "avg":
        if is_wide_sum(in_t):
            return _wide_sum_fields(in_t, prefix) + [
                T.Field(f"{prefix}#count", T.INT64, False)
            ]
        return [
            T.Field(f"{prefix}#sum", sum_type(in_t), True),
            T.Field(f"{prefix}#count", T.INT64, False),
        ]
    if a.func in ("min", "max"):
        return [T.Field(f"{prefix}#{a.func}", in_t, True)]
    if a.func in ("first", "first_ignores_null"):
        return [
            T.Field(f"{prefix}#value", in_t, True),
            T.Field(f"{prefix}#seen", T.BOOL, False),
        ]
    if a.func in ("collect_list", "collect_set"):
        return [
            T.Field(
                f"{prefix}#items",
                T.DataType(T.TypeKind.LIST, inner=(in_t,)),
                True,
            )
        ]
    if a.func == "host_udaf":
        # pickled accumulator state per group (bounded by state size, not
        # input count — SparkUDAFWrapperContext's state-batch FFI analog)
        return [T.Field(f"{prefix}#state", T.BINARY, True)]
    raise ValueError(a.func)


class HashAggExec(ExecOperator):
    def __init__(
        self,
        child: ExecOperator,
        groupings: list[tuple[ir.Expr, str]],
        aggs: list[tuple[AggExpr, str]],
        mode: str,
    ):
        assert mode in (PARTIAL, PARTIAL_MERGE, FINAL)
        self.mode = mode
        self.groupings = groupings
        self.aggs = aggs
        in_schema = child.schema

        key_fields = []
        for e, name in groupings:
            if mode == PARTIAL:
                key_fields.append(T.Field(name, e.dtype_of(in_schema), True))
            else:
                # keys arrive by position at the front of the child schema
                key_fields.append(in_schema[len(key_fields)])

        self._agg_input_types: list[T.DataType | None] = []
        inter_fields: list[T.Field] = []
        ofs = len(key_fields)
        for a, name in aggs:
            if mode == PARTIAL:
                in_t = a.expr.dtype_of(in_schema) if a.expr is not None else None
            else:
                # recover input type from the intermediate schema (the
                # first field carries the logical type, so the layout
                # width — e.g. wide-sum limbs — derives from it)
                first_f = in_schema[ofs]
                in_t = _input_type_from_intermediate(a, first_f)
                n_inter = len(
                    intermediate_fields(a, in_t if in_t is not None else T.INT64, name)
                )
                ofs += n_inter
            self._agg_input_types.append(in_t)
            inter_fields += intermediate_fields(a, in_t, name)

        if mode == FINAL:
            out_fields = key_fields + [
                T.Field(name, final_type(a, t), True)
                for (a, name), t in zip(aggs, self._agg_input_types)
            ]
        else:
            out_fields = key_fields + inter_fields
        super().__init__([child], T.Schema(tuple(out_fields)))
        self.n_keys = len(key_fields)
        self.inter_schema = T.Schema(tuple(key_fields + inter_fields))
        self._has_host_aggs = any(
            a.func in ("collect_list", "collect_set", "host_udaf") for a, _ in aggs
        )
        self._reduce_cfg = (
            self.n_keys,
            tuple(f.dtype for f in key_fields),
            tuple((a, t) for (a, _), t in zip(aggs, self._agg_input_types)),
        )

    def _sort_flags(self, sel, force_full_sort: bool = False, conf=None) -> tuple:
        """(host_sort, device_impl, fingerprint, fp_bits) resolved from
        config at call time — static members of the reduce cfg so the jit
        cache retraces on a config change instead of reusing a stale
        compiled sort choice. ``force_full_sort`` pins the legacy
        full-word segmentation regardless of config (the dedup reduce a
        FINAL-mode merge needs after a fingerprint collision)."""
        conf = conf if conf is not None else active_conf()
        fingerprint = (
            not force_full_sort
            and self.n_keys >= 1
            and self._fingerprint_on(conf)
        )
        fp_bits = conf.get(AGG_INCREMENTAL_FP_BITS) if fingerprint else 64
        if hostsort.use_host_sort(conf, rows=int(sel.shape[0])):
            return (True, "lax", fingerprint, fp_bits)
        if fingerprint:
            # fixed 3-operand (dead, fp, iota) sort: lax.sort is the right
            # impl at that width on every backend (ops/bitonic tuning
            # targets the wide-operand case this path removes)
            return (False, "lax", True, fp_bits)
        from auron_tpu.ops import bitonic

        n_words = self.n_keys + (1 if self.n_keys else 0)  # + null-bits word
        n_narrow = 1 if 0 < self.n_keys <= 32 else 0  # null-bits word rides narrow
        return (
            False,
            bitonic.sort_impl_for(n_words, int(sel.shape[0]), n_narrow, conf=conf),  # auronlint: sort-payload -- legacy full-word grouping fallback (fingerprint off / collision dedup): exactness needs every key word as a sort plane
            False,
            64,
        )

    @staticmethod
    def _tri(opt, conf=None) -> bool:
        """Resolve an on|off|auto incremental knob: auto = accelerators
        only. Every incremental building block (fingerprint hash amortized
        by a narrower sort, scatter-add, merge-rank permutation build) is
        a win on vector units and a loss on XLA:CPU, whose scatters lower
        to serial loops and whose grouping sort is already the host
        lexsort (ops/hostsort.py) — same fork, same default.

        ``conf``: REQUIRED on any path a cross-thread spill can reach
        (_merge and below): active_conf() is thread-local, so the spilling
        thread would otherwise resolve a FOREIGN task's knobs and e.g.
        fingerprint a layout sorted under different fp.bits."""
        return resolve_tri(
            (conf if conf is not None else active_conf()).get(opt),
            jax.default_backend() != "cpu",
        )

    def _fingerprint_on(self, conf=None) -> bool:
        conf = conf if conf is not None else active_conf()
        return bool(
            conf.get(AGG_INCREMENTAL_ENABLE)
            and self._tri(AGG_INCREMENTAL_FINGERPRINT, conf)
        )

    def _keys_dict_free(self) -> bool:
        """No group-key column is dictionary-encoded: fingerprints of key
        words are then stable across batches (dict codes are per-batch
        vocabularies — a cross-batch remap would reorder every fp-sorted
        run), the precondition for sorted-state probing and merge-path."""
        return all(
            not self.inter_schema[i].dtype.is_dict_encoded
            for i in range(self.n_keys)
        )

    def _mergepath_eligible(self, conf=None) -> bool:
        return (
            self.n_keys >= 1
            and not self._has_host_aggs
            and self._keys_dict_free()
            and self._fingerprint_on(conf)
            and self._tri(AGG_INCREMENTAL_MERGEPATH, conf)
        )

    def _probe_eligible(self) -> bool:
        """Sorted-state probe/scatter: every aggregate must have a pure
        device scatter-update form and every column it touches a stable
        cross-batch encoding (no per-batch dictionaries)."""
        if self.n_keys < 1 or self._has_host_aggs or not self._keys_dict_free():
            return False
        if not (self._fingerprint_on() and self._tri(AGG_INCREMENTAL_PROBE)):
            return False
        for (a, _), in_t in zip(self.aggs, self._agg_input_types):
            if a.func not in (
                "sum", "avg", "count", "count_star", "min", "max",
                "first", "first_ignores_null",
            ):
                return False
            if in_t is not None and in_t.is_dict_encoded:
                # covers strings AND wide (p>18) decimal inputs; narrow
                # inputs with wide SUM types keep the device limb path
                return False
        return True

    # ------------------------------------------------------------------

    def _dense_eligible(self) -> bool:
        """Up to three small-range integer group keys + simple aggregates run
        as a DENSE direct-address table (one fused scatter-reduce per
        batch, no sort — the TPU-idiomatic analog of the reference's
        integer-keyed agg hash map, agg/agg_hash_map.rs). Range discovery
        and mid-stream fallback live in _DenseAggState.update and the
        dense block of _execute."""
        if not (1 <= self.n_keys <= 3) or self._has_host_aggs:
            return False
        for i in range(self.n_keys):
            kt = self.inter_schema[i].dtype
            # BOOL is the densest possible key (2 value lanes + NULL):
            # its exclusion kept q93-class IsNull-keyed aggregates on the
            # per-batch sort-segmentation path — every fold path casts
            # keys through int64 and reconstructs through the field's
            # physical dtype, so 0/1 round-trips exactly
            if kt.is_dict_encoded or kt.kind not in (
                T.TypeKind.INT8, T.TypeKind.INT16, T.TypeKind.INT32,
                T.TypeKind.INT64, T.TypeKind.DATE32, T.TypeKind.TIMESTAMP,
                T.TypeKind.BOOL,
            ):
                return False
        for (a, _), in_t in zip(self.aggs, self._agg_input_types):
            if a.func not in ("sum", "avg", "count", "count_star", "min", "max"):
                return False
            if a.func in ("sum", "avg") and is_wide_sum(in_t):
                return False
            if in_t is not None and in_t.is_dict_encoded:
                return False
        return True

    def _execute(self, partition: int, ctx: ExecutionContext) -> Iterator[Batch]:
        conf = ctx.conf
        skipping_enabled = (
            self.mode == PARTIAL and conf.get(PARTIAL_AGG_SKIPPING_ENABLE)
        )
        skip_ratio = conf.get(PARTIAL_AGG_SKIPPING_RATIO)
        skip_min_rows = conf.get(PARTIAL_AGG_SKIPPING_MIN_ROWS)

        from auron_tpu.exec.sort_exec import batch_nbytes
        from auron_tpu.memory.memmgr import MemManager

        mm = MemManager.get()
        table = _AggTableConsumer(self, ctx)
        # registration happens inside the try below, next to dense's and
        # probe's: ~300 lines of setup (knob resolution, dense/probe/
        # window construction) run between here and the stream loop, and
        # an exception there must not leak registered consumers in the
        # process-wide manager (R11; the unregisters in the finally are
        # membership-checked, so never-registered consumers are safe)
        seen_rows = 0
        seen_groups = 0
        skipping = False
        merge_threshold = max(ctx.batch_size() * 4, 1 << 15)

        # device scalar: group count of the PREVIOUS batch — synced together
        # with the next batch's row count (one transfer per batch); the skip
        # heuristic tolerates the one-batch lag
        pending_g = None
        pending_proxy = 0
        # dense direct-address accumulator (no sort, one fused scatter-
        # reduce per batch); drains into the generic table when the key
        # range outgrows the dense limit
        # dense is a fixed-footprint table (<= LIMIT slots x field
        # widths): registered below as an UNSPILLABLE consumer so its
        # bytes shrink the pool others fair-share (same citizenship as
        # resident join builds)
        dense = _DenseAggState(self, ctx) if self._dense_eligible() else None

        def drain_dense_into_table():
            sb, g = dense.state_batch_and_count()
            if sb is not None:
                sb._groups = g
                mm.acquire(table, batch_nbytes(sb))
                table.add(sb, g)

        def skip_or_stage(inter, g):
            """One counted intermediate of the generic path, blocking or
            deferred: pass it through in partial-agg skipping mode (yields),
            else stage it into the table and merge when due."""
            nonlocal skipping
            if skipping:
                self._note_emit(inter)
                yield inter
                return
            if (
                skipping_enabled
                and seen_rows >= skip_min_rows
                and seen_groups >= skip_ratio * seen_rows
                and not table.parked
            ):
                # high cardinality: stop accumulating, stream through
                ctx.metrics.add("partial_agg_skipped", 1)
                skipping = True
                for held in table.drain():
                    self._note_emit(held)
                    yield held
                self._note_emit(inter)
                yield inter
                return
            mm.acquire(table, batch_nbytes(inter))
            table.add(inter, g)
            # geometric amortization: compacting re-reduces the WHOLE
            # state, so only do it once the staged rows rival the state
            # size — otherwise high-cardinality aggs go quadratic in
            # merge work (measured as the q5-class merge_time blowup)
            if table.staged_rows >= max(merge_threshold, table.state_capacity()):
                with ctx.metrics.timer("merge_time"):
                    table.compact()
                ctx.metrics.add("num_merges", 1)

        def process_generic(b):
            # generic (sort-segmentation) path for ONE batch; yields
            # pass-through output in partial-agg skipping mode
            nonlocal pending_g, pending_proxy, seen_rows, seen_groups
            in_capacity = b.capacity
            if self.mode == PARTIAL:
                # sync the live count FIRST: sparse batches (post-filter/
                # join output still at input capacity) are compacted
                # before the O(cap log cap) sort-segmentation — grouping
                # cost follows live rows, not the capacity bucket.
                # The previous batch's group count rides the same
                # transfer (its reduce has completed by now), so steady
                # state pays ONE host round-trip per batch.
                if pending_g is None:
                    # auronlint: disable=R9 -- first-batch-only branch: pending_g is None exactly once per stream (plus spill restarts, covered by the 4/task budget)
                    n = int(jax.device_get(b.device.num_rows()))  # auronlint: sync-point(4/task) -- first-batch live-count read (see comment above)
                else:
                    g_dev, coll_dev, inter_ref = pending_g
                    scalars = [b.device.num_rows(), g_dev]
                    if coll_dev is not None:
                        scalars.append(coll_dev)
                    got = [
                        int(x)
                        for x in jax.device_get(tuple(scalars))  # auronlint: sync-point(1/batch) -- steady state: ONE round-trip per batch (count + prior group count + fp collision flag)
                    ]
                    n, gp = got[0], got[1]
                    if coll_dev is not None:
                        _note_collision(inter_ref, got[2], ctx.metrics)
                    seen_groups += gp
                    # replace the previous batch's staged-rows proxy with
                    # its exact group count, so low-cardinality aggs don't
                    # cross the merge threshold on inflated estimates
                    table.adjust_staged(gp - pending_proxy)
                    # groups live in a valid prefix: shrink the staged
                    # intermediate to its group bucket so the eventual
                    # merge concat scales with GROUPS, not input
                    # capacity (low-cardinality aggs were paying a
                    # full-capacity concat per staged batch)
                    table.shrink_last(gp)
                    pending_g = None
                if n == 0:
                    return
                if 4 * n <= b.capacity:
                    from auron_tpu.columnar.batch import compact_batch

                    b = compact_batch(b, bucket_capacity(n))
                obs.note_agg_fold(b.capacity, in_capacity, path="sort",
                                  mode=self.mode, live=n)
                with ctx.metrics.timer("elapsed_compute"):
                    inter = self._to_intermediate(b, ctx)
                pending_g = (
                    inter.device.num_rows(),
                    getattr(inter, "_fp_collision", None),
                    inter,
                )
                g = pending_proxy = min(n, inter.capacity)  # proxy; the
                # exact count settles one batch later via pending_g
            else:
                # merge modes never compact: one combined transfer
                obs.note_agg_fold(b.capacity, in_capacity, path="sort",
                                  mode=self.mode)
                with ctx.metrics.timer("elapsed_compute"):
                    inter = self._to_intermediate(b, ctx)
                coll_dev = getattr(inter, "_fp_collision", None)
                scalars = [b.device.num_rows(), inter.device.num_rows()]
                if coll_dev is not None:
                    scalars.append(coll_dev)
                got = [
                    int(x)
                    for x in jax.device_get(tuple(scalars))  # auronlint: sync-point(1/batch) -- merge modes: one combined transfer per batch (+ fp collision flag)
                ]
                n, g = got[0], got[1]
                if coll_dev is not None:
                    _note_collision(inter, got[2], ctx.metrics)
                if n == 0:
                    return
                # groups live in a valid prefix and g is exact here:
                # stage at the group bucket so merge concat scales
                # with groups, not the input capacity
                inter = self._at_group_bucket(inter, g)
            seen_rows += n
            if self.mode != PARTIAL:
                seen_groups += g
            yield from skip_or_stage(inter, g)

        def fold_dense(nb, defer: bool = True, noted: dict | None = None,
                       ) -> list | None:
            """Fold one batch through the dense table, driving the
            drain/re-anchor protocol (the anchored fold is deferred: its
            in-range flag is read when the NEXT batch arrives, so steady
            state pays no per-batch blocking sync; defer=False resolves
            synchronously — used at end of stream). ``noted`` is what the
            compaction boundary said of ``nb`` for the fold's ring event
            (``in_rows``, ``live``, ``take``: obs.note_agg_fold); a held
            batch folded again after a restart has none. Returns None when
            folded, or — after a permanent fallback (dense set to None) —
            the batches that must flow to the generic path instead."""
            nonlocal dense, skipping_enabled
            todo = [nb]
            while todo:
                cur = todo.pop(0)
                obs.note_agg_fold(cur.capacity, path="dense", mode=self.mode,
                                  scatters=(None if dense._host else
                                            dense.fold_scatters(cur.capacity)),
                                  **(noted or {"in_rows": cur.capacity}))
                noted = None
                r = dense.update(cur, defer=defer)
                if r == "restart":
                    # ranges outgrew the anchored table: drain the
                    # accumulated groups into the generic consumer and
                    # re-anchor on the failed batches' union ranges
                    drain_dense_into_table()
                    todo = dense.reset_with_retry() + [cur] + todo
                elif r is False:
                    # the union range can never fit: permanent fallback to
                    # the sort-segmentation path from this batch on
                    if dense.bases is not None or table.staged:
                        # rows already folded/drained: the skip heuristic's
                        # row/group counters never saw them — keep it off
                        skipping_enabled = False
                    drain_dense_into_table()
                    left = dense.take_retry() + [cur] + todo
                    mm.unregister(dense)
                    dense.release(mm)
                    dense = None
                    return left
            return None

        # the dense arm's compaction boundary (exec/selectivity.py, docs/
        # pipeline.md section 1): a batch behind a selective filter or join
        # comes in at its input's capacity, and one scatter a row of
        # capacity is what the fold costs, so the batch is folded at the
        # bucket of its live rows and one of no rows not at all. The rule
        # is compaction_bucket's, by shape: staying dense scatters
        # fold_planes(capacity) elements a row of capacity; compacting
        # builds the index, gathers the planes the fold reads and scatters,
        # a row of bucket (XLA:CPU: the quarter rule, in front of the host
        # fold too)
        dense_boundary = None
        if dense is not None:
            from auron_tpu.columnar.batch import compact_batch, compaction_bucket
            from auron_tpu.exec.selectivity import CompactionBoundary

            fold_cols, take_planes = self._fold_columns()
            # bound now: ``dense`` goes to None on a permanent fallback while
            # the boundary still sizes the batches it holds
            fold_planes_at = dense.fold_planes

            def dense_bucket_of(n_live: int, capacity: int) -> int | None:
                fold_planes = fold_planes_at(capacity)
                return compaction_bucket(
                    n_live, capacity, dense_planes=fold_planes,
                    taken_planes=take_planes + fold_planes,
                )

            dense_boundary = CompactionBoundary(
                conf, dense_bucket_of, ctx.metrics)

        def take_dense(b, mode: str, out_cap: int | None):
            """The boundary's take: (mode, the batch to fold), ``b`` itself
            where it stays dense, else the columns the fold reads compacted
            into ``out_cap`` rows; nothing where the count just read is 0."""
            if dense_boundary.live == 0:
                return mode, None
            if out_cap is None:
                return mode, b
            ctx.metrics.add("agg_compacted_batches", 1)
            return mode, compact_batch(b, out_cap, cols=fold_cols)

        def offer_dense(b):
            """One batch into the dense arm's boundary; yields what the
            generic path passes through of the batches that come out."""
            dense.waiting_bytes += batch_nbytes(b)
            for held, taken in dense_boundary.offer(
                b.device.num_rows(), b.capacity, partial(take_dense, b), b
            ):
                yield from fold_taken(held, taken)

        def fold_taken(held, taken):
            """Fold one batch the boundary emitted (FIFO, up to the window's
            depth behind its dispatch) at the width it was taken at; one
            whose count came out 0 leaves its event and no program."""
            mode, nb = taken
            live = dense_boundary.live
            if dense is not None:
                dense.waiting_bytes -= batch_nbytes(held)
            if live == 0:
                obs.note_agg_fold(0, held.capacity, path="dense",
                                  mode=self.mode, live=0, take="empty")
                return
            if dense is None:
                # a permanent fallback while this batch waited
                yield from feed_generic(nb)
                return
            with ctx.metrics.timer("elapsed_compute", count=True):
                leftovers = fold_dense(nb, noted={
                    "in_rows": held.capacity, "live": live, "take": mode})
            for gb in leftovers or ():
                yield from feed_generic(gb)

        # sorted-state probe/scatter: engages once a compact() has produced
        # an fp-sorted state batch (and the dense table, which runs in
        # front, is out of the picture)
        probe = _ProbeScatter(self, ctx, table) if self._probe_eligible() else None

        # deferred PARTIAL counts (docs/fusion.md): the generic path's
        # steady-state "ONE round-trip per batch" read (the device_get
        # above at the sync-point(1/batch) site) becomes a k-deep read
        # through the async transfer window — the upstream probe/stage
        # pipeline dispatches ahead instead of blocking per batch
        # (q93-class: 227 blocking syncs / 38s of drain). Compaction
        # buckets come from the selectivity predictor; a truncating
        # mispredict recomputes the reduce from the still-held batch (bit-
        # identical, rare: the predictor grows immediately). Not armed
        # when host aggregates sync internally anyway, or when the
        # sorted-state probe is active (its direct state folds must not
        # overtake window-pending batches — the first/first_ignores_null
        # stream-order contract its spill-park test pins).
        defer_win = None
        defer_pred = None
        if self.mode == PARTIAL and not self._has_host_aggs and probe is None:
            from auron_tpu.exec.selectivity import SelectivityPredictor
            from auron_tpu.runtime.transfer import TransferWindow

            defer_win = TransferWindow(conf.get(TRANSFER_WINDOW_DEPTH))
            defer_pred = SelectivityPredictor()

        def fold_deferred(bb, in_capacity):
            """One PARTIAL raw fold of the deferred arm (dispatch and
            mispredict repair alike), noted in the rings with the capacity
            the reduce runs at beside the capacity the batch came in with
            (an event, not a region: the pump's self time keeps its
            meaning)."""
            obs.note_agg_fold(bb.capacity, in_capacity)
            with ctx.metrics.timer("elapsed_compute"):
                return self._to_intermediate(bb, ctx)

        def dispatch_deferred(b):
            """Dispatch half: device work only — predicted compaction +
            the grouped reduce; the (live count, group count, collision
            flag) scalars ride the window host-ward. A stream's FIRST
            batch has no history to predict from: its live count is read
            here, once a stream, and seeds the predictor, so compaction
            engages from batch 1 and not from the first harvest (depth + 1
            batches in — past the end of a short stream)."""
            from auron_tpu.columnar.batch import compact_batch, compaction_bucket

            pred_cap = defer_pred.predict(b.capacity)
            seeded = pred_cap is None
            if seeded:
                # auronlint: disable=R9 -- first-batch-only branch: predict() is None exactly once per stream (the observe below seeds it)
                n_seed = int(jax.device_get(b.device.num_rows()))  # auronlint: sync-point(4/task) -- deferred-agg seed: the stream's first live count, read before its reduce is dispatched
                defer_pred.observe(n_seed)
                ctx.metrics.add("sel_seed_reads", 1)
                pred_cap = defer_pred.predict(b.capacity)
            used_cap = compaction_bucket(pred_cap, b.capacity)
            bb = b
            if used_cap is not None:
                # may truncate on a mispredict — resolve_deferred
                # detects n > used_cap and recomputes from ``b``
                bb = compact_batch(b, used_cap)
                ctx.metrics.add("agg_compacted_batches", 1)
            inter = fold_deferred(bb, b.capacity)
            coll = getattr(inter, "_fp_collision", None)
            scalars = [b.device.num_rows(), inter.device.num_rows()]
            if coll is not None:
                scalars.append(coll)
            return tuple(scalars), (b, inter, used_cap, coll is not None, seeded)

        def resolve_deferred(resolved, state):
            """Harvest half, k batches behind dispatch: exact (n, g) land
            together — no pending_g carry — and the intermediate stages at
            its exact group bucket."""
            nonlocal seen_rows, seen_groups
            b, inter, used_cap, has_coll, seeded = state
            n, g = int(resolved[0]), int(resolved[1])
            if not seeded:
                # the seed batch was observed at dispatch: the EWMA and the
                # shrink streak count batches, never one twice
                defer_pred.observe(n, predicted=used_cap)
            if n == 0:
                return
            if used_cap is not None and n > used_cap:
                # predicted bucket truncated live rows: recompute from the
                # still-held original batch at the exact bucket
                from auron_tpu.columnar.batch import (
                    compact_batch, compaction_bucket,
                )

                ctx.metrics.add("sel_mispredicts", 1)
                out_cap = compaction_bucket(n, b.capacity)
                bb = b if out_cap is None else compact_batch(b, out_cap)
                inter = fold_deferred(bb, b.capacity)
                coll = getattr(inter, "_fp_collision", None)
                scalars = [inter.device.num_rows()]
                if coll is not None:
                    scalars.append(coll)
                # auronlint: disable=R9 -- mispredict repair only: fires when the predictor under-sized a bucket; growth-on-mispredict bounds it per stream
                got = [int(x) for x in jax.device_get(tuple(scalars))]  # auronlint: sync-point(4/task) -- deferred-agg mispredict repair: exact group-count re-read after a truncating bucket miss
                g = got[0]
                if coll is not None:
                    _note_collision(inter, got[1], ctx.metrics)
            elif has_coll:
                _note_collision(inter, int(resolved[2]), ctx.metrics)
            seen_rows += n
            seen_groups += g
            inter = self._at_group_bucket(inter, g)
            yield from skip_or_stage(inter, g)

        def feed_generic(b):
            """Route one batch to the generic path: through the deferred
            window when armed, else the classic blocking protocol."""
            if defer_win is not None:
                arrays, state = dispatch_deferred(b)
                for resolved, st in defer_win.push(arrays, state):
                    yield from resolve_deferred(resolved, st)
            else:
                yield from process_generic(b)

        try:
            mm.register(table)
            if dense is not None:
                mm.register(dense, spillable=False)
            if probe is not None:
                mm.register(probe, spillable=False)
            for b in self.child_stream(0, partition, ctx):
                ctx.check_cancelled()
                if dense is not None:
                    yield from offer_dense(b)
                    continue
                if probe is not None and not skipping:
                    with ctx.metrics.timer("elapsed_compute", count=True):
                        folded, misses, hit_rows = probe.fold(b)
                    if folded:
                        obs.note_agg_fold(b.capacity, b.capacity, path="probe",
                                          mode=self.mode)
                    # probed hits are rows with ZERO new groups: they must
                    # keep pulling the skip heuristic's cardinality ratio
                    # down (only the generic path updates it otherwise)
                    seen_rows += hit_rows
                    for mb in misses:
                        yield from process_generic(mb)
                    if folded:
                        continue
                    yield from process_generic(b)
                    continue
                yield from feed_generic(b)
            # end of stream: fold what still waits in the dense arm's
            # boundary for its count (after a permanent fallback: hand it
            # to the generic path), THEN resolve the in-flight deferred
            # dense folds (up to window-depth of them) via the same
            # protocol, synchronously (there is no next batch to piggyback
            # on)
            if dense_boundary is not None:
                for held, taken in dense_boundary.drain():
                    yield from fold_taken(held, taken)
                if dense_boundary.predictions:
                    ctx.metrics.add("sel_pred_batches", dense_boundary.predictions)
            if dense is not None:
                for nb in dense.finish_pending():
                    if dense is None:
                        # a prior retry forced permanent fallback
                        yield from feed_generic(nb)
                        continue
                    with ctx.metrics.timer("elapsed_compute"):
                        leftovers = fold_dense(nb, defer=False)
                    for gb in leftovers or ():
                        yield from feed_generic(gb)
            if probe is not None:
                for mb in probe.finish():
                    yield from process_generic(mb)
            if pending_g is not None:
                # end of stream: the last blocking PARTIAL fold's group
                # count has no next batch to ride with. Read it here, so
                # that the state goes on at its group bucket and not at the
                # capacity its batch came in with (a 13-group average would
                # otherwise reach the shuffle writer 131,072 rows wide)
                g_dev, coll_dev, inter_ref = pending_g
                scalars = (g_dev,) if coll_dev is None else (g_dev, coll_dev)
                got = [int(x) for x in jax.device_get(scalars)]  # auronlint: sync-point(4/task) -- end-of-stream settle of the last blocking partial fold's group count (+ fp collision flag)
                if coll_dev is not None:
                    _note_collision(inter_ref, got[1], ctx.metrics)
                seen_groups += got[0]
                table.adjust_staged(got[0] - pending_proxy)
                table.shrink_last(got[0])
                pending_g = None
            # drain the deferred-count window: entries resolve in FIFO
            # order with the same exactly-once staging as the in-stream
            # harvests (a cancellation skips this — the finally below
            # drops in-flight intermediates with the table)
            if defer_win is not None:
                for resolved, st in defer_win.drain():
                    yield from resolve_deferred(resolved, st)
        finally:
            if dense is not None:
                drain_dense_into_table()
                mm.unregister(dense)
                dense.release(mm)
                dense = None
            if probe is not None:
                mm.unregister(probe)
                probe.release()
            mm.unregister(table)

        if skipping:
            return
        with ctx.metrics.timer("merge_time"):
            state = table.collect_state()
        if state is None:
            if self.n_keys == 0:
                yield self._empty_global_agg(ctx)
            return
        self._note_emit(state)
        if self.mode == FINAL:
            yield self._finalize(state)
        else:
            yield state

    # ------------------------------------------------------------------

    def _keys_and_inputs(self, b: Batch):
        """(key ColumnVals, per-agg ((values, validity), ...) input pairs)
        for one batch — the raw-vs-merge input extraction shared by the
        dense table and the probe/scatter path (column alignment against
        inter_schema must never diverge between them)."""
        if self.mode == PARTIAL:
            ev = Evaluator(self.children[0].schema)
            keys = ev.evaluate(b, [g for g, _ in self.groupings])
            per_agg = []
            for (a, _), in_t in zip(self.aggs, self._agg_input_types):
                if a.expr is None:
                    per_agg.append(())
                    continue
                cv = ev.evaluate(b, [a.expr])[0]
                if a.func in ("sum", "avg") and not is_wide_sum(in_t):
                    # wide sums consume the raw input (limb machinery) —
                    # same rule as _to_intermediate
                    cv = ev._cast(cv, sum_type(in_t))
                per_agg.append(((cv.values, cv.validity),))
            return keys, tuple(per_agg)
        keys = self._state_keys(b)
        per_agg = tuple(
            tuple((cv.values, cv.validity) for cv in grp)
            for grp in self._intermediate_groups(b)
        )
        return keys, per_agg

    def _fold_columns(self) -> tuple[tuple[int, ...] | None, int]:
        """(the child's columns ``_keys_and_inputs`` reads, the planes a
        take of them gathers): None where a fold reads every column (the
        merge modes' intermediate layout). A plane is one array gathered
        at the take's width, values and validities alike, a 64-bit one
        counted twice (PERF.md section 5, "unit costs")."""
        schema = self.children[0].schema
        cols = None
        if self.mode == PARTIAL:
            exprs = [g for g, _ in self.groupings]
            exprs += [a.expr for a, _ in self.aggs if a.expr is not None]
            cols = tuple(sorted({
                n.index for e in exprs for n in ir.walk(e)
                if isinstance(n, ir.Column)
            }))
        planes = 0
        for ci in (range(len(schema)) if cols is None else cols):
            planes += 3 if _is_64bit_plane(schema[ci].dtype) else 2
        return cols, planes

    def _state_keys(self, b: Batch) -> list[ColumnVal]:
        """Key-column ColumnVal view of an intermediate-layout batch — THE
        key extraction shared by merge/dedup/merge-path/probe so their key
        views can never diverge."""
        return [
            ColumnVal(b.col_values(i), b.col_validity(i),
                      self.inter_schema[i].dtype, b.dicts[i])
            for i in range(self.n_keys)
        ]

    def _intermediate_groups(self, b: Batch, ofs: int | None = None):
        """Per-agg groups of intermediate-field ColumnVals starting at
        column ``ofs`` (defaults to n_keys) — THE offset walk over
        intermediate_fields, shared by the merge path, _to_intermediate's
        merge branch and the dense accumulator so column alignment against
        inter_schema can never diverge between them."""
        ofs = self.n_keys if ofs is None else ofs
        groups: list[list[ColumnVal]] = []
        for (a, name), in_t in zip(self.aggs, self._agg_input_types):
            k = len(intermediate_fields(a, in_t if in_t is not None else T.INT64, name))
            groups.append([
                ColumnVal(
                    b.col_values(ofs + j),
                    b.col_validity(ofs + j),
                    self.inter_schema[ofs + j].dtype,
                    b.dicts[ofs + j],
                )
                for j in range(k)
            ])
            ofs += k
        return groups

    def _to_intermediate(self, b: Batch, ctx: ExecutionContext) -> Batch:
        """Group one batch and reduce it to intermediate form."""
        ev = Evaluator(self.children[0].schema)
        if self.mode == PARTIAL:
            keys = ev.evaluate(b, [e for e, _ in self.groupings])
            agg_inputs: list[list[ColumnVal]] = []
            for (a, _), in_t in zip(self.aggs, self._agg_input_types):
                if a.expr is None:
                    agg_inputs.append([])
                else:
                    cv = ev.evaluate(b, [a.expr])[0]
                    if a.func in ("sum", "avg") and not is_wide_sum(in_t):
                        # wide sums consume the raw input (limb machinery);
                        # a cast to the (dict-encoded) wide sum type is
                        # neither needed nor representable here
                        cv = ev._cast(cv, sum_type(in_t))
                    agg_inputs.append([cv])
            return self._group_reduce(b.device.sel, keys, agg_inputs, raw=True)
        else:
            keys = self._state_keys(b)
            return self._group_reduce(
                b.device.sel, keys, self._intermediate_groups(b), raw=False
            )

    def _merge(
        self,
        state: list[Batch],
        staged: list[Batch],
        metrics=None,
        final: bool = False,
        conf=None,
    ) -> Batch | None:
        """Merge prefix-packed group batches into one state batch.

        Three forms, picked per call from cheap host evidence:
        - merge-path (the incremental fast path): every part is an
          fp-sorted collision-free run → pairwise binsearch merge-rank
          merges (segment_merged), no sort at all;
        - legacy concat + sort-segmentation: any part without fp
          provenance (dense drains, disk runs) or with a collision flag;
        - forced FULL-WORD legacy: ``final`` and a collision was seen —
          the output IS the operator's final state, and only the full-word
          sort guarantees a colliding key can't surface as two split
          groups."""
        parts = [s for s in state + staged if s is not None]
        if not parts:
            return None
        collided = self._resolve_fp_flags(parts, metrics)
        if len(parts) == 1 and not (final and collided):
            return parts[0]
        if (
            not collided
            and len(parts) > 1
            and self._mergepath_eligible(conf)
            and all(getattr(p, "_fp_order", False) for p in parts)
        ):
            if metrics is not None:
                with metrics.timer("merge_path_s"):
                    acc = self._merge_path(parts, metrics, conf)
            else:
                acc = self._merge_path(parts, metrics, conf)
            if final and getattr(acc, "_fp_collision_host", False):
                # the collision AROSE in this very merge (two clean runs,
                # colliding keys across them): the output would be the
                # final state, so dedup with the full-word sort now
                acc = self._dedup_full_sort(acc, conf)
            return acc
        big = device_concat(parts)
        keys = self._state_keys(big)
        merged = self._group_reduce(
            big.device.sel, keys, self._intermediate_groups(big), raw=False,
            force_full_sort=final and collided, conf=conf,
        )
        # shrink back to a compact capacity bucket (host sync on group count)
        coll_dev = getattr(merged, "_fp_collision", None)
        if coll_dev is not None:
            g, coll = (
                # auronlint: disable=R9 -- amortized: _merge fires once per merge_threshold (>= 4 batches) of staged rows, not per batch
                int(x) for x in jax.device_get((merged.device.num_rows(), coll_dev))  # auronlint: sync-point(2/task) -- merge group-count read; the collision flag rides the same transfer
            )
            if coll and metrics is not None:
                # merged is this call's fresh reduce output — no other
                # thread can have counted it yet (unlike the shared staged
                # batches behind _FP_FLAG_LOCK)
                metrics.add("fp_collision_batches", 1)
            merged._fp_collision_host = bool(coll)
            out = self._at_group_bucket(merged, g)
            if final and coll:
                # collision arose in THIS fp-ordered merge — same dedup
                out = self._dedup_full_sort(out, conf)
            return out
        return self._at_group_bucket(merged, merged.num_rows())

    def _dedup_full_sort(self, b: Batch, conf=None) -> Batch:
        """Re-reduce one merged state batch with the legacy FULL-WORD sort:
        the exactness backstop for a FINAL-mode merge whose own layout
        picked up a fingerprint collision (split groups must never surface
        as output rows). One extra sort over the (group-bucketed) state —
        collisions are ~n²/2⁻⁶⁴, so this path is test-hook territory."""
        keys = self._state_keys(b)
        merged = self._group_reduce(
            b.device.sel, keys, self._intermediate_groups(b), raw=False,
            force_full_sort=True, conf=conf,
        )
        g = merged.num_rows()
        out = prefix_slice(merged, bucket_capacity(max(g, 1)))
        out._groups = g
        return out

    def _merge_path(self, parts: list[Batch], metrics, conf=None) -> Batch:
        """Sequential pairwise merge-rank merges: acc ⊕ part is two
        fp-sorted runs laid back to back by device_concat, permuted by two
        binary searches and segment-reduced — O(n log n) compares instead
        of re-sorting state + staged from scratch every merge (the q5-class
        merge_time blowup at agg_exec.py:393-396)."""
        acc = parts[0]
        for p in parts[1:]:
            big = device_concat([acc, p])
            keys = self._state_keys(big)
            fp_a = getattr(acc, "_inc_fp", None)
            fp_b = getattr(p, "_inc_fp", None)
            if fp_a is not None and fp_b is not None:
                # both runs carry their (dead-masked) fingerprints from the
                # reduce that produced them — concatenate instead of
                # re-hashing every key word per pair merge; pad rows are
                # dead, so they take the MAX sentinel like any dead slot
                fp_cat = jnp.concatenate([fp_a, fp_b])
                pad = big.capacity - fp_cat.shape[0]
                if pad:
                    fp_cat = jnp.pad(
                        fp_cat, (0, pad),
                        constant_values=np.uint64(0xFFFFFFFFFFFFFFFF),
                    )
            else:
                fp_cat = None
            merged = self._group_reduce(
                big.device.sel, keys, self._intermediate_groups(big),
                raw=False, merge_cap_a=acc.capacity, fp=fp_cat, conf=conf,
            )
            # ONE transfer: the compaction bucket read the legacy path pays
            # anyway, plus the cross-run collision flag riding along
            g, coll = (
                # auronlint: disable=R9 -- amortized: merge-path merges fire once per merge_threshold of staged rows, not per batch
                int(x) for x in jax.device_get(  # auronlint: sync-point(2/task) -- merge-path group-count + collision read, once per pair merge (amortized by the staging threshold)
                    (merged.device.num_rows(),
                     getattr(merged, "_fp_collision"))
                )
            )
            merged._fp_collision_host = bool(coll)
            if coll and metrics is not None:
                metrics.add("fp_collision_batches", 1)
            acc = self._at_group_bucket(merged, g)
        return acc

    def _resolve_fp_flags(self, parts: list[Batch], metrics) -> bool:
        """Read (once, batched) the not-yet-read collision flags of
        fp-segmented parts; returns whether ANY part is collision-flagged.
        Parts with no fp provenance count as clean here — they only
        disqualify the merge-path, not correctness."""
        unread = [
            p for p in parts
            if getattr(p, "_fp_order", False)
            and not hasattr(p, "_fp_collision_host")
            and hasattr(p, "_fp_collision")
        ]
        if unread:
            # auronlint: disable=R9 -- merge-boundary read: executes only inside _merge/_merge_path, whose rate is merge_threshold-amortized
            flags = jax.device_get(  # auronlint: sync-point(2/task) -- batched read of per-run collision flags at merge boundaries only
                tuple(p._fp_collision for p in unread)
            )
            for p, f in zip(unread, flags):
                with _FP_FLAG_LOCK:
                    fresh = not hasattr(p, "_fp_collision_host")
                    if fresh:
                        p._fp_collision_host = bool(f)
                if fresh and f and metrics is not None:
                    metrics.add("fp_collision_batches", 1)
        return any(getattr(p, "_fp_collision_host", False) for p in parts)

    @staticmethod
    def _prefix_slice_meta(b: Batch, new_cap: int) -> Batch:
        """prefix_slice that carries the fp provenance over to the sliced
        handle (groups live in the prefix, so sortedness survives)."""
        out = prefix_slice(b, new_cap)
        if out is not b:
            for attr in ("_fp_order", "_fp_collision", "_fp_collision_host",
                         "_groups"):
                if hasattr(b, attr):
                    setattr(out, attr, getattr(b, attr))
            if hasattr(b, "_inc_fp"):
                out._inc_fp = b._inc_fp[:new_cap]
        return out

    @staticmethod
    def _at_group_bucket(b: Batch, g: int) -> Batch:
        """``b`` sliced to the bucket of its ``g`` settled groups, the count
        noted on it: what its emission reports (``obs.note_agg_emit``)."""
        out = HashAggExec._prefix_slice_meta(b, bucket_capacity(max(g, 1)))
        out._groups = g
        return out

    def _note_emit(self, b: Batch) -> None:
        obs.note_agg_emit(getattr(b, "_groups", None), self.mode)

    # ------------------------------------------------------------------

    def _group_reduce(
        self,
        sel: jnp.ndarray,
        keys: list[ColumnVal],
        agg_cols: list[list[ColumnVal]],
        raw: bool,
        merge_cap_a: int | None = None,
        force_full_sort: bool = False,
        fp: jnp.ndarray | None = None,
        conf=None,
    ) -> Batch:
        """Group + reduce one batch. When every aggregate is device-native
        the whole reduction runs as ONE jitted program (cached per shape
        signature); host-side aggregates (collect/UDAF pull data to host)
        keep the eager path.

        ``merge_cap_a`` switches segmentation to the sort-free merge-rank
        over two fp-sorted runs (merge-path _merge); ``force_full_sort``
        pins the legacy full-word sort (collision-dedup reduces)."""
        if not self._has_host_aggs:
            key_v = tuple(k.values for k in keys)
            key_m = tuple(k.validity for k in keys)
            agg_v = tuple(tuple(c.values for c in cols) for cols in agg_cols)
            agg_m = tuple(tuple(c.validity for c in cols) for cols in agg_cols)
            agg_aux = tuple(
                _agg_aux(a, in_t, cols)
                for ((a, _), in_t), cols in zip(
                    zip(self.aggs, self._agg_input_types), agg_cols
                )
            )
            flags = self._sort_flags(sel, force_full_sort=force_full_sort,
                                     conf=conf)
            obs.note_agg_reduce(
                int(sel.shape[0]),
                "mergepath" if merge_cap_a is not None
                else "hostsort" if flags[0] else "sort")
            # host-sort order computes EAGERLY and enters the jit as data:
            # no pure_callback may live inside the compiled program
            # (concurrent callback-bearing XLA:CPU programs wedge). The
            # canonical words ride along so the jit doesn't recompute them.
            if flags[0] and self.n_keys and merge_cap_a is None:
                words = S.key_words(keys)
                if flags[2]:
                    order, fp = S.host_order_fp(words, sel, flags[3])
                else:
                    order = S.host_order(words, sel)
                words = tuple(words)
            else:
                words, order = None, None
            out_v, out_m, group_valid, collision, group_fp = _reduce_arrays_jit(
                sel, key_v, key_m, agg_v, agg_m, agg_aux, order, words, fp,
                cfg=self._reduce_cfg + flags, raw=raw, merge_cap_a=merge_cap_a,
            )
            out_vals = []
            dict_map = self._output_dicts(keys, agg_cols)
            for i, (v, m) in enumerate(zip(out_v, out_m)):
                f = self.inter_schema[i]
                out_vals.append(ColumnVal(v, m, f.dtype, dict_map[i]))
            out = batch_from_columns(out_vals, self.inter_schema.names, group_valid)
            res = Batch(self.inter_schema, out.device, out.dicts)
            self._attach_fp_meta(res, flags, collision, merge_cap_a)
            if group_fp is not None:
                res._inc_fp = group_fp
            return res
        return self._group_reduce_eager(
            sel, keys, agg_cols, raw,
            force_full_sort=force_full_sort, conf=conf,
        )

    @staticmethod
    def _attach_fp_meta(out: Batch, flags, collision, merge_cap_a=None) -> None:
        """Fingerprint-mode provenance on a reduce output: ``_fp_order``
        (groups emerged in fingerprint order — probe/merge-path capable)
        and ``_fp_collision`` (device scalar, read lazily: some fp run held
        more than one key, so fps are NOT unique in this batch)."""
        fp_used = bool(flags[2]) or merge_cap_a is not None
        if fp_used and collision is not None:
            out._fp_order = True
            out._fp_collision = collision

    def _output_dicts(self, keys: list[ColumnVal], agg_cols: list[list[ColumnVal]]):
        """Host dictionaries for each intermediate output column (positions
        must mirror _reduce_arrays' output order)."""
        dicts: list = [k.dict for k in keys]
        for (a, _), in_t, cols in zip(self.aggs, self._agg_input_types, agg_cols):
            n_out = len(
                intermediate_fields(a, in_t if in_t is not None else T.INT64, "x")
            )
            src = cols[0].dict if (cols and a.func in ("min", "max", "first", "first_ignores_null")) else None
            dicts.append(src)
            dicts.extend([None] * (n_out - 1))
        return dicts

    def _group_reduce_eager(
        self,
        sel: jnp.ndarray,
        keys: list[ColumnVal],
        agg_cols: list[list[ColumnVal]],
        raw: bool,
        force_full_sort: bool = False,
        conf=None,
    ) -> Batch:
        # force_full_sort/conf MUST thread through like the jit branch:
        # dropping them here would turn the FINAL-merge collision dedup
        # into a no-op for host-agg operators (same colliding fps, same
        # split group re-emitted) and let a cross-thread spill resolve
        # fingerprint knobs from a foreign task's conf
        flags = self._sort_flags(sel, force_full_sort=force_full_sort,
                                 conf=conf)
        obs.note_agg_reduce(int(sel.shape[0]), "hostsort" if flags[0] else "sort")
        # same invariant as the jit path: segment_by_keys is itself jitted,
        # so the host-sort order must enter it as data (never a callback
        # inside a compiled program — pump threads run concurrently)
        fp = None
        if flags[0] and self.n_keys:
            words = S.key_words(keys)
            if flags[2]:
                order, fp = S.host_order_fp(words, sel, flags[3])
            else:
                order = S.host_order(words, sel)
            words = tuple(words)
        else:
            words, order = None, None
        out_vals, group_valid, seg = _reduce_columns(
            sel, keys, agg_cols, raw,
            self._reduce_cfg + flags,
            collect_cb=self._host_agg_cb, order=order, words=words, fp=fp,
        )
        out = batch_from_columns(out_vals, self.inter_schema.names, group_valid)
        res = Batch(self.inter_schema, out.device, out.dicts)
        self._attach_fp_meta(res, flags, seg.collision)
        return res


    def _host_agg_cb(self, a, in_t, cols, order, seg, cap, raw, group_valid):
        """Dispatch host-side aggregates: collect_* vs accumulator UDAFs."""
        if a.func == "host_udaf":
            return self._reduce_udaf_state(
                a, in_t, cols, order, seg, cap, raw, group_valid
            )
        return self._reduce_collect(a, in_t, cols, order, seg, cap, raw, group_valid)

    def _reduce_udaf_state(
        self, a: AggExpr, in_t, cols, order, seg, cap, raw, group_valid
    ) -> list[ColumnVal]:
        """Incremental host-UDAF accumulation (SparkUDAFWrapperContext's
        initialize/update/merge state batches, .scala:59-235): fold this
        batch's inputs into per-group states (raw) or merge partial states
        (merge/final input). One device->host pull per reduce; memory per
        group is the accumulator state, never the input count."""
        import pickle

        import jax

        from auron_tpu.bridge.udf import lookup_udaf
        from auron_tpu.columnar.batch import _device_to_arrow

        spec = lookup_udaf(a.udaf)
        cv = cols[0]
        sv = cv.values[order]
        sm = cv.validity[order] & seg.sel_sorted
        # auronlint: sync-point(call) -- host UDAF accumulation is host work by contract; one batched transfer
        ids_d, sv_d, sm_d, ng_d = jax.device_get((seg.seg_ids, sv, sm, seg.num_groups))
        ids_np, sv_np, sm_np = np.asarray(ids_d), np.asarray(sv_d), np.asarray(sm_d)
        n_groups = int(ng_d)
        n_slots = max(n_groups, 1)
        states: list = [None] * n_slots
        if raw:
            decoded = _device_to_arrow(sv_np, sm_np, in_t, cv.dict).to_pylist()
            for gid, val, ok in zip(ids_np, decoded, sm_np):
                if 0 <= gid < n_groups and ok:
                    st = states[gid] if states[gid] is not None else spec.init()
                    states[gid] = spec.update(st, val)
        else:
            entries = cv.dict.to_pylist()
            for gid, code, ok in zip(ids_np, sv_np, sm_np):
                if not (0 <= gid < n_groups and ok):
                    continue
                blob = entries[code] if 0 <= code < len(entries) else None
                if not blob:
                    continue
                other = pickle.loads(blob)
                states[gid] = (
                    other if states[gid] is None
                    else spec.merge(states[gid], other)
                )
        blobs = [
            pickle.dumps(st if st is not None else spec.init())
            for st in states
        ]
        d = pa.array(blobs, type=pa.binary())
        codes = jnp.arange(cap, dtype=jnp.int32) % n_slots
        return [ColumnVal(codes, group_valid, T.BINARY, d)]

    def _reduce_collect(
        self, a: AggExpr, in_t, cols, order, seg, cap, raw, group_valid
    ) -> list[ColumnVal]:
        """collect_list / collect_set (reference: agg/collect.rs).

        Variable-length group state can't live in fixed device arrays, so
        the collected lists ride the LIST dictionary representation: values
        are decoded host-side segment-by-segment (one device->host pull of
        the sorted column per reduce) and the per-group lists become the
        dictionary; the device sees identity codes. Heavy by design — the
        reference's native collect is its largest accumulator too.
        """
        import jax

        from auron_tpu.columnar.batch import _device_to_arrow

        cv = cols[0]
        sv = cv.values[order]
        sm = cv.validity[order] & seg.sel_sorted
        # auronlint: sync-point(call) -- collect_list/set materializes per-group python lists; one batched transfer
        ids_d, sv_d, sm_d, ng_d = jax.device_get((seg.seg_ids, sv, sm, seg.num_groups))
        ids_np, sv_np, sm_np = np.asarray(ids_d), np.asarray(sv_d), np.asarray(sm_d)
        n_groups = int(ng_d)

        list_t = T.DataType(T.TypeKind.LIST, inner=(in_t,))
        if raw:
            decoded = _device_to_arrow(sv_np, sm_np, in_t, cv.dict).to_pylist()
            lists: list[list] = [[] for _ in range(max(n_groups, 1))]
            for gid, val, ok in zip(ids_np, decoded, sm_np):
                if 0 <= gid < n_groups and ok:
                    lists[gid].append(val)
        else:
            entries = cv.dict.to_pylist()
            lists = [[] for _ in range(max(n_groups, 1))]
            for gid, code, ok in zip(ids_np, sv_np, sm_np):
                if 0 <= gid < n_groups and ok:
                    sub = entries[code] if 0 <= code < len(entries) else None
                    if sub:
                        lists[gid].extend(sub)
        if a.func == "collect_set":
            lists = [
                sorted(set(l), key=lambda x: (x is None, str(x))) for l in lists
            ]
        d = pa.array(lists, type=list_t.to_arrow())
        codes = jnp.arange(cap, dtype=jnp.int32) % max(n_groups, 1)
        return [ColumnVal(codes, group_valid, list_t, d)]

    def _final_udaf(self, a: AggExpr, in_t, state_cv: ColumnVal) -> ColumnVal:
        """finish() each group's accumulator state (the evaluate leg of the
        SparkUDAFWrapperContext protocol)."""
        import pickle

        import jax

        from auron_tpu.bridge.udf import lookup_udaf
        from auron_tpu.columnar.batch import _arrow_to_device

        spec = lookup_udaf(a.udaf)
        cap = int(state_cv.values.shape[0])
        # auronlint: sync-point(call) -- UDAF state decode is host work by contract; one batched transfer
        codes_d, valid_d = jax.device_get((state_cv.values, state_cv.validity))
        codes, valid = np.asarray(codes_d), np.asarray(valid_d)
        entries = state_cv.dict.to_pylist()
        out_rows = []
        for i in range(cap):
            blob = (
                entries[codes[i]]
                if valid[i] and 0 <= codes[i] < len(entries)
                else None
            )
            if blob:
                out_rows.append(spec.finish(pickle.loads(blob)))
            else:
                out_rows.append(None)
        arr = pa.array(out_rows, type=spec.out_dtype.to_arrow())
        v, m, d = _arrow_to_device(arr, spec.out_dtype, cap)
        return ColumnVal(v, m & state_cv.validity, spec.out_dtype, d)

    # ------------------------------------------------------------------

    def _finalize(self, state: Batch) -> Batch:
        vals: list[ColumnVal] = []
        names: list[str] = []
        for i in range(self.n_keys):
            vals.append(
                ColumnVal(
                    state.col_values(i), state.col_validity(i),
                    self.inter_schema[i].dtype, state.dicts[i],
                )
            )
            names.append(self.schema[i].name)
        ofs = self.n_keys
        for (a, name), in_t in zip(self.aggs, self._agg_input_types):
            k = len(intermediate_fields(a, in_t if in_t is not None else T.INT64, name))
            cols = [
                ColumnVal(
                    state.col_values(ofs + j), state.col_validity(ofs + j),
                    self.inter_schema[ofs + j].dtype, state.dicts[ofs + j],
                )
                for j in range(k)
            ]
            ofs += k
            vals.append(self._final_one(a, in_t, cols))
            names.append(name)
        out = batch_from_columns(vals, names, state.device.sel)
        return Batch(self.schema, out.device, out.dicts)

    def _final_one(self, a: AggExpr, in_t, cols: list[ColumnVal]) -> ColumnVal:
        if a.func in ("count", "count_star"):
            return ColumnVal(cols[0].values, jnp.ones_like(cols[0].validity), T.INT64)
        if a.func == "sum":
            if is_wide_sum(in_t):
                return self._final_wide(a, in_t, cols)
            st = sum_type(in_t)
            if st.kind == T.TypeKind.DECIMAL:
                ok = D.precision_ok(cols[0].values, st.precision)
                return ColumnVal(cols[0].values, cols[0].validity & ok, st)
            return cols[0]
        if a.func == "avg":
            if is_wide_sum(in_t):
                return self._final_wide(a, in_t, cols)
            st = sum_type(in_t)
            at = avg_type(in_t)
            sm, cnt = cols[0], cols[1]
            nz = cnt.values > 0
            if at.kind == T.TypeKind.DECIMAL:
                v, ok = D.div(
                    sm.values, st.scale, cnt.values, 0, at.precision, at.scale
                )
                return ColumnVal(v, sm.validity & nz & ok, at)
            v = sm.values.astype(jnp.float64) / jnp.where(nz, cnt.values, 1)
            return ColumnVal(v, sm.validity & nz, at)
        if a.func in ("min", "max"):
            return cols[0]
        if a.func in ("first", "first_ignores_null"):
            return cols[0]
        if a.func in ("collect_list", "collect_set"):
            return cols[0]
        if a.func == "host_udaf":
            return self._final_udaf(a, in_t, cols[0])
        raise ValueError(a.func)

    def _final_wide(self, a: AggExpr, in_t, cols: list[ColumnVal]) -> ColumnVal:
        """Reconstruct exact wide sums from base-1e9 limbs (vectorized
        host-side object math — one transfer, no per-group python loop).
        Wide result types emit as dict-encoded Decimal128 columns, so
        p>18 values survive downstream exactly; narrow results emit as
        scaled int64 with out-of-domain values going NULL."""
        import decimal as pydec

        import jax

        st = sum_type(in_t)
        k = _n_limbs(st.precision)
        # auronlint: sync-point(call) -- exact wide-decimal totals need python ints (host by design); one batched transfer incl. the avg count column
        limbs, valid_d, cnt_d = jax.device_get((
            tuple(c.values for c in cols[:k]), cols[0].validity,
            cols[k].values if len(cols) > k else None,
        ))
        valid = np.asarray(valid_d)
        obs.note_decimal_host_cells(len(valid), "final")
        # exact totals: vectorized python-int accumulation over k arrays
        total = np.zeros(len(valid), dtype=object)
        base = 1
        for limb in limbs:
            total = total + np.asarray(limb).astype(object) * base
            base *= _LIMB_BASE
        if a.func == "sum":
            emit_t = st
            unscaled = total
            ok = valid.copy()
        else:  # avg: exact HALF_UP division at the avg scale
            emit_t = avg_type(in_t)
            cnt = np.asarray(cnt_d)
            ok = valid & (cnt > 0)
            diff = emit_t.scale - st.scale
            num_shift = 10 ** max(diff, 0)  # pure-int shifts: a float
            den_shift = 10 ** max(-diff, 0)  # 10**negative would corrupt
            q = pydec.Decimal(1)
            unscaled = np.zeros(len(valid), dtype=object)
            for i in np.nonzero(ok)[0]:
                unscaled[i] = int(
                    (
                        pydec.Decimal(int(total[i]) * num_shift)
                        / pydec.Decimal(int(cnt[i]) * den_shift)
                    ).quantize(q, rounding=pydec.ROUND_HALF_UP)
                )
        if emit_t.is_wide_decimal:
            # dict-encoded exact emission (identity codes); totals beyond
            # the precision budget go NULL (Spark non-ANSI overflow)
            bound = 10 ** emit_t.precision
            decs = [
                T.decimal_from_unscaled(int(u), emit_t.scale)
                if o and -bound < int(u) < bound
                else None
                for u, o in zip(unscaled, ok)
            ]
            import pyarrow as pa

            d = pa.array(
                [x if x is not None else pydec.Decimal(0) for x in decs],
                type=pa.decimal128(emit_t.precision, emit_t.scale),
            )
            codes = jnp.arange(len(decs), dtype=jnp.int32)
            ok_dev = jnp.asarray(np.array([x is not None for x in decs]))
            return ColumnVal(codes, ok_dev & cols[0].validity, emit_t, d)
        bound = 10 ** min(emit_t.precision, 18)
        out_vals = np.zeros(len(valid), dtype=np.int64)
        out_ok = np.zeros(len(valid), dtype=bool)
        for i in np.nonzero(ok)[0]:
            u = int(unscaled[i])
            if -bound < u < bound and -(2**63) <= u < 2**63:
                out_vals[i] = u
                out_ok[i] = True
        return ColumnVal(
            jnp.asarray(out_vals), jnp.asarray(out_ok) & cols[0].validity, emit_t
        )

    def _empty_global_agg(self, ctx: ExecutionContext) -> Batch:
        """Global aggregation over empty input: one row (count=0, sum=null)."""
        from auron_tpu.columnar.batch import MIN_CAPACITY

        cap = MIN_CAPACITY
        vals = []
        names = []
        schema = self.schema if self.mode == FINAL else self.inter_schema
        for f in schema:
            zero = jnp.zeros(cap, f.dtype.physical_dtype())
            is_count = f.name.endswith("#count") or (
                self.mode == FINAL
                and any(
                    n == f.name and a.func in ("count", "count_star")
                    for a, n in self.aggs
                )
            )
            valid = jnp.zeros(cap, bool).at[0].set(bool(is_count))
            d = None
            if f.dtype.is_dict_encoded:
                from auron_tpu.columnar.batch import _empty_dict

                d = _empty_dict(f.dtype)
            vals.append(ColumnVal(zero, valid, f.dtype, d))
            names.append(f.name)
        sel = jnp.zeros(cap, bool).at[0].set(True)
        out = batch_from_columns(vals, names, sel)
        return Batch(schema, out.device, out.dicts)


class _AggTableConsumer:
    """Spillable aggregation state (reference: agg/agg_table.rs —
    in-memory table + spill with bucketed merge; here: device state batches
    + disk-parked intermediate runs merged back at output)."""

    def __init__(self, exec_: "HashAggExec", ctx: ExecutionContext):
        self.name = f"agg-{id(exec_):x}"
        self.exec = exec_
        self.ctx = ctx
        self.state: Batch | None = None
        self.staged: list[Batch] = []
        self.staged_rows = 0
        self._staged_bytes = 0
        self._state_bytes = 0
        self.parked: list = []  # DiskSpill objects
        # tasks run concurrently; MemManager.acquire may spill this consumer
        # from ANOTHER task's thread. Lock order is manager -> consumer (the
        # owner never holds this lock while calling acquire), so no deadlock.
        self._lock = threading.RLock()

    def add(self, inter: Batch, groups: int) -> None:
        from auron_tpu.exec.sort_exec import batch_nbytes

        with self._lock:
            self.staged.append(inter)
            self.staged_rows += groups
            self._staged_bytes += batch_nbytes(inter)

    def state_capacity(self) -> int:
        """Locked snapshot (a cross-thread spill may null state between
        a bare None-check and a .capacity read)."""
        with self._lock:
            return self.state.capacity if self.state is not None else 0

    def adjust_staged(self, delta: int) -> None:
        """Correct the staged-rows estimate once an exact group count settles
        (clamped: a concurrent compact() may already have reset it)."""
        with self._lock:
            self.staged_rows = max(0, self.staged_rows + delta)

    def shrink_last(self, groups: int) -> None:
        """Slice the most recently staged intermediate down to the bucket of
        its ``groups`` settled groups (they occupy a valid prefix), the count
        noted on it. No-op if a concurrent compact/spill already consumed
        it."""
        from auron_tpu.exec.sort_exec import batch_nbytes

        with self._lock:
            if not self.staged:
                return
            old = self.staged[-1]
            shrunk = HashAggExec._at_group_bucket(old, groups)
            self.staged[-1] = shrunk
            self._staged_bytes += batch_nbytes(shrunk) - batch_nbytes(old)

    def compact(self) -> None:
        from auron_tpu.exec.sort_exec import batch_nbytes

        with self._lock:
            self.state = self.exec._merge(
                [self.state] if self.state is not None else [], self.staged,
                metrics=self.ctx.metrics, conf=self.ctx.conf,
            )
            self.staged, self.staged_rows, self._staged_bytes = [], 0, 0
            self._state_bytes = (
                batch_nbytes(self.state) if self.state is not None else 0
            )

    def mem_used(self) -> int:
        # incremental accounting: the manager polls every consumer's
        # mem_used on EVERY acquire, so an O(len(staged)) scan here turns
        # the whole pipeline quadratic in staged-batch count (measured as
        # the q72-class superlinear blowup: 124k batch_nbytes calls at SF=2)
        with self._lock:
            return self._staged_bytes + self._state_bytes

    def spill(self) -> int:  # auronlint: thread-root(foreign) -- MemManager dispatches spills (and the compact/merge below) on the requesting task's thread
        """Park the merged state as a compressed run (host-RAM tier first,
        demoted to disk under ledger pressure — memmgr.make_spill)."""
        from auron_tpu.memory.memmgr import make_spill

        with self._lock:
            freed = self.mem_used()
            if freed == 0:
                return 0
            with self.ctx.metrics.timer("spill_time"):
                self.compact()
                if self.state is not None:
                    ds = make_spill(conf=self.ctx.conf)
                    try:
                        ds.write_table(
                            self.state.to_arrow(preserve_dicts=True))
                    except BaseException:
                        # a failed park (disk full, encode error) must
                        # not strand the container's ledger bytes (R11)
                        ds.release()
                        raise
                    self.parked.append(ds)
            self.ctx.metrics.add("spilled_aggs", 1)
            self.state = None
            self._state_bytes = 0
            return freed

    def drain(self):
        """Yield ALL contents without merging (partial-skip path).

        Atomically takes staged + state + parked under the lock: a
        concurrent cross-thread spill between the caller's decision and
        this drain parks batches on disk, and those must still be emitted
        (they are decoded back here) or rows would silently vanish."""
        with self._lock:
            staged, state, parked = self.staged, self.state, self.parked
            self.staged, self.staged_rows, self.state, self.parked = [], 0, None, []
            self._staged_bytes = self._state_bytes = 0
        yield from staged
        if state is not None:
            yield state
        for ds in parked:
            for rb in ds.read_tables():
                yield Batch.from_arrow(rb)
            ds.release()

    def collect_state(self) -> Batch | None:
        """Merge state + staged + parked disk runs into the final state.

        State FIRST — the same part order compact() uses — so
        position-resolved aggregates (`first`) prefer the earliest data in
        stream order; the probe/scatter path relies on this (a probed hit
        keeps the state's value, which must match what the merge of an
        unprobed run would have picked)."""
        with self._lock:
            parts: list[Batch] = []
            if self.state is not None:
                parts.append(self.state)
            parts.extend(self.staged)
            parked, self.parked = self.parked, []
            self.staged, self.staged_rows, self.state = [], 0, None
            self._staged_bytes = self._state_bytes = 0
        for ds in parked:
            for rb in ds.read_tables():
                parts.append(Batch.from_arrow(rb))
            ds.release()
        if not parts:
            return None
        # `final`: in FINAL mode this merge's output IS the operator output
        # — a fingerprint collision anywhere forces the full-word dedup
        return self.exec._merge(
            [], parts, metrics=self.ctx.metrics,
            final=self.exec.mode == FINAL, conf=self.ctx.conf,
        )


def _input_type_from_intermediate(a: AggExpr, first_field: T.Field) -> T.DataType | None:
    """Invert intermediate typing to recover the agg input type."""
    t = first_field.dtype
    if a.func in ("count", "count_star"):
        return None
    if a.func == "host_udaf":
        return None  # state column carries no input type
    if a.func in ("collect_list", "collect_set"):
        return t.inner[0]
    if a.func == "sum" or a.func == "avg":
        if "#sum0p" in first_field.name:
            # wide-sum limb layout: the exact input precision rides in
            # the field name (see _wide_sum_fields)
            p = int(first_field.name.rsplit("#sum0p", 1)[1])
            return T.decimal(p, t.scale)
        if t.kind == T.TypeKind.DECIMAL:
            return T.decimal(max(t.precision - 10, 1), t.scale)
        return T.INT64 if t.kind == T.TypeKind.INT64 else T.FLOAT64
    return t  # min/max/first carry the input type


# ---------------------------------------------------------------------------
# module-level reduce core (shared jit cache across all HashAggExec instances)
# ---------------------------------------------------------------------------


def _agg_aux(a: AggExpr, in_t, cols: list[ColumnVal]):
    """Per-agg device-array side tables for dict-encoded inputs, traced
    into the fused reduce program (host dictionaries can't enter jit):

    - min/max over dict codes: (rank, inv) lexicographic tables;
    - sum/avg over wide-decimal dicts: base-1e9 limb tables."""
    if not cols or cols[0].dict is None or len(cols[0].dict) == 0:
        return None
    d = cols[0].dict
    if a.func in ("min", "max"):
        from auron_tpu.ops.sortkeys import dict_rank_maps

        rank, inv = dict_rank_maps(d)
        return jnp.asarray(rank), jnp.asarray(inv)
    if (
        a.func in ("sum", "avg")
        and in_t is not None
        and in_t.is_wide_decimal
    ):
        k = _n_limbs(sum_type(in_t).precision)
        return tuple(
            jnp.asarray(t) for t in _decimal_limb_tables(d, in_t.scale, k)
        )
    return None


# backward-compat alias used by the eager min/max fallback
def _minmax_rank_aux(a: AggExpr, cols: list[ColumnVal]):
    if a.func not in ("min", "max"):
        return None
    return _agg_aux(a, None, cols)


def _reduce_columns(sel, keys, agg_cols, raw, cfg, collect_cb=None, agg_aux=None,
                    order=None, words=None, fp=None, merge_cap_a=None):
    """Segment + reduce already-evaluated columns.

    cfg = (n_keys, key_dtypes, ((AggExpr, in_t), ...), host_sort,
    device_impl, fingerprint, fp_bits) — pure
    values, so the jitted wrapper's compile cache is shared by every operator
    instance with the same aggregate signature; host_sort rides in cfg so a
    config change retraces instead of hitting a stale compiled choice.

    ``merge_cap_a``: segment TWO back-to-back fp-sorted runs (state ⊕
    staged, split at that capacity) via the binsearch merge-rank instead of
    any sort — the merge-path form of _merge."""
    n_keys, key_dtypes, agg_specs, host_sort, device_impl, fingerprint, fp_bits = cfg
    cap = int(sel.shape[0])
    if n_keys == 0:
        # global aggregation: single segment containing all live rows
        seg = S.Segmentation(
            order=jnp.arange(cap, dtype=jnp.int32),
            seg_ids=jnp.where(sel, 0, cap),
            boundary=jnp.zeros(cap, bool),
            group_of_slot=jnp.zeros(cap, jnp.int32),
            num_groups=jnp.minimum(jnp.sum(sel), 1),
            sel_sorted=sel,
        )
    else:
        if words is None:
            with jax.named_scope("auron.agg.key_words"):
                words = S.key_words(keys)
        if merge_cap_a is not None:
            with jax.named_scope("auron.agg.merge"):
                seg = S.segment_merged(list(words), sel, merge_cap_a,
                                       fp_bits, fp)
        else:
            seg = S.segment_by_keys(
                list(words), sel, order, fp, host_sort=host_sort,
                device_impl=device_impl, n_key_cols=n_keys,
                fingerprint=fingerprint, fp_bits=fp_bits,
            )
    order = seg.order

    out_vals: list[ColumnVal] = []
    with jax.named_scope("auron.agg.key_gather"):
        slot = jnp.clip(seg.group_of_slot, 0, cap - 1)
        group_valid = jnp.arange(cap, dtype=jnp.int32) < seg.num_groups
        if n_keys == 0:
            # a global agg always yields exactly one group, even over 0 rows
            group_valid = jnp.zeros(cap, bool).at[0].set(True)
        for kv in keys:
            sorted_vals = kv.values[order]
            sorted_mask = kv.validity[order]
            out_vals.append(
                ColumnVal(sorted_vals[slot], sorted_mask[slot] & group_valid,
                          kv.dtype, kv.dict)
            )
    if agg_aux is None:
        agg_aux = (None,) * len(agg_specs)
    for (a, in_t), cols, aux in zip(agg_specs, agg_cols, agg_aux):
        with jax.named_scope(f"auron.agg.reduce.{a.func}"):
            out_vals.extend(
                _reduce_one(a, in_t, cols, order, seg, cap, raw, group_valid,
                            collect_cb, aux)
            )
    return out_vals, group_valid, seg


def _reduce_one(a, in_t, cols, order, seg, cap, raw, group_valid,
                collect_cb=None, aux=None):
    import jax

    ids = seg.seg_ids

    def sortg(cv):
        return cv.values[order], cv.validity[order] & seg.sel_sorted

    if a.func == "count_star":
        if raw:
            cnt = S.seg_count(seg.sel_sorted, ids, cap)
        else:
            v, m = sortg(cols[0])
            cnt, _ = S.seg_sum(v, m, ids, cap)
        return [ColumnVal(cnt, group_valid, T.INT64)]
    if a.func == "count":
        v, m = sortg(cols[0])
        if raw:
            cnt = S.seg_count(m, ids, cap)
        else:
            cnt, _ = S.seg_sum(v, m, ids, cap)
        return [ColumnVal(cnt, group_valid, T.INT64)]
    if a.func == "sum":
        if is_wide_sum(in_t):
            return _reduce_wide_sum(in_t, cols, sortg, ids, cap, raw,
                                    group_valid, aux)
        v, m = sortg(cols[0])
        sm, any_valid = S.seg_sum(v, m, ids, cap)
        return [ColumnVal(sm, any_valid & group_valid, sum_type(in_t))]
    if a.func == "avg":
        if is_wide_sum(in_t):
            limbs = _reduce_wide_sum(in_t, cols, sortg, ids, cap, raw,
                                     group_valid, aux)
            if raw:
                _, m0 = sortg(cols[0])
                cnt = S.seg_count(m0, ids, cap)
            else:
                cv, cm = sortg(cols[len(limbs)])  # count rides after the limbs
                cnt, _ = S.seg_sum(cv, cm, ids, cap)
            return limbs + [ColumnVal(cnt, group_valid, T.INT64)]
        v, m = sortg(cols[0])
        sm, any_valid = S.seg_sum(v, m, ids, cap)
        if raw:
            cnt = S.seg_count(m, ids, cap)
        else:
            cv, cm = sortg(cols[1])
            cnt, _ = S.seg_sum(cv, cm, ids, cap)
        return [
            ColumnVal(sm, any_valid & group_valid, sum_type(in_t)),
            ColumnVal(cnt, group_valid, T.INT64),
        ]
    if a.func in ("min", "max"):
        v, m = sortg(cols[0])
        fn = S.seg_min if a.func == "min" else S.seg_max
        if aux is None and cols[0].dict is not None and len(cols[0].dict) > 0:
            aux = _minmax_rank_aux(a, cols)  # eager path: build from the dict
        if aux is not None:
            # codes are in first-occurrence order: reduce in lexicographic
            # rank space, then invert the winning rank back to a code
            rank, inv = aux
            nd = rank.shape[0]
            vr = rank[jnp.clip(v, 0, nd - 1)]
            mr, any_valid = fn(vr, m, ids, cap)
            mv = inv[jnp.clip(mr, 0, nd - 1)].astype(v.dtype)
            return [ColumnVal(mv, any_valid & group_valid, in_t, cols[0].dict)]
        mv, any_valid = fn(v, m, ids, cap)
        return [ColumnVal(mv, any_valid & group_valid, in_t, cols[0].dict)]
    if a.func in ("collect_list", "collect_set", "host_udaf"):
        assert collect_cb is not None, "host aggregates need the eager path"
        return collect_cb(a, in_t, cols, order, seg, cap, raw, group_valid)
    if a.func in ("first", "first_ignores_null"):
        ignores = a.func == "first_ignores_null"
        v, m = sortg(cols[0])
        if raw:
            eligible = seg.sel_sorted & (m if ignores else jnp.ones_like(m))
        else:
            sv, smask = sortg(cols[1])
            eligible = seg.sel_sorted & sv.astype(bool)
        n = v.shape[0]
        pos = jnp.arange(n, dtype=jnp.int32)
        pos_or_inf = jnp.where(eligible, pos, n)
        first_pos = jax.ops.segment_min(pos_or_inf, ids, num_segments=cap + 1)[:cap]
        safe = jnp.clip(first_pos, 0, n - 1)
        fv = v[safe]
        fm = m[safe] & (first_pos < n)
        seen = (first_pos < n) & group_valid
        return [
            ColumnVal(fv, fm & group_valid, in_t, cols[0].dict),
            ColumnVal(seen, group_valid, T.BOOL),
        ]
    raise ValueError(a.func)


_LIMB_BASE = 1_000_000_000

# bounded memo of per-dictionary limb tables (wide decimal inputs): the
# decomposition of every dictionary entry is pure host work shared across
# batches with the same dictionary object
_LIMB_TABLE_CACHE: dict[int, tuple] = {}


def _decimal_limb_tables(d, scale: int, k: int):
    """k base-1e9 limb tables (np.int64, bucket-padded) for a wide-decimal
    dictionary: entry e decomposes as sum(limb_i * 1e9^i) of its unscaled
    value (floored division; the top limb carries the sign)."""
    key = (id(d), k)
    hit = _LIMB_TABLE_CACHE.get(key)
    if hit is not None and hit[0] is d:
        return hit[1]
    entries = d.to_pylist()
    n = len(entries)
    cap = max(8, 1 << (n - 1).bit_length()) if n else 8
    tabs = [np.zeros(cap, dtype=np.int64) for _ in range(k)]
    for i, e in enumerate(entries):
        if e is None:
            continue
        u = T.unscaled_int(e, scale)
        for j in range(k - 1):
            u, r = divmod(u, _LIMB_BASE)
            tabs[j][i] = r
        tabs[k - 1][i] = u
    if len(_LIMB_TABLE_CACHE) >= 64:
        _LIMB_TABLE_CACHE.pop(next(iter(_LIMB_TABLE_CACHE)))  # auronlint: disable=R10 -- deliberate trace-time memo eviction: bounded cache of deterministic values, replay-safe
    # auronlint: disable=R10 -- deliberate trace-time memo: the limb tables are a pure function of the dictionary key, so a cache hit on replay is bit-identical
    _LIMB_TABLE_CACHE[key] = (d, tabs)
    return tabs


def _reduce_wide_sum(in_t, cols, sortg, ids, cap, raw, group_valid, aux=None):
    """Base-1e9 limb accumulation for wide decimal sums (exact; per-limb
    int64 sums stay wrap-free for any realistic group size). Wide INPUT
    columns (dict-encoded Decimal128) gather per-row limbs from host
    tables; narrow scaled-int64 inputs decompose on device."""
    st = sum_type(in_t)
    k = _n_limbs(st.precision)
    limb0_t = T.decimal(18, in_t.scale)
    if raw:
        v, m = sortg(cols[0])
        if in_t.is_wide_decimal:
            tabs = (
                list(aux)
                if aux is not None
                else [
                    jnp.asarray(t)
                    for t in _decimal_limb_tables(cols[0].dict, in_t.scale, k)
                ]
            )
            idx = jnp.clip(v, 0, tabs[0].shape[0] - 1)
            limb_vals = [t[idx] for t in tabs]
        else:
            cur = jnp.where(m, v.astype(jnp.int64), jnp.int64(0))
            limb_vals = []
            for _ in range(k - 1):
                limb_vals.append(jnp.mod(cur, _LIMB_BASE))
                cur = jnp.floor_divide(cur, _LIMB_BASE)
            limb_vals.append(cur)
        masks = [m] * k
    else:
        limb_vals, masks = [], []
        for i in range(k):
            v, m = sortg(cols[i])
            limb_vals.append(jnp.where(m, v.astype(jnp.int64), jnp.int64(0)))
            masks.append(m)
    out = []
    any_valid = None
    for i, (lv, m) in enumerate(zip(limb_vals, masks)):
        sm, av = S.seg_sum(jnp.where(m, lv, jnp.int64(0)), m, ids, cap)
        any_valid = av if any_valid is None else any_valid
        out.append(
            ColumnVal(sm, any_valid & group_valid,
                      limb0_t if i == 0 else T.INT64)
        )
    return out


def _reduce_arrays_impl(sel, key_v, key_m, agg_v, agg_m, agg_aux, order, words,
                        fp, cfg, raw, merge_cap_a=None):
    n_keys = cfg[0]
    key_dtypes = cfg[1]
    keys = [
        ColumnVal(v, m, dt, None) for (v, m, dt) in zip(key_v, key_m, key_dtypes)
    ]
    agg_cols = [
        [ColumnVal(v, m, T.NULL, None) for v, m in zip(vs, ms)]
        for vs, ms in zip(agg_v, agg_m)
    ]
    out_vals, group_valid, seg = _reduce_columns(
        sel, keys, agg_cols, raw, cfg, agg_aux=agg_aux, order=order,
        words=words, fp=fp, merge_cap_a=merge_cap_a,
    )
    if seg.fp_sorted is not None:
        # per-OUTPUT-ROW fingerprints (dead slots -> MAX, the probe's dead
        # sentinel): cached on the state batch so steady-state probing
        # never re-hashes the invariant state keys
        cap = sel.shape[0]
        with jax.named_scope("auron.agg.group_fp"):
            slot = jnp.clip(seg.group_of_slot, 0, cap - 1)
            group_fp = jnp.where(
                group_valid, seg.fp_sorted[slot],
                jnp.uint64(0xFFFFFFFFFFFFFFFF)
            )
    else:
        group_fp = None
    return (
        tuple(cv.values for cv in out_vals),
        tuple(cv.validity for cv in out_vals),
        group_valid,
        seg.collision,  # None on the legacy full-word path (static per cfg)
        group_fp,
    )


import jax as _jax  # noqa: E402

_reduce_arrays_jit = _jax.jit(
    _reduce_arrays_impl, static_argnames=("cfg", "raw", "merge_cap_a")
)


# ---------------------------------------------------------------------------
# Dense direct-address aggregation (integer keys, small range)
# ---------------------------------------------------------------------------


#: (two's-complement bits a summed plane's values occupy by their TYPE, sign
#: included; whether that is a promise the fold must check): a row count's
#: 0 / 1 flags, and the int64 ``#count`` field a merge sums
_ROW_COUNT = (2, False)
_MERGED_COUNT = (64, False)


def _sum_bits(raw: bool, in_t: T.DataType) -> tuple[int, bool] | None:
    """``(bits, checked)`` of the plane a dense ``sum`` / ``avg`` scatters, by
    the aggregate's input type alone, or None where it is no integer (a
    float sum is not exact under regrouping and keeps its one scatter). A
    raw fold reads the input cast to ``sum_type`` (the same values); a merge
    reads the ``#sum`` field, which holds ``sum_type``'s digits. DECIMAL(p):
    ``ceil(log2(10^p))`` bits and a sign, a promise the int64 plane of
    unscaled values does not keep by itself, so ``checked``; the physical
    integers hold their width and no more."""
    if in_t.kind == T.TypeKind.DECIMAL:
        p = in_t.precision if raw else sum_type(in_t).precision
        return (10 ** p - 1).bit_length() + 1, True
    if not in_t.is_integer:
        return None
    return (8 * in_t.physical_dtype().itemsize if raw else 64), False


def _seg_sum(vals, ids, nseg, bits: tuple[int, bool] | None):
    """One value plane of the dense fold summed by slot: an integer plane by
    int32 limbs (``ops/segments.py seg_sum_limbs``; ``bits`` as ``_sum_bits``
    gives them), any other by one scatter at its own width."""
    if bits is None:
        return jax.ops.segment_sum(vals, ids, num_segments=nseg)
    return S.seg_sum_limbs(vals, ids, nseg, *bits)


def _seg_any(flags, ids, nseg):
    # `> 0`, NOT astype(bool): segment_max fills segments that received no
    # element with the dtype minimum (a nonzero int), which astype(bool)
    # would turn into True — every empty slot would look occupied
    return jax.ops.segment_max(flags.astype(jnp.int32), ids, num_segments=nseg) > 0


@partial(jax.jit, static_argnames=("cfg", "size"), donate_argnums=(0, 1, 2))
def _dense_update_jit(
    state_vals, state_valids, present, base, hi, key_v, key_m, sel, agg_ins,
    *, cfg, size: int,
):
    """ONE fused scatter-reduce folding a batch into the dense table.

    Slot 0 is the NULL-key group; real keys land at ``key - base + 1``;
    dead rows route to segment ``size`` (dropped). No sort, no
    segmentation — the whole per-batch aggregation is segment_* scatters
    at O(rows + size), the dense analog of the reference's integer-keyed
    agg hash map (agg/agg_hash_map.rs). The TPU has no 64-bit integer
    scatter, so integer and DECIMAL sums and every count are scattered as
    int32 limbs whose widths follow from ``cfg``'s types and the batch's
    rows, and carried into the int64 table once a batch (``_seg_sum``)."""
    raw, funcs, dims = cfg
    nseg = size + 1
    # in-table guard, fused with the fold: if ANY live key falls outside
    # the anchored ranges every row routes to the drop segment (all-or-
    # nothing no-op) and the returned flag tells the host to drain +
    # re-anchor + retry this batch — the host never has to sync a
    # range-check BEFORE issuing the fold, so the steady-state pipeline
    # has no per-batch blocking round-trip.
    imax = jnp.iinfo(jnp.int64).max
    imin = jnp.iinfo(jnp.int64).min
    okall = jnp.ones((), bool)
    for i, (v, m) in enumerate(zip(key_v, key_m)):
        okv = sel & m
        anyval = jnp.any(okv)
        if dims[i] == 1:
            bad = anyval  # NULL-lane-only key saw a real value
        else:
            s = v.astype(jnp.int64)
            mn = jnp.min(jnp.where(okv, s, imax))
            mx = jnp.max(jnp.where(okv, s, imin))
            # pure comparisons against host-computed bounds (hi = base +
            # dims - 2 clamped to int64): device-side `mx - base + 2`
            # would WRAP for sentinel keys near the int64 extremes and
            # let an out-of-range row fold into a clamped slot
            bad = anyval & ((mn < base[i]) | (mx > hi[i]))
        okall = okall & ~bad
    live = sel & okall
    # packed multi-dimensional slot: per key, offset 0 is that key's NULL
    # lane and 1..dim_i-1 its value lanes; slot = sum(off_i * stride_i).
    # Partial-null combinations land in distinct slots by construction.
    idx = jnp.zeros(sel.shape, jnp.int32)
    stride = 1
    for i, (v, m) in enumerate(zip(key_v, key_m)):
        off = jnp.where(
            m,
            jnp.clip(v.astype(jnp.int64) - base[i] + 1, 1, dims[i] - 1),
            0,
        ).astype(jnp.int32)
        idx = idx + off * stride
        stride *= dims[i]
    idx = jnp.where(live, jnp.clip(idx, 0, size - 1), size)
    new_present = present | _seg_any(live, idx, nseg)[:size]
    out_vals = []
    out_valids = []
    fi = 0
    for (func, in_t), ins in zip(funcs, agg_ins):
        if func in ("count", "count_star"):
            if not raw:
                # merge: SUM the intermediate #count field
                v, _ = ins[0]
                contrib = _seg_sum(jnp.where(sel, v, 0), idx, nseg, _MERGED_COUNT)[:size]
            elif func == "count_star":
                contrib = _seg_sum(sel, idx, nseg, _ROW_COUNT)[:size]
            else:
                _, m = ins[0]
                contrib = _seg_sum(m & sel, idx, nseg, _ROW_COUNT)[:size]
            out_vals.append(state_vals[fi] + contrib)
            out_valids.append(None)
            fi += 1
            continue
        if func in ("sum", "avg"):
            v, m = ins[0]
            ok = m & sel
            s = _seg_sum(
                jnp.where(ok, v, jnp.zeros_like(v)), idx, nseg, _sum_bits(raw, in_t)
            )[:size]
            sv = _seg_any(ok, idx, nseg)[:size]
            out_vals.append(state_vals[fi] + s)
            out_valids.append(state_valids[fi] | sv)
            fi += 1
            if func == "avg":
                if raw:
                    c = _seg_sum(ok, idx, nseg, _ROW_COUNT)[:size]
                else:
                    cv, _ = ins[1]
                    c = _seg_sum(jnp.where(sel, cv, 0), idx, nseg, _MERGED_COUNT)[:size]
                out_vals.append(state_vals[fi] + c)
                out_valids.append(None)
                fi += 1
            continue
        if func in ("min", "max"):
            v, m = ins[0]
            ok = m & sel
            if func == "min":
                ident = S._max_identity(v.dtype)
                contrib = jax.ops.segment_min(
                    jnp.where(ok, v, jnp.asarray(ident, v.dtype)), idx,
                    num_segments=nseg,
                )[:size]
                both = jnp.minimum(state_vals[fi], contrib)
            else:
                ident = S._min_identity(v.dtype)
                contrib = jax.ops.segment_max(
                    jnp.where(ok, v, jnp.asarray(ident, v.dtype)), idx,
                    num_segments=nseg,
                )[:size]
                both = jnp.maximum(state_vals[fi], contrib)
            cv_valid = _seg_any(ok, idx, nseg)[:size]
            old_valid = state_valids[fi]
            merged = jnp.where(
                old_valid & cv_valid, both,
                jnp.where(cv_valid, contrib, state_vals[fi]),
            )
            out_vals.append(merged)
            out_valids.append(old_valid | cv_valid)
            fi += 1
            continue
        raise AssertionError(func)
    return tuple(out_vals), tuple(out_valids), new_present, okall


@jax.jit
def _dense_key_range_jit(key_vs, key_ms, sel):
    """[n_live, min0, max0, min1, max1, ...] over live valid-key rows per
    key column — one tiny program."""
    imax = jnp.iinfo(jnp.int64).max
    imin = jnp.iinfo(jnp.int64).min
    parts = [jnp.sum(sel).astype(jnp.int64)]
    for v, m in zip(key_vs, key_ms):
        ok = sel & m
        s = v.astype(jnp.int64)
        parts.append(jnp.min(jnp.where(ok, s, imax)))
        parts.append(jnp.max(jnp.where(ok, s, imin)))
    return jnp.stack(parts)


def _next_pow2_agg(n: int) -> int:
    p = 1
    while p < n:
        p <<= 1
    return p


def _dense_fold_scatters(raw: bool, funcs: tuple, rows: int) -> tuple[int, int]:
    """(narrow, wide): the 32-bit and the 64-bit planes ``_dense_update_jit``
    scatters for one batch of ``rows`` rows under the static ``cfg`` members
    ``raw`` and ``funcs``: the ``present`` flags, for every nullable field its
    valid flags, and each value plane as the program takes it: an integer
    sum or a count by ``S.limb_plan``'s limbs (the very function the
    program asks), a float sum, a minimum or a maximum at its own width."""
    narrow, wide = 1, 0

    def plane(bits, t=None):
        nonlocal narrow, wide
        plan = S.limb_plan(bits[0], rows) if bits is not None else None
        if plan is not None:
            narrow += plan.limbs
        elif bits is not None or _is_64bit_plane(t):
            wide += 1
        else:
            narrow += 1

    for func, in_t in funcs:
        count = _ROW_COUNT if raw else _MERGED_COUNT
        if func in ("count", "count_star"):
            plane(count)
            continue
        narrow += 1
        if func in ("sum", "avg"):
            plane(_sum_bits(raw, in_t), sum_type(in_t))
            if func == "avg":
                plane(count)
        else:
            plane(None, in_t)
    return narrow, wide


def _bincount_i64(idx: np.ndarray, v: np.ndarray, size: int) -> np.ndarray:
    """Exact int64 segment sums via np.bincount: bincount accumulates in
    float64 (exact only to 2^53), so the value splits into four 16-bit
    limbs whose per-limb sums stay exact (<= 2^16 * cap << 2^53); the
    recombination wraps mod 2^64 — the same wrapping the device int64
    scatter-add exhibits."""
    u = v.astype(np.uint64)
    out = np.zeros(size, np.uint64)
    for shift in (0, 16, 32, 48):
        part = ((u >> np.uint64(shift)) & np.uint64(0xFFFF)).astype(np.float64)
        s = np.bincount(idx, weights=part, minlength=size + 1)[:size]
        out += s.astype(np.uint64) << np.uint64(shift)
    return out.view(np.int64)


class _DenseAggState:
    """Dense table accumulator for HashAggExec (1-3 packed integer keys).

    Multi-key grouping packs per-key offsets into ONE slot index
    (dimension strides; offset 0 per key = that key's NULL lane), so a
    (year, item) group-by runs the same single scatter-reduce as a
    one-key agg. Range growth drains the table into the generic consumer
    and RESTARTS with the union ranges (amortized: ranges stabilize
    after the first batches)."""

    LIMIT = 1 << 21  # max slots (product of per-key dims)

    def __init__(self, exec_: "HashAggExec", ctx: ExecutionContext):
        self.name = f"dense-agg-{id(exec_):x}"
        self.exec = exec_
        self.ctx = ctx
        self.bases: list[int] | None = None  # per-key value of offset 1
        self._his: list[int] | None = None  # per-key covered-value max
        self._bases_dev = self._his_dev = None  # device copies (per anchor)
        self.dims: tuple[int, ...] | None = None  # per-key lane count
        self.size = 0  # bucketed product of dims
        self.vals: tuple | None = None
        self.valids: tuple | None = None
        self.present: jnp.ndarray | None = None
        self._hint: list | None = None  # (mn, mx) per key across resets
        # k-deep deferred folds in flight: (batch, ok-flag) FIFO whose flag
        # transfers ride the async window (runtime/transfer.py) — resolved
        # k batches late so the steady state never blocks on a fold outcome.
        # Holds up to k batches' device arrays; k is the transfer window
        # depth (runtime.transfer.window.depth).
        from collections import deque

        self._pending: "deque" = deque()
        # owner-thread mutations vs MemManager mem_used() polls from OTHER
        # operator threads: deque iteration during a concurrent append
        # raises — take this lock around every _pending touch
        self._pending_lock = threading.Lock()
        self._depth = max(1, ctx.conf.get(TRANSFER_WINDOW_DEPTH))
        self._retry: list = []  # batches whose deferred fold was a no-op
        self._base_cfg = (
            exec_.mode == PARTIAL,
            tuple(
                (a.func, t) for (a, _), t in
                zip(exec_.aggs, exec_._agg_input_types)
            ),
        )
        # CPU-backend fold substrate: XLA:CPU lowers the segment scatters
        # to serial loops (~8x slower than np.bincount at 1M rows — the
        # hostsort fork, applied to scatter-reduce), so on that backend the
        # table lives in host numpy: sums/counts fold via bincount, min/max
        # via np.minimum/maximum.at (vectorized ufunc.at, numpy >= 1.24).
        from auron_tpu.ops import hostscatter

        # _dense_eligible() already restricted the aggregate set to
        # sum/avg/count/count_star/min/max — all of which the host fold
        # implements — so backend policy is the only remaining question
        self._host = hostscatter.use_host_scatter()
        # whole-stage fusion hand-off (plan/fusion.py DensePrepLink): when
        # the child is a fused stage built by agg-input prefusion, publish
        # the anchored table's geometry there so the stage compiles the
        # fold's guard/index/mask prep into ITS program; epoch stamps
        # every publication so stale-prepped batches fold via the raw path
        self._link = getattr(exec_, "_dense_prep_link", None)
        self._epoch = 0
        # bytes of the batches that wait in the arm's compaction boundary
        # (HashAggExec._execute) for their live counts: up to the transfer
        # window's depth of them, whole, beside the up to k folded ones
        # that _pending pins for their flags
        self.waiting_bytes = 0

    def fold_scatters(self, rows: int) -> tuple[int, int]:
        """(narrow, wide) planes one fold of a batch of ``rows`` rows
        scatters (``_dense_fold_scatters``): what its ``fold`` event says."""
        return _dense_fold_scatters(*self._base_cfg, rows)

    def fold_planes(self, rows: int) -> float:
        """What folding one dead row of a batch of ``rows`` rows into the
        table costs, in gathered elements (``S.SCATTER_NARROW`` a 32-bit
        plane, ``S.SCATTER_WIDE`` a 64-bit one): the ``present`` and valid
        flags, and every value plane as the program scatters it, a limbed
        sum as its ``k`` narrow planes. The ``dense_planes`` of the arm's
        ``compaction_bucket`` rule: what a row of capacity costs where the
        batch is folded as it came."""
        narrow, wide = self.fold_scatters(rows)
        return narrow * S.SCATTER_NARROW + wide * S.SCATTER_WIDE

    def reset(self) -> None:
        """Forget the table (after a drain) so the next update re-anchors.
        The covered value range survives as a HINT: the re-anchor pads the
        UNION of old+new ranges, so a steadily drifting key pays
        O(log(total_span)) restarts, not one per batch."""
        if self.bases is not None and self.dims is not None:
            # covered VALUES are [b, b+d-2] (offset = v - b + 1; offset 0
            # is the NULL lane); a dims==1 key was never anchored to real
            # values — no hint for it, or a bogus range would poison the
            # union re-anchor
            self._hint = [
                ((b, b + d - 2) if d > 1 else None)
                for b, d in zip(self.bases, self.dims)
            ]
        self.bases = None
        self.dims = None
        self.size = 0
        self.vals = self.valids = self.present = None
        self._epoch += 1
        if self._link is not None:
            self._link.clear()

    # -- input extraction lives on the exec (_keys_and_inputs): shared with
    # the probe/scatter path so column alignment can't diverge -----------

    def _keys_and_inputs(self, b: Batch):
        return self.exec._keys_and_inputs(b)

    def _alloc(self, size: int) -> None:
        ex = self.exec
        vals, valids = [], []
        for (a, _), in_t in zip(ex.aggs, ex._agg_input_types):
            fields = intermediate_fields(a, in_t if in_t is not None else T.INT64, "x")
            for f in fields:
                dt = f.dtype.physical_dtype()
                if a.func == "min" and f.name.endswith("#min"):
                    fill = S._max_identity(dt)
                elif a.func == "max" and f.name.endswith("#max"):
                    fill = S._min_identity(dt)
                else:
                    fill = 0
                vals.append(jnp.full(size, fill, dt))
                valids.append(
                    jnp.zeros(size, bool) if f.nullable else None
                )
        self.vals = tuple(vals)
        self.valids = tuple(valids)
        self.present = jnp.zeros(size, bool)
        self.size = size

    def take_retry(self) -> list:
        """Batches whose deferred fold turned out to be a no-op (out of
        range); they must be re-folded after drain+reset or routed to the
        generic path. Any still-unresolved in-flight folds are resolved
        first (a drain+reset invalidates their table)."""
        self._retry.extend(self.finish_pending())
        r, self._retry = self._retry, []
        return r

    def reset_with_retry(self) -> list:
        r = self.take_retry()
        self.reset()
        return r

    def finish_pending(self) -> list:
        """Resolve EVERY in-flight deferred fold; returns the batch(es)
        that were NOT folded (empty when all folds landed). The flag/column
        transfers were started at dispatch, so these harvests are
        normally already host-resident (async-read accounting)."""
        from auron_tpu.runtime.transfer import harvest

        failed = []
        while self._pending:
            with self._pending_lock:
                pb, payload = self._pending.popleft()
            if self._host:
                if self._fold_host(payload) != True:
                    failed.append(pb)
            else:
                (ok,) = harvest(payload)
                if not bool(ok):
                    failed.append(pb)
        return failed

    def update(self, b: Batch, defer: bool = True):
        """Fold one batch in. Returns True (folded, or fold in flight),
        "restart" (key ranges fell outside the anchored table: the caller
        drains + resets, then re-folds take_retry() + this batch), or
        False (the union range can never fit LIMIT: fall back for good).

        The anchored fold is ONE fused program that checks ranges and
        conditionally folds (all-or-nothing), returning a flag whose
        device->host transfer starts at dispatch; with ``defer`` the flag
        is harvested k batches later from the async window, so the steady
        state has no blocking host round-trip per batch. Table footprint
        is bounded by LIMIT slots x field widths (+ up to k in-flight
        batches, + up to k more that wait in the arm's compaction boundary
        for their live counts: ``waiting_bytes``), accounted as an
        unspillable consumer."""
        from auron_tpu.runtime.transfer import harvest, start_host_transfer

        if self._host:
            return self._update_host(b, defer=defer)
        if defer and len(self._pending) >= self._depth:
            # window full: harvest the OLDEST fold's outcome (its transfer
            # has ridden behind k batches of device compute)
            with self._pending_lock:
                pb0, flag0 = self._pending.popleft()
            (ok0,) = harvest(flag0)
            if not bool(ok0):
                self._retry.append(pb0)
                return "restart"
        elif not defer:
            failed = self.finish_pending()
            if failed:
                self._retry.extend(failed)
                return "restart"
        keys, per_agg = self._keys_and_inputs(b)
        if self.bases is not None:
            self.vals, self.valids, self.present, flag = _dense_update_jit(
                self.vals, self.valids, self.present,
                self._bases_dev, self._his_dev,
                tuple(k.values for k in keys),
                tuple(k.validity for k in keys),
                b.device.sel,
                per_agg, cfg=self._base_cfg + (self.dims,), size=self.size,
            )
            if defer:
                start_host_transfer(flag)
                with self._pending_lock:
                    self._pending.append((b, flag))
                return True
            if not bool(jax.device_get(flag)):  # auronlint: sync-point(8/task) -- fold-outcome read on the synchronous (end-of-stream/restart) path only
                # the fold was an all-or-nothing no-op; the CALLER re-folds
                # this batch after drain+reset (it is NOT queued in _retry —
                # every restart handler already re-submits the batch it
                # passed in, and queuing it here would fold it twice)
                return "restart"
            return True
        stats = [
            int(x) for x in jax.device_get(_dense_key_range_jit(  # auronlint: sync-point(8/task) -- dense-table anchor/re-anchor stats: first batch + O(log span) restarts, not steady state
                tuple(k.values for k in keys),
                tuple(k.validity for k in keys),
                b.device.sel,
            ))
        ]
        n = stats[0]
        if n == 0:
            return True
        if not self._anchor_from_stats(stats[1::2], stats[2::2]):
            return False
        # constant between re-anchors: upload once, reuse per batch
        self._bases_dev = jnp.asarray(self.bases, jnp.int64)
        self._his_dev = jnp.asarray(self._his, jnp.int64)
        self._alloc(bucket_capacity(self.size_hint))
        self.vals, self.valids, self.present, _ = _dense_update_jit(
            self.vals, self.valids, self.present,
            self._bases_dev, self._his_dev,
            tuple(k.values for k in keys),
            tuple(k.validity for k in keys),
            b.device.sel,
            per_agg, cfg=self._base_cfg + (self.dims,), size=self.size,
        )
        return True

    def _anchor_from_stats(self, mins, maxs) -> bool:
        """Anchor the table from observed per-key [min, max] ranges (plus
        the drained-range hint): pick padded pow-2 dims, bases and guard
        bounds. Returns False when the union range can never fit LIMIT.
        Shared by the device and host-scatter paths; the caller allocates."""
        spans = []
        for i, (mn, mx) in enumerate(zip(mins, maxs)):
            hint = self._hint[i] if self._hint is not None else None
            if mn > mx:  # all-null in this batch: anchor from the hint
                if hint is None:
                    # never saw a real value: NULL lane only (dim 1);
                    # the first real value later triggers a restart
                    # that anchors on ITS range, not a fake 0-anchor
                    spans.append((0, 0))
                    continue
                mn, mx = hint
            elif hint is not None:  # union with the drained range
                mn = min(mn, hint[0])
                mx = max(mx, hint[1])
            spans.append((mn, mx - mn + 1))
        # headroom: pad each dim to a power of two ~2x the observed
        # span and CENTER the span in it, so drifting key ranges
        # (time-ordered date keys) stay in-table instead of paying a
        # drain+restart per batch; pow-2 dims keep the static-dims jit
        # cache bounded. Shed padding largest-first when the product
        # would blow the LIMIT; exact spans are the floor.
        pads = [
            (1 if s == 0 else max(_next_pow2_agg(2 * (s + 1)), 4))
            for _, s in spans
        ]
        exact = [s + 1 for _, s in spans]
        def product(ds):
            t = 1
            for d in ds:
                t *= d
            return t
        while product(pads) > self.LIMIT and pads != exact:
            i = max(range(len(pads)), key=lambda i: pads[i] / exact[i])
            pads[i] = exact[i] if pads[i] // 2 < exact[i] else pads[i] // 2
        if product(pads) > self.LIMIT:
            return False
        bases = []
        for (mn, s), d in zip(spans, pads):
            slack = d - (s + 1)
            # center: headroom both ways (clamped so the base stays int64
            # even when anchoring right at the type minimum)
            bases.append(max(mn - slack // 2, -(1 << 63)))
        self.bases = bases
        self.dims = tuple(pads)
        # covered-value upper bounds for the fused guard, computed in
        # overflow-free Python ints and clamped to int64 (see kernel note)
        i64max = (1 << 63) - 1
        self._his = [min(b + d - 2, i64max) for b, d in zip(bases, pads)]
        self.size_hint = product(pads)
        return True

    # -- host-scatter fold (CPU backend: np.bincount beats XLA scatters) --

    def _publish_prep(self) -> None:
        """Publish the freshly anchored table geometry to the fused stage
        feeding this aggregate (plan/fusion.py DensePrepLink), so its NEXT
        batches arrive with the fold's guard/index/mask prep computed
        inside the stage program. Host-scatter substrate only — the device
        fold is already one fused scatter program. Per-key stride 0 marks
        a NULL-lane-only key (dims==1): its offset never contributes, and
        a real value there surfaces through the guard as a restart."""
        if self._link is None or not self._host:
            return
        self._epoch += 1
        strides, st = [], 1
        for d in self.dims:
            strides.append(st if d > 1 else 0)
            st *= d
        self._link.publish(
            epoch=self._epoch,
            bases=tuple(self.bases),
            his=tuple(self._his),
            dims=tuple(self.dims),
            size=self.size,
            bases_dev=jnp.asarray(self.bases, jnp.int64),
            his_dev=jnp.asarray(self._his, jnp.int64),
            strides_dev=jnp.asarray(strides, jnp.int64),
            size_dev=jnp.int64(self.size),
        )

    def _update_host(self, b: Batch, defer: bool = True):
        """Host-scatter fold with the SAME k-deep deferred protocol as the
        device path: the batch's key/input columns start their device->host
        copies at dispatch and the numpy fold (guard + np.bincount) runs
        when the entry falls out of the window — the pull is an
        async-window harvest, not a per-batch stall. Anchoring (no table
        yet / post-restart) resolves synchronously like the device path's
        stats read. int64 sums split into 16-bit limbs so bincount's
        float64 accumulator stays exact (wraps mod 2^64 like the device
        scatter)."""
        from auron_tpu.runtime.transfer import start_host_transfer

        if defer and len(self._pending) >= self._depth:
            with self._pending_lock:
                pb, payload = self._pending.popleft()
            if self._fold_host(payload) != True:
                self._retry.append(pb)
                # unlike the device path (whose deferred folds already
                # LANDED on device — only flags are pending), host folds
                # execute at harvest: resolve every remaining in-flight
                # entry into the still-anchored table NOW, or the caller's
                # drain would discard their rows
                self._retry.extend(self.finish_pending())
                return "restart"
        elif not defer:
            failed = self.finish_pending()
            if failed:
                self._retry.extend(failed)
                return "restart"
        # stage-prepped fold (plan/fusion.py): the fused stage already
        # computed guard stats, slot index and masked planes on device in
        # ITS program — transfer those instead of the raw columns and keep
        # only the bincount scatter-reduces on host. Stale-epoch payloads
        # (prepped under a pre-restart anchor) fall through to the raw path.
        prep = getattr(b, "_dense_prep", None)
        if prep is not None and self.bases is not None and prep.epoch == self._epoch:
            leaves, treedef = jax.tree_util.tree_flatten(prep.tree())
            if not defer:
                # same synchronous end-of-stream/retry contract as the raw
                # branch below (one budget, one reason)
                got = jax.device_get(tuple(leaves))  # auronlint: sync-point(8/task) -- host-scatter end-of-stream/retry fold (prepped planes): same bound as the raw branch
                return self._fold_prepped_arrays(
                    prep, jax.tree_util.tree_unflatten(treedef, got)
                )
            start_host_transfer(*leaves)
            with self._pending_lock:
                self._pending.append((b, ("prep", prep, leaves, treedef)))
            return True
        keys, per_agg = self._keys_and_inputs(b)
        pytree = (
            b.device.sel,
            tuple(k.values for k in keys),
            tuple(k.validity for k in keys),
            per_agg,
        )
        leaves, treedef = jax.tree_util.tree_flatten(pytree)
        if self.bases is None or not defer:
            # resolve NOW: no anchored table yet (first batch, post-restart
            # refolds — a can-never-fit range must report False
            # synchronously so the fallback protocol terminates), or the
            # caller is on the synchronous end-of-stream/retry path. A
            # blocking read by design, so it carries its own per-task
            # budget instead of riding the async-harvest site.
            got = jax.device_get(tuple(leaves))  # auronlint: sync-point(8/task) -- host-scatter anchor/re-anchor/end-of-stream fold: first batch + O(log span) restarts, not steady state
            return self._fold_host_arrays(
                *jax.tree_util.tree_unflatten(treedef, got)
            )
        start_host_transfer(*leaves)
        with self._pending_lock:
            self._pending.append((b, ("raw", leaves, treedef)))
        return True

    def _fold_host(self, payload):
        """Resolve one deferred entry: harvest the landed arrays and fold."""
        from auron_tpu.runtime.transfer import harvest

        if payload[0] == "prep":
            _, prep, leaves, treedef = payload
            return self._fold_prepped_arrays(
                prep, jax.tree_util.tree_unflatten(treedef, harvest(*leaves))
            )
        _, leaves, treedef = payload
        return self._fold_host_arrays(
            *jax.tree_util.tree_unflatten(treedef, harvest(*leaves))
        )

    def _fold_prepped_arrays(self, prep, tree):
        """Fold one STAGE-PREPPED batch: the fused stage program computed
        the guard statistics, the packed slot index and the per-agg masked
        planes (mirroring _fold_host_arrays' arithmetic bit-for-bit); this
        keeps only the range-guard comparison and the bincount
        scatter-reduces. Guard bounds come from the payload's OWN anchor
        copy — the one its planes were computed under."""
        sel_d, idx_d, guards, planes = tree
        sel = np.asarray(sel_d)
        if not sel.any():
            return True
        any_ok, mns, mxs = (np.asarray(g) for g in guards)
        for i in range(len(prep.dims)):
            if not bool(any_ok[i]):
                continue
            if prep.dims[i] == 1:
                return "restart"  # NULL-lane-only key saw a real value
            if int(mns[i]) < prep.bases[i] or int(mxs[i]) > prep.his[i]:
                return "restart"
        if prep.epoch != self._epoch or prep.size != self.size:
            # defensive: submission-time checks make this unreachable (a
            # restart resolves every pending fold before re-anchoring)
            return "restart"
        size = self.size
        idx = np.asarray(idx_d)

        def bc(weights=None):
            return np.bincount(idx, weights=weights, minlength=size + 1)[:size]

        live_cnt = bc(sel.astype(np.float64))
        self.present |= live_cnt > 0
        fi = 0
        for (a, _), plane in zip(self.exec.aggs, planes):
            func = a.func
            if func in ("count", "count_star"):
                if func == "count_star":
                    contrib = live_cnt.astype(np.int64)
                else:
                    ok = np.asarray(plane[0])
                    contrib = bc(ok.astype(np.float64)).astype(np.int64)
                self.vals[fi] += contrib
                fi += 1
                continue
            if func in ("min", "max"):
                vm = np.asarray(plane[0])
                ok = np.asarray(plane[1])
                old = self.vals[fi]
                if func == "min":
                    ident = S._max_identity(old.dtype)
                    contrib = np.full(size + 1, ident, old.dtype)
                    np.minimum.at(contrib, idx, vm)
                    both = np.minimum(old, contrib[:size])
                else:
                    ident = S._min_identity(old.dtype)
                    contrib = np.full(size + 1, ident, old.dtype)
                    np.maximum.at(contrib, idx, vm)
                    both = np.maximum(old, contrib[:size])
                cv_valid = bc(ok.astype(np.float64)) > 0
                old_valid = self.valids[fi]
                self.vals[fi] = np.where(
                    old_valid & cv_valid, both,
                    np.where(cv_valid, contrib[:size], old),
                )
                self.valids[fi] = old_valid | cv_valid
                fi += 1
                continue
            # sum / avg: vm is where(ok, cast(v), 0) computed on device
            vm = np.asarray(plane[0])
            ok = np.asarray(plane[1])
            ok_cnt = bc(ok.astype(np.float64))
            if self.vals[fi].dtype.kind == "f":
                s = bc(vm)
            else:
                s = _bincount_i64(idx, vm, size)
            self.vals[fi] += s.astype(self.vals[fi].dtype)
            self.valids[fi] |= ok_cnt > 0
            fi += 1
            if func == "avg":
                self.vals[fi] += ok_cnt.astype(np.int64)
                fi += 1
        return True

    def _fold_host_arrays(self, sel_d, kv_d, km_d, agg_d):
        sel = np.asarray(sel_d)
        kvs = [np.asarray(v) for v in kv_d]
        kms = [np.asarray(m) for m in km_d]
        if not sel.any():
            return True
        if self.bases is None:
            mins, maxs = [], []
            imax = np.iinfo(np.int64).max
            imin = np.iinfo(np.int64).min
            for v, m in zip(kvs, kms):
                ok = sel & m
                if ok.any():
                    s = v[ok].astype(np.int64)
                    mins.append(int(s.min()))
                    maxs.append(int(s.max()))
                else:
                    mins.append(imax)
                    maxs.append(imin)
            if not self._anchor_from_stats(mins, maxs):
                return False
            self._alloc_host(bucket_capacity(self.size_hint))
            self._publish_prep()
        # range guard, same semantics as the fused device guard
        for i, (v, m) in enumerate(zip(kvs, kms)):
            ok = sel & m
            if not ok.any():
                continue
            if self.dims[i] == 1:
                return "restart"  # NULL-lane-only key saw a real value
            s = v[ok].astype(np.int64)
            if int(s.min()) < self.bases[i] or int(s.max()) > self._his[i]:
                return "restart"
        size = self.size
        idx = np.zeros(sel.shape, np.int64)
        stride = 1
        for i, (v, m) in enumerate(zip(kvs, kms)):
            if self.dims[i] > 1:
                off = np.where(
                    m,
                    np.clip(v.astype(np.int64), self.bases[i], self._his[i])
                    - self.bases[i] + 1,
                    0,
                )
                idx += off * stride
            stride *= self.dims[i]
        idx = np.where(sel, np.clip(idx, 0, size - 1), size)

        def bc(weights=None):
            return np.bincount(idx, weights=weights, minlength=size + 1)[:size]

        live_cnt = bc(sel.astype(np.float64))
        self.present |= live_cnt > 0
        raw = self._base_cfg[0]
        fi = 0
        for (a, _), ins in zip(self.exec.aggs, agg_d):
            func = a.func
            ins = [(np.asarray(v), np.asarray(m)) for v, m in ins]
            if func in ("count", "count_star"):
                if not raw:
                    v, _ = ins[0]
                    contrib = _bincount_i64(idx, np.where(sel, v, 0), size)
                elif func == "count_star":
                    contrib = live_cnt.astype(np.int64)
                else:
                    _, m = ins[0]
                    contrib = bc((m & sel).astype(np.float64)).astype(np.int64)
                self.vals[fi] += contrib
                fi += 1
                continue
            if func in ("min", "max"):
                # np.minimum/maximum.at: vectorized since numpy 1.24, ~9x
                # the XLA serial scatter at 1M rows. NaN-propagating like
                # the device path's lax.min/max.
                v, m = ins[0]
                ok = m & sel
                old = self.vals[fi]
                if func == "min":
                    ident = S._max_identity(old.dtype)
                    contrib = np.full(size + 1, ident, old.dtype)
                    np.minimum.at(contrib, idx, np.where(ok, v, ident).astype(old.dtype))
                    both = np.minimum(old, contrib[:size])
                else:
                    ident = S._min_identity(old.dtype)
                    contrib = np.full(size + 1, ident, old.dtype)
                    np.maximum.at(contrib, idx, np.where(ok, v, ident).astype(old.dtype))
                    both = np.maximum(old, contrib[:size])
                cv_valid = bc(ok.astype(np.float64)) > 0
                old_valid = self.valids[fi]
                self.vals[fi] = np.where(
                    old_valid & cv_valid, both,
                    np.where(cv_valid, contrib[:size], old),
                )
                self.valids[fi] = old_valid | cv_valid
                fi += 1
                continue
            # sum / avg
            v, m = ins[0]
            ok = m & sel
            if self.vals[fi].dtype.kind == "f":
                s = bc(np.where(ok, v.astype(np.float64), 0.0))
            else:
                s = _bincount_i64(idx, np.where(ok, v.astype(np.int64), 0), size)
            self.vals[fi] += s.astype(self.vals[fi].dtype)
            self.valids[fi] |= bc(ok.astype(np.float64)) > 0
            fi += 1
            if func == "avg":
                if raw:
                    c = bc(ok.astype(np.float64)).astype(np.int64)
                else:
                    cv, _ = ins[1]
                    c = _bincount_i64(idx, np.where(sel, cv, 0), size)
                self.vals[fi] += c
                fi += 1
        return True

    def _alloc_host(self, size: int) -> None:
        ex = self.exec
        vals, valids = [], []
        for (a, _), in_t in zip(ex.aggs, ex._agg_input_types):
            fields = intermediate_fields(a, in_t if in_t is not None else T.INT64, "x")
            for f in fields:
                dt = np.dtype(f.dtype.physical_dtype().name)
                if a.func == "min" and f.name.endswith("#min"):
                    fill = S._max_identity(dt)
                elif a.func == "max" and f.name.endswith("#max"):
                    fill = S._min_identity(dt)
                else:
                    fill = 0
                vals.append(np.full(size, fill, dt))
                valids.append(np.zeros(size, bool) if f.nullable else None)
        self.vals = vals
        self.valids = valids
        self.present = np.zeros(size, bool)
        self.size = size

    def state_batch_and_count(self) -> tuple[Batch | None, int]:
        """Materialize the table as a (sparse-sel) intermediate batch."""
        if self.bases is None or self.present is None:
            return None, 0
        ex = self.exec
        if self._host:
            g = int(self.present.sum())  # host arrays: no device sync
            present = jnp.asarray(self.present)
            acc_vals = [jnp.asarray(v) for v in self.vals]
            acc_valids = [
                jnp.asarray(m) if m is not None else None for m in self.valids
            ]
        else:
            # auronlint: disable=R9 -- dense drains happen on dense-limit overflow (bounded by table growth, O(log) per task) and at stream end, not per batch
            g = int(jax.device_get(jnp.sum(self.present)))  # auronlint: sync-point(4/task) -- group count read once at table emission (blocking boundary)
            present = self.present
            acc_vals = list(self.vals)
            acc_valids = list(self.valids)
        if g == 0:
            return None, 0
        slot = jnp.arange(self.size, dtype=jnp.int64)
        cols = []
        stride = 1
        for i in range(ex.n_keys):
            key_f = ex.inter_schema[i]
            phys = key_f.dtype.physical_dtype()
            coord = (slot // stride) % self.dims[i]
            vals = (coord - 1 + self.bases[i]).astype(phys)
            cols.append(ColumnVal(vals, present & (coord > 0), key_f.dtype, None))
            stride *= self.dims[i]
        for fi, f in enumerate(ex.inter_schema.fields[ex.n_keys:]):
            m = acc_valids[fi]
            cols.append(ColumnVal(
                acc_vals[fi],
                (m & present) if m is not None else present,
                f.dtype,
                None,
            ))
        out = batch_from_columns(cols, ex.inter_schema.names, present)
        sb = Batch(ex.inter_schema, out.device, out.dicts)
        from auron_tpu.columnar.batch import compact_batch

        # compact to the GROUP bucket: a sparse range-sized batch (2 groups
        # in a 2^21-slot table) must not flow downstream at range capacity
        return compact_batch(sb, bucket_capacity(g)), g

    def mem_used(self) -> int:
        from auron_tpu.exec.sort_exec import batch_nbytes

        # in-flight deferred folds pin their batches until harvest, and
        # the batches ahead of them wait in the compaction boundary
        with self._pending_lock:
            pending = list(self._pending)
        total = self.waiting_bytes + sum(batch_nbytes(pb) for pb, _ in pending)
        if self.vals is None:
            return total
        total += self.size  # present bools
        for v in self.vals:
            total += v.size * v.dtype.itemsize
        for m in self.valids:
            if m is not None:
                total += m.size
        return total

    def spill(self) -> int:  # auronlint: thread-root(foreign) -- MemManager polls/dispatches from other tasks' threads
        return 0  # unspillable (fixed footprint); drained at stream end

    def release(self, mm) -> None:
        self.vals = self.valids = self.present = None
        if self._link is not None:
            self._link.clear()  # permanent fallback: stage stops prepping
        with self._pending_lock:
            self._pending.clear()  # drop in-flight fold refs (cancel path)


# ---------------------------------------------------------------------------
# Incremental sorted-state probe/scatter (exec.agg.incremental.probe)
# ---------------------------------------------------------------------------


#: check-and-set guard for a Batch's ``_fp_collision_host``: the operator
#: thread (_note_collision) and a cross-thread spill's merge
#: (_resolve_fp_flags, under the table lock the operator does NOT hold
#: here) may race on the same staged batch — without this, both could see
#: the flag unset and double-count fp_collision_batches
_FP_FLAG_LOCK = threading.Lock()


def _note_collision(ref: Batch, coll: int, metrics) -> None:
    """Record a just-read fingerprint collision flag exactly once per
    reduce output (merge boundaries may race the per-batch read)."""
    with _FP_FLAG_LOCK:
        if hasattr(ref, "_fp_collision_host"):
            return
        ref._fp_collision_host = bool(coll)
    if coll:
        metrics.add("fp_collision_batches", 1)


@partial(jax.jit, static_argnames=("cfg",))
def _state_fp_jit(skey_v, skey_m, state_sel, *, cfg):
    """State-row fingerprints (dead slots -> MAX): computed ONCE per state
    batch and cached as ``_inc_fp`` — merges produce it for free, this is
    the fallback for states that predate the cache (e.g. read back from a
    spill run)."""
    _raw, _specs, key_dtypes, fp_bits = cfg
    skeys = [ColumnVal(v, m, dt, None)
             for v, m, dt in zip(skey_v, skey_m, key_dtypes)]
    return jnp.where(
        state_sel,
        hashing.fingerprint64(S.key_words(skeys), fp_bits),
        jnp.uint64(0xFFFFFFFFFFFFFFFF),
    )


@partial(jax.jit, static_argnames=("cfg",))
def _probe_scatter_jit(
    state_sel, state_fp, skey_v, skey_m, sacc_v, sacc_m, key_v, key_m, sel,
    agg_ins, *, cfg,
):
    """ONE fused program: binary-search every batch row into the
    fingerprint-sorted state, verify TRUE key-word equality at the found
    slot (a colliding fingerprint is a miss, never a wrong fold), and
    scatter-add the hit rows straight into the state accumulators.

    Steady-state repeating-key batches therefore cost O(n log S) compares
    plus one scatter per accumulator column — no sort. Miss rows come back
    as a selection mask; the host resolves their count k batches later
    through the async transfer window and routes only those through
    sort-segmentation + staging."""
    raw, agg_specs, key_dtypes, fp_bits = cfg
    s_cap = state_sel.shape[0]
    cap = sel.shape[0]
    skeys = [ColumnVal(v, m, dt, None)
             for v, m, dt in zip(skey_v, skey_m, key_dtypes)]
    bkeys = [ColumnVal(v, m, dt, None)
             for v, m, dt in zip(key_v, key_m, key_dtypes)]
    # state WORDS are still needed for the equality check (cheap views);
    # the state fp — the expensive chained hash — arrives precomputed
    swords = S.key_words(skeys)
    bwords = S.key_words(bkeys)
    fp = hashing.fingerprint64(bwords, fp_bits)
    slot = binsearch.lower_bound_dyn([state_fp], [fp], jnp.int32(s_cap))
    slotc = jnp.clip(slot, 0, s_cap - 1)
    hit = sel & state_sel[slotc] & (state_fp[slotc] == fp)
    for sw, bw in zip(swords, bwords):
        hit = hit & (sw[slotc] == bw)
    idx = jnp.where(hit, slotc, s_cap)
    nseg = s_cap + 1

    def ssum(vals):
        return jax.ops.segment_sum(vals, idx, num_segments=nseg)[:s_cap]

    def sany(flags):
        return _seg_any(flags, idx, nseg)[:s_cap]

    new_v = list(sacc_v)
    new_m = list(sacc_m)
    fi = 0
    for (a, in_t), ins in zip(agg_specs, agg_ins):
        func = a.func
        if func in ("count", "count_star"):
            if not raw:
                v, _ = ins[0]
                contrib = ssum(jnp.where(hit, v, 0).astype(jnp.int64))
            elif func == "count_star":
                contrib = ssum(hit.astype(jnp.int64))
            else:
                _, m = ins[0]
                contrib = ssum((hit & m).astype(jnp.int64))
            new_v[fi] = sacc_v[fi] + contrib
            fi += 1
            continue
        if func in ("sum", "avg"):
            wide = is_wide_sum(in_t)
            k = _n_limbs(sum_type(in_t).precision) if wide else 1
            if wide:
                if raw:
                    v, m = ins[0]
                    ok = hit & m
                    cur = jnp.where(ok, v.astype(jnp.int64), jnp.int64(0))
                    limb_vals = []
                    for _ in range(k - 1):
                        limb_vals.append(jnp.mod(cur, _LIMB_BASE))
                        cur = jnp.floor_divide(cur, _LIMB_BASE)
                    limb_vals.append(cur)
                    oks = [ok] * k
                else:
                    limb_vals, oks = [], []
                    for i in range(k):
                        v, m = ins[i]
                        oks.append(hit & m)
                        limb_vals.append(
                            jnp.where(oks[-1], v.astype(jnp.int64), jnp.int64(0))
                        )
                for i, (lv, ok) in enumerate(zip(limb_vals, oks)):
                    new_v[fi + i] = sacc_v[fi + i] + ssum(lv)
                    new_m[fi + i] = sacc_m[fi + i] | sany(ok)
            else:
                v, m = ins[0]
                ok = hit & m
                new_v[fi] = sacc_v[fi] + ssum(jnp.where(ok, v, jnp.zeros_like(v)))
                new_m[fi] = sacc_m[fi] | sany(ok)
            fi += k
            if func == "avg":
                if raw:
                    c = ssum((hit & ins[0][1]).astype(jnp.int64))
                else:
                    cv, _ = ins[k]
                    c = ssum(jnp.where(hit, cv, 0).astype(jnp.int64))
                new_v[fi] = sacc_v[fi] + c
                fi += 1
            continue
        if func in ("min", "max"):
            v, m = ins[0]
            ok = hit & m
            if func == "min":
                ident = S._max_identity(v.dtype)
                contrib = jax.ops.segment_min(
                    jnp.where(ok, v, jnp.asarray(ident, v.dtype)), idx,
                    num_segments=nseg,
                )[:s_cap]
                both = jnp.minimum(sacc_v[fi], contrib)
            else:
                ident = S._min_identity(v.dtype)
                contrib = jax.ops.segment_max(
                    jnp.where(ok, v, jnp.asarray(ident, v.dtype)), idx,
                    num_segments=nseg,
                )[:s_cap]
                both = jnp.maximum(sacc_v[fi], contrib)
            cv_valid = sany(ok)
            old_valid = sacc_m[fi]
            new_v[fi] = jnp.where(
                old_valid & cv_valid, both,
                jnp.where(cv_valid, contrib, sacc_v[fi]),
            )
            new_m[fi] = old_valid | cv_valid
            fi += 1
            continue
        if func in ("first", "first_ignores_null"):
            v, m = ins[0]
            if raw:
                elig = hit & (m if func == "first_ignores_null" else jnp.ones_like(m))
            else:
                sv, _ = ins[1]
                elig = hit & sv.astype(bool)
            pos = jnp.arange(cap, dtype=jnp.int32)
            first_pos = jax.ops.segment_min(
                jnp.where(elig, pos, cap), idx, num_segments=nseg
            )[:s_cap]
            has = first_pos < cap
            safe = jnp.clip(first_pos, 0, cap - 1)
            fv = v[safe]
            fm = m[safe] & has
            seen_old = sacc_v[fi + 1].astype(bool)
            take = has & ~seen_old
            new_v[fi] = jnp.where(take, fv.astype(sacc_v[fi].dtype), sacc_v[fi])
            new_m[fi] = jnp.where(take, fm, sacc_m[fi])
            new_v[fi + 1] = seen_old | has
            fi += 2
            continue
        raise AssertionError(func)
    miss = sel & ~hit
    return (
        tuple(new_v), tuple(new_m), miss,
        jnp.sum(miss).astype(jnp.int64), jnp.sum(hit).astype(jnp.int64),
    )


class _ProbeScatter:
    """Sorted-state probe/scatter driver (exec.agg.incremental.probe).

    Wraps the per-batch _probe_scatter_jit fold with the table-lock
    discipline (a cross-thread spill must serialize against the in-place
    state swap) and the k-deep deferred miss window (the miss count is
    harvested from the async transfer window, so a fully-hitting steady
    state never blocks on a per-batch read). Registered as an unspillable
    memory consumer for the up-to-k pinned in-flight batches."""

    def __init__(self, exec_: "HashAggExec", ctx: ExecutionContext,
                 table: "_AggTableConsumer"):
        from collections import deque

        self.name = f"agg-probe-{id(exec_):x}"
        self.exec = exec_
        self.ctx = ctx
        self.table = table
        self._pending: "deque" = deque()
        # same discipline as _DenseAggState: MemManager polls mem_used()
        # from other operator threads while fold()/harvest mutate
        self._pending_lock = threading.Lock()
        self._depth = max(1, ctx.conf.get(TRANSFER_WINDOW_DEPTH))
        self._cfg = (
            exec_.mode == PARTIAL,
            tuple((a, t) for (a, _), t in
                  zip(exec_.aggs, exec_._agg_input_types)),
            tuple(exec_.inter_schema[i].dtype for i in range(exec_.n_keys)),
            # ctx.conf, NOT active_conf(): the probe cfg must match the fp
            # layout of THIS task's state even when a cross-thread spill
            # merge touches it (the PR 3 fp.bits lesson, R7)
            ctx.conf.get(AGG_INCREMENTAL_FP_BITS),
        )

    def _ready(self) -> bool:
        st = self.table.state
        return st is not None and getattr(st, "_fp_order", False)

    def fold(self, b: Batch) -> tuple[bool, list[Batch], int]:
        """Probe one batch into the state. Returns (folded, miss_batches,
        hit_rows): miss_batches are PRIOR batches whose deferred miss count
        came back nonzero — the caller routes them through the generic path
        with their selection narrowed to the miss rows — and hit_rows is
        the number of rows those prior folds scattered into the state,
        which the caller must feed into the partial-skip heuristic's row
        counter (rows with ZERO new groups: hit-heavy streams must pull
        the observed cardinality ratio DOWN, not vanish from it)."""
        from auron_tpu.runtime.transfer import start_host_transfer

        self._harvested_hits = 0
        out: list[Batch] = []
        if len(self._pending) >= self._depth:
            out += self._harvest_one()
        with self.table._lock:
            ready = self._ready()
        if not ready:
            # a spill parked the state mid-window: the caller stages THIS
            # batch generically right away, so every older in-flight
            # batch's miss rows must stage first — drain the window now or
            # first/first_ignores_null would see rows out of stream order
            out += self.finish()
            return False, out, self._harvested_hits
        keys, per_agg = self.exec._keys_and_inputs(b)
        nk = self.exec.n_keys
        ncols = len(self.exec.inter_schema.fields)
        with self.table._lock:
            st = self.table.state
            if st is None or not getattr(st, "_fp_order", False):
                # a concurrent spill took the state between the peek and
                # the fold — same stream-order obligation as above
                st = None
            else:
                skey_v = tuple(st.col_values(i) for i in range(nk))
                skey_m = tuple(st.col_validity(i) for i in range(nk))
                state_fp = getattr(st, "_inc_fp", None)
                if state_fp is None:
                    # cache miss (state predating the reduce-attached cache,
                    # e.g. decoded from a spill run): hash once, keep forever —
                    # probe folds never change the key columns
                    state_fp = st._inc_fp = _state_fp_jit(
                        skey_v, skey_m, st.device.sel, cfg=self._cfg
                    )
                new_v, new_m, miss, miss_n, hit_n = _probe_scatter_jit(
                    st.device.sel, state_fp, skey_v, skey_m,
                    tuple(st.col_values(i) for i in range(nk, ncols)),
                    tuple(st.col_validity(i) for i in range(nk, ncols)),
                    tuple(k.values for k in keys),
                    tuple(k.validity for k in keys),
                    b.device.sel, per_agg, cfg=self._cfg,
                )
                dev = DeviceBatch(
                    st.device.sel,
                    skey_v + new_v,
                    skey_m + new_m,
                )
                ns = Batch(st.schema, dev, st.dicts)
                ns._inc_fp = state_fp
                for attr in ("_fp_order", "_fp_collision", "_fp_collision_host",
                             "_groups"):
                    if hasattr(st, attr):
                        setattr(ns, attr, getattr(st, attr))
                # in-place accumulator swap: keys, sel, capacity, bytes all
                # unchanged, so the consumer's memory accounting stands
                self.table.state = ns
        if st is None:
            out += self.finish()
            return False, out, self._harvested_hits
        start_host_transfer(miss_n, hit_n)
        with self._pending_lock:
            self._pending.append((b, miss, miss_n, hit_n))
        return True, out, self._harvested_hits

    def _harvest_one(self) -> list[Batch]:
        from auron_tpu.runtime.transfer import harvest

        with self._pending_lock:
            b, miss, miss_n, hit_n = self._pending.popleft()
        mn, hn = (int(x) for x in harvest(miss_n, hit_n))
        self.ctx.metrics.add("probe_hit_rows", hn)
        self._harvested_hits = getattr(self, "_harvested_hits", 0) + hn
        if mn == 0:
            return []
        return [
            b.with_device(DeviceBatch(miss, b.device.values, b.device.validity))
        ]

    def finish(self) -> list[Batch]:
        """Resolve every in-flight deferred fold (end of stream)."""
        out: list[Batch] = []
        while self._pending:
            out += self._harvest_one()
        return out

    def mem_used(self) -> int:
        from auron_tpu.exec.sort_exec import batch_nbytes

        with self._pending_lock:
            pending = list(self._pending)
        return sum(batch_nbytes(pb) for pb, _, _, _ in pending)

    def spill(self) -> int:  # auronlint: thread-root(foreign) -- MemManager polls/dispatches from other tasks' threads
        return 0  # pinned in-flight batches only; resolved within k batches

    def release(self) -> None:
        with self._pending_lock:
            self._pending.clear()
