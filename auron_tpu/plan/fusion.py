"""Whole-stage fusion: pipeline segments -> single XLA programs.

PR 3 proved the thesis at operator scope (``exec.filter.fuse``: one jitted
program per predicate chain). This pass generalizes it Flare-style (PAPERS
1703.08219): a **segment finder** walks the instantiated exec tree and
identifies maximal scan->filter->project(->partial-agg-input) pipeline
segments between blocking boundaries (sort, agg state, join build, shuffle,
collect — every operator that is not a stateless row-pipeline stage), a
**stage compiler** traces each segment's per-batch work into ONE jitted XLA
program keyed on ``(schema, segment signature, compaction bucket)``, and a
**cost model** chooses fuse vs. materialize per segment (SystemML-style
selection, PAPERS 1801.00829): operator cost = estimated eager dispatches
(expression DAG nodes + per-operator overhead), substrate-resolved through
``utils.config.resolve_tri`` — accelerators always fuse, XLA:CPU fuses only
segments whose eager cost reaches ``exec.fuse.min.ops`` (the PR-3-measured
CPU exception: fused chains beat eager dispatch there too).

Fusion is an EXEC-TREE rewrite (``task_from_proto`` applies it after column
pruning): the protobuf plan, plan goldens and ``plan/explain`` output are
untouched, and results are bit-identical with the pass off
(``exec.fuse.enable=off`` — the A/B lever the fuzz suite and the perf gate
exercise).

Invariants the fused stage preserves (docs/fusion.md):

- R10 jit-boundary purity: the traced region is the same trace-safe
  expression machinery behind ``exec.filter.fuse`` (``exprs/eval.py``
  evaluated over a dict-less device batch); no conf reads, host transfers
  or captured-state mutation inside the trace (auronlint R10 checks the
  closure, R2 the cache-key discipline).
- Dictionary passthrough: a dict-encoded column may ride THROUGH a fused
  segment only as a bare column reference — its codes flow through the
  program, the host-side dictionary re-attaches on emission. Expressions
  that *transform* dictionaries (string compare/LIKE/casts) stay eager.
- Batch protocol: fused stages refine the selection mask exactly like
  FilterExec (no compaction inside the stage), so downstream compaction
  boundaries — including the selectivity predictor's mispredict repair —
  see the same batches they would without fusion, and emitted batches
  remain prefetchable through the async transfer window.
- Metric attribution: fused-program wall time is split back into
  per-operator MetricNode children (proportional to the cost model's
  per-operator weights), and the SAME split nanos are handed to the obs
  flight recorder as ``op`` events — ``top_ops`` sees
  FilterExec/ProjectExec/HashAggExec, never one opaque stage.
"""

from __future__ import annotations

import threading
import time
from functools import partial as _partial

import jax
import jax.numpy as jnp
import numpy as np

from auron_tpu import obs
from auron_tpu import types as T
from auron_tpu.columnar.batch import Batch, DeviceBatch, note_index
from auron_tpu.exec.base import ExecOperator, ExecutionContext
from auron_tpu.exprs import Evaluator, ir
from auron_tpu.utils.config import (
    FUSE_AGG_INPUTS,
    FUSE_ENABLE,
    FUSE_MIN_OPS,
    FUSE_PROBE,
    FUSE_SHUFFLE,
    Configuration,
    resolve_tri,
)

# ---------------------------------------------------------------------------
# trace safety
# ---------------------------------------------------------------------------

#: expression nodes whose evaluation is a pure jnp program over dict-free
#: operands — the exec.filter.fuse whitelist plus In (numeric membership is
#: a pure compare/or chain). Everything else (scalar funcs, host UDFs,
#: row-offset context, LIKE, subqueries) stays eager.
_FUSABLE_NODES = (
    ir.Literal, ir.Cast, ir.BinaryOp, ir.Not, ir.IsNull, ir.IsNotNull,
    ir.If, ir.Case, ir.Coalesce, ir.In,
)

_NESTED_KINDS = (T.TypeKind.LIST, T.TypeKind.MAP, T.TypeKind.STRUCT)


def expr_trace_safe(e: ir.Expr, schema: T.Schema, allow_dict_out: bool = False) -> bool:
    """True when evaluating ``e`` inside a jit over a dict-less batch is
    exactly the eager evaluation. ``allow_dict_out`` permits a BARE
    dict-encoded column reference (projection passthrough: codes flow
    through the program, the dictionary re-attaches host-side); computed
    dict-encoded results never fuse — their evaluation transforms host
    dictionaries. IsNull/IsNotNull over a bare column are safe even for
    dict columns (they read only the validity plane)."""
    if isinstance(e, ir.Column):
        dt = e.dtype_of(schema)
        return allow_dict_out or not (dt.is_dict_encoded or dt.kind in _NESTED_KINDS)
    if isinstance(e, (ir.IsNull, ir.IsNotNull)) and isinstance(e.child, ir.Column):
        return True
    if not isinstance(e, _FUSABLE_NODES):
        return False
    dt = e.dtype_of(schema)
    if dt.is_dict_encoded or dt.kind in _NESTED_KINDS:
        return False
    return all(expr_trace_safe(c, schema) for c in e.children())


def _expr_nodes(e: ir.Expr) -> int:
    return 1 + sum(_expr_nodes(c) for c in e.children())


# ---------------------------------------------------------------------------
# the stage program (ONE jit; cache key = static (steps, emit) + shapes)
# ---------------------------------------------------------------------------


def _trace_steps(dev: DeviceBatch, steps: tuple):
    """The shared traced step walk: apply ("filter", schema, predicates) /
    ("project", schema, exprs) stages in order; each step carries the
    ORIGINAL operator's input schema so expression typing is exactly the
    eager path's. Returns (sel, values, validity, final projection's
    ColumnVals or None). The common-subexpression memo is shared across
    consecutive steps over the same input columns and reset at every
    projection (which replaces the column planes). Each step's operations
    carry the scope name ``auron.stage.step<i>.<filter|project>`` (HLO
    metadata only: what a device trace names them by)."""
    sel = dev.sel
    values, validity = dev.values, dev.validity
    outs = None
    memo: dict = {}
    for i, step in enumerate(steps):
        kind, schema, exprs = step
        with jax.named_scope(f"auron.stage.step{i}.{kind}"):
            b = Batch(schema, DeviceBatch(sel, values, validity),
                      (None,) * len(schema.fields))
            ev = Evaluator(schema, partition_id=0, row_offset=0, resources={})
            if kind == "filter":
                for p in exprs:
                    cv = ev._eval(p, b, memo)
                    sel = sel & cv.validity & cv.values.astype(bool)
            else:
                outs = [ev._eval(e, b, memo) for e in exprs]
                values = tuple(cv.values for cv in outs)
                validity = tuple(cv.validity for cv in outs)
                memo = {}
    return sel, values, validity, outs


@_partial(obs.jit, static_argnames=("steps", "emit"))
def _stage_program(dev: DeviceBatch, *, steps: tuple, emit: str):
    """The whole segment's per-batch work as ONE compiled program.
    ``emit`` is "sel" (filter-only segment: the caller reuses the input
    columns) or "cols" (the final projection's columns are returned)."""
    sel, values, validity, _ = _trace_steps(dev, steps)
    if emit == "sel":
        return sel
    return sel, values, validity


# 2^62 sentinels for the per-key guard min/max reductions (ignored by the
# consumer unless the key saw a live valid row — the any_ok flag)
_GUARD_HI = (1 << 62)


@_partial(obs.jit, static_argnames=("steps", "prep"))
def _stage_program_prep(dev: DeviceBatch, bases, his, strides, size, *,
                        steps: tuple, prep: tuple):
    """Stage program variant for segments feeding a DENSE partial
    aggregate on the host-scatter substrate: in the SAME compiled program
    as the filter/project work, compute the dense fold's per-batch prep —
    the range-guard statistics, the packed slot index and the per-agg
    masked value planes — so the host keeps only the bincount
    scatter-reduces (the substrate choice PR 3 measured; the ~6 numpy
    passes of guard/index/mask arithmetic move into this one XLA pass).

    ``bases``/``his``/``strides``/``size`` are the anchor geometry owned
    by the aggregate's dense table — ALL device ARGUMENTS, never statics,
    so a re-anchor (even onto a different table size) reuses the compiled
    program; ``prep`` is the static (n_keys, agg plane spec). Every
    computation mirrors _DenseAggState._fold_host_arrays bit-for-bit:
    same masks, same clip arithmetic, same identities."""
    from auron_tpu.ops import segments as S

    sel, values, validity, outs = _trace_steps(dev, steps)
    n_keys, aggs = prep
    idx = jnp.zeros(dev.sel.shape, jnp.int64)
    any_l, mn_l, mx_l = [], [], []
    for i in range(n_keys):
        kv = outs[i]
        v64 = kv.values.astype(jnp.int64)
        ok = sel & kv.validity
        off = jnp.where(
            kv.validity, jnp.clip(v64, bases[i], his[i]) - bases[i] + 1, 0
        )
        idx = idx + off * strides[i]
        any_l.append(jnp.any(ok))
        mn_l.append(jnp.min(jnp.where(ok, v64, jnp.int64(_GUARD_HI))))
        mx_l.append(jnp.max(jnp.where(ok, v64, jnp.int64(-_GUARD_HI))))
    idx = jnp.where(sel, jnp.clip(idx, 0, size - 1), size).astype(jnp.int32)
    ev = Evaluator(T.Schema())  # casts only (mirrors _keys_and_inputs)
    planes: list[tuple] = []
    for spec in aggs:
        func = spec[0]
        if func == "count_star":
            planes.append(())
            continue
        cv = outs[spec[1]]
        if func == "count":
            planes.append((sel & cv.validity,))
            continue
        if func in ("sum", "avg"):
            _, _, sum_dt, kind = spec
            cvv = ev._cast(cv, sum_dt)
            ok = sel & cvv.validity
            if kind == "f":
                vm = jnp.where(ok, cvv.values.astype(jnp.float64), 0.0)
            else:
                vm = jnp.where(ok, cvv.values.astype(jnp.int64), jnp.int64(0))
            planes.append((vm, ok))
        else:  # min / max
            _, _, acc_name = spec
            accdt = np.dtype(acc_name)
            ok = sel & cv.validity
            ident = S._max_identity(accdt) if func == "min" else S._min_identity(accdt)
            vm = jnp.where(ok, cv.values, ident).astype(accdt)
            planes.append((vm, ok))
    guards = (jnp.stack(any_l), jnp.stack(mn_l), jnp.stack(mx_l))
    return sel, values, validity, (idx, guards, tuple(planes))


@_partial(obs.jit, static_argnames=("steps", "emit", "probe"))
def _stage_program_probe(dev, lut, lut_base, bwords, n_live, table,
                         pack_args, exists_lut, bvals, bmasks, *,
                         steps: tuple, emit: str, probe: tuple):
    """Stage program variant for segments feeding a hash-join probe: in the
    SAME compiled program as the filter/project work, run the probe
    prologue — key evaluation, canonical-word packing, the unique/existence
    hash-map lookup and (per ``take``) the build-row gather or the
    predicted compact-take — mirroring ``exec/joins/driver.py``'s eager
    chain (``_pack_probe_jit`` -> ``_unique_probe_jit`` ->
    ``_gather_build_jit`` / ``_unique_compact_take_pred_jit``) bit-for-bit.

    Build-side state (``lut``/``bwords``/``n_live``/the ``table`` of
    ``PreparedBuild.probe_table``/pack ranges/build columns) arrives as
    DEVICE ARGUMENTS published at runtime by the join exec (ProbePrepLink),
    so a fresh build — even a different one — reuses the compiled program;
    ``probe`` is the static half: (key_exprs, key_schema, key_kinds,
    use_lut, cmp_width, probe_outer, bcap, packed, pcol_ids, take) with
    cmp_width the width of the table's first array, the key list's or the
    hash map's (0: the build carries none) and take one of ("probe",) |
    ("gather",) | ("compact", out_cap) | ("exists",)."""
    from auron_tpu.columnar.batch import compaction_index
    from auron_tpu.exec.joins import core as jcore

    sel, values, validity, _ = _trace_steps(dev, steps)
    (key_exprs, key_schema, kinds, use_lut, cmp_width, probe_outer, bcap,
     packed, pcol_ids, take) = probe
    with jax.named_scope("auron.probe.pack"):
        b = Batch(key_schema, DeviceBatch(sel, values, validity),
                  (None,) * len(key_schema.fields))
        ev = Evaluator(key_schema, partition_id=0, row_offset=0, resources={})
        memo: dict = {}
        kcvs = [ev._eval(e, b, memo) for e in key_exprs]
        if packed:
            # multi-key packing with the build's ranges (driver:
            # _pack_probe_jit then a single synthetic INT64 key column)
            w0, v0 = jcore._canon_words(kcvs)
            mins, maxs, shifts = pack_args
            pw, pv = jcore._pack_probe_words_jit(tuple(w0), v0, mins, maxs,
                                                 shifts)
            probe_words = [jnp.where(pv, pw, jnp.uint64(0))]
            pvalid = pv
        else:
            probe_words, pvalid = jcore._canon_words_traced(
                tuple(cv.values for cv in kcvs),
                tuple(cv.validity for cv in kcvs), kinds,
            )
        ok_base = sel & (pvalid if pvalid is not None else jnp.ones_like(sel))
    if take[0] == "exists":
        # duplicate-tolerant existence LUT (driver: _probe_exists_jit)
        with jax.named_scope("auron.probe.lookup"):
            size = exists_lut.shape[0]
            eidx = probe_words[0].view(jnp.int64) - lut_base
            in_range = (eidx >= 0) & (eidx < size)
            hit = exists_lut[jnp.clip(eidx, 0, size - 1).astype(jnp.int32)]
            out = (ok_base & in_range & hit,)
        if emit == "cols":
            return sel, values, validity, out
        return sel, out
    with jax.named_scope("auron.probe.lookup"):
        bi, ok = jcore._probe_unique_ops(
            probe_words, ok_base, lut if use_lut else None, lut_base,
            list(bwords), n_live, bcap, table if cmp_width else None,
        )
        sel_out = sel if probe_outer else (sel & ok)
        live = jnp.sum(sel_out.astype(jnp.int32))
    if take[0] == "probe":
        out = (bi, ok, sel_out, live)
    elif take[0] == "gather":
        with jax.named_scope("auron.probe.gather"):
            bv = tuple(v[bi] for v in bvals)
            bm = tuple(m[bi] & ok for m in bmasks)
        out = (bi, ok, sel_out, live, bv, bm)
    else:  # ("compact", out_cap) — the predicted sync-free take
        out_cap = take[1]
        with jax.named_scope("auron.probe.compact"):
            idx, new_sel = compaction_index(sel_out, out_cap)
            c_pvals = tuple(values[c][idx] for c in pcol_ids)
            c_pmasks = tuple(validity[c][idx] & new_sel for c in pcol_ids)
            c_bi = bi[idx]
            c_ok = ok[idx] & new_sel
        with jax.named_scope("auron.probe.gather"):
            out_bvals = tuple(v[c_bi] for v in bvals)
            out_bmasks = tuple(m[c_bi] & c_ok for m in bmasks)
        out = (bi, ok, sel_out, live,
               (c_pvals, c_pmasks, out_bvals, out_bmasks, new_sel))
    if emit == "cols":
        return sel, values, validity, out
    return sel, out


@_partial(obs.jit, static_argnames=("steps", "emit", "shuffle"))
def _stage_program_shuffle(dev, rr_start, *, steps: tuple, emit: str,
                           shuffle: tuple):
    """Stage program variant for segments feeding a shuffle writer: in the
    SAME compiled program as the filter/project work, compute the per-row
    partition ids (partitioning.partition_ids_traced — the eager policy
    minus the pallas fast path, bit-identical ids) and, on the device
    clustering substrate, the pid-clustered gather + per-partition counts
    (writer.cluster_rows — the one clustering policy the host fallback
    shares). ``shuffle`` is the static (spec, schema, n_out, mode) with
    mode "device" (clustered batch + counts ride the payload) or "host"
    (only the pids ride; the writer's numpy path clusters host-side)."""
    from auron_tpu.exec.shuffle.partitioning import partition_ids_traced
    from auron_tpu.exec.shuffle.writer import cluster_rows

    sel, values, validity, _ = _trace_steps(dev, steps)
    spec, schema, n_out, mode = shuffle
    with jax.named_scope("auron.shuffle.partition"):
        pids = partition_ids_traced(
            spec, schema, n_out, sel, values, validity, rr_start
        )
        if mode == "host":
            extra = (pids,)
        else:
            out_dev, counts = cluster_rows(
                DeviceBatch(sel, values, validity), pids, n_out
            )
            extra = (out_dev, counts)
    if emit == "cols":
        return sel, values, validity, extra
    return sel, extra


class ProbePrepLink:
    """Anchor hand-off from a hash-join exec to the fused stage feeding its
    probe side. The join publishes once its build is prepared (device
    arrays + host ints of the build layout, and the stream's
    CompactionBoundary where the join compacts); the stage then
    runs the probe prologue inside its program and attaches a
    ProbePrepPayload to each emitted batch. Same thread-model as
    DensePrepLink: stage and join share the task pump thread, the lock
    guards foreign observers only. The payload carries the BUILD IT WAS
    COMPUTED UNDER — the driver refuses a payload whose build is not the
    one it is probing (identity check), falling back to the eager
    prologue bit-identically."""

    def __init__(self):
        self._lock = threading.Lock()
        self._anchor: dict | None = None

    def publish(self, **anchor) -> None:
        with self._lock:
            self._anchor = anchor

    def clear(self) -> None:
        with self._lock:
            self._anchor = None

    def snapshot(self) -> dict | None:
        with self._lock:
            return self._anchor


class ProbePrepPayload:
    """One probe batch's stage-computed prologue results riding to the join
    driver (attached to the Batch as ``_probe_prep``). ``take`` names the
    eager twin the stage replaced: "probe" (lookup only — the driver
    finishes: a stream's seed, or a batch predicted too wide to compact,
    whose take waits for its own count), "gather" (build columns gathered
    at probe width: the non-compact emit), "compact" (the predicted
    compact-take, ``taken`` =
    _unique_compact_take_pred_jit's output tuple), "exists"
    (existence-LUT probe flags). ``plan`` is what the join's
    CompactionBoundary said of this batch at dispatch; the driver hands it
    back to the boundary, which therefore predicts once a batch."""

    __slots__ = ("build", "kind", "take", "plan", "bi", "ok", "sel_out",
                 "live", "bvals", "bmasks", "taken", "probe_matched")

    def __init__(self, build, kind, take, plan=None, bi=None, ok=None,
                 sel_out=None, live=None, bvals=None, bmasks=None,
                 taken=None, probe_matched=None):
        self.build = build
        self.kind = kind
        self.take = take
        self.plan = plan
        self.bi = bi
        self.ok = ok
        self.sel_out = sel_out
        self.live = live
        self.bvals = bvals
        self.bmasks = bmasks
        self.taken = taken
        self.probe_matched = probe_matched


class ShufflePrepPayload:
    """One batch's stage-computed repartition riding to the shuffle writer
    (attached as ``_shuffle_prep``): mode "device" carries the
    pid-clustered DeviceBatch + per-partition counts, mode "host" carries
    the partition ids (the writer's numpy path clusters host-side). The
    writer validates n_out and the substrate policy before consuming —
    a mismatch falls back to the eager repartition bit-identically."""

    __slots__ = ("n_out", "mode", "pids", "clustered_dev", "counts")

    def __init__(self, n_out, mode, pids=None, clustered_dev=None, counts=None):
        self.n_out = n_out
        self.mode = mode
        self.pids = pids
        self.clustered_dev = clustered_dev
        self.counts = counts


class DensePrepLink:
    """Anchor hand-off from a dense partial aggregate to the fused stage
    feeding it. Stage and aggregate run on the SAME task pump thread (the
    stage generator resumes inside the aggregate's pull), so publish /
    snapshot / clear never race; the lock is defense against foreign
    observers (memory-manager polls) only. ``epoch`` increments on every
    re-anchor — a payload prepped under a stale anchor is refused by the
    aggregate at submission and its batch folds through the raw path."""

    def __init__(self):
        self._lock = threading.Lock()
        self._anchor: dict | None = None

    def publish(self, **anchor) -> None:
        with self._lock:
            self._anchor = anchor

    def clear(self) -> None:
        with self._lock:
            self._anchor = None

    def snapshot(self) -> dict | None:
        with self._lock:
            return self._anchor


class DensePrepPayload:
    """One batch's device-resident prep planes riding from the fused stage
    to the dense aggregate (attached to the Batch as ``_dense_prep``).
    Guard comparisons use the ANCHOR THE PLANES WERE COMPUTED UNDER
    (bases/his/dims captured here), never the aggregate's current one."""

    __slots__ = ("epoch", "bases", "his", "dims", "size", "sel", "idx",
                 "guards", "planes")

    def __init__(self, epoch, bases, his, dims, size, sel, idx, guards, planes):
        self.epoch = epoch
        self.bases = bases
        self.his = his
        self.dims = dims
        self.size = size
        self.sel = sel
        self.idx = idx
        self.guards = guards
        self.planes = planes

    def tree(self):
        return (self.sel, self.idx, self.guards, self.planes)


# -- compile accounting: the retrace guard's evidence (tools/perfcheck.py) --

_FUSE_LOCK = threading.Lock()
_SEEN_PROGRAMS: set = set()  # segment signatures
_SEEN_TRACES: set = set()  # (segment signature, capacity bucket)
_SEEN_BUCKETS: set = set()  # capacity buckets observed (any segment)
_STATS = {"segments": 0, "programs": 0, "compiles": 0, "buckets": 0,
          "probe_segments": 0, "writer_segments": 0}


def fusion_stats() -> dict:
    """Snapshot of fused-segment accounting: ``segments`` = FusedStageExec
    instances built (``probe_segments`` / ``writer_segments`` = the subset
    carrying a join-probe / shuffle-repartition extension), ``programs`` =
    distinct segment signatures dispatched, ``buckets`` = distinct
    capacity buckets observed, ``compiles`` = distinct (signature,
    capacity-bucket) traces — the number perfcheck's retrace guard bounds
    by programs x buckets and requires FLAT across a replay."""
    with _FUSE_LOCK:
        return dict(_STATS)


def reset_fusion_stats() -> None:
    with _FUSE_LOCK:
        _SEEN_PROGRAMS.clear()
        _SEEN_TRACES.clear()
        _SEEN_BUCKETS.clear()
        for k in _STATS:
            _STATS[k] = 0


def _note_dispatch(sig, capacity: int) -> bool:
    """Record one program dispatch; True when it is a NEW (signature,
    bucket) trace — i.e. a compile, not a cache hit."""
    with _FUSE_LOCK:
        if sig not in _SEEN_PROGRAMS:
            _SEEN_PROGRAMS.add(sig)
            _STATS["programs"] += 1
        if capacity not in _SEEN_BUCKETS:
            _SEEN_BUCKETS.add(capacity)
            _STATS["buckets"] = len(_SEEN_BUCKETS)
        key = (sig, capacity)
        if key in _SEEN_TRACES:
            return False
        _SEEN_TRACES.add(key)
        _STATS["compiles"] += 1
        return True


# ---------------------------------------------------------------------------
# the fused operator
# ---------------------------------------------------------------------------


# auronlint: thread-owned -- one fused operator per query/stream plan instance; its link/prep memo fields are touched only by the single thread driving that plan's batch stream (task pump, serving handler, or stream pump — never two at once)
class FusedStageExec(ExecOperator):
    """One pipeline segment compiled as a single per-batch XLA program.

    Built only by ``fuse_exec_tree`` — it carries the segment's static
    description precomputed by ``_plan_segment``:

    - ``steps``: the static half of the program cache key;
    - ``out_stamp``: schema to stamp on emitted batches (None = the input
      batch's schema rides through, exactly like FilterExec);
    - ``dict_src``: per-output-column input index for dictionary
      passthrough (None = identity — all input dictionaries ride through);
    - ``op_shares``: (operator name, cost weight) per constituent operator,
      the proportional split of fused-program wall time back into
      per-operator metric/span accounting.
    """

    def __init__(self, child: ExecOperator, steps: tuple, out_stamp,
                 dict_src, op_shares: tuple, schema: T.Schema):
        super().__init__([child], schema)
        self.steps = steps
        self.out_stamp = out_stamp
        self.dict_src = dict_src
        self.op_shares = op_shares
        self.has_project = any(s[0] == "project" for s in steps)
        #: set by _try_prefuse_agg when the consumer is a dense-eligible
        #: partial aggregate: once the aggregate anchors its table, the
        #: stage compiles the dense fold's guard/index/mask prep into the
        #: same program (_stage_program_prep)
        self.dense_link: DensePrepLink | None = None
        self._prep_nkeys = 0
        self._prep_aggs: tuple = ()
        #: set by the probe-side rewrite when the consumer is a hash join:
        #: once the join publishes its prepared build, the stage compiles
        #: the probe prologue into the same program (_stage_program_probe)
        self.probe_link: ProbePrepLink | None = None
        self._probe_keys: tuple = ()
        self._probe_kinds: tuple = ()
        self._probe_outer = False
        self._probe_pcols: tuple = ()
        #: set by the writer-side rewrite: (spec, schema, n_out) — the
        #: repartition rides the stage program (_stage_program_shuffle)
        self.shuffle: tuple | None = None
        with _FUSE_LOCK:
            _STATS["segments"] += 1

    def attach_dense_link(self, link: DensePrepLink, n_keys: int,
                          aggs_spec: tuple) -> None:
        self.dense_link = link
        self._prep_nkeys = n_keys
        self._prep_aggs = aggs_spec
        # the prep arithmetic is per-batch aggregate work: charge its cost
        # share to the aggregate's name in the proportional split
        extra = n_keys * 4 + len(aggs_spec) * 2
        self.op_shares = tuple(
            (nm, w + extra if nm == "HashAggExec" else w)
            for nm, w in self.op_shares
        )

    def attach_probe_link(self, link: ProbePrepLink, key_exprs: tuple,
                          key_kinds: tuple, probe_outer: bool,
                          pcol_ids: tuple, op_name: str, cost: int) -> None:
        """Arm the stage as a join-probe prologue carrier. The probe work's
        cost share is charged to the JOIN's operator name — fused-program
        wall nanos spent on the lookup/gather surface under the join in
        top_ops, exactly where the eager prologue books them."""
        self.probe_link = link
        self._probe_keys = key_exprs
        self._probe_kinds = key_kinds
        self._probe_outer = probe_outer
        self._probe_pcols = pcol_ids
        self.op_shares = tuple(self.op_shares) + ((op_name, cost),)
        with _FUSE_LOCK:
            _STATS["probe_segments"] += 1

    def attach_shuffle(self, spec: tuple, schema, n_out: int,
                       cost: int) -> None:
        """Arm the stage as a shuffle-repartition carrier; the repartition
        cost share is charged to ShuffleWriterExec's name (the eager twin
        books it under the writer's repart_time)."""
        self.shuffle = (spec, schema, n_out)
        self.op_shares = tuple(self.op_shares) + (("ShuffleWriterExec", cost),)
        with _FUSE_LOCK:
            _STATS["writer_segments"] += 1

    def fused_op_names(self) -> list[str]:
        return [nm for nm, _ in self.op_shares]

    def _dispatch_probe(self, b: Batch, anchor: dict, node):
        """One probe-extended program dispatch: ask the join's
        CompactionBoundary what this batch takes (the program is traced
        per take), run _stage_program_probe, and wrap the results as a
        ProbePrepPayload for the join driver."""
        kind = anchor["kind"]
        plan = None
        if kind == "exists":
            take_prog = ("exists",)
        elif anchor["boundary"] is None:
            take_prog = ("gather",)
        else:
            plan = anchor["boundary"].plan_take(b.capacity)
            # no bucket: lookup only. The driver takes once the batch's
            # count is read (a seed) or has landed (too wide to pay)
            take_prog = (
                ("probe",) if plan.cap is None else ("compact", plan.cap)
            )
        key_schema = self.out_stamp or self.children[0].schema
        table = anchor["table"]
        cmp_width = table[0].shape[0] if table is not None else 0
        cfg = (self._probe_keys, key_schema, self._probe_kinds,
               anchor["use_lut"], cmp_width, self._probe_outer,
               anchor["bcap"], anchor["packed"], self._probe_pcols, take_prog)
        emit = "cols" if self.has_project else "sel"
        if _note_dispatch((self.steps, "probe", cfg), b.capacity):
            node.add("stage_compiles", 1)
        if take_prog[0] == "compact":
            note_index(b.capacity, plan.cap)
        res = _stage_program_probe(
            b.device, anchor["lut"], anchor["lut_base"], anchor["words"],
            anchor["n_live"], table, anchor["pack_args"],
            anchor["exists_lut"],
            anchor["bvals"], anchor["bmasks"],
            steps=self.steps, emit=emit, probe=cfg,
        )
        if emit == "cols":
            sel, values, validity, extra = res
            out = (sel, values, validity)
        else:
            sel, extra = res
            out = sel
        build = anchor["build"]
        if kind == "exists":
            payload = ProbePrepPayload(
                build, kind, "exists", probe_matched=extra[0]
            )
        elif take_prog[0] == "probe":
            bi, ok, sel_out, live = extra
            payload = ProbePrepPayload(
                build, kind, "probe", plan=plan,
                bi=bi, ok=ok, sel_out=sel_out, live=live,
            )
        elif take_prog[0] == "gather":
            bi, ok, sel_out, live, bv, bm = extra
            payload = ProbePrepPayload(
                build, kind, "gather",
                bi=bi, ok=ok, sel_out=sel_out, live=live, bvals=bv, bmasks=bm,
            )
        else:
            # taken mirrors _unique_compact_take_pred_jit's output layout:
            # (c_pvals, c_pmasks, bvals, bmasks, new_sel)
            bi, ok, sel_out, live, taken = extra
            payload = ProbePrepPayload(
                build, kind, "compact", plan=plan,
                bi=bi, ok=ok, sel_out=sel_out, live=live, taken=taken,
            )
        return out, payload

    def _dispatch_shuffle(self, b: Batch, mode: str, rr_start, node):
        spec, schema, n_out = self.shuffle
        cfg = (spec, schema, n_out, mode)
        emit = "cols" if self.has_project else "sel"
        if _note_dispatch((self.steps, "shuffle", cfg), b.capacity):
            node.add("stage_compiles", 1)
        res = _stage_program_shuffle(
            b.device, rr_start, steps=self.steps, emit=emit, shuffle=cfg
        )
        if emit == "cols":
            sel, values, validity, extra = res
            out = (sel, values, validity)
        else:
            sel, extra = res
            out = sel
        if mode == "host":
            payload = ShufflePrepPayload(n_out, mode, pids=extra[0])
        else:
            payload = ShufflePrepPayload(
                n_out, mode, clustered_dev=extra[0], counts=extra[1]
            )
        return out, payload

    def _execute(self, partition: int, ctx: ExecutionContext):
        node = ctx.metrics
        emit = "cols" if self.has_project else "sel"
        sig = (self.steps, emit)
        shares = [(nm, w) for nm, w in self.op_shares if w > 0]
        total_w = sum(w for _, w in shares) or 1
        # per-constituent-operator metric nodes (index 0 is the child
        # operator's node, claimed by child_stream)
        attr = []
        for k, (nm, _) in enumerate(shares):
            c = node.child(1 + k)
            c.name = nm
            attr.append(c)
        rr_start = None
        shuffle_mode = None
        if self.shuffle is not None:
            from auron_tpu.exec.shuffle.writer import repartition_substrate

            rr_start = jnp.int32(ctx.partition_id % self.shuffle[2])
        for b in self.child_stream(0, partition, ctx):
            if self.shuffle is not None:
                # the SAME policy the eager writer resolves, by the task's
                # conf and the batch's capacity, so fused and fallback
                # repartition cannot diverge
                shuffle_mode = repartition_substrate(ctx.conf, b.capacity)
            t_all = time.perf_counter_ns()
            anchor = self.dense_link.snapshot() if self.dense_link else None
            probe_anchor = (
                self.probe_link.snapshot() if self.probe_link else None
            )
            payload = None
            probe_payload = None
            shuffle_payload = None
            t0 = time.perf_counter_ns()
            if anchor is not None:
                prep_cfg = (self._prep_nkeys, self._prep_aggs)
                if _note_dispatch((self.steps, "prep", prep_cfg), b.capacity):
                    node.add("stage_compiles", 1)
                sel, values, validity, (idx, guards, planes) = _stage_program_prep(
                    b.device, anchor["bases_dev"], anchor["his_dev"],
                    anchor["strides_dev"], anchor["size_dev"],
                    steps=self.steps, prep=prep_cfg,
                )
                out = (sel, values, validity)
                payload = DensePrepPayload(
                    anchor["epoch"], anchor["bases"], anchor["his"],
                    anchor["dims"], anchor["size"], sel, idx, guards, planes,
                )
            elif probe_anchor is not None:
                out, probe_payload = self._dispatch_probe(b, probe_anchor, node)
            elif self.shuffle is not None:
                out, shuffle_payload = self._dispatch_shuffle(
                    b, shuffle_mode, rr_start, node
                )
            elif not self.steps:
                # bare prologue carrier with nothing published (e.g. the
                # join fell back to a build shape the stage can't serve):
                # pure passthrough, no program dispatch
                yield b
                continue
            else:
                if _note_dispatch(sig, b.capacity):
                    node.add("stage_compiles", 1)
                out = _stage_program(b.device, steps=self.steps, emit=emit)
            dt = time.perf_counter_ns() - t0
            node.add("fused_batches", 1)
            # split the stage's wall nanos back into per-operator timers,
            # handing the SAME split to the flight recorder (obs.note_op)
            spent = 0
            for i, ((nm, w), cnode) in enumerate(zip(shares, attr)):
                dt_i = dt - spent if i == len(shares) - 1 else dt * w // total_w
                spent += dt_i
                cnode.add("elapsed_compute", dt_i)
                obs.note_op(nm, "elapsed_compute", dt_i)
            if self.has_project:
                sel, values, validity = out
                dicts = tuple(
                    b.dicts[s] if s is not None else None for s in self.dict_src
                )
                nb = Batch(self.out_stamp, DeviceBatch(sel, values, validity), dicts)
            else:
                dev = DeviceBatch(out, b.device.values, b.device.validity)
                nb = Batch(self.out_stamp or b.schema, dev, b.dicts)
            if payload is not None:
                nb._dense_prep = payload
            if probe_payload is not None:
                nb._probe_prep = probe_payload
            if shuffle_payload is not None:
                nb._shuffle_prep = shuffle_payload
            # residual stage overhead (batch re-wrap, anchor snapshot,
            # payload assembly) not covered by the per-constituent split is
            # attributed to the STAGE node — top_ops must conserve nanos
            # (sum of splits + residual == stage wall; test_fusion pins it)
            total = time.perf_counter_ns() - t_all
            residual = max(total - dt, 0)
            node.add("stage_wall", total)
            node.add("elapsed_compute", residual)
            obs.note_op(node.name or "FusedStageExec", "elapsed_compute",
                        residual)
            yield nb


# ---------------------------------------------------------------------------
# segment planning
# ---------------------------------------------------------------------------

# import here (not at top) keeps plan/ free of a hard exec-module cycle
from auron_tpu.exec.basic import (  # noqa: E402
    FilterExec,
    ProjectExec,
    RenameColumnsExec,
)
from auron_tpu.exec.joins.core import key_kind as core_key_kind  # noqa: E402

_CHAIN_OPS = (FilterExec, ProjectExec, RenameColumnsExec)


def _op_safe(op: ExecOperator) -> bool:
    schema = op.children[0].schema
    if isinstance(op, FilterExec):
        return all(expr_trace_safe(p, schema) for p in op.predicates)
    if isinstance(op, ProjectExec):
        return all(
            expr_trace_safe(e, schema, allow_dict_out=True) for e in op.exprs
        )
    return isinstance(op, RenameColumnsExec)


def _collect_chain(op: ExecOperator):
    """Maximal stateless pipeline chain from ``op`` downward. Returns
    (ops top-down, source below the chain). Everything that is not a
    filter/project/rename is a blocking boundary: sorts, aggregations,
    join builds, shuffle writers/readers, unions, limits, generators —
    segments NEVER cross them."""
    ops = []
    cur = op
    while isinstance(cur, _CHAIN_OPS):
        ops.append(cur)
        cur = cur.children[0]
    return ops, cur


def _mirror_project_schema(exprs, names, schema: T.Schema) -> T.Schema:
    """The schema ProjectExec's batch_from_columns stamps on emitted
    batches (NULL-kind values surface as INT32 fields) — mirrored exactly
    so fused and eager streams are indistinguishable downstream."""
    fields = []
    for e, n in zip(exprs, names):
        dt = e.dtype_of(schema)
        fields.append(T.Field(n, dt if dt.kind != T.TypeKind.NULL else T.INT32, True))
    return T.Schema(tuple(fields))


# auronlint: thread-owned -- segments are built and mutated only inside one fuse_exec_tree call on the thread lowering that plan
class _Segment:
    """Static description of one fusable run, built bottom-up."""

    def __init__(self):
        self.steps: list = []
        self.op_shares: list = []
        self.stamp: T.Schema | None = None
        self.src: list | None = None  # None = identity passthrough
        self.n_ops = 0

    def add_filter(self, schema: T.Schema, preds: tuple) -> None:
        self.steps.append(("filter", schema, preds))
        self.op_shares.append(("FilterExec", sum(_expr_nodes(p) for p in preds)))
        self.n_ops += 1

    def add_project(self, schema: T.Schema, exprs: tuple, names,
                    op_name: str = "ProjectExec") -> None:
        self.steps.append(("project", schema, exprs))
        self.op_shares.append((op_name, sum(_expr_nodes(e) for e in exprs)))
        self.stamp = _mirror_project_schema(exprs, names, schema)
        prev = self.src
        self.src = [
            (e.index if prev is None else prev[e.index])
            if isinstance(e, ir.Column) else None
            for e in exprs
        ]
        self.n_ops += 1

    def add_rename(self, schema: T.Schema) -> None:
        # renames are pure schema bookkeeping: no step, no device work
        self.stamp = schema
        self.n_ops += 1

    def cost(self) -> int:
        """Estimated eager per-batch dispatches the fused program replaces:
        one per expression DAG node plus one per constituent operator
        (batch re-wrap + dispatch overhead)."""
        return sum(w for _, w in self.op_shares) + self.n_ops

    def build(self, child: ExecOperator, schema: T.Schema) -> FusedStageExec:
        return FusedStageExec(
            child,
            tuple(self.steps),
            self.stamp,
            None if self.src is None else tuple(self.src),
            tuple(self.op_shares),
            schema,
        )


def _plan_segment(ops_top_down: list) -> _Segment:
    seg = _Segment()
    for o in reversed(ops_top_down):
        schema = o.children[0].schema
        if isinstance(o, FilterExec):
            seg.add_filter(schema, tuple(o.predicates))
        elif isinstance(o, ProjectExec):
            seg.add_project(schema, tuple(o.exprs), o.names)
        else:
            seg.add_rename(o.schema)
    return seg


# ---------------------------------------------------------------------------
# the pass
# ---------------------------------------------------------------------------


def _should_fuse(cost: int, conf: Configuration, knob=FUSE_ENABLE) -> bool:
    """The fuse-vs-materialize decision (docs/fusion.md): explicit on/off
    win; auto fuses on accelerators always (dispatch round-trips dominate)
    and on XLA:CPU only when the eager path's estimated dispatch count
    reaches exec.fuse.min.ops — the substrate-dependent selection PR 3
    measured for the operator-scope knobs. ``knob`` selects the tri-state
    governing a stage extension (exec.fuse.probe / exec.fuse.shuffle)."""
    accel = jax.default_backend() != "cpu"
    return resolve_tri(
        conf.get(knob), accel or cost >= conf.get(FUSE_MIN_OPS)
    )


def _safe_runs(ops: list) -> list:
    """Partition a chain (top-down) into maximal runs tagged fusable or
    not: a single host-evaluated expression splits the segment around it
    rather than killing the whole chain."""
    runs: list[tuple[bool, list]] = []
    for o in ops:
        ok = _op_safe(o)
        if runs and runs[-1][0] == ok:
            runs[-1][1].append(o)
        else:
            runs.append((ok, [o]))
    return runs


def _rebuild_chain(runs: list, bottom: ExecOperator, conf: Configuration) -> ExecOperator:
    """Reassemble a chain over ``bottom``, fusing each fusable run that
    passes the cost model and keeping the others' original operators."""
    cur = bottom
    for ok, run in reversed(runs):
        seg = _plan_segment(run) if ok else None
        if seg is not None and seg.steps and _should_fuse(seg.cost(), conf):
            cur = seg.build(cur, run[0].schema)
        else:
            for o in reversed(run):
                o.children[0] = cur
                cur = o
    return cur


def _try_prefuse_agg(agg, conf: Configuration):
    """Extend the segment THROUGH a partial-mode HashAggExec: compile the
    chain below it plus the agg's grouping/argument expressions into one
    stage program and rewrite the aggregate over bare column refs. Returns
    the rebuilt aggregate, or None when the shape doesn't qualify (the
    normal chain pass then runs below the untouched aggregate)."""
    from auron_tpu.exec.agg_exec import AggExpr, HashAggExec

    in_schema = agg.children[0].schema
    exprs = [g for g, _ in agg.groupings] + [
        a.expr for a, _ in agg.aggs if a.expr is not None
    ]
    if not exprs:
        return None
    if not all(expr_trace_safe(e, in_schema, allow_dict_out=True) for e in exprs):
        return None
    ops, source = _collect_chain(agg.children[0])
    runs = _safe_runs(ops)
    top_run = runs[0][1] if runs and runs[0][0] else []
    rest = runs[1:] if top_run else runs
    names = [n for _, n in agg.groupings] + [
        n for a, n in agg.aggs if a.expr is not None
    ]
    seg = _plan_segment(top_run)
    seg.add_project(in_schema, tuple(exprs), names, op_name="HashAggExec")
    if not _should_fuse(seg.cost(), conf):
        return None

    new_groupings = [
        (ir.Column(i, n), n) for i, (_, n) in enumerate(agg.groupings)
    ]
    k = len(agg.groupings)
    new_aggs = []
    for a, n in agg.aggs:
        if a.expr is None:
            new_aggs.append((AggExpr(a.func, None, udaf=a.udaf), n))
        else:
            new_aggs.append((AggExpr(a.func, ir.Column(k, n), udaf=a.udaf), n))
            k += 1
    # validate the rewrite BEFORE any side effects (segment accounting,
    # chain rewiring): probe the rebuilt aggregate's typing against a
    # schema-only carrier of the stage's emitted layout
    from auron_tpu.exec.basic import EmptyPartitionsExec

    probe = HashAggExec(
        EmptyPartitionsExec(seg.stamp, 1), new_groupings, new_aggs, agg.mode
    )
    if probe.schema != agg.schema or probe.inter_schema != agg.inter_schema:
        # typing drift (e.g. a NULL-kind grouping literal surfacing as
        # INT32 through the stage): materialize instead of fusing wrong
        return None
    below = _rebuild_chain(rest, _visit(source, conf), conf)
    fused = seg.build(below, seg.stamp)
    new_agg = HashAggExec(fused, new_groupings, new_aggs, agg.mode)
    spec = _dense_prep_spec(new_agg)
    if spec is not None:
        link = DensePrepLink()
        fused.attach_dense_link(link, new_agg.n_keys, spec)
        new_agg._dense_prep_link = link
    return new_agg


def _dense_prep_spec(agg) -> tuple | None:
    """Static per-agg plane spec for _stage_program_prep, or None when the
    aggregate can't run its dense fold off stage-prepped planes. Column
    indices address the stage's OUTPUT layout (keys first, then aggregate
    arguments in declaration order). Publication stays runtime-gated: the
    aggregate only publishes an anchor when its dense table is live AND
    the host-scatter substrate is chosen, so attaching a link to a plan
    that ends up on the device-scatter path costs nothing."""
    from auron_tpu.exec.agg_exec import is_wide_sum, sum_type

    if not agg._dense_eligible():
        return None
    spec = []
    col = agg.n_keys
    for (a, _), in_t in zip(agg.aggs, agg._agg_input_types):
        if a.func == "count_star":
            spec.append(("count_star",))
            continue
        if a.func == "count":
            spec.append(("count", col))
        elif a.func in ("sum", "avg"):
            if is_wide_sum(in_t):
                return None  # _dense_eligible already excludes; stay safe
            st = sum_type(in_t)
            kind = "f" if st.is_float else "i"
            spec.append((a.func, col, st, kind))
        elif a.func in ("min", "max"):
            spec.append((a.func, col, np.dtype(in_t.physical_dtype().name).name))
        else:
            return None
        col += 1
    return tuple(spec)


def _fallback_chain(child: ExecOperator, conf: Configuration) -> ExecOperator:
    """The ordinary chain-fusion pass over a prologue-stage candidate that
    didn't qualify — the SAME step `_visit` takes for a bare chain, kept
    in one place so the probe/writer fallbacks can't diverge from it."""
    if isinstance(child, _CHAIN_OPS):
        ops, source = _collect_chain(child)
        return _rebuild_chain(_safe_runs(ops), _visit(source, conf), conf)
    return _visit(child, conf)


def _chain_segment_below(child: ExecOperator, conf: Configuration):
    """Shared prologue-stage planning: split the chain under ``child`` into
    (segment for the TOP fusable run, remaining runs, source below) — the
    same top-run carve-out _try_prefuse_agg performs. The top segment may
    be EMPTY (child is not a chain op, or its top run is unsafe): the
    extension then rides a bare carrier stage with steps=()."""
    ops, source = _collect_chain(child)
    runs = _safe_runs(ops)
    top_run = runs[0][1] if runs and runs[0][0] else []
    rest = runs[1:] if top_run else runs
    seg = _plan_segment(top_run)
    out_schema = top_run[0].schema if top_run else child.schema
    return seg, rest, source, out_schema


def _probe_side_rewrite(join, child: ExecOperator,
                        conf: Configuration) -> ExecOperator:
    """Extend the fused stage feeding ``join``'s probe side through the
    probe prologue (docs/fusion.md): the stage carries a ProbePrepLink the
    join publishes its prepared build into at run time; until (or unless)
    a publishable build exists the stage is a plain segment (or a zero-
    cost passthrough). Falls back to the ordinary chain pass when the
    join's shape can't run off stage-prepped probes."""
    from auron_tpu.exec.joins.bhj import BroadcastHashJoinExec

    def fallback():
        return _fallback_chain(child, conf)

    d = join.driver
    # a probe child that is itself a BHJ is (potentially) a fused-chain
    # stack member (exec/joins/chain.py): never wedge a stage between
    # stacked joins — the chain's own fused probe already covers them
    if isinstance(child, BroadcastHashJoinExec):
        return fallback()
    if d.condition is not None:
        return fallback()  # residual conditions assemble pair batches
    probe_keys = d.left_keys if d.probe_is_left else d.right_keys
    seg, rest, source, out_schema = _chain_segment_below(child, conf)
    # keys must evaluate inside the program over the stage's emitted
    # layout: trace-safe, no dict-encoded or nested operands
    if not probe_keys or not all(
        expr_trace_safe(k, out_schema) for k in probe_keys
    ):
        return fallback()
    proj, pcol_ids, bcol_ids = d._unique_probe_cfg()
    probe_cost = (
        sum(_expr_nodes(k) for k in probe_keys) + 6 + len(bcol_ids)
    )
    if not _should_fuse(seg.cost() + probe_cost, conf, knob=FUSE_PROBE):
        return fallback()
    below = _rebuild_chain(rest, _visit(source, conf), conf)
    fused = seg.build(below, out_schema)
    link = ProbePrepLink()
    kinds = tuple(
        core_key_kind(k.dtype_of(out_schema)) for k in probe_keys
    )
    fused.attach_probe_link(
        link, tuple(probe_keys), kinds, d.probe_outer, tuple(pcol_ids),
        type(join).__name__, probe_cost,
    )
    join._probe_prep_link = link
    return fused


def _writer_side_rewrite(writer, child: ExecOperator,
                         conf: Configuration) -> ExecOperator:
    """Extend the fused stage feeding a shuffle writer through the
    repartition prologue: partition-id hashing (and device pid-clustering)
    ride the stage program; the writer consumes the ShufflePrepPayload
    instead of re-deriving both (docs/fusion.md)."""

    def fallback():
        return _fallback_chain(child, conf)

    spec = writer.partitioning.fuse_spec(child.schema)
    if spec is None:
        return fallback()
    seg, rest, source, out_schema = _chain_segment_below(child, conf)
    key_exprs = spec[1] if spec[0] == "hash" else ()
    if not all(expr_trace_safe(e, out_schema) for e in key_exprs):
        return fallback()
    n_out = writer.partitioning.num_partitions
    shuffle_cost = sum(_expr_nodes(e) for e in key_exprs) + 4 + len(out_schema)
    if not _should_fuse(seg.cost() + shuffle_cost, conf, knob=FUSE_SHUFFLE):
        return fallback()
    below = _rebuild_chain(rest, _visit(source, conf), conf)
    fused = seg.build(below, out_schema)
    fused.attach_shuffle(spec, out_schema, n_out, shuffle_cost)
    return fused


def _visit(op: ExecOperator, conf: Configuration) -> ExecOperator:
    from auron_tpu.exec.agg_exec import HashAggExec
    from auron_tpu.exec.joins.bhj import BroadcastHashJoinExec
    from auron_tpu.exec.shuffle.writer import (
        RssShuffleWriterExec,
        ShuffleWriterExec,
    )

    if (
        isinstance(op, HashAggExec)
        and op.mode == "partial"
        and conf.get(FUSE_AGG_INPUTS)
    ):
        new = _try_prefuse_agg(op, conf)
        if new is not None:
            return new
    if isinstance(op, BroadcastHashJoinExec):
        pc = 1 if op.build_side == "left" else 0
        op.children[1 - pc] = _visit(op.children[1 - pc], conf)
        op.children[pc] = _probe_side_rewrite(op, op.children[pc], conf)
        return op
    if isinstance(op, (ShuffleWriterExec, RssShuffleWriterExec)):
        op.children[0] = _writer_side_rewrite(op, op.children[0], conf)
        return op
    if isinstance(op, _CHAIN_OPS):
        ops, source = _collect_chain(op)
        return _rebuild_chain(_safe_runs(ops), _visit(source, conf), conf)
    for i, c in enumerate(op.children):
        op.children[i] = _visit(c, conf)
    return op


def fuse_exec_tree(plan: ExecOperator, conf: Configuration) -> ExecOperator:
    """Apply whole-stage fusion to an instantiated exec tree. A no-op when
    ``exec.fuse.enable`` resolves off for every segment; bit-identical
    results either way (tests/test_fusion.py fuzzes the equivalence)."""
    if not resolve_tri(conf.get(FUSE_ENABLE), True):
        return plan
    return _visit(plan, conf)
