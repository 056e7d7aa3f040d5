"""Join-type driver shared by SortMergeJoinExec and the hash joins.

Runs one prepared build side against a stream of probe batches, emitting
pair chunks and the outer/semi/anti/existence completions. The build side
may be the plan's left or right child (PartitionMode BuildLeft/BuildRight,
auron.proto:457-461 analog); output columns are always (left ++ right).
"""

from __future__ import annotations

from functools import partial
from typing import Iterator

import jax.numpy as jnp

from auron_tpu import obs
from auron_tpu import types as T
from auron_tpu.columnar.batch import Batch, compaction_bucket, note_index
from auron_tpu.exec.basic import batch_from_columns
from auron_tpu.exec.selectivity import CompactionBoundary
from auron_tpu.exprs import Evaluator, ir
from auron_tpu.exprs.eval import ColumnVal
from auron_tpu.exec.joins import core
from auron_tpu.exec.joins.core import (
    EXISTENCE, FULL, INNER, LEFT, LEFT_ANTI, LEFT_SEMI, RIGHT,
    PreparedBuild, expand_pairs, gather_columns, null_columns, probe_ranges,
    unify_key_dicts, _canon_words, _key_columns,
)


# auronlint: thread-owned -- one driver per join operator instance; its memo fields are touched only by the thread driving that query's probe stream
class EquiJoinDriver:
    def __init__(
        self,
        left_schema: T.Schema,
        right_schema: T.Schema,
        left_keys: list[ir.Expr],
        right_keys: list[ir.Expr],
        join_type: str,
        build_side: str,  # "left" | "right"
        condition: ir.Expr | None = None,
        exists_col: str = "exists",
        projection: list[int] | None = None,
    ):
        assert join_type in core.JOIN_TYPES
        assert build_side in ("left", "right")
        self.left_schema = left_schema
        self.right_schema = right_schema
        self.left_keys = left_keys
        self.right_keys = right_keys
        self.join_type = join_type
        self.build_side = build_side
        self.condition = condition
        self._cond_reduced = None  # lazy (schema, expr, assemble) cache
        self._take_planes = None  # lazy (dense, taken) of take_bucket
        self.exists_col = exists_col
        full_schema = core.join_output_schema(
            left_schema, right_schema, join_type, exists_col
        )
        # column-pruning projection (indices into the full output schema):
        # pair gathers move only the projected columns — on TPU the join
        # cost is gather bytes, so this is the reference's column_pruning.rs
        # analog with a direct roofline payoff
        self.projection = list(projection) if projection is not None else None
        if self.projection is None:
            self.out_schema = full_schema
        else:
            self.out_schema = T.Schema(
                tuple(full_schema[i] for i in self.projection)
            )
        self.probe_is_left = build_side == "right"
        jt = join_type
        self.wants_pairs = jt in (INNER, LEFT, RIGHT, FULL)
        self.probe_outer = (
            jt == FULL
            or (jt == LEFT and self.probe_is_left)
            or (jt == RIGHT and not self.probe_is_left)
        )
        self.build_outer = (
            jt == FULL
            or (jt == LEFT and not self.probe_is_left)
            or (jt == RIGHT and self.probe_is_left)
        )
        # semi/anti/existence are defined on the LEFT input
        self.probe_mark = jt in (LEFT_SEMI, LEFT_ANTI, EXISTENCE) and self.probe_is_left
        self.build_mark = jt in (LEFT_SEMI, LEFT_ANTI, EXISTENCE) and not self.probe_is_left

    # ------------------------------------------------------------------

    def _unique_probe_cfg(self) -> tuple[list[int], list[int], list[int]]:
        """(proj, pcol_ids, bcol_ids) of the unique-build probe — THE one
        definition shared by _probe_batch_unique, _emit_unique_compacted
        AND the fused probe stage's plan-time config (plan/fusion.py), so
        the stage-gathered columns can never diverge from the eager
        twin's."""
        nl = len(self.left_schema)
        full_n = nl + len(self.right_schema)
        needs_all_pairs = self.condition is not None
        proj = (
            list(range(full_n))
            if (self.projection is None or not self.wants_pairs or needs_all_pairs)
            else self.projection
        )
        if self.wants_pairs or needs_all_pairs:
            bcol_ids = [
                (oi if oi < nl else oi - nl)
                for oi in proj
                if (oi < nl) != self.probe_is_left
            ]
        else:
            bcol_ids = []
        pcol_ids = [
            (oi if oi < nl else oi - nl)
            for oi in proj
            if (oi < nl) == self.probe_is_left
        ]
        return proj, pcol_ids, bcol_ids

    @property
    def _compacts(self) -> bool:
        """Does the unique-build probe run the compaction boundary? Semi,
        anti and existence joins emit no pairs and a residual condition
        needs every pair: those stay on _unique_join_emit_jit."""
        return self.wants_pairs and self.condition is None

    def compaction_boundary(self, ctx) -> CompactionBoundary:
        """The boundary of ONE probe stream (the driver itself is shared
        across concurrently running partitions): the exec makes it, passes
        it into ``probe_batch`` and drains it through ``finish_probe``."""
        return CompactionBoundary(ctx.conf, self.take_bucket, ctx.metrics)

    def publish_probe_prep(
        self, link, build: PreparedBuild, boundary: CompactionBoundary,
    ) -> bool:
        """Publish the runtime probe anchor into a fused stage's
        ProbePrepLink (plan/fusion.py). Returns False — with the link
        cleared — when this build's shape can't run off stage-prepped
        probes (dict keys, duplicate build without an existence LUT): the
        stage then passes batches through and the eager prologue runs."""
        import jax.numpy as _jnp

        probe_keys = self.left_keys if self.probe_is_left else self.right_keys
        key_schema = (
            self.left_schema if self.probe_is_left else self.right_schema
        )
        if any(
            k.dtype_of(key_schema).is_dict_encoded for k in probe_keys
        ):
            link.clear()  # per-batch vocabulary unification: eager only
            return False
        need_pairs = self.wants_pairs or self.condition is not None
        if build.unique:
            kind = "unique"
        elif build.exists_lut is not None and not need_pairs:
            kind = "exists"
        else:
            link.clear()  # general ragged probe: eager only
            return False
        _, _, bcol_ids = self._unique_probe_cfg()
        bb = build.batch
        if build.pack is not None:
            spec = build.pack
            pack_args = (
                _jnp.asarray(spec.mins, _jnp.int64),
                _jnp.asarray(spec.maxs, _jnp.int64),
                _jnp.asarray(spec.shifts, _jnp.uint64),
            )
        else:
            pack_args = None
        link.publish(
            build=build,
            kind=kind,
            # the stage asks it for each batch's plan; None: never compacts
            boundary=(
                boundary if kind == "unique" and self._compacts else None
            ),
            bcap=bb.capacity,
            use_lut=build.lut is not None,
            lut=build.lut,
            lut_base=_jnp.int64(build.lut_base),
            words=tuple(build.words),
            n_live=_jnp.int32(build.n_live),
            table=build.probe_table,
            packed=build.pack is not None,
            pack_args=pack_args,
            exists_lut=build.exists_lut,
            bvals=tuple(bb.col_values(c) for c in bcol_ids),
            bmasks=tuple(bb.col_validity(c) for c in bcol_ids),
        )
        return True

    def prepare(self, build_batches: list[Batch], conf=None) -> PreparedBuild:
        schema = self.left_schema if self.build_side == "left" else self.right_schema
        keys = self.left_keys if self.build_side == "left" else self.right_keys
        # existence-only probes (probe-side semi/anti with no residual
        # condition and no build-side marking) never enumerate pairs, so a
        # duplicate-keyed build may skip its sort behind an existence LUT
        need_pairs = (
            self.wants_pairs
            or self.condition is not None
            or self.build_mark
            or self.build_outer
        )
        return core.prepare_build(
            build_batches, keys, schema, need_pairs=need_pairs, conf=conf
        )

    def probe_batch(
        self, build: PreparedBuild, pb: Batch, boundary: CompactionBoundary,
    ) -> Iterator[Batch]:
        """Probe one batch; updates build.matched in place. On the
        unique-build fast path emissions lag dispatch by up to
        ``boundary``'s window depth, and the caller must drain via
        ``finish_probe``.

        A batch arriving from a fused probe stage carries a
        ``_probe_prep`` payload (plan/fusion.py): the prologue — key eval,
        packing, lookup, gather/compact-take — already ran inside the
        stage program under the build THIS driver published. A payload
        computed under any other build is refused (identity check) and
        the eager prologue runs instead, bit-identically."""
        prep = getattr(pb, "_probe_prep", None)
        if prep is not None and prep.build is not build:
            prep = None  # stale/foreign anchor: eager prologue
        if prep is not None and prep.kind == "unique" and build.unique:
            yield from self._probe_batch_unique(build, pb, None, boundary, prep)
            return
        if (
            prep is not None
            and prep.kind == "exists"
            and build.exists_lut is not None
            and not (self.wants_pairs or self.condition is not None)
        ):
            probe_matched = prep.probe_matched
            if self.probe_mark:
                if self.join_type == LEFT_SEMI:
                    yield self._emit_probe_only(pb, pb.device.sel & probe_matched)
                elif self.join_type == LEFT_ANTI:
                    yield self._emit_probe_only(pb, pb.device.sel & ~probe_matched)
                else:  # existence
                    yield self._emit_probe_exists(pb, probe_matched)
            return
        probe_keys = self.left_keys if self.probe_is_left else self.right_keys
        pvals = _key_columns(pb, probe_keys)
        if build.pack is not None:
            # the build packed its multi-integer keys into one word; pack
            # the probe keys with the SAME spec and substitute a single
            # synthetic int64 key column — every downstream path (unique
            # LUT, exists LUT, binary search) then runs single-word.
            # Bit-exact: canonical(int64 view of packed) == packed.
            w0, v0 = core._canon_words(pvals)
            packed, pvalid2 = core._pack_probe_jit(tuple(w0), v0, build.pack)
            pvals = [ColumnVal(
                packed.view(jnp.int64),
                pvalid2 if pvalid2 is not None else jnp.ones(packed.shape, bool),
                T.INT64,
            )]
        has_dict_keys = any(v.dtype.is_dict_encoded for v in pvals)
        orig_build = build  # matched-flag updates must land on the caller's object
        if has_dict_keys:
            # only dict keys need the build side re-keyed (joint vocabulary);
            # for fixed-width keys build.words from prepare_build are final
            build_keys = self.left_keys if self.build_side == "left" else self.right_keys
            bvals = _key_columns(build.batch, build_keys)
            bvals, pvals = unify_key_dicts(bvals, pvals)
            bwords, _ = _canon_words(bvals)
            # re-keying preserves equality but the fast path also needs the
            # sorted order / LUT built from the ORIGINAL words, which only
            # survives when the build remap was the identity — conservatively
            # drop to the general path for dict keys
            build = PreparedBuild(build.batch, bwords, build.n_live, build.matched)
            # note: build rows are already clustered by their own codes; a
            # joint vocabulary preserves equality but NOT order, so remap
            # must keep the original sort order valid -> it does, because
            # unify_key_dicts maps build codes first (identity order).
        if build.unique:
            yield from self._probe_batch_unique(build, pb, pvals, boundary)
            if orig_build is not build:
                orig_build.matched = build.matched
            return

        pwords, pvalid = _canon_words(pvals)

        condition = None
        if self.condition is not None:
            if self._cond_reduced is None:
                # depends only on immutable driver state: compute once
                self._cond_reduced = self._reduced_condition()
            condition = self._cond_reduced

        need_pairs = self.wants_pairs or condition is not None
        if need_pairs:
            lo, counts = probe_ranges(build, pwords, pvalid, pb.device.sel)
            chunks, probe_matched, build_delta = expand_pairs(
                pb, build, lo, counts, condition, True
            )
            build.matched = build.matched | build_delta
        elif build.exists_lut is not None:
            chunks = []
            probe_matched = core._probe_exists_jit(
                build.exists_lut, jnp.int64(build.lut_base),
                pwords[0], pvalid, pb.device.sel,
            )
        else:
            chunks = []
            # one fused program: search + probe flags + build-mark fold
            probe_matched, build.matched = core.probe_mark(
                build, pwords, pvalid, pb.device.sel,
                need_build_delta=self.build_mark or self.build_outer,
            )
        if orig_build is not build:
            orig_build.matched = build.matched

        if self.wants_pairs:
            for li, ri, ok in chunks:
                yield self._emit_pairs(pb, build.batch, li, ri, ok)
            if self.probe_outer:
                unmatched = pb.device.sel & ~probe_matched
                yield self._emit_probe_extended(pb, unmatched)
        elif self.probe_mark:
            if self.join_type == LEFT_SEMI:
                yield self._emit_probe_only(pb, pb.device.sel & probe_matched)
            elif self.join_type == LEFT_ANTI:
                yield self._emit_probe_only(pb, pb.device.sel & ~probe_matched)
            else:  # existence
                yield self._emit_probe_exists(pb, probe_matched)

    def _probe_batch_unique(
        self, build: PreparedBuild, pb: Batch, pvals,
        boundary: CompactionBoundary, prep=None,
    ) -> Iterator[Batch]:
        """Unique-build probe: each probe row has <=1 match, so one batch at
        probe capacity covers every join type — probe columns stay as views
        (zero gather), only projected build columns are gathered at ``bi``.
        No ragged expansion and no host sync on the match count. ``prep``
        (a fused-stage ProbePrepPayload) supplies the lookup/gather results
        the stage program already computed — the per-op jits below are then
        skipped, everything else is identical."""
        bb = build.batch
        nl = len(self.left_schema)
        full_n = nl + len(self.right_schema)
        proj, _, bcol_ids = self._unique_probe_cfg()
        import jax.numpy as _jnp

        obs.note_join_lookup(core.lookup_kind(build), pb.capacity)
        # sparse-output compaction: densify BEFORE gathering build columns,
        # wherever take_bucket's rule says the gathers saved outweigh the
        # compaction
        if self._compacts:
            yield from self._emit_unique_compacted(
                build, pb, pvals, bcol_ids, proj, boundary, prep
            )
            return

        if bcol_ids:
            obs.note_join_take("dense", pb.capacity, pb.capacity)
        if prep is not None and prep.take == "gather":
            bi, ok, sel_out = prep.bi, prep.ok, prep.sel_out
            bvals, bmasks = prep.bvals, prep.bmasks
        else:
            bi, ok, bvals, bmasks, sel_out = core._unique_join_emit_jit(
                tuple(cv.values for cv in pvals),
                tuple(cv.validity for cv in pvals),
                pb.device.sel,
                build.lut,
                _jnp.int64(build.lut_base) if build.lut is not None else None,
                build.words,
                _jnp.int32(build.n_live),
                build.probe_table,
                tuple(bb.col_values(c) for c in bcol_ids),
                tuple(bb.col_validity(c) for c in bcol_ids),
                bcap=bb.capacity,
                use_lut=build.lut is not None,
                probe_outer=self.probe_outer,
                key_kinds=tuple(core.key_kind(cv.dtype) for cv in pvals),
            )
        b_at = {c: k for k, c in enumerate(bcol_ids)}

        def build_col(ci: int) -> ColumnVal:
            k = b_at[ci]
            return ColumnVal(bvals[k], bmasks[k], bb.schema[ci].dtype, bb.dicts[ci])

        def probe_col(ci: int) -> ColumnVal:
            return ColumnVal(
                pb.col_values(ci), pb.col_validity(ci),
                pb.schema[ci].dtype, pb.dicts[ci],
            )

        if self.condition is not None:
            pcols = [probe_col(i) for i in range(len(pb.schema))]
            bcols = [build_col(i) for i in range(len(bb.schema))]
            lcols, rcols = (pcols, bcols) if self.probe_is_left else (bcols, pcols)
            comb = core.join_output_schema(self.left_schema, self.right_schema, INNER)
            pair = batch_from_columns(lcols + rcols, comb.names, ok)
            cv = Evaluator(comb).evaluate(Batch(comb, pair.device, pair.dicts), [self.condition])[0]
            ok = ok & cv.validity & cv.values.astype(bool)
            # condition may veto matches: rebuild outputs that depend on ok
            bmasks = tuple(m & ok for m in bmasks)
            sel_out = pb.device.sel if self.probe_outer else (pb.device.sel & ok)

        if self.build_mark or self.build_outer:
            build.matched = build.matched.at[bi].max(ok, mode="drop")

        if self.wants_pairs:
            out_cols = []
            for oi in (self.projection if self.projection is not None else range(full_n)):
                on_left = oi < nl
                ci = oi if on_left else oi - nl
                out_cols.append(
                    probe_col(ci) if on_left == self.probe_is_left else build_col(ci)
                )
            out = batch_from_columns(out_cols, self.out_schema.names, sel_out)
            yield Batch(self.out_schema, out.device, out.dicts)
        elif self.probe_mark:
            if self.join_type == LEFT_SEMI:
                yield self._emit_probe_only(pb, pb.device.sel & ok)
            elif self.join_type == LEFT_ANTI:
                yield self._emit_probe_only(pb, pb.device.sel & ~ok)
            else:  # existence
                yield self._emit_probe_exists(pb, ok & pb.device.sel)

    def take_bucket(self, n_live: int, capacity: int) -> int | None:
        """``compaction_bucket`` at this join's output boundary: the bucket
        a probe batch of ``capacity`` rows with ``n_live`` survivors (or a
        predicted bucket of them) compacts into, or None where it stays
        dense. The ONE decision the eager driver, its mispredict repair
        and the fused probe stage (through the published anchor) share."""
        if self._take_planes is None:
            _, pcol_ids, bcol_ids = self._unique_probe_cfg()
            build_planes = 2 * len(bcol_ids)  # a column: values + validity
            # compacting also takes the probe columns, bi and ok
            self._take_planes = (
                build_planes, 2 * len(pcol_ids) + build_planes + 2
            )
        dense_planes, taken_planes = self._take_planes
        return compaction_bucket(
            n_live, capacity, dense_planes=dense_planes,
            taken_planes=taken_planes,
        )

    def _take_unique(self, build, pb, pcol_ids, bcol_ids, bi, ok, sel_out,
                     mode, out_cap):
        """One take of the boundary on the device, noted in the rings:
        the build columns gathered at the batch's capacity (``out_cap``
        None: probe columns stay views) or everything taken at the bucket
        ``out_cap``, its index computed in the same program. Returns
        _unique_compact_take_pred_jit's layout."""
        bb = build.batch
        bvals = tuple(bb.col_values(c) for c in bcol_ids)
        bmasks = tuple(bb.col_validity(c) for c in bcol_ids)
        obs.note_join_take(mode, out_cap or pb.capacity, pb.capacity)
        if out_cap is None:
            bv, bm = core._gather_build_jit(bvals, bmasks, bi, ok)
            return (None, None, bv, bm, sel_out)
        note_index(pb.capacity, out_cap)
        return core._unique_compact_take_pred_jit(
            tuple(pb.col_values(c) for c in pcol_ids),
            tuple(pb.col_validity(c) for c in pcol_ids),
            bi, ok, bvals, bmasks, sel_out, out_cap=out_cap,
        )

    def _emit_unique_compacted(
        self, build: PreparedBuild, pb: Batch, pvals, bcol_ids, proj,
        boundary: CompactionBoundary, prep=None,
    ) -> Iterator[Batch]:
        bb = build.batch
        nl = len(self.left_schema)
        if prep is not None:
            bi, ok, sel_out, n_live_dev = prep.bi, prep.ok, prep.sel_out, prep.live
        else:
            bi, ok, sel_out, n_live_dev = core._unique_probe_jit(
                tuple(cv.values for cv in pvals),
                tuple(cv.validity for cv in pvals),
                pb.device.sel,
                build.lut,
                jnp.int64(build.lut_base) if build.lut is not None else None,
                build.words, jnp.int32(build.n_live), build.probe_table,
                bcap=bb.capacity,
                use_lut=build.lut is not None,
                probe_outer=self.probe_outer,
                key_kinds=tuple(core.key_kind(cv.dtype) for cv in pvals),
            )
        if self.build_mark or self.build_outer:
            build.matched = build.matched.at[bi].max(ok, mode="drop")
        pcol_ids = [
            (oi if oi < nl else oi - nl)
            for oi in proj
            if (oi < nl) == self.probe_is_left
        ]
        # a fused-stage payload carries the plan the boundary made for
        # this batch at the stage's dispatch (predicting again would
        # double-count and could disagree) and, where that plan compacts,
        # what the stage program took at its bucket
        plan = prep.plan if prep is not None else None
        taken = None
        if prep is not None and prep.take == "compact":
            obs.note_join_take("compact", plan.cap, pb.capacity)
            taken = prep.taken
        take = partial(
            self._take_unique, build, pb, pcol_ids, bcol_ids, bi, ok, sel_out
        )
        state = (pb, bb, proj, pcol_ids, bcol_ids)
        for st, out in boundary.offer(
            n_live_dev, pb.capacity, take, state, plan, taken
        ):
            yield self._unique_out_batch(*st, *out)

    def finish_probe(self, boundary: CompactionBoundary) -> Iterator[Batch]:
        """Drain the boundary at end of the probe stream (emissions lag
        dispatch by the window depth)."""
        for st, out in boundary.drain():
            yield self._unique_out_batch(*st, *out)

    def _unique_out_batch(
        self, pb, bb, proj, pcol_ids, bcol_ids,
        c_pvals, c_pmasks, bvals, bmasks, new_sel,
    ) -> Batch:
        """Assemble the projected output batch; c_pvals None = dense output
        (probe columns stay zero-copy views at full width)."""
        nl = len(self.left_schema)
        p_at = (
            None if c_pvals is None else {c: k for k, c in enumerate(pcol_ids)}
        )
        b_at = {c: k for k, c in enumerate(bcol_ids)}
        out_cols = []
        for oi in proj:
            on_left = oi < nl
            ci = oi if on_left else oi - nl
            if on_left == self.probe_is_left:
                if p_at is None:
                    out_cols.append(
                        ColumnVal(pb.col_values(ci), pb.col_validity(ci),
                                  pb.schema[ci].dtype, pb.dicts[ci])
                    )
                else:
                    k = p_at[ci]
                    out_cols.append(
                        ColumnVal(c_pvals[k], c_pmasks[k],
                                  pb.schema[ci].dtype, pb.dicts[ci])
                    )
            else:
                k = b_at[ci]
                out_cols.append(
                    ColumnVal(bvals[k], bmasks[k],
                              bb.schema[ci].dtype, bb.dicts[ci])
                )
        out = batch_from_columns(out_cols, self.out_schema.names, new_sel)
        return Batch(self.out_schema, out.device, out.dicts)

    def finish(self, build: PreparedBuild) -> Iterator[Batch]:
        bb = build.batch
        if self.build_outer:
            unmatched = bb.device.sel & ~build.matched
            yield self._emit_build_extended(bb, unmatched)
        elif self.build_mark:
            if self.join_type == LEFT_SEMI:
                yield self._emit_build_only(bb, bb.device.sel & build.matched)
            elif self.join_type == LEFT_ANTI:
                yield self._emit_build_only(bb, bb.device.sel & ~build.matched)
            else:  # existence: all build rows + flag
                cols = [
                    ColumnVal(bb.col_values(i), bb.col_validity(i), f.dtype, bb.dicts[i])
                    for i, f in enumerate(bb.schema)
                ]
                cols.append(
                    ColumnVal(build.matched, jnp.ones_like(build.matched), T.BOOL)
                )
                yield self._finish_batch(cols, bb.device.sel)

    # ------------------------------------------------------------------

    def _reduced_condition(self):
        """(schema, expr, assemble) for residual-condition evaluation over
        ONLY the columns the condition references: expansion chunks used
        to assemble the FULL combined schema just to evaluate a 2-4 column
        predicate, gathering every pair column twice (once here, once at
        emit) — a measured q72-class sink."""
        comb = core.join_output_schema(self.left_schema, self.right_schema, INNER)
        refs = sorted({
            c.index for c in ir.walk(self.condition)
            if isinstance(c, ir.Column)
        })
        expr = ir.remap_columns(
            self.condition, {old: new for new, old in enumerate(refs)})
        sub_schema = T.Schema(tuple(comb.fields[r] for r in refs))
        nl = len(self.left_schema)
        side_col = [
            ((r < nl) == self.probe_is_left, r if r < nl else r - nl)
            for r in refs
        ]
        pcols = [c for onp, c in side_col if onp]
        bcols = [c for onp, c in side_col if not onp]

        def assemble(probe_b, build_b, li, ri, ok) -> Batch:
            pv, pm, bv, bm = core.gather_pair_arrays(
                tuple(probe_b.col_values(c) for c in pcols),
                tuple(probe_b.col_validity(c) for c in pcols),
                tuple(build_b.col_values(c) for c in bcols),
                tuple(build_b.col_validity(c) for c in bcols),
                li, ri, ok,
            )
            it_p, it_b = iter(zip(pv, pm)), iter(zip(bv, bm))
            colvals = []
            for (onp, c), r in zip(side_col, refs):
                if onp:
                    v, m = next(it_p)
                    d = probe_b.dicts[c]
                else:
                    v, m = next(it_b)
                    d = build_b.dicts[c]
                colvals.append(ColumnVal(v, m, comb.fields[r].dtype, d))
            out = batch_from_columns(colvals, [comb.names[r] for r in refs], ok)
            return Batch(sub_schema, out.device, out.dicts)

        return sub_schema, expr, assemble

    def _assemble_pairs_batch(self, probe_b, build_b, li, ri, ok) -> Batch:
        pv, pm, bv, bm = core.gather_pair_arrays(
            probe_b.device.values, probe_b.device.validity,
            build_b.device.values, build_b.device.validity, li, ri, ok,
        )
        pcols = [
            ColumnVal(v, m, f.dtype, probe_b.dicts[i])
            for i, (v, m, f) in enumerate(zip(pv, pm, probe_b.schema))
        ]
        bcols = [
            ColumnVal(v, m, f.dtype, build_b.dicts[i])
            for i, (v, m, f) in enumerate(zip(bv, bm, build_b.schema))
        ]
        lcols, rcols = (pcols, bcols) if self.probe_is_left else (bcols, pcols)
        comb = core.join_output_schema(self.left_schema, self.right_schema, INNER)
        out = batch_from_columns(lcols + rcols, comb.names, ok)
        return Batch(comb, out.device, out.dicts)

    def _emit_pairs(self, probe_b, build_b, li, ri, ok) -> Batch:
        if self.projection is None:
            b = self._assemble_pairs_batch(probe_b, build_b, li, ri, ok)
            return Batch(self.out_schema, b.device, b.dicts)
        # projected pair gather: move only the pruned column set
        nl = len(self.left_schema)
        lb, rb = (probe_b, build_b) if self.probe_is_left else (build_b, probe_b)
        lidx = li if self.probe_is_left else ri
        ridx = ri if self.probe_is_left else li
        lcols = [i for i in self.projection if i < nl]
        rcols = [i - nl for i in self.projection if i >= nl]
        lv, lm, rv, rm = core.gather_pair_arrays(
            tuple(lb.col_values(c) for c in lcols),
            tuple(lb.col_validity(c) for c in lcols),
            tuple(rb.col_values(c) for c in rcols),
            tuple(rb.col_validity(c) for c in rcols),
            lidx, ridx, ok,
        )
        l_at = {c: k for k, c in enumerate(lcols)}
        r_at = {c: k for k, c in enumerate(rcols)}
        out_cols = []
        for oi in self.projection:
            if oi < nl:
                k = l_at[oi]
                out_cols.append(
                    ColumnVal(lv[k], lm[k], lb.schema[oi].dtype, lb.dicts[oi])
                )
            else:
                c = oi - nl
                k = r_at[c]
                out_cols.append(
                    ColumnVal(rv[k], rm[k], rb.schema[c].dtype, rb.dicts[c])
                )
        out = batch_from_columns(out_cols, self.out_schema.names, ok)
        return Batch(self.out_schema, out.device, out.dicts)

    def _emit_probe_extended(self, pb: Batch, sel) -> Batch:
        probe_cols = [
            ColumnVal(pb.col_values(i), pb.col_validity(i) & sel, f.dtype, pb.dicts[i])
            for i, f in enumerate(pb.schema)
        ]
        other_schema = self.right_schema if self.probe_is_left else self.left_schema
        from auron_tpu.columnar.batch import _empty_dict

        other_dicts = tuple(
            (_empty_dict(f.dtype) if f.dtype.is_dict_encoded else None)
            for f in other_schema
        )
        nulls = null_columns(other_schema, pb.capacity, other_dicts)
        cols = probe_cols + nulls if self.probe_is_left else nulls + probe_cols
        return self._finish_batch(cols, sel)

    def _emit_build_extended(self, bb: Batch, sel) -> Batch:
        build_cols = [
            ColumnVal(bb.col_values(i), bb.col_validity(i) & sel, f.dtype, bb.dicts[i])
            for i, f in enumerate(bb.schema)
        ]
        other_schema = self.right_schema if self.build_side == "left" else self.left_schema
        from auron_tpu.columnar.batch import _empty_dict

        other_dicts = tuple(
            (_empty_dict(f.dtype) if f.dtype.is_dict_encoded else None)
            for f in other_schema
        )
        nulls = null_columns(other_schema, bb.capacity, other_dicts)
        cols = build_cols + nulls if self.build_side == "left" else nulls + build_cols
        return self._finish_batch(cols, sel)

    def _emit_probe_only(self, pb: Batch, sel) -> Batch:
        cols = [
            ColumnVal(pb.col_values(i), pb.col_validity(i), f.dtype, pb.dicts[i])
            for i, f in enumerate(pb.schema)
        ]
        return self._finish_batch(cols, sel)

    def _emit_build_only(self, bb: Batch, sel) -> Batch:
        cols = [
            ColumnVal(bb.col_values(i), bb.col_validity(i), f.dtype, bb.dicts[i])
            for i, f in enumerate(bb.schema)
        ]
        return self._finish_batch(cols, sel)

    def _emit_probe_exists(self, pb: Batch, matched) -> Batch:
        cols = [
            ColumnVal(pb.col_values(i), pb.col_validity(i), f.dtype, pb.dicts[i])
            for i, f in enumerate(pb.schema)
        ]
        cols.append(ColumnVal(matched, jnp.ones_like(matched), T.BOOL))
        return self._finish_batch(cols, pb.device.sel)

    def _finish_batch(self, cols: list[ColumnVal], sel) -> Batch:
        """cols arrive in full-output-schema order; projection subsets them
        (free — ColumnVals are views, the gather happened upstream)."""
        if self.projection is not None:
            cols = [cols[i] for i in self.projection]
        out = batch_from_columns(cols, self.out_schema.names, sel)
        return Batch(self.out_schema, out.device, out.dicts)
