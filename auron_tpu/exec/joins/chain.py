"""Fused star-schema join chains.

A stack of inner broadcast hash joins over unique (PK-like) build sides —
the classic fact-to-dimensions shape — is sel-refining at every level: each
probe row either survives with exactly one match per dimension or dies.
Executing the stack operator-at-a-time materializes an intermediate batch
per level; fused, the chain costs

    ONE probe program for every level (key canon + LUT/binsearch, the
    combined selection and its live count; no gathers)
    ONE take program: the compaction index of the bottom probe stream and
    every projected column gathered at the compacted width (probe columns
    at idx, each level's build columns at bi_level[idx]) — or, where
    ``compaction_bucket``'s rule says the bucket is too wide to pay, the
    build columns alone gathered at the batch's capacity

which is the minimum memory traffic for the whole subtree (the reference's
column-pruned multi-BHJ pipelines approximate this with its fused
row-stream; here it is one XLA program chain per batch).

Fusion requirements per link (checked at run time, falling back to the
plain per-operator path): inner join, no residual condition, unique build,
and the parent's probe keys resolving to pass-through probe columns of the
child join.
"""

from __future__ import annotations

from functools import partial
from typing import Iterator

import jax
import jax.numpy as jnp

from auron_tpu import obs
from auron_tpu.columnar.batch import Batch, compaction_bucket, compaction_index
from auron_tpu.exec.basic import batch_from_columns
from auron_tpu.exec.selectivity import CompactionBoundary
from auron_tpu.exprs import ir
from auron_tpu.exprs.eval import ColumnVal
from auron_tpu.exec.joins import core


def clear_chain_memos(top, partition: int, ctx) -> None:
    """Drop any fallback build memos this chain stashed but never consumed
    (an operator that raised before its _build ran leaves its entry behind).
    Called by the chain top's per-operator path on completion."""
    keys = ctx.resources.pop(
        ("fusion_build_memo_keys", id(top), partition), None
    )
    for k in keys or ():
        ctx.resources.pop(k, None)


def try_fused_chain(top, partition: int, ctx) -> Iterator[Batch] | None:
    """Attempt to run `top` (a BroadcastHashJoinExec) as a fused chain.

    Returns a batch iterator, or None when the shape doesn't qualify (the
    caller then runs the ordinary per-operator path)."""
    from auron_tpu.exec.joins.bhj import BroadcastHashJoinExec

    # collect the stack of fusable links, top-down
    links = []  # (exec, probe_child_index)
    node = top
    while isinstance(node, BroadcastHashJoinExec):
        d = node.driver
        if d.join_type != core.INNER or d.condition is not None:
            break
        probe_child = 1 if node.build_side == "left" else 0
        links.append((node, probe_child))
        node = node.children[probe_child]
    if len(links) < 2:
        return None  # single joins take the existing fast path
    links.reverse()  # bottom-up
    bottom = node  # the probe source operator

    # dict-encoded keys need per-batch vocabulary unification, which the
    # fused probe skips — the per-operator path handles them
    for ex, _ in links:
        d = ex.driver
        probe_schema = d.left_schema if d.probe_is_left else d.right_schema
        build_schema = d.right_schema if d.probe_is_left else d.left_schema
        keys = d.left_keys if d.probe_is_left else d.right_keys
        bkeys = d.right_keys if d.probe_is_left else d.left_keys
        for k, schema in [(x, probe_schema) for x in keys] + [
            (x, build_schema) for x in bkeys
        ]:
            if not isinstance(k, ir.Column):
                return None
            if schema[k.index].dtype.is_dict_encoded:
                return None

    # resolve each level's probe keys down to BOTTOM columns: keys must be
    # plain Column refs that pass through the lower links' probe side
    def passthrough(ex, oi: int) -> int | None:
        """Map an output column of link `ex` to its probe-side input column
        (None when the column comes from the build side)."""
        d = ex.driver
        nl = len(d.left_schema)
        proj = d.projection if d.projection is not None else list(
            range(nl + len(d.right_schema))
        )
        full_i = proj[oi]
        on_left = full_i < nl
        if on_left != d.probe_is_left:
            return None
        return full_i if on_left else full_i - nl

    def resolve_to_bottom(level: int, col_idx: int) -> int | None:
        """Map a probe-input column index at `level` to a bottom column."""
        i = col_idx
        for lv in range(level - 1, -1, -1):
            i = passthrough(links[lv][0], i)
            if i is None:
                return None
        return i

    key_cols_per_level: list[list[int]] = []
    for level, (ex, _) in enumerate(links):
        d = ex.driver
        keys = d.left_keys if d.probe_is_left else d.right_keys
        cols = []
        for k in keys:
            bc = resolve_to_bottom(level, k.index)
            if bc is None:
                return None
            cols.append(bc)
        key_cols_per_level.append(cols)

    # resolve the TOP output columns to (source, index): source -1 = bottom
    # probe column, source l>=0 = build column of level l
    top_ex = links[-1][0]
    d_top = top_ex.driver
    out_map: list[tuple[int, int]] = []

    def resolve_out(level: int, oi: int) -> tuple[int, int] | None:
        ex = links[level][0]
        d = ex.driver
        nl = len(d.left_schema)
        proj = d.projection if d.projection is not None else list(
            range(nl + len(d.right_schema))
        )
        full_i = proj[oi]
        on_left = full_i < nl
        if on_left == d.probe_is_left:
            ci = full_i if on_left else full_i - nl
            if level == 0:
                return (-1, ci)
            return resolve_out(level - 1, ci)
        ci = full_i if on_left else full_i - nl
        return (level, ci)

    for oi in range(len(d_top.out_schema)):
        r = resolve_out(len(links) - 1, oi)
        if r is None:
            return None
        out_map.append(r)

    # all structural checks passed — NOW prepare the builds (building
    # before the checks would re-run build child streams on fallback).
    # Uniqueness is only knowable after building; when a non-unique build
    # forces fallback, stash everything built so far in the task resource
    # map so the per-operator path (and inner sub-chain re-attempts) pop
    # the prepared maps instead of re-streaming build children.
    builds = []
    for ex, _ in links:
        b = ex._build(partition, ctx)
        builds.append(b)
        # packed builds carry a single synthetic word the fused probe's raw
        # per-column canonicalization knows nothing about — fall back to the
        # per-operator path, whose probe_batch packs with the build's spec
        if not b.unique or b.pack is not None:
            keys = []
            for (ex2, _), b2 in zip(links, builds):
                k = ("fusion_build_memo", id(ex2), partition)
                ctx.resources[k] = b2
                keys.append(k)
            # scope the memo to THIS fallback attempt: the chain top clears
            # leftovers when its per-operator execution ends, so an operator
            # never reached (e.g. an upstream raise) can't pin prepared
            # builds for the rest of the task's lifetime
            ctx.resources[("fusion_build_memo_keys", id(top), partition)] = keys
            return None

    return _run_chain(
        top_ex, bottom, links, builds, key_cols_per_level, out_map,
        partition, ctx,
    )


def _run_chain(
    top_ex, bottom, links, builds, key_cols_per_level, out_map, partition, ctx,
) -> Iterator[Batch]:
    d_top = top_ex.driver
    out_schema = d_top.out_schema
    probe_child_stream = bottom.execute(partition, ctx)

    # loop invariants (column maps, key kinds, build column tuples) — the
    # probe loop runs per batch and must not rebuild these
    bottom_schema = bottom.schema
    kinds_per_level = [
        tuple(core.key_kind(bottom_schema[c].dtype) for c in key_cols)
        for key_cols in key_cols_per_level
    ]
    probe_cols = sorted({c for s, c in out_map if s == -1})
    bcols_per_level = [
        sorted({c for s, c in out_map if s == lv}) for lv in range(len(links))
    ]
    p_at = {c: k for k, c in enumerate(probe_cols)}
    b_at = [{c: k for k, c in enumerate(cs)} for cs in bcols_per_level]
    bvals_all = tuple(
        tuple(b.batch.col_values(c) for c in cs)
        for b, cs in zip(builds, bcols_per_level)
    )
    bmasks_all = tuple(
        tuple(b.batch.col_validity(c) for c in cs)
        for b, cs in zip(builds, bcols_per_level)
    )

    level_cfgs = tuple(
        (b.batch.capacity, b.lut is not None, kinds)
        for b, kinds in zip(builds, kinds_per_level)
    )
    luts = tuple(b.lut for b in builds)
    lut_bases = tuple(
        jnp.int64(b.lut_base) if b.lut is not None else None for b in builds
    )
    bwords_all = tuple(b.words for b in builds)
    n_lives = tuple(jnp.int32(b.n_live) for b in builds)
    key_lists = tuple(b.key_list for b in builds)
    lookup_kinds = tuple(core.lookup_kind(b) for b in builds)

    n_levels = len(links)
    build_planes = 2 * sum(len(cs) for cs in bcols_per_level)
    # compacting also takes the probe columns and every level's bi
    taken_planes = 2 * len(probe_cols) + build_planes + n_levels

    def bucket_of(n_live: int, capacity: int) -> int | None:
        return compaction_bucket(
            n_live, capacity, dense_planes=build_planes,
            taken_planes=taken_planes,
        )

    # the chain's output is ONE compaction boundary (exec/selectivity.py):
    # it picks each batch's bucket ahead of time and carries the live
    # count host-ward while later batches compute (docs/pipeline.md)
    boundary = CompactionBoundary(ctx.conf, bucket_of, ctx.metrics)

    def assemble(pb, c_p, c_pm, c_b, c_bm, new_sel) -> Batch:
        """Output batch from gathered arrays; c_p None = probe columns
        stay zero-copy views at full width (dense output)."""
        out_cols = []
        for (src, ci), f in zip(out_map, out_schema):
            if src == -1:
                if c_p is None:
                    out_cols.append(ColumnVal(
                        pb.col_values(ci), pb.col_validity(ci),
                        f.dtype, pb.dicts[ci],
                    ))
                else:
                    out_cols.append(ColumnVal(
                        c_p[p_at[ci]], c_pm[p_at[ci]], f.dtype, pb.dicts[ci]
                    ))
            else:
                bb = builds[src].batch
                out_cols.append(ColumnVal(
                    c_b[src][b_at[src][ci]], c_bm[src][b_at[src][ci]],
                    f.dtype, bb.dicts[ci],
                ))
        out = batch_from_columns(out_cols, out_schema.names, new_sel)
        return Batch(out_schema, out.device, out.dicts)

    def take(pb, sel_out, bis, mode: str, out_cap: int | None):
        """One take on the device, noted in the rings: compaction index
        and every gather at the static bucket ``out_cap`` in ONE program,
        or (None) the build columns alone gathered at the batch's width.
        Returns (c_p, c_pm, c_b, c_bm, new_sel)."""
        obs.note_join_take(
            mode, (out_cap or pb.capacity) * n_levels, pb.capacity
        )
        if out_cap is None:
            c_b, c_bm = _chain_take_dense_jit(
                bvals_all, bmasks_all, tuple(bis), sel_out
            )
            return None, None, c_b, c_bm, sel_out
        return _chain_take_pred_jit(
            tuple(pb.col_values(c) for c in probe_cols),
            tuple(pb.col_validity(c) for c in probe_cols),
            bvals_all, bmasks_all, tuple(bis), sel_out,
            out_cap=out_cap,
        )

    def dispatch(pb) -> list[Batch]:
        """ALL levels' canon + probe + selection AND + live count as ONE
        program (single pass over the probe keys); the take is the
        boundary's. Returns the batches ready to emit: FIFO, up to the
        window's depth behind dispatch."""
        kv_all = tuple(
            tuple(pb.col_values(c) for c in key_cols)
            for key_cols in key_cols_per_level
        )
        km_all = tuple(
            tuple(pb.col_validity(c) for c in key_cols)
            for key_cols in key_cols_per_level
        )
        for kind in lookup_kinds:
            obs.note_join_lookup(kind, pb.capacity)
        sel_out, bis, live = _chain_probe_all_jit(
            kv_all, km_all, pb.device.sel,
            luts, lut_bases, bwords_all, n_lives, key_lists,
            cfgs=level_cfgs,
        )
        return [
            assemble(b, *taken)
            for b, taken in boundary.offer(
                live, pb.capacity, partial(take, pb, sel_out, bis), pb
            )
        ]

    for pb in probe_child_stream:
        ctx.check_cancelled()
        with ctx.metrics.timer("probe_time", count=True):
            ready = dispatch(pb)
        yield from ready
    for pb, taken in boundary.drain():
        with ctx.metrics.timer("probe_time"):
            ready = assemble(pb, *taken)
        yield ready
    if boundary.predictions:
        ctx.metrics.add("sel_pred_batches", boundary.predictions)


@partial(jax.jit, static_argnames=("cfgs",))
def _chain_probe_all_jit(kv_all, km_all, psel, luts, lut_bases, bwords_all,
                         n_lives, key_lists, cfgs):
    """Every level's key canonicalization + unique probe + the combined
    selection AND (with its live count) in ONE program: XLA fuses the
    per-level lookups into a single pass over the probe stream, and no
    per-level ok/live-count intermediates are materialized. A level's
    ``key_lists`` entry (None, or a small build's live key list: the
    pytree's shape is static) picks its map."""
    sel = psel
    bis = []
    for kv, km, lut, lb, bw, nl, kl, (bcap, use_lut, kinds) in zip(
        kv_all, km_all, luts, lut_bases, bwords_all, n_lives, key_lists, cfgs
    ):
        words, pvalid = core._canon_words_traced(kv, km, kinds)
        ok_base = psel & (pvalid if pvalid is not None else jnp.ones_like(psel))
        with jax.named_scope("auron.probe.lookup"):
            bi, ok = core._probe_unique_ops(
                words, ok_base, lut if use_lut else None, lb, bw, nl, bcap, kl
            )
        bis.append(bi)
        sel = sel & ok
    return sel, tuple(bis), jnp.sum(sel.astype(jnp.int32))


@jax.jit
def _chain_take_dense_jit(build_vals, build_masks, bis, sel):
    """Dense-output variant: gather each level's build columns at the probe
    width (no compaction index, no probe-column copies)."""
    c_b = []
    c_bm = []
    for lv_vals, lv_masks, bi in zip(build_vals, build_masks, bis):
        c_b.append(tuple(v[bi] for v in lv_vals))
        c_bm.append(tuple(m[bi] & sel for m in lv_masks))
    return tuple(c_b), tuple(c_bm)


@partial(jax.jit, static_argnames=("out_cap",))
def _chain_take_pred_jit(
    probe_vals, probe_masks, build_vals, build_masks, bis, sel, out_cap: int
):
    """One program: the compaction index computed ON DEVICE from the
    selection mask at a static bucket (predicted, or a seed's or a
    repair's exact one), the bottom probe columns taken at it and every
    level's build columns gathered at the compacted width. Rows beyond
    out_cap are truncated: the caller harvests the true live count
    asynchronously and repairs by re-taking at the correct bucket."""
    idx, new_sel = compaction_index(sel, out_cap)
    c_p = tuple(v[idx] for v in probe_vals)
    c_pm = tuple(m[idx] & new_sel for m in probe_masks)
    c_b = []
    c_bm = []
    for lv_vals, lv_masks, bi in zip(build_vals, build_masks, bis):
        c_bi = bi[idx]
        c_b.append(tuple(v[c_bi] for v in lv_vals))
        c_bm.append(tuple(m[c_bi] & new_sel for m in lv_masks))
    return c_p, c_pm, tuple(c_b), tuple(c_bm), new_sel
