"""Plan-level optimizer passes over the protobuf IR.

``prune_columns`` is the column-pruning pass (analog of the reference's
common/column_pruning.rs and DataFusion join projections): a top-down
required-column analysis that shrinks join outputs to exactly the columns
consumed downstream. On TPU this matters more than on CPU — the dominant
join cost is pair-gather bytes through HBM, which scales linearly with the
emitted column count, so pruning a 12-column join to 3 columns cuts the
expansion roofline by 4x.

The pass returns a REWRITTEN plan; column references in every affected
node are remapped. Nodes the pass doesn't understand act as pruning
barriers (they require all their children's columns) but the recursion
still descends so joins below a barrier are pruned too.
"""

from __future__ import annotations

from auron_tpu.proto import plan_pb2 as pb

# nodes whose output schema is exactly their (single) child's schema;
# rename_columns is NOT here — its names list is sized to the unpruned
# child, so it acts as a pruning barrier
_PASSTHROUGH = ("limit", "coalesce_batches", "debug")


def prune_columns(plan: pb.PhysicalPlanNode) -> pb.PhysicalPlanNode:
    new, _ = _prune(plan, None)
    return new


# ---------------------------------------------------------------------------
# proto-expression helpers
# ---------------------------------------------------------------------------


def _walk_columns(msg, fn) -> None:
    """Apply fn to every ColumnExpr reachable from msg (any proto message)."""
    if isinstance(msg, pb.PhysicalExprNode) and msg.WhichOneof("expr") == "column":
        fn(msg.column)
        return
    for fd, val in msg.ListFields():
        if fd.type != fd.TYPE_MESSAGE:
            continue
        if fd.label == fd.LABEL_REPEATED:
            for v in val:
                _walk_columns(v, fn)
        else:
            _walk_columns(val, fn)


def _collect_cols(*msgs) -> set[int]:
    out: set[int] = set()
    for m in msgs:
        _walk_columns(m, lambda c: out.add(c.index))
    return out


def _remap_exprs(mapping: dict[int, int] | None, *msgs) -> None:
    if mapping is None:
        return

    def rewrite(c):
        c.index = mapping[c.index]

    for m in msgs:
        _walk_columns(m, rewrite)


def _out_width(node: pb.PhysicalPlanNode) -> int:
    """Output column count of a plan subtree, computed structurally where
    the node type makes it cheap; falls back to instantiating the planner's
    exec tree only for width-opaque nodes (agg intermediates etc.)."""
    which = node.WhichOneof("plan")
    inner = getattr(node, which)
    if which in ("memory_scan", "ipc_reader", "ffi_reader", "parquet_scan",
                 "orc_scan", "empty_partitions"):
        return len(inner.schema.fields)
    if which == "project":
        return len(inner.exprs)
    if which in ("filter", "sort", "limit", "coalesce_batches", "debug",
                 "shuffle_writer", "rss_shuffle_writer", "mesh_exchange"):
        return _out_width(inner.child)
    if which == "rename_columns":
        return len(inner.names)
    if which in ("hash_join", "sort_merge_join"):
        if inner.has_projection:
            return len(inner.projection)
        jt = inner.join_type
        if jt in (pb.JOIN_LEFT_SEMI, pb.JOIN_LEFT_ANTI):
            return _out_width(inner.left)
        if jt == pb.JOIN_EXISTENCE:
            return _out_width(inner.left) + 1
        return _out_width(inner.left) + _out_width(inner.right)
    if which == "union":
        return _out_width(inner.children[0])
    if which == "hash_agg" and inner.mode == pb.AGG_FINAL:
        return len(inner.groupings) + len(inner.aggs)
    if which == "kafka_scan":
        return len(inner.schema.fields)
    # fallback instantiates the exec subtree; never valid across a
    # mesh_exchange (driver-resolved), so width-opaque nodes above one
    # must be covered structurally above
    from auron_tpu.plan.planner import plan_from_proto

    return len(plan_from_proto(node).schema)


def _req_or_all(required: list[int] | None, width: int) -> list[int]:
    return list(range(width)) if required is None else required


# ---------------------------------------------------------------------------
# the pass
# ---------------------------------------------------------------------------


def _prune(
    node: pb.PhysicalPlanNode, required: list[int] | None
) -> tuple[pb.PhysicalPlanNode, dict[int, int] | None]:
    """Returns (rewritten node, old->new output index mapping or None=id)."""
    which = node.WhichOneof("plan")
    handler = _HANDLERS.get(which)
    if handler is not None:
        return handler(node, required)
    # barrier: keep the node, but descend into any plan children with
    # "all required" so deeper joins still get pruned
    new = pb.PhysicalPlanNode()
    new.CopyFrom(node)
    inner = getattr(new, which)
    if which == "union":
        for c in inner.children:
            c.CopyFrom(_prune(c, None)[0])
        return new, None
    for f in ("child", "left", "right"):
        try:
            present = inner.HasField(f)
        except ValueError:
            continue
        if present:
            sub, cmap = _prune(getattr(inner, f), None)
            assert cmap is None, f"barrier child of {which} must not remap"
            getattr(inner, f).CopyFrom(sub)
    return new, None


def _prune_project(node, required):
    n = node.project
    keep = sorted(set(_req_or_all(required, len(n.exprs))))
    child_req = sorted(_collect_cols(*(n.exprs[i].expr for i in keep)))
    new_child, cmap = _prune(n.child, child_req)
    new = pb.PhysicalPlanNode()
    p = new.project
    p.child.CopyFrom(new_child)
    for i in keep:
        ne = p.exprs.add()
        ne.CopyFrom(n.exprs[i])
        _remap_exprs(cmap, ne.expr)
    if len(keep) == len(n.exprs):
        return new, None
    return new, {old: i for i, old in enumerate(keep)}


def _prune_filter(node, required):
    n = node.filter
    pred_cols = _collect_cols(*n.predicates)
    child_req = (
        None if required is None else sorted(set(required) | pred_cols)
    )
    new_child, cmap = _prune(n.child, child_req)
    new = pb.PhysicalPlanNode()
    f = new.filter
    f.child.CopyFrom(new_child)
    for p in n.predicates:
        np_ = f.predicates.add()
        np_.CopyFrom(p)
        _remap_exprs(cmap, np_)
    return new, cmap


def _prune_sort(node, required):
    n = node.sort
    sort_cols = _collect_cols(*(f.expr for f in n.fields))
    child_req = (
        None if required is None else sorted(set(required) | sort_cols)
    )
    new_child, cmap = _prune(n.child, child_req)
    new = pb.PhysicalPlanNode()
    new.CopyFrom(node)
    s = new.sort
    s.child.CopyFrom(new_child)
    for f in s.fields:
        _remap_exprs(cmap, f.expr)
    return new, cmap


def _prune_passthrough(node, required):
    which = node.WhichOneof("plan")
    new = pb.PhysicalPlanNode()
    new.CopyFrom(node)
    inner = getattr(new, which)
    new_child, cmap = _prune(inner.child, required)
    inner.child.CopyFrom(new_child)
    return new, cmap


def _prune_hash_agg(node, required):
    n = node.hash_agg
    new = pb.PhysicalPlanNode()
    new.CopyFrom(node)
    a = new.hash_agg
    if n.mode == pb.AGG_PARTIAL:
        child_req = sorted(
            _collect_cols(
                *(g.expr for g in n.groupings),
                *(sp.expr for sp in n.aggs if sp.has_expr),
            )
        )
        new_child, cmap = _prune(n.child, child_req)
        a.child.CopyFrom(new_child)
        for g in a.groupings:
            _remap_exprs(cmap, g.expr)
        for sp in a.aggs:
            if sp.has_expr:
                _remap_exprs(cmap, sp.expr)
    else:
        # merge/final consume positional intermediate columns: all required
        new_child, cmap = _prune(n.child, None)
        assert cmap is None
        a.child.CopyFrom(new_child)
    return new, None  # agg output layout unchanged


def _prune_exchange_like(node, required):
    """shuffle/mesh-exchange writers emit every child column; the
    partitioning expressions address child coordinates directly."""
    which = node.WhichOneof("plan")
    new = pb.PhysicalPlanNode()
    new.CopyFrom(node)
    inner = getattr(new, which)
    new_child, cmap = _prune(inner.child, None)
    assert cmap is None
    inner.child.CopyFrom(new_child)
    return new, None


def _prune_join(node, required):
    which = node.WhichOneof("plan")
    n = getattr(node, which)
    if n.has_projection:  # already projected (pass ran twice): barrier
        return node, None
    jt = n.join_type
    nl = _out_width(n.left)
    nr = _out_width(n.right)
    semi_like = jt in (pb.JOIN_LEFT_SEMI, pb.JOIN_LEFT_ANTI)
    existence = jt == pb.JOIN_EXISTENCE
    out_width = nl if semi_like else (nl + 1 if existence else nl + nr)
    R = _req_or_all(required, out_width)

    lkeys = _collect_cols(*n.left_keys)
    rkeys = _collect_cols(*n.right_keys)
    cond_cols = _collect_cols(n.condition) if n.has_condition else set()
    cond_l = {c for c in cond_cols if c < nl}
    cond_r = {c - nl for c in cond_cols if c >= nl}

    left_need = {c for c in R if c < nl}
    right_need = (
        set() if (semi_like or existence) else {c - nl for c in R if c >= nl}
    )
    child_req_l = sorted(left_need | lkeys | cond_l)
    child_req_r = sorted(right_need | rkeys | cond_r)

    new_left, lmap = _prune(n.left, child_req_l if len(child_req_l) < nl else None)
    new_right, rmap = _prune(
        n.right, child_req_r if len(child_req_r) < nr else None
    )
    lmap = lmap or {i: i for i in range(nl)}
    rmap = rmap or {i: i for i in range(nr)}
    new_nl = _out_width(new_left)
    new_nr = _out_width(new_right)

    new = pb.PhysicalPlanNode()
    new.CopyFrom(node)
    j = getattr(new, which)
    j.left.CopyFrom(new_left)
    j.right.CopyFrom(new_right)
    for k in j.left_keys:
        _remap_exprs(lmap, k)
    for k in j.right_keys:
        _remap_exprs(rmap, k)
    if n.has_condition:
        comb = {c: lmap[c] for c in cond_l}
        comb.update({c + nl: new_nl + rmap[c] for c in cond_r})
        _remap_exprs(comb, j.condition)

    # projection over the PRUNED combined/left coordinates, in R's order
    if semi_like:
        proj = [lmap[c] for c in R]
        new_width = new_nl
    elif existence:
        proj = [(lmap[c] if c < nl else new_nl) for c in R]
        new_width = new_nl + 1
    else:
        proj = [(lmap[c] if c < nl else new_nl + rmap[c - nl]) for c in R]
        new_width = new_nl + new_nr
    if proj != list(range(new_width)):
        j.projection.extend(proj)
        j.has_projection = True
    mapping = None if required is None else {c: i for i, c in enumerate(R)}
    return new, mapping


_HANDLERS = {
    "project": _prune_project,
    "filter": _prune_filter,
    "sort": _prune_sort,
    "hash_agg": _prune_hash_agg,
    "hash_join": _prune_join,
    "sort_merge_join": _prune_join,
    "shuffle_writer": _prune_exchange_like,
    "rss_shuffle_writer": _prune_exchange_like,
    "mesh_exchange": _prune_exchange_like,
    "parquet_sink": _prune_exchange_like,
    "orc_sink": _prune_exchange_like,
    "ipc_writer": _prune_exchange_like,
}
for _p in _PASSTHROUGH:
    _HANDLERS[_p] = _prune_passthrough


# ---------------------------------------------------------------------------
# Sort elision under sort-merge join
# ---------------------------------------------------------------------------

# the only operator in the IR whose OUTPUT depends on its input's row order
# (head-N). Sort/TakeOrdered/Window establish their own order internally.
_ORDER_SENSITIVE = ("limit",)
# operators that neither need their input's order nor keep it in a way an
# ancestor could read: an order beneath them lives on only as far as they do
_ORDER_FREE = ("project", "filter")
_WRITERS = ("shuffle_writer", "rss_shuffle_writer")


def elide_smj_input_sorts(
    plan: "pb.PhysicalPlanNode", mode: str = "build"
) -> "pb.PhysicalPlanNode":
    """Drop SortExec children feeding a sort-merge join.

    The host engine plans Sort->SMJ because ITS merge-join streams two
    ordered cursors; this engine's SMJ builds its right side into a map
    (joins/core.prepare_build: a LUT, a hash map or sorted words) and
    probes with order-independent lookups, so explicit input sorts are
    pure overhead — at perf-gate scale each one is a full materialized
    lexsort of a fact partition.

    ``mode`` controls how aggressive the rewrite is:

    - "build" (default): elide the BUILD-side (right) sort, and the probe
      side's (left) where the task plan itself proves the join's order
      dies: the path from the join to the task's root passes only
      order-free operators (projection, filter, a hash join's probe side)
      and ends at a hash aggregate or a hash-partitioning shuffle writer,
      neither of which reads or keeps its input's order. The join's output
      order follows the probe side, so elsewhere the probe's sort stays —
      safe even when the host relied on the SMJ's output ordering to
      satisfy a downstream requirement invisible in this task plan
      (Spark's EnsureRequirements plants no sort above a join whose
      outputOrdering already satisfies the parent).
    - "full": elide both sides. Only the host can know no ancestor outside
      the converted section needs the order; it asserts that by setting
      ``auron.smj.elide.sorts=full`` in the task conf.
    - "off": no rewrite.

    Either way a fetch-carrying sort (TakeOrdered — changes the row SET) is
    never touched, and the rewrite is skipped under an order-sensitive
    ancestor inside the plan (head-N limit).
    """
    if mode == "off":
        return plan
    new = pb.PhysicalPlanNode()
    new.CopyFrom(plan)
    _elide(new, order_sensitive=False, full=(mode == "full"), dies=False)
    return new


def _order_dies_below(node: "pb.PhysicalPlanNode", dies: bool) -> tuple:
    """For each child of ``node`` (``protowalk.child_nodes``' order), does
    its output's order die on the way to the task's root (``dies``: it
    dies above ``node``)?"""
    which = node.WhichOneof("plan")
    if which == "hash_agg":
        return (True,)
    if which in _WRITERS:
        return (getattr(node, which).partitioning.kind == pb.Partitioning.HASH,)
    if which in _ORDER_FREE:
        return (dies,)
    if which == "hash_join":
        probe_left = node.hash_join.build_side == pb.BUILD_RIGHT
        return (dies and probe_left, dies and not probe_left)
    return ()


def _elide(node: "pb.PhysicalPlanNode", order_sensitive: bool, full: bool,
           dies: bool) -> None:
    from auron_tpu.plan.protowalk import child_nodes

    which = node.WhichOneof("plan")
    sensitive = order_sensitive or which in _ORDER_SENSITIVE
    if which == "sort_merge_join" and not sensitive:
        j = node.sort_merge_join
        sides = ("left", "right") if (full or dies) else ("right",)
        for side in sides:
            child = getattr(j, side)
            if (
                child.WhichOneof("plan") == "sort"
                and not child.sort.has_fetch
            ):
                grand = pb.PhysicalPlanNode()
                grand.CopyFrom(child.sort.child)
                getattr(j, side).CopyFrom(grand)
    below = _order_dies_below(node, dies)
    for i, c in enumerate(child_nodes(node)):
        _elide(c, sensitive, full, i < len(below) and below[i])
