"""Physical planner: protobuf plan IR -> executable operator tree.

Analog of the reference's PhysicalPlanner::create_plan recursive match
(native-engine/auron-planner/src/planner.rs:122-740): every
``PhysicalPlanNode`` variant maps to one exec operator, every
``PhysicalExprNode`` variant to one exprs.ir node. The TaskDefinition
carries (stage, partition, conf) — the runtime installs the conf scope and
drives the root operator (runtime/task.py).
"""

from __future__ import annotations

from auron_tpu import obs
from auron_tpu import types as T
from auron_tpu.exec.base import ExecOperator, ExecutionContext
from auron_tpu.exprs import ir
from auron_tpu.ops.sortkeys import SortSpec
from auron_tpu.proto import plan_pb2 as pb
from auron_tpu.utils.config import Configuration


class ResourceScanExec(ExecOperator):
    """memory_scan proto node: batches provided via the task resource map
    (how the host engine hands pre-imported data to a task — analog of the
    JniBridge resource map feeding readers, JniBridge.java:65-70)."""

    def __init__(self, schema: T.Schema, resource_id: str):
        super().__init__([], schema)
        self.resource_id = resource_id

    def _execute(self, partition: int, ctx: ExecutionContext):
        # per-partition form first ("rid.pid" — what a per-task host
        # executor registers; the payload IS this partition's stream),
        # then the shared per-partition-indexed source
        parts = ctx.resources.get(f"{self.resource_id}.{partition}")
        if parts is None:
            source = ctx.resources[self.resource_id]
            import pyarrow as _pa

            if callable(source):
                parts = source(partition)
            elif isinstance(source, dict):
                # partition-keyed mapping (SPMD drivers expose only the
                # locally-addressable partitions this way)
                parts = source[partition]
            elif source and isinstance(source[0], _pa.RecordBatch):
                # flat RecordBatch list — the unambiguous C-ABI host form
                # (put_resource decodes one IPC payload per task); every
                # other shape keeps the per-partition indexing semantics
                parts = source
            else:
                parts = source[partition]
        from auron_tpu.columnar.batch import Batch as _B

        for b in parts:
            if isinstance(b, _B):
                yield b
            elif b.num_rows:
                yield _B.from_arrow(b)

# ---------------------------------------------------------------------------
# types
# ---------------------------------------------------------------------------

_KIND_TO_T = {
    pb.DataType.NULL: T.TypeKind.NULL,
    pb.DataType.BOOL: T.TypeKind.BOOL,
    pb.DataType.INT8: T.TypeKind.INT8,
    pb.DataType.INT16: T.TypeKind.INT16,
    pb.DataType.INT32: T.TypeKind.INT32,
    pb.DataType.INT64: T.TypeKind.INT64,
    pb.DataType.FLOAT32: T.TypeKind.FLOAT32,
    pb.DataType.FLOAT64: T.TypeKind.FLOAT64,
    pb.DataType.DECIMAL: T.TypeKind.DECIMAL,
    pb.DataType.DATE32: T.TypeKind.DATE32,
    pb.DataType.TIMESTAMP: T.TypeKind.TIMESTAMP,
    pb.DataType.STRING: T.TypeKind.STRING,
    pb.DataType.BINARY: T.TypeKind.BINARY,
    pb.DataType.LIST: T.TypeKind.LIST,
    pb.DataType.MAP: T.TypeKind.MAP,
    pb.DataType.STRUCT: T.TypeKind.STRUCT,
}
_T_TO_KIND = {v: k for k, v in _KIND_TO_T.items()}


def dtype_from_proto(p: pb.DataType) -> T.DataType:
    kind = _KIND_TO_T[p.kind]
    if kind == T.TypeKind.LIST:
        return T.DataType(kind, inner=(dtype_from_proto(p.inner),))
    if kind in (T.TypeKind.MAP, T.TypeKind.STRUCT):
        return T.DataType(
            kind,
            inner=tuple(dtype_from_proto(i) for i in p.inners),
            struct_names=tuple(p.struct_names),
        )
    return T.DataType(kind, p.precision, p.scale)


def dtype_to_proto(t: T.DataType) -> pb.DataType:
    p = pb.DataType(kind=_T_TO_KIND[t.kind], precision=t.precision, scale=t.scale)
    if t.kind == T.TypeKind.LIST:
        p.inner.CopyFrom(dtype_to_proto(t.inner[0]))
    elif t.kind in (T.TypeKind.MAP, T.TypeKind.STRUCT):
        p.inners.extend(dtype_to_proto(i) for i in t.inner)
        if t.struct_names:
            p.struct_names.extend(t.struct_names)
    return p


def schema_from_proto(p: pb.Schema) -> T.Schema:
    return T.Schema(
        tuple(T.Field(f.name, dtype_from_proto(f.dtype), f.nullable) for f in p.fields)
    )


def schema_to_proto(s: T.Schema) -> pb.Schema:
    return pb.Schema(
        fields=[
            pb.Field(name=f.name, dtype=dtype_to_proto(f.dtype), nullable=f.nullable)
            for f in s.fields
        ]
    )


# ---------------------------------------------------------------------------
# expressions
# ---------------------------------------------------------------------------


def _literal_from_proto(p: pb.LiteralExpr) -> ir.Literal:
    dt = dtype_from_proto(p.dtype)
    if p.is_null:
        return ir.Literal(None, dt)
    which = p.WhichOneof("value")
    if which == "bool_value":
        return ir.Literal(p.bool_value, dt)
    if which == "int_value":
        return ir.Literal(p.int_value, dt)
    if which == "float_value":
        return ir.Literal(p.float_value, dt)
    if which == "string_value":
        return ir.Literal(p.string_value, dt)
    if which == "bytes_value":
        return ir.Literal(p.bytes_value, dt)
    if which == "decimal_unscaled":
        import decimal as pd

        return ir.Literal(
            pd.Decimal(p.decimal_unscaled).scaleb(-dt.scale), dt
        )
    return ir.Literal(None, dt)


def expr_from_proto(p: pb.PhysicalExprNode) -> ir.Expr:
    which = p.WhichOneof("expr")
    if which == "column":
        return ir.Column(p.column.index, p.column.name)
    if which == "literal":
        return _literal_from_proto(p.literal)
    if which == "cast":
        return ir.Cast(expr_from_proto(p.cast.child), dtype_from_proto(p.cast.to), p.cast.try_cast)
    if which == "binary":
        return ir.BinaryOp(
            p.binary.op, expr_from_proto(p.binary.left), expr_from_proto(p.binary.right)
        )
    if which == "is_null":
        return ir.IsNull(expr_from_proto(p.is_null.child))
    if which == "is_not_null":
        return ir.IsNotNull(expr_from_proto(p.is_not_null.child))
    if which == "not":
        return ir.Not(expr_from_proto(getattr(p, "not").child))
    if which == "if_expr":
        return ir.If(
            expr_from_proto(p.if_expr.cond),
            expr_from_proto(p.if_expr.then),
            expr_from_proto(p.if_expr.orelse),
        )
    if which == "case_expr":
        return ir.Case(
            tuple(
                (expr_from_proto(b.when), expr_from_proto(b.then))
                for b in p.case_expr.branches
            ),
            expr_from_proto(p.case_expr.orelse)
            if p.case_expr.HasField("orelse")
            else None,
        )
    if which == "in_list":
        return ir.In(
            expr_from_proto(p.in_list.child),
            tuple(_literal_from_proto(i).value for i in p.in_list.items),
            p.in_list.negated,
        )
    if which == "coalesce":
        return ir.Coalesce(tuple(expr_from_proto(a) for a in p.coalesce.args))
    if which == "like":
        return ir.Like(
            expr_from_proto(p.like.child), p.like.pattern, p.like.negated,
            p.like.escape or "\\",
        )
    if which == "scalar_func":
        return ir.ScalarFunc(
            p.scalar_func.name,
            tuple(expr_from_proto(a) for a in p.scalar_func.args),
            dtype_from_proto(p.scalar_func.out_dtype)
            if p.scalar_func.has_out_dtype
            else None,
        )
    if which == "host_udf":
        return ir.HostUDF(
            p.host_udf.name,
            tuple(expr_from_proto(a) for a in p.host_udf.args),
            dtype_from_proto(p.host_udf.out_dtype),
        )
    if which == "spark_partition_id":
        return ir.SparkPartitionId()
    if which == "monotonic_id":
        return ir.MonotonicId()
    if which == "row_num":
        return ir.RowNum()
    if which == "scalar_subquery":
        return ir.ScalarSubquery(
            p.scalar_subquery.resource_id, dtype_from_proto(p.scalar_subquery.dtype)
        )
    raise ValueError(f"unknown expr variant {which}")


def _sort_fields(fields) -> tuple[list[ir.Expr], list[SortSpec]]:
    exprs = [expr_from_proto(f.expr) for f in fields]
    specs = [SortSpec(asc=f.asc, nulls_first=f.nulls_first) for f in fields]
    return exprs, specs


# ---------------------------------------------------------------------------
# plans
# ---------------------------------------------------------------------------

_JOIN_TYPE = {
    pb.JOIN_INNER: "inner",
    pb.JOIN_LEFT: "left",
    pb.JOIN_RIGHT: "right",
    pb.JOIN_FULL: "full",
    pb.JOIN_LEFT_SEMI: "left_semi",
    pb.JOIN_LEFT_ANTI: "left_anti",
    pb.JOIN_EXISTENCE: "existence",
}

_AGG_FUNC = {
    pb.AGG_SUM: "sum",
    pb.AGG_COUNT: "count",
    pb.AGG_COUNT_STAR: "count_star",
    pb.AGG_AVG: "avg",
    pb.AGG_MIN: "min",
    pb.AGG_MAX: "max",
    pb.AGG_FIRST: "first",
    pb.AGG_FIRST_IGNORES_NULL: "first_ignores_null",
    pb.AGG_COLLECT_LIST: "collect_list",
    pb.AGG_COLLECT_SET: "collect_set",
    pb.AGG_HOST_UDAF: "host_udaf",
}

_AGG_MODE = {
    pb.AGG_PARTIAL: "partial",
    pb.AGG_PARTIAL_MERGE: "partial_merge",
    pb.AGG_FINAL: "final",
}


def partitioning_from_proto(p: pb.Partitioning):
    from auron_tpu.exec.shuffle import (
        HashPartitioning,
        RangePartitioning,
        RoundRobinPartitioning,
        SinglePartitioning,
    )

    if p.kind == pb.Partitioning.SINGLE:
        return SinglePartitioning()
    if p.kind == pb.Partitioning.HASH:
        return HashPartitioning(
            [expr_from_proto(e) for e in p.hash_exprs], p.num_partitions
        )
    if p.kind == pb.Partitioning.ROUND_ROBIN:
        return RoundRobinPartitioning(p.num_partitions)
    if p.kind == pb.Partitioning.RANGE:
        import numpy as np

        exprs, specs = _sort_fields(p.range_fields)
        w = p.range_words_per_bound
        arr = np.array(list(p.range_bound_words), dtype=np.uint64)
        bounds = arr.reshape(-1, w) if w else np.zeros((0, 1), np.uint64)
        return RangePartitioning(exprs, specs, p.num_partitions, bounds)
    raise ValueError(p.kind)


def plan_from_proto(p: pb.PhysicalPlanNode):
    from auron_tpu.exec import basic
    from auron_tpu.exec.agg_exec import AggExpr, HashAggExec
    from auron_tpu.exec.generate_exec import GenerateExec
    from auron_tpu.exec.joins import (
        BroadcastHashJoinExec,
        SortMergeJoinExec,
    )
    from auron_tpu.exec.shuffle import IpcReaderExec, ShuffleWriterExec
    from auron_tpu.exec.sort_exec import SortExec
    from auron_tpu.exec.window_exec import WindowExec, WindowFunc

    which = p.WhichOneof("plan")
    if which == "memory_scan":
        return ResourceScanExec(schema_from_proto(p.memory_scan.schema), p.memory_scan.resource_id)
    if which == "ffi_reader":
        from auron_tpu.exec.scan import FFIReaderExec

        return FFIReaderExec(schema_from_proto(p.ffi_reader.schema), p.ffi_reader.resource_id)
    if which == "parquet_scan":
        from auron_tpu.exec.scan import ParquetScanExec

        return ParquetScanExec(
            schema_from_proto(p.parquet_scan.schema),
            list(p.parquet_scan.file_paths),
            [expr_from_proto(e) for e in p.parquet_scan.pruning_predicates],
            p.parquet_scan.fs_resource_id or None,
            partitions=[list(fp.paths) for fp in p.parquet_scan.partitions] or None,
        )
    if which == "project":
        return basic.ProjectExec(
            plan_from_proto(p.project.child),
            [expr_from_proto(e.expr) for e in p.project.exprs],
            [e.name for e in p.project.exprs],
        )
    if which == "filter":
        return basic.FilterExec(
            plan_from_proto(p.filter.child),
            [expr_from_proto(e) for e in p.filter.predicates],
        )
    if which == "limit":
        return basic.LimitExec(plan_from_proto(p.limit.child), p.limit.limit)
    if which == "union":
        return basic.UnionExec([plan_from_proto(c) for c in p.union.children])
    if which == "expand":
        return basic.ExpandExec(
            plan_from_proto(p.expand.child),
            [[expr_from_proto(e) for e in proj.exprs] for proj in p.expand.projections],
            list(p.expand.names),
        )
    if which == "rename_columns":
        return basic.RenameColumnsExec(
            plan_from_proto(p.rename_columns.child), list(p.rename_columns.names)
        )
    if which == "empty_partitions":
        return basic.EmptyPartitionsExec(
            schema_from_proto(p.empty_partitions.schema), p.empty_partitions.num_partitions
        )
    if which == "coalesce_batches":
        return basic.CoalesceBatchesExec(
            plan_from_proto(p.coalesce_batches.child),
            p.coalesce_batches.target_rows or None,
        )
    if which == "hash_agg":
        n = p.hash_agg
        return HashAggExec(
            plan_from_proto(n.child),
            [(expr_from_proto(g.expr), g.name) for g in n.groupings],
            [
                (
                    AggExpr(
                        _AGG_FUNC[a.func],
                        expr_from_proto(a.expr) if a.has_expr else None,
                        udaf=a.udaf or None,
                    ),
                    a.name,
                )
                for a in n.aggs
            ],
            _AGG_MODE[n.mode],
        )
    if which == "sort":
        n = p.sort
        exprs, specs = _sort_fields(n.fields)
        return SortExec(
            plan_from_proto(n.child), exprs, specs,
            fetch=n.fetch if n.has_fetch else None,
        )
    if which == "sort_merge_join":
        n = p.sort_merge_join
        return SortMergeJoinExec(
            plan_from_proto(n.left),
            plan_from_proto(n.right),
            [expr_from_proto(e) for e in n.left_keys],
            [expr_from_proto(e) for e in n.right_keys],
            _JOIN_TYPE[n.join_type],
            condition=expr_from_proto(n.condition) if n.has_condition else None,
            exists_col=n.exists_col or "exists",
            projection=list(n.projection) if n.has_projection else None,
        )
    if which == "hash_join":
        n = p.hash_join
        return BroadcastHashJoinExec(
            plan_from_proto(n.left),
            plan_from_proto(n.right),
            [expr_from_proto(e) for e in n.left_keys],
            [expr_from_proto(e) for e in n.right_keys],
            _JOIN_TYPE[n.join_type],
            build_side="left" if n.build_side == pb.BUILD_LEFT else "right",
            condition=expr_from_proto(n.condition) if n.has_condition else None,
            cached_build_id=n.cached_build_id or None,
            exists_col=n.exists_col or "exists",
            projection=list(n.projection) if n.has_projection else None,
        )
    if which == "shuffle_writer":
        n = p.shuffle_writer
        return ShuffleWriterExec(
            plan_from_proto(n.child),
            partitioning_from_proto(n.partitioning),
            n.output_data_file,
            n.output_index_file,
        )
    if which == "rss_shuffle_writer":
        from auron_tpu.exec.shuffle.writer import RssShuffleWriterExec

        n = p.rss_shuffle_writer
        return RssShuffleWriterExec(
            plan_from_proto(n.child),
            partitioning_from_proto(n.partitioning),
            n.rss_resource_id,
        )
    if which == "ipc_reader":
        return IpcReaderExec(schema_from_proto(p.ipc_reader.schema), p.ipc_reader.resource_id)
    if which == "window":
        n = p.window
        order_exprs, order_specs = _sort_fields(n.order_by)
        return WindowExec(
            plan_from_proto(n.child),
            [expr_from_proto(e) for e in n.partition_by],
            list(zip(order_exprs, order_specs)),
            [
                (
                    WindowFunc(
                        f.kind,
                        agg=f.agg or None,
                        expr=expr_from_proto(f.expr) if f.has_expr else None,
                        offset=f.offset or 1,
                        frame_whole=f.frame_whole,
                    ),
                    f.name,
                )
                for f in n.funcs
            ],
        )
    if which == "generate":
        n = p.generate
        return GenerateExec(
            plan_from_proto(n.child),
            n.generator,
            expr_from_proto(n.gen_expr),
            list(n.required_cols),
            outer=n.outer,
            json_fields=list(n.json_fields),
            elem_name=n.elem_name or "col",
            pos_name=n.pos_name or "pos",
            udtf=n.udtf or None,
        )
    if which == "orc_scan":
        from auron_tpu.exec.scan import OrcScanExec

        return OrcScanExec(
            schema_from_proto(p.orc_scan.schema),
            list(p.orc_scan.file_paths),
            [expr_from_proto(e) for e in p.orc_scan.pruning_predicates],
            p.orc_scan.fs_resource_id or None,
            partitions=[list(fp.paths) for fp in p.orc_scan.partitions] or None,
        )
    if which == "orc_sink":
        from auron_tpu.exec.sink import OrcSinkExec

        return OrcSinkExec(
            plan_from_proto(p.orc_sink.child),
            p.orc_sink.output_path,
            dict(p.orc_sink.props),
        )
    if which == "parquet_sink":
        from auron_tpu.exec.sink import ParquetSinkExec

        return ParquetSinkExec(
            plan_from_proto(p.parquet_sink.child),
            p.parquet_sink.output_path,
            dict(p.parquet_sink.props),
            partition_by=list(p.parquet_sink.partition_by) or None,
        )
    if which == "ipc_writer":
        from auron_tpu.exec.sink import IpcWriterExec

        return IpcWriterExec(plan_from_proto(p.ipc_writer.child), p.ipc_writer.resource_id)
    if which == "debug":
        return basic.DebugExec(plan_from_proto(p.debug.child), p.debug.tag)
    if which == "kafka_scan":
        from auron_tpu.exec.streaming import KafkaScanExec

        n = p.kafka_scan
        return KafkaScanExec(
            schema_from_proto(n.schema),
            n.topic,
            n.source_resource_id,
            startup_mode=n.startup_mode or "earliest",
            start_offsets={int(k): int(v) for k, v in n.start_offsets.items()},
            data_format=n.format or "json",
            on_error=n.on_error or "skip",
            pb_field_ids=list(n.pb_field_ids) or None,
            max_batch_records=n.max_batch_records or 8192,
            zigzag_cols=set(n.zigzag_cols) or None,
        )
    if which == "mesh_exchange":
        raise ValueError(
            "mesh_exchange is a stage boundary resolved by "
            "parallel.mesh_driver.MeshQueryDriver, not a streaming operator; "
            "run the plan through the driver"
        )
    raise ValueError(f"unknown plan variant {which}")


def task_from_proto(task: pb.TaskDefinition):
    """Returns (root exec, stage_id, partition_id, Configuration)."""
    from auron_tpu.plan.fusion import fuse_exec_tree
    from auron_tpu.plan.optimizer import elide_smj_input_sorts, prune_columns

    with obs.span("task", cat="plan"):
        _resolve_shuffle_templates(task)
        conf = Configuration(dict(task.conf))
        mode = dict(task.conf).get("auron.smj.elide.sorts", "build")
        # column pruning runs on EVERY task (idempotent): join pair-gather
        # bytes scale with emitted column count, the dominant join cost
        proto = prune_columns(elide_smj_input_sorts(task.plan, mode=mode))
        plan = plan_from_proto(proto)
        # whole-stage fusion rewrites the EXEC tree (protos/goldens
        # untouched): pipeline segments between blocking boundaries compile
        # into single XLA programs where the cost model says fusion wins
        # (plan/fusion.py)
        with obs.span("fusion", cat="plan"):
            plan = fuse_exec_tree(plan, conf)
    return plan, task.stage_id, task.partition_id, conf


def _resolve_shuffle_templates(task: pb.TaskDefinition) -> None:
    """Fill {work_dir}/{partition} placeholders in shuffle-writer paths from
    the task conf + partition id. Lets a host assemble stage tasks from the
    conversion service's per-stage plan template with byte-level surgery
    only (TaskDefs appends partition_id + conf; it never edits nested plan
    strings) — the host computes the same paths from the stage's
    output_*_template fields to commit/fetch map outputs."""
    from auron_tpu.plan.protowalk import child_nodes

    work_dir = task.conf.get("auron.work_dir", "")

    def rec(node: pb.PhysicalPlanNode) -> None:
        if node.WhichOneof("plan") == "shuffle_writer":
            w = node.shuffle_writer
            for attr in ("output_data_file", "output_index_file"):
                v = getattr(w, attr)
                if "{work_dir}" in v or "{partition}" in v:
                    if "{work_dir}" in v and not work_dir:
                        raise ValueError(
                            "shuffle path template needs task conf auron.work_dir"
                        )
                    setattr(
                        w, attr,
                        v.replace("{work_dir}", work_dir)
                        .replace("{partition}", str(task.partition_id)),
                    )
        for c in child_nodes(node):
            rec(c)

    rec(task.plan)
