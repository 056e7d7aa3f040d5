"""Sort-merge join exec.

Analog of the reference's SMJ (sort_merge_join_exec.rs + joins/smj/*, join
types auron.proto:508-517, incl. inequality-join residual conditions).
TPU-native strategy: the right side is accumulated into a key-clustered
sorted-array map (one device sort — the inputs arrive sorted from SortExec,
so this is a near-no-op merge), and the left side streams through batched
binary-search probes with ragged pair expansion (exec/joins/core.py).
"""

from __future__ import annotations

from typing import Iterator

from auron_tpu.columnar.batch import Batch
from auron_tpu.exec.base import ExecOperator, ExecutionContext
from auron_tpu.exec.joins import core
from auron_tpu.exec.joins.driver import EquiJoinDriver
from auron_tpu.exprs import ir


class SortMergeJoinExec(ExecOperator):
    def __init__(
        self,
        left: ExecOperator,
        right: ExecOperator,
        left_keys: list[ir.Expr],
        right_keys: list[ir.Expr],
        join_type: str,
        condition: ir.Expr | None = None,
        exists_col: str = "exists",
        projection: list[int] | None = None,
    ):
        self.driver = EquiJoinDriver(
            left.schema, right.schema, left_keys, right_keys,
            join_type, build_side="right", condition=condition,
            exists_col=exists_col, projection=projection,
        )
        super().__init__([left, right], self.driver.out_schema)

    def _execute(self, partition: int, ctx: ExecutionContext) -> Iterator[Batch]:
        with ctx.metrics.timer("build_time"):
            build_batches = list(self.child_stream(1, partition, ctx))
            build = self.driver.prepare(build_batches, conf=ctx.conf)
        # the unique-build fast path compacts through the same boundary
        # as BHJ (exec/selectivity.py)
        boundary = self.driver.compaction_boundary(ctx)
        for pb in self.child_stream(0, partition, ctx):
            ctx.check_cancelled()
            # no empty-batch pre-check: it costs a host sync per batch, and
            # the probe itself already syncs once on the match total
            with ctx.metrics.timer("probe_time", count=True):
                yield from self.driver.probe_batch(build, pb, boundary)
        with ctx.metrics.timer("probe_time"):
            yield from self.driver.finish_probe(boundary)
        yield from self.driver.finish(build)
