"""Broadcast / shuffled hash join execs.

Analogs of the reference's broadcast_join_exec.rs +
broadcast_join_build_hash_map_exec.rs: the build side (broadcast data or the
shuffled small side) becomes a sorted-array key map, optionally **cached per
executor through the task resource map** so many tasks probing the same
broadcast reuse one build (the reference caches its built hash map the same
way). PartitionMode BuildLeft/BuildRight decides which child builds.
"""

from __future__ import annotations

import threading
from typing import Iterator

from auron_tpu.columnar.batch import Batch
from auron_tpu.exec.base import ExecOperator, ExecutionContext
from auron_tpu.exec.joins.core import PreparedBuild
from auron_tpu.exec.joins.driver import EquiJoinDriver
from auron_tpu.exprs import ir


_key_locks: dict[str, threading.Lock] = {}
_key_locks_guard = threading.Lock()


def _build_key_lock(key: str) -> threading.Lock:
    with _key_locks_guard:
        lk = _key_locks.get(key)
        if lk is None:
            lk = _key_locks[key] = threading.Lock()
        return lk


class _BuildMemGuard:
    """Accounting-only consumer pinning a join build's footprint for the
    probe's duration. spill() frees nothing — the build is needed — but
    registration makes the bytes visible to fair-share math."""

    def __init__(self, ex, build):
        from auron_tpu.exec.sort_exec import batch_nbytes

        self.name = f"join-build-{id(ex):x}"
        self._bytes = batch_nbytes(build.batch) + sum(
            w.size * w.dtype.itemsize for w in build.words
        )

    def mem_used(self) -> int:
        return self._bytes

    def spill(self) -> int:  # auronlint: thread-root(foreign) -- MemManager polls/dispatches from other tasks' threads
        return 0


def evict_build_lock(key: str) -> None:
    """Drop the build lock for a cached_build_id. Called by the host's
    resource-removal path (bridge/api.remove_resource) when a broadcast is
    destroyed — without this, a long-lived executor leaks one Lock per
    broadcast instance."""
    with _key_locks_guard:
        _key_locks.pop(key, None)


class BroadcastHashJoinExec(ExecOperator):
    def __init__(
        self,
        left: ExecOperator,
        right: ExecOperator,
        left_keys: list[ir.Expr],
        right_keys: list[ir.Expr],
        join_type: str,
        build_side: str = "right",
        condition: ir.Expr | None = None,
        cached_build_id: str | None = None,
        exists_col: str = "exists",
        projection: list[int] | None = None,
    ):
        self.driver = EquiJoinDriver(
            left.schema, right.schema, left_keys, right_keys,
            join_type, build_side=build_side, condition=condition,
            exists_col=exists_col, projection=projection,
        )
        self.build_side = build_side
        self.cached_build_id = cached_build_id
        super().__init__([left, right], self.driver.out_schema)

    def _build(self, partition: int, ctx: ExecutionContext) -> PreparedBuild:
        build_child = 0 if self.build_side == "left" else 1
        memo = ctx.resources.pop(("fusion_build_memo", id(self), partition), None)
        if memo is not None:
            return memo  # prepared during a fused-chain attempt that fell back
        key = self.cached_build_id
        if key is not None:
            # Executor-shared when the bridge hands us the live resource map
            # (ctx.shared): concurrent tasks probing the same broadcast wait
            # on one build instead of each building their own — the same
            # executor-wide broadcast-build cache the reference keeps.
            # CONTRACT: cached_build_id must uniquely identify the build
            # DATA (the host side mints a fresh id per broadcast instance,
            # like a Spark broadcast variable id) and the host removes the
            # resource when the broadcast is destroyed.
            store = ctx.shared if ctx.shared is not None else ctx.resources
            import dataclasses

            import jax.numpy as jnp

            lk = _build_key_lock(key)
            # bounded wait: plans whose cached joins nest in opposite key
            # orders could otherwise ABBA-deadlock; on timeout just build
            # locally (duplicate work, never a wrong result)
            acquired = lk.acquire(timeout=30.0)
            try:
                cached = store.get(key)
                if cached is None:
                    with ctx.metrics.timer("build_hash_map_time"):
                        batches = list(self.child_stream(build_child, partition, ctx))
                        cached = self.driver.prepare(batches, conf=ctx.conf)
                    if acquired:
                        store[key] = cached
            finally:
                if acquired:
                    lk.release()
            # fresh matched-flags per task; the map itself is shared
            return dataclasses.replace(
                cached, matched=jnp.zeros(cached.batch.capacity, bool)
            )
        with ctx.metrics.timer("build_hash_map_time"):
            batches = list(self.child_stream(build_child, partition, ctx))
            built = self.driver.prepare(batches, conf=ctx.conf)
        return built

    def _execute(self, partition: int, ctx: ExecutionContext) -> Iterator[Batch]:
        from auron_tpu.exec.joins.chain import clear_chain_memos, try_fused_chain
        from auron_tpu.memory.memmgr import MemManager

        fused = try_fused_chain(self, partition, ctx)
        if fused is not None:
            yield from fused
            return
        mm = MemManager.get()
        guard = None
        # fused probe stage hand-off (plan/fusion.py): the probe child may
        # be a FusedStageExec carrying our ProbePrepLink — publishing the
        # prepared build arms it to run the probe prologue in-program
        link = getattr(self, "_probe_prep_link", None)
        try:
            build = self._build(partition, ctx)
            # the build must stay resident for probing: register it as an
            # UNSPILLABLE consumer so its footprint shrinks the managed
            # pool others fair-share, instead of blowing the budget
            # invisibly (auron-memmgr mem_unspillable accounting)
            guard = _BuildMemGuard(self, build)
            mm.register(guard, spillable=False)
            probe_child = 1 if self.build_side == "left" else 0
            # this partition's compaction boundary (exec/selectivity.py):
            # emissions lag dispatch by the window depth, drained by
            # finish_probe below
            boundary = self.driver.compaction_boundary(ctx)
            if link is not None:
                self.driver.publish_probe_prep(link, build, boundary)
            for pb in self.child_stream(probe_child, partition, ctx):
                ctx.check_cancelled()
                # no empty-batch pre-check: it costs a host sync per batch,
                # and the probe itself already syncs once on the match total
                with ctx.metrics.timer("probe_time", count=True):
                    yield from self.driver.probe_batch(build, pb, boundary)
            with ctx.metrics.timer("probe_time"):
                yield from self.driver.finish_probe(boundary)
            yield from self.driver.finish(build)
        finally:
            if link is not None:
                link.clear()
            if guard is not None:
                mm.unregister(guard)
            # fallback memos scope to this attempt (ADVICE r3): entries for
            # operators never reached must not outlive the chain top
            clear_chain_memos(self, partition, ctx)


class ShuffledHashJoinExec(BroadcastHashJoinExec):
    """Same machinery, build side fed by a shuffle instead of a broadcast
    (the reference routes both through the same join core; SMJ fallback for
    oversized build sides is a planner decision via SMJ_FALLBACK_* confs)."""

    def __init__(self, *args, **kwargs):
        kwargs.pop("cached_build_id", None)
        super().__init__(*args, cached_build_id=None, **kwargs)
