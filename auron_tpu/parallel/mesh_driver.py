"""Planned-query execution over a device mesh.

The reference wires its shuffle into the plan IR as a writer/reader node
pair executed by separate Spark stages (NativeShuffleExchangeBase.scala:
187-296 building ShuffleWriterExecNode, shuffle/mod.rs:56-121 executing
it). The TPU-native plan IR instead carries a single ``mesh_exchange``
node: when producer and consumer stages live on the same mesh, rows move
over ICI via ``lax.all_to_all`` with no intermediate files; when they
don't (or the payload is too large to stay device-resident), the driver
lowers the SAME node onto the durable file-shuffle pair.

A mesh of width N over N devices is a deployment, and the one-device mesh
runs the same code: partition ``p`` of every stage runs on mesh device
``p``, on the table splits the server placed there (serve/server.py) or on
the shard an exchange delivered there, and a stage's partitions are pumped
side by side: the first on the driver's thread, each other on a task thread
of its own (``_pump_stage``).

``MeshQueryDriver.run`` resolves every ``mesh_exchange`` node bottom-up:

1. run the child sub-plan for each mesh partition (the map stage), the
   partitions side by side, each on its own device;
2. compute per-row destination partition ids with the *same*
   ``Partitioning`` code the file shuffle writer uses — mesh and file
   exchanges route bit-identically (spark-exact murmur3, dict strings,
   range bounds);
3. pick the transport: ``exchange.mode`` conf = mesh | file | auto
   (auto = mesh when the estimated per-shard payload fits
   ``exchange.mesh.max.bytes``, else file) — the ICI-vs-file decision rule;
4. mesh: unify dictionaries across shards, pad every shard to a common
   capacity bucket, assemble the [P, cap] operands from the shards'
   planes where they lie (no stack on one chip), exchange with
   ``pid_exchange_step`` (slot capacity sized exactly from host-side
   per-(src,dst) counts, so overflow is impossible), and expose each
   partition's received shard, on its own device, as a memory-scan
   resource;
   file: execute a ShuffleWriterExec per shard and expose the blocks
   through IpcReader — byte-identical to the standalone file path;
5. splice a scan node where the exchange was and continue planning.

A ``mesh_exchange`` marked ``broadcast`` (a derived table on a join's build
side, sql/lowering.py) runs its child stage the same way and hands every
partition every row, copied to its chip (``_broadcast``).

Exchange statistics (rows per (src, dst)) are recorded on the driver —
the same numbers AQE coalescing consumes (parallel/broadcast.py
map_output_stats analog).
"""

from __future__ import annotations

import os
import tempfile
import threading
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from auron_tpu import obs
from auron_tpu import types as T
from auron_tpu.columnar.batch import (
    Batch,
    DeviceBatch,
    bucket_capacity,
    device_concat,
    unify_dict,
)
from auron_tpu.exec.base import ExecutionContext
from auron_tpu.parallel.exchange import pid_exchange_step
from auron_tpu.parallel.mesh import PARTITION_AXIS, shard_spec
from auron_tpu.plan.planner import (
    partitioning_from_proto,
    plan_from_proto,
    schema_to_proto,
)
from auron_tpu.proto import plan_pb2 as pb
from auron_tpu.utils.config import (
    EXCHANGE_COALESCE_ENABLE,
    EXCHANGE_COALESCE_TARGET_BYTES,
    EXCHANGE_MESH_MAX_BYTES,
    EXCHANGE_MODE,
    Configuration,
    conf_scope,
)


@dataclass
class ExchangeStats:
    """Map-output statistics of one resolved exchange (AQE input)."""

    exchange_id: str
    mode: str  # "mesh" | "file" | "broadcast"
    rows: np.ndarray  # [P_src, P_dst] routed row counts
    est_bytes_per_shard: int  # payload of the hottest receiving shard
    coalesced_groups: list | None = None  # AQE partition grouping, if applied
    #: AQE skew-split task table, if applied: [(pid, map_lo, map_hi|None)]
    skew_tasks: list | None = None
    #: mesh transport: how many devices the partitions it handed out lie on
    n_devices: int = 0

    def partition_sizes(self) -> np.ndarray:
        return self.rows.sum(axis=0)


class SkewSplitProvider:
    """AQE skew-join split consumer (Spark OptimizeSkewedJoin analog): the
    stage widens to one task per (partition, slice) pair; the SPLIT side
    reads a map-range slice of its skewed partition, the other side
    re-reads the full partition per slice. tasks[i] = (pid, map_lo,
    map_hi) with map_hi=None meaning all maps."""

    def __init__(self, inner, tasks: list[tuple[int, int, int | None]]):
        self.inner = inner
        self.tasks = tasks

    def __call__(self, task: int):
        pid, lo, hi = self.tasks[task]
        if hi is None:
            yield from self.inner(pid)
        else:
            yield from self.inner.read_slice(pid, lo, hi)


#: join types whose semantics survive splitting a given side: every row of
#: the split side lands in exactly one slice, and the OTHER side must not
#: produce unmatched-row output (it would duplicate per slice)
_SPLITTABLE_SIDES = {
    pb.JOIN_INNER: ("left", "right"),
    pb.JOIN_LEFT: ("left",),
    pb.JOIN_LEFT_SEMI: ("left",),
    pb.JOIN_LEFT_ANTI: ("left",),
    pb.JOIN_RIGHT: ("right",),
}


class CoalescedBlockProvider:
    """AQE post-shuffle coalescing consumer: reduce task p reads every
    original partition of its group (Spark CoalesceShufflePartitions —
    grouping whole hash partitions preserves group-by/join co-partitioning).
    """

    def __init__(self, inner, groups: list[list[int]]):
        self.inner = inner
        self.groups = groups

    def __call__(self, partition: int):
        for orig in self.groups[partition]:
            yield from self.inner(orig)


class MeshQueryDriver:
    """Executes a protobuf plan containing mesh_exchange nodes on a Mesh."""

    def __init__(self, mesh, conf: Configuration | None = None,
                 work_dir: str | None = None, spmd: bool = False):
        self.mesh = mesh
        self.n_parts = mesh.shape[PARTITION_AXIS]
        self.conf = conf or Configuration()
        self.work_dir = work_dir
        self.stats: list[ExchangeStats] = []
        self._exchange_seq = 0
        self._tmp_dirs: list[str] = []
        self._reduce_parts: int | None = None  # AQE-coalesced stage width
        self._workdir_shared: bool | None = None  # SPMD probe, cached
        #: pending per-exchange AQE candidates:
        #: ex_id -> (provider, per-partition totals, per-(map,partition)
        #: byte matrix) — coalescing consumes the totals, skew splitting
        #: the matrix
        self._coalesce_candidates: dict[str, tuple] = {}
        #: SPMD multi-host mode: every process runs this SAME driver over
        #: the global mesh (parallel/multihost.py), executing only the
        #: partitions whose mesh device it owns; exchanges ride the global
        #: all_to_all (ICI within a slice, DCN across). Single-process runs
        #: ignore the flag. The reference's analog is executor-fleet tasks
        #: + netty shuffle (SURVEY §2.3); here XLA partitions the
        #: collective and the driver partitions the host-side stages.
        self.spmd = bool(spmd) and jax.process_count() > 1
        devs = list(mesh.devices.flat)
        self.local_parts = (
            [i for i, d in enumerate(devs)
             if d.process_index == jax.process_index()]
            if self.spmd else list(range(self.n_parts))
        )
        if self.spmd:
            lp = self.local_parts
            assert lp, (
                "SPMD driver: this process owns no device of the mesh — "
                "every participating process must contribute devices"
            )
            assert len(lp) * jax.process_count() == self.n_parts, (
                "SPMD driver needs an equal device count per process "
                f"(local {len(lp)} x {jax.process_count()} != {self.n_parts})"
            )
            # make_array_from_process_local_data hands this process's rows
            # to its addressable shards in GLOBAL order — require the
            # standard process-contiguous device layout so local row order
            # matches shard order
            assert lp == list(range(lp[0], lp[0] + len(lp))), (
                "SPMD driver needs process-contiguous mesh device order"
            )

    # ------------------------------------------------------------------

    def run(self, plan: pb.PhysicalPlanNode, resources: dict) -> list[list[Batch]]:
        """Resolve exchanges, then run the residual plan on every partition.

        Returns per-partition batch lists (the reduce-stage outputs)."""
        try:
            from auron_tpu.plan.optimizer import prune_columns

            # per-run state (drivers are reusable across queries)
            self.stats = []
            self._exchange_seq = 0
            self._reduce_parts = None
            self._coalesce_candidates = {}

            resolved = self._rewrite(prune_columns(plan), resources)
            n_reduce = self._maybe_coalesce_inputs(resolved, resources)
            if n_reduce == self.n_parts and not self.spmd:
                n_reduce = self._maybe_split_skew(resolved, resources)
            self._reduce_parts = n_reduce if n_reduce != self.n_parts else None
            outs: list[list[Batch]] = [
                [] for _ in range(self._reduce_parts or self.n_parts)
            ]
            parts = (
                self.local_parts if self.spmd
                else range(self._reduce_parts or self.n_parts)
            )
            for p, (_, got) in zip(parts, self._pump_stage(resolved, parts,
                                                          resources)):
                outs[p] = got
            return outs
        finally:
            self._cleanup_tmp()

    def _plan_stage(self, proto: pb.PhysicalPlanNode):
        """A driver-executed stage's exec tree, under the spans
        ``task_from_proto`` opens for a bridge task (``plan:task``,
        ``plan:fusion`` inside it). Whole-stage fusion applies here exactly
        as there (plan/fusion.py; protos untouched, bit-identical by the
        PR-7 contract): before the serving work this path ran every
        SQL-lowered mesh stage EAGER — per-batch python dispatch the fused
        programs remove, which under concurrent queries was pure GIL
        serialization."""
        from auron_tpu.plan.fusion import fuse_exec_tree

        with obs.span("task", cat="plan"):
            plan = plan_from_proto(proto)
            with obs.span("fusion", cat="plan"):
                return fuse_exec_tree(plan, self.conf)

    def _device_of(self, partition: int):
        """The chip a stage's partition runs on: the mesh's device of that
        index (a stage that a skew split widened past the mesh wraps)."""
        devs = self.mesh.devices.flat
        return devs[partition % len(devs)]

    def _pump_stage(self, proto: pb.PhysicalPlanNode, parts, resources: dict,
                    concat: bool = False) -> list[tuple]:
        """Run one stage: its partitions pumped side by side as the
        bridge's tasks of a stage are, the first on the calling thread and
        each other on a task thread of its own. Each pump plans its own
        exec tree (operators keep per-partition state) and runs under the query's conf and span (the R7 hand-off
        of runtime/task.py) with its partition's chip as JAX's default
        device: what an operator makes from the host lands beside the
        partition's batches, and nothing is committed to chip 0 on the way.
        Returns ``(schema, batches)`` per partition in ``parts``' order
        (``concat``: the batches as one, an empty batch where there were
        none). The stage is a ``pump:stage`` span on the calling thread,
        each partition a ``pump:partition`` span under it that carries its
        partition and the device its input lies on (docs/observability.md).
        """
        parts = list(parts)
        scans = [rid for kind, rid in self._collect_sources(proto)
                 if kind == "memory_scan"]
        parent_arg = {"parts": len(parts), "devices": 0}
        results: list = [None] * len(parts)
        errors: list = [None] * len(parts)
        devices: list = [None] * len(parts)

        with obs.span("stage", cat="pump", arg=parent_arg) as stage:

            def pump_partition(i: int, p: int) -> None:  # auronlint: thread-root(conf-scoped) -- stage partition pump; installs conf_scope(self.conf) before touching engine code
                try:
                    arg = {"partition": p, "device": None}
                    with conf_scope(self.conf), \
                            jax.default_device(self._device_of(p)), \
                            obs.span("partition", cat="pump", parent=stage,
                                     arg=arg):
                        op = self._plan_stage(proto)
                        ctx = ExecutionContext(partition_id=p,
                                               conf=self.conf.copy(),
                                               resources=resources)
                        got = _pump(op, p, ctx)
                        if concat:
                            got = [device_concat(got) if got
                                   else Batch.empty(op.schema)]
                        # the device of what the partition scanned; of what
                        # it made where it read no resident batch (a file
                        # exchange's blocks); else the one it ran on
                        lay = _one_device(
                            _stage_inputs(scans, resources, p) or got)
                        arg["device"] = devices[i] = (
                            self._device_of(p).id if lay is None else lay)
                        results[i] = (op.schema, got)
                except BaseException as e:  # noqa: BLE001 -- raised again on the driver's thread, below
                    errors[i] = e

            # the first partition is pumped here, on the driver's thread,
            # the others beside it on a task thread each: one path at every
            # width, and a one-wide mesh hands nothing over (a hand-over to
            # a thread and back costs two waits for the interpreter's lock,
            # 10 ms a query with four queries in flight: PERF.md section 6,
            # PR 36)
            threads = [
                threading.Thread(target=pump_partition, args=(i, p),
                                 daemon=True, name=f"auron-mesh-pump-p{p}")
                for i, p in enumerate(parts) if i
            ]
            for t in threads:
                t.start()
            pump_partition(0, parts[0])
            for t in threads:
                t.join()
            parent_arg["devices"] = len(
                {d for d in devices if d is not None and d >= 0})
        for e in errors:
            if e is not None:
                raise e
        return results

    @staticmethod
    def _collect_sources(plan: pb.PhysicalPlanNode) -> list[tuple[str, str]]:
        """All leaf source nodes of a resolved sub-plan as (kind, rid)."""
        sources: list[tuple[str, str]] = []

        def rec(node):
            which = node.WhichOneof("plan")
            inner = getattr(node, which)
            if which == "union":
                for c in inner.children:
                    rec(c)
                return
            has_child = False
            for f in ("child", "left", "right"):
                try:
                    present = inner.HasField(f)
                except ValueError:
                    continue
                if present:
                    has_child = True
                    rec(getattr(inner, f))
            if not has_child:
                rid = getattr(inner, "resource_id", "")
                sources.append((which, rid))

        rec(plan)
        return sources

    def _maybe_coalesce_inputs(self, plan: pb.PhysicalPlanNode, resources: dict) -> int:
        """AQE post-shuffle coalescing, per consuming stage (the reference
        re-plans each stage from map-output statistics the same way —
        CoalesceShufflePartitions over every shuffle feeding the stage).

        Sound iff EVERY leaf of the stage is a just-resolved file exchange:
        the same partition grouping is then applied to all of them, which
        preserves hash co-partitioning across the stage's inputs (a
        multi-shuffle join stays aligned). Returns the stage width."""
        if not self.conf.get(EXCHANGE_COALESCE_ENABLE):
            # candidates may exist for skew splitting alone
            return self.n_parts
        leaves = self._collect_sources(plan)
        ex_ids = [
            rid
            for kind, rid in leaves
            if kind == "ipc_reader" and rid in self._coalesce_candidates
        ]
        if not ex_ids or len(ex_ids) != len(leaves):
            return self.n_parts
        # a self-join may read the SAME exchange on both sides: one grouping
        # decision, sizes counted once
        ex_ids = list(dict.fromkeys(ex_ids))
        from auron_tpu.parallel.broadcast import plan_coalesced_partitions

        combined = None
        for ex in ex_ids:
            _, sizes, _ = self._coalesce_candidates[ex]
            combined = sizes if combined is None else combined + sizes
        groups = plan_coalesced_partitions(
            combined, self.conf.get(EXCHANGE_COALESCE_TARGET_BYTES)
        )
        if len(groups) >= self.n_parts:
            return self.n_parts
        by_id = {s.exchange_id: s for s in self.stats}
        for ex in ex_ids:
            provider, _, _ = self._coalesce_candidates.pop(ex)
            resources[ex] = CoalescedBlockProvider(provider, groups)
            if ex in by_id:
                by_id[ex].coalesced_groups = groups
        return len(groups)

    def _maybe_split_skew(self, plan: pb.PhysicalPlanNode, resources: dict) -> int:
        """AQE skew-join splitting over a two-exchange SMJ stage: a reduce
        partition much larger than the median splits into map-range slices
        of the SKEWED side, each joined against the full other side; the
        stage widens to one task per slice. Applies only when the split
        side's join semantics allow it (_SPLITTABLE_SIDES) and both stage
        leaves are just-resolved file exchanges."""
        from auron_tpu.utils.config import (
            EXCHANGE_SKEW_ENABLE,
            EXCHANGE_SKEW_FACTOR,
            EXCHANGE_SKEW_MIN_BYTES,
        )

        if not self.conf.get(EXCHANGE_SKEW_ENABLE):
            return self.n_parts
        smj = _find_single_smj(plan)
        if smj is None:
            return self.n_parts
        sides = {}
        for side in ("left", "right"):
            leaves = self._collect_sources(getattr(smj, side))
            if (
                len(leaves) != 1
                or leaves[0][0] != "ipc_reader"
                or leaves[0][1] not in self._coalesce_candidates
            ):
                return self.n_parts
            sides[side] = leaves[0][1]
        if sides["left"] == sides["right"]:
            return self.n_parts  # self-join on one exchange: slices collide
        # the WHOLE stage must read only these two exchanges: widening the
        # task range would mis-index any other source (broadcast dims etc.)
        all_leaves = self._collect_sources(plan)
        if {rid for _, rid in all_leaves} != set(sides.values()) or len(
            all_leaves
        ) != 2:
            return self.n_parts

        sizes = {
            s: self._coalesce_candidates[ex][1] for s, ex in sides.items()
        }
        factor = self.conf.get(EXCHANGE_SKEW_FACTOR)
        min_bytes = self.conf.get(EXCHANGE_SKEW_MIN_BYTES)
        total = sizes["left"] + sizes["right"]
        median = float(np.median(total)) if total.size else 0.0
        threshold = max(median * factor, float(min_bytes))
        allowed = _SPLITTABLE_SIDES.get(smj.join_type, ())

        tasks: dict[str, list[tuple[int, int, int | None]]] = {
            "left": [], "right": []
        }
        split_any = False
        for pid in range(self.n_parts):
            split_side = None
            if total[pid] > threshold:
                # split the larger side when its semantics allow it
                order = sorted(
                    ("left", "right"), key=lambda s: -int(sizes[s][pid])
                )
                split_side = next((s for s in order if s in allowed), None)
            if split_side is None:
                for s in ("left", "right"):
                    tasks[s].append((pid, 0, None))
                continue
            per_map = self._coalesce_candidates[sides[split_side]][2][:, pid]
            target = max(median, float(min_bytes) / 2, 1.0)
            groups = _group_maps_by_bytes(per_map, target)
            other = "left" if split_side == "right" else "right"
            for lo, hi in groups:
                tasks[split_side].append((pid, lo, hi))
                tasks[other].append((pid, 0, None))  # full re-read per slice
            split_any = split_any or len(groups) > 1

        if not split_any:
            return self.n_parts
        by_id = {s.exchange_id: s for s in self.stats}
        for side, ex in sides.items():
            provider, _, _ = self._coalesce_candidates.pop(ex)
            resources[ex] = SkewSplitProvider(provider, tasks[side])
            if ex in by_id:
                by_id[ex].skew_tasks = tasks[side]
        return len(tasks["left"])

    def _cleanup_tmp(self) -> None:
        import shutil

        for d in self._tmp_dirs:
            shutil.rmtree(d, ignore_errors=True)
        self._tmp_dirs.clear()

    def collect(self, plan: pb.PhysicalPlanNode, resources: dict):
        """run() then concatenate all partitions to one pandas frame."""
        import pandas as pd

        frames = [
            b.to_pandas() for part in self.run(plan, resources) for b in part
        ]
        if not frames:
            return None
        return pd.concat(frames).reset_index(drop=True)

    # ------------------------------------------------------------------

    def _rewrite(self, node: pb.PhysicalPlanNode, resources: dict) -> pb.PhysicalPlanNode:
        from auron_tpu.plan.protowalk import rewrite_children

        which = node.WhichOneof("plan")
        if which == "mesh_exchange":
            child = self._rewrite(node.mesh_exchange.child, resources)
            return self._execute_exchange(node.mesh_exchange, child, resources)
        return rewrite_children(node, lambda c: self._rewrite(c, resources))

    # ------------------------------------------------------------------

    def _execute_exchange(
        self, spec: pb.MeshExchangeNode, child: pb.PhysicalPlanNode, resources: dict
    ) -> pb.PhysicalPlanNode:
        if spec.broadcast:
            return self._broadcast(spec, child, resources)
        part = partitioning_from_proto(spec.partitioning)
        assert part.num_partitions == self.n_parts, (
            f"exchange over {part.num_partitions} partitions on a "
            f"{self.n_parts}-device mesh"
        )
        ex_id = spec.exchange_id or f"__mesh_exchange_{self._exchange_seq}"
        self._exchange_seq += 1

        # ---- map stage: run the child sub-plan per shard (AQE may have
        # coalesced this stage's shuffle inputs, shrinking its width, or
        # skew-split a hot SMJ partition, widening it);
        # SPMD: only this process's shards run here, peers run theirs
        n_src = self._maybe_coalesce_inputs(child, resources)
        if n_src == self.n_parts and not self.spmd:
            n_src = self._maybe_split_skew(child, resources)
        map_parts = self.local_parts if self.spmd else range(n_src)
        pumped = self._pump_stage(child, map_parts, resources, concat=True)
        schema = pumped[0][0]
        shard_batches: list[Batch] = [got[0] for _, got in pumped]
        with obs.span("write", cat="exchange") as sp:
            out = self._route(spec, part, schema, shard_batches, n_src,
                              ex_id, resources)
            if sp is not None:
                st = self.stats[-1]
                sp.arg = {"mode": st.mode, "rows": int(st.rows.sum()),
                          "bytes": int(st.rows.sum()) * _row_width_bytes(schema),
                          "devices": _n_devices(shard_batches)}
        if isinstance(out, pb.PhysicalPlanNode):
            return out          # file transport: its readers are IpcReaders
        with obs.span("read", cat="exchange") as sp:
            node = self._mesh_receive(schema, ex_id, resources, *out)
            if sp is not None:
                sp.arg = {"devices": self.stats[-1].n_devices}
            return node

    def _broadcast(self, spec: pb.MeshExchangeNode,
                   child: pb.PhysicalPlanNode,
                   resources: dict) -> pb.PhysicalPlanNode:
        """A broadcast exchange: the child stage runs once at mesh width,
        and every partition is handed every row of its output, copied to
        its own chip (a derived table on a join's build side, a few rows:
        sql/lowering.py). No routing and no collective program: N x N
        device-to-device copies of the shards that hold rows. One
        ``exchange:write`` span (``mode`` ``broadcast``, ``bytes`` what all
        the copies carry) and one ``exchange:read``."""
        if self.spmd:
            raise NotImplementedError(
                "a broadcast exchange in SPMD mode: a peer process's shards "
                "are not addressable here (it needs a host-level allgather "
                "of the rows)")
        ex_id = spec.exchange_id or f"__mesh_exchange_{self._exchange_seq}"
        self._exchange_seq += 1
        n_src = self._maybe_coalesce_inputs(child, resources)
        pumped = self._pump_stage(child, range(n_src), resources, concat=True)
        schema = pumped[0][0]
        shards: list[Batch] = [got[0] for _, got in pumped]
        with obs.span("write", cat="exchange") as sp:
            # auronlint: sync-point(4/task) -- a broadcast's live rows a shard, read once at the stage boundary; one batched transfer
            live = np.asarray(jax.device_get(
                [jnp.sum(b.device.sel, dtype=jnp.int32) for b in shards]),
                dtype=np.int64)
            counts = np.repeat(live[:, None], self.n_parts, axis=1)
            width = _row_width_bytes(schema)
            self.stats.append(ExchangeStats(
                ex_id, "broadcast", counts, int(live.sum()) * width))
            if sp is not None:
                sp.arg = {"mode": "broadcast", "rows": int(counts.sum()),
                          "bytes": int(counts.sum()) * width,
                          "devices": _n_devices(shards)}
            out_parts = {
                p: [b.on_device(self._device_of(p))
                    for b, n in zip(shards, live) if n]
                for p in range(self.n_parts)
            }
        with obs.span("read", cat="exchange") as sp:
            resources[ex_id] = out_parts
            self.stats[-1].n_devices = _n_devices(
                [b for bs in out_parts.values() for b in bs])
            if sp is not None:
                sp.arg = {"devices": self.stats[-1].n_devices}
        return pb.PhysicalPlanNode(
            memory_scan=pb.MemoryScanNode(
                schema=schema_to_proto(schema), resource_id=ex_id
            )
        )

    def _route(self, spec, part, schema: T.Schema, shard_batches: list[Batch],
               n_src: int, ex_id: str, resources: dict):
        """The exchange's write side, one ``exchange:write`` region:
        destination ids, the routing matrix, the transport decision and
        the send. File transport: the spliced reader node (the writers
        open their own ``exchange:write`` regions inside this one). Mesh:
        what ``_mesh_receive`` takes, the collective dispatched."""
        pids: list[jnp.ndarray] = [
            part.partition_ids(b, ExecutionContext(
                partition_id=p, conf=self.conf.copy(), resources=resources))
            for p, b in zip(self.local_parts if self.spmd else range(n_src),
                            shard_batches)
        ]

        # ---- statistics + transport decision
        counts = self._routing_counts(shard_batches, pids)
        spmd_cap = None
        if self.spmd:
            local_cap = max((b.capacity for b in shard_batches), default=1)
            counts, spmd_cap = self._allgather_counts(counts, local_cap)
        # the hot RECEIVING shard bounds device residency, not the mean
        max_shard_rows = int(counts.sum(axis=0).max()) if counts.size else 0
        est_shard_bytes = max_shard_rows * _row_width_bytes(schema)
        mode = self.conf.get(EXCHANGE_MODE)
        if mode == "auto":
            mode = (
                "mesh"
                if est_shard_bytes <= self.conf.get(EXCHANGE_MESH_MAX_BYTES)
                else "file"
            )
        if n_src != self.n_parts:
            # ICI all_to_all is square (P src = P dst); a coalesced map
            # stage routes through the file transport
            mode = "file"
        if self.spmd and mode == "file":
            # the file transport needs every process to see every map
            # output: probe work_dir shared-ness ONCE (token write +
            # barrier + everyone-sees-it allgather)
            if self._workdir_is_shared():
                pass  # durable cross-process transport below
            elif self.conf.get(EXCHANGE_MODE) == "file":
                raise RuntimeError(
                    "exchange.mode=file in SPMD mode requires a SHARED "
                    "auron.work_dir (capability probe failed: peers cannot "
                    "see this process's files). Point work_dir at shared "
                    "storage or use exchange.mode=mesh."
                )
            else:
                # auto routed to file (payload over exchange.mesh.max.bytes)
                # but no shared storage: stay on the collective and say so —
                # the budget exists to protect device residency
                import logging

                logging.getLogger("auron_tpu").warning(
                    "SPMD exchange %s: est %d bytes/shard exceeds "
                    "exchange.mesh.max.bytes and work_dir is not shared; "
                    "riding all_to_all anyway",
                    ex_id, est_shard_bytes,
                )
                mode = "mesh"
        self.stats.append(ExchangeStats(ex_id, mode, counts, est_shard_bytes))

        if mode == "file":
            return self._file_exchange(spec, schema, shard_batches, ex_id, resources)
        return self._mesh_send(schema, shard_batches, pids, counts,
                               spmd_cap=spmd_cap)

    def _routing_counts(self, batches: list[Batch], pids: list[jnp.ndarray]) -> np.ndarray:
        """Exact [P_src, P_dst] live-row routing matrix (one host sync):
        the histogram runs on the device and only n_parts ints per shard
        cross to the host."""
        if not batches:
            return np.zeros((0, self.n_parts), dtype=np.int64)
        # auronlint: sync-point(4/task) -- exchange routing histogram read at the stage boundary; one batched transfer
        got = jax.device_get([
            _live_pid_counts(b.device.sel, pid, n_parts=self.n_parts)
            for b, pid in zip(batches, pids)
        ])
        return np.stack(got).astype(np.int64)

    def _allgather_counts(
        self, local: np.ndarray, local_cap: int
    ) -> tuple[np.ndarray, int]:
        """SPMD: merge each process's [n_local, P] routing counts into the
        global [P, P] matrix every process needs for slot sizing, and agree
        on the global stacking capacity — ONE host-level allgather per
        exchange (cap rides as an extra column)."""
        from jax.experimental import multihost_utils

        full = np.zeros((self.n_parts, self.n_parts), dtype=np.int64)
        payload = np.concatenate(
            [
                np.asarray(self.local_parts, dtype=np.int64)[:, None],
                local,
                np.full((len(self.local_parts), 1), local_cap, dtype=np.int64),
            ],
            axis=1,
        )
        gathered = multihost_utils.process_allgather(payload)
        rows = gathered.reshape(-1, payload.shape[1])
        for proc_rows in rows:
            full[int(proc_rows[0])] = proc_rows[1:-1]
        return full, int(rows[:, -1].max())

    def _unify_dicts_global(
        self, schema: T.Schema, batches: list[Batch], dict_cols: list[int]
    ) -> dict:
        """SPMD cross-process dictionary unification (closes the planner
        gap where any string group-by key failed in SPMD mode).

        Every process first unifies its LOCAL shards per column, then all
        processes exchange their local vocabularies over TWO host-level
        allgathers (payload lengths, then padded pickled payloads — the
        same multihost channel the counts barrier uses) and build the SAME
        global vocabulary in process-rank order. Codes then remap to
        global ids with one device gather per shard. Two barriers per
        exchange regardless of column count."""
        import pickle

        import pyarrow as pa
        from jax.experimental import multihost_utils

        local_vocab: dict[int, list] = {}
        local_remaps: dict[int, list[np.ndarray]] = {}
        for ci in dict_cols:
            unified, remaps = unify_dict(batches, ci)
            local_vocab[ci] = unified.to_pylist()
            local_remaps[ci] = remaps
        blob = pickle.dumps(local_vocab, protocol=4)
        lengths = multihost_utils.process_allgather(
            np.array([len(blob)], dtype=np.int64)
        ).reshape(-1)
        buf = np.zeros(int(lengths.max()), dtype=np.uint8)
        buf[: len(blob)] = np.frombuffer(blob, dtype=np.uint8)
        gathered = multihost_utils.process_allgather(buf)
        gathered = np.asarray(gathered).reshape(len(lengths), -1)
        per_proc = [
            pickle.loads(bytes(gathered[p, : int(lengths[p])].tobytes()))
            for p in range(len(lengths))
        ]
        from auron_tpu.columnar.batch import merge_vocab

        out: dict[int, tuple] = {}
        my_rank = jax.process_index()
        for ci in dict_cols:
            # the SAME merge as in-process unification, fed per-process
            # entry lists in rank order -> identical vocab on every process
            unified, proc_remaps = merge_vocab(
                [pv.get(ci, []) for pv in per_proc], schema[ci].dtype
            )
            my_global = proc_remaps[my_rank]
            # compose: local batch codes -> local unified -> global
            local_to_global = [
                jnp.asarray(
                    my_global[np.clip(r, 0, max(len(my_global) - 1, 0))]
                    .astype(np.int32)
                )
                for r in local_remaps[ci]
            ]
            out[ci] = (unified, local_to_global)
        return out

    # ---- ICI transport ------------------------------------------------

    def _mesh_send(
        self,
        schema: T.Schema,
        batches: list[Batch],
        pids: list[jnp.ndarray],
        counts: np.ndarray,
        spmd_cap: int | None = None,
    ) -> tuple:
        ncols = len(schema)
        # unify dictionaries so codes are meaningful across shards
        dicts: list = [None] * ncols
        remapped: dict[int, list[jnp.ndarray]] = {}
        dict_cols = [ci for ci, f in enumerate(schema) if f.dtype.is_dict_encoded]
        if dict_cols and self.spmd:
            global_dicts = self._unify_dicts_global(schema, batches, dict_cols)
            for ci, (unified, local_to_global) in global_dicts.items():
                dicts[ci] = unified
                remapped[ci] = [
                    local_to_global[bi][
                        jnp.clip(b.col_values(ci), 0, local_to_global[bi].shape[0] - 1)
                    ]
                    for bi, b in enumerate(batches)
                ]
        else:
            for ci in dict_cols:
                unified, remaps = unify_dict(batches, ci)
                dicts[ci] = unified
                remapped[ci] = [
                    jnp.asarray(r)[jnp.clip(b.col_values(ci), 0, len(r) - 1)]
                    for b, r in zip(batches, remaps)
                ]

        # SPMD: capacity agreed in the counts allgather (one barrier)
        cap = spmd_cap if spmd_cap is not None else max(b.capacity for b in batches)

        def padded(a, fill=False):
            pad = cap - a.shape[0]
            return jnp.pad(a, (0, pad)) if pad else a

        # the [P, cap] operands, sharded over p, assembled from the shards'
        # planes WHERE THEY LIE: shard p's row is a [1, cap] array on mesh
        # device p (a plane that is there already does not move), and the
        # global array is those rows. Nothing is stacked on one chip and
        # scattered again; in SPMD mode each process brings its own rows
        devs = [self._device_of(p) for p in self.local_parts]
        sharding = jax.sharding.NamedSharding(self.mesh, shard_spec())

        def place(planes: list) -> jax.Array:
            rows = [jax.device_put(padded(a)[None], d)
                    for a, d in zip(planes, devs)]
            return jax.make_array_from_single_device_arrays(
                (self.n_parts,) + rows[0].shape[1:], sharding, rows)

        sel = place([b.device.sel for b in batches])
        pid = place([p.astype(jnp.int32) for p in pids])
        values = tuple(
            place([remapped[ci][i] if ci in remapped else b.col_values(ci)
                   for i, b in enumerate(batches)])
            for ci in range(ncols)
        )
        validity = tuple(
            place([b.col_validity(ci) for b in batches])
            for ci in range(ncols)
        )

        # slot capacity from the exact routing matrix -> overflow impossible
        slot_cap = bucket_capacity(max(int(counts.max()), 1))
        step = pid_exchange_step(self.mesh, slot_cap)
        (rvals, rmasks), rsel, overflow = step((values, validity), sel, pid)
        return tuple(dicts), rvals, rmasks, rsel, overflow

    def _mesh_receive(self, schema: T.Schema, ex_id: str, resources: dict,
                      dicts: tuple, rvals, rmasks, rsel,
                      overflow) -> pb.PhysicalPlanNode:
        """The exchange's read side, one ``exchange:read`` region: the wait
        for the collective (the overflow check reads one scalar of its
        result) and each partition's received rows taken out of the
        exchanged arrays."""
        assert int(jax.device_get(overflow)) == 0, "sized from exact counts"  # auronlint: sync-point(4/task) -- one-scalar overflow invariant check per exchange

        # expose the addressable partitions (all of them single-process;
        # only this process's shards in SPMD) as a partition-keyed mapping
        # — ResourceScanExec indexes dicts and lists identically. Partition
        # p is its shard of the exchanged arrays, on mesh device p: every
        # later stage runs on the chip that received its rows
        out_parts: dict[int, list[Batch]] = {}
        for p in self.local_parts:
            dev = DeviceBatch(
                _local_shard(rsel, p),
                tuple(_local_shard(v, p) for v in rvals),
                tuple(_local_shard(m, p) for m in rmasks),
            )
            out_parts[p] = [Batch(schema, dev, dicts)]
        self.stats[-1].n_devices = _n_devices(
            [b for bs in out_parts.values() for b in bs])
        resources[ex_id] = out_parts
        return pb.PhysicalPlanNode(
            memory_scan=pb.MemoryScanNode(
                schema=schema_to_proto(schema), resource_id=ex_id
            )
        )

    # ---- durable file transport ---------------------------------------

    def _workdir_is_shared(self) -> bool:
        """SPMD capability probe (once per driver): process 0 writes a
        token under work_dir, a cross-process barrier lands, every process
        checks visibility, and an allgather ANDs the answers — file
        transport is offered only when ALL processes see the token."""
        if self._workdir_shared is not None:
            return self._workdir_shared
        from jax.experimental import multihost_utils

        # EVERY process must walk the same collective sequence even when
        # its own work_dir is unset — an early local return would leave
        # peers blocked in the barrier (silent distributed wedge)
        token = (
            os.path.join(self.work_dir, ".auron_shared_probe")
            if self.work_dir
            else None
        )
        if token and jax.process_index() == 0:
            os.makedirs(self.work_dir, exist_ok=True)
            with open(token, "w") as f:
                f.write("probe")
        multihost_utils.sync_global_devices("auron_workdir_probe")
        saw = np.array(
            [1 if token and os.path.exists(token) else 0], dtype=np.int64
        )
        all_saw = multihost_utils.process_allgather(saw)
        self._workdir_shared = bool(np.asarray(all_saw).min() == 1)
        return self._workdir_shared

    def _file_exchange(
        self,
        spec: pb.MeshExchangeNode,
        schema: T.Schema,
        batches: list[Batch],
        ex_id: str,
        resources: dict,
    ) -> pb.PhysicalPlanNode:
        from auron_tpu.exec.shuffle.reader import MultiMapBlockProvider
        from auron_tpu.exec.shuffle.writer import ShuffleWriterExec
        from auron_tpu.plan.planner import ResourceScanExec

        if self.work_dir:
            work = self.work_dir
            os.makedirs(work, exist_ok=True)
        else:
            work = tempfile.mkdtemp(prefix="auron_exchange_")
            self._tmp_dirs.append(work)  # removed after the residual run
        part = partitioning_from_proto(spec.partitioning)
        src_id = ex_id + "__src"
        resources[src_id] = [[b] for b in batches]
        # SPMD: this process writes only its LOCAL shards' map outputs
        # (named by GLOBAL shard id onto the probed-shared work_dir), then
        # a barrier makes every peer's files visible before any read
        map_ids = list(self.local_parts) if self.spmd else list(range(len(batches)))
        try:
            for local_i, p in enumerate(map_ids):
                data_f = os.path.join(work, f"{ex_id}_map{p}.data")
                index_f = os.path.join(work, f"{ex_id}_map{p}.index")
                w = ShuffleWriterExec(
                    ResourceScanExec(schema, src_id), part, data_f, index_f
                )
                ctx = ExecutionContext(partition_id=local_i,
                                       conf=self.conf.copy(),
                                       resources=resources)
                for _ in w.execute(local_i, ctx):
                    pass
        finally:
            resources.pop(src_id, None)
        if self.spmd:
            from jax.experimental import multihost_utils

            multihost_utils.sync_global_devices(f"auron_file_exchange_{ex_id}")
            all_map_ids = range(self.n_parts)
        else:
            all_map_ids = range(len(batches))
        pairs = [
            (os.path.join(work, f"{ex_id}_map{p}.data"),
             os.path.join(work, f"{ex_id}_map{p}.index"))
            for p in all_map_ids
        ]
        provider = MultiMapBlockProvider(pairs)
        # ---- AQE: statistics-driven candidate for post-shuffle coalescing
        # AND skew-join splitting (both consume the same per-partition
        # sizes). The grouping decision is made PER CONSUMING STAGE
        # (_maybe_coalesce_inputs): every shuffle feeding a stage gets the
        # same groups, so hash co-partitioning across inputs is preserved.
        from auron_tpu.utils.config import EXCHANGE_SKEW_ENABLE

        # SPMD: coalescing/skew-splitting would resize the reduce stage,
        # but every process owns a FIXED set of global partition ids —
        # regrouping needs a globally coordinated decision (not wired);
        # partition ownership stays 1:1 with mesh devices
        if not self.spmd and (
            self.conf.get(EXCHANGE_COALESCE_ENABLE)
            or self.conf.get(EXCHANGE_SKEW_ENABLE)
        ):
            from auron_tpu.exec.shuffle.format import read_index

            # per-(map, partition) byte matrix: coalescing consumes the
            # per-partition totals, skew splitting the per-map breakdown
            per_map = np.stack([
                np.diff(np.asarray(read_index(i), dtype=np.int64))
                for _, i in pairs
            ]) if pairs else np.zeros((0, self.n_parts), np.int64)
            self._coalesce_candidates[ex_id] = (
                provider, per_map.sum(axis=0), per_map
            )
        resources[ex_id] = provider
        return pb.PhysicalPlanNode(
            ipc_reader=pb.IpcReaderNode(
                schema=schema_to_proto(schema), resource_id=ex_id
            )
        )


def _pump(op, partition: int, ctx: ExecutionContext) -> list[Batch]:
    """Drain one partition of a stage's operator tree: one ``pump:batch``
    region per PULL, as runtime/task.py's pump has it (the last pull,
    which ends the stream, included), so the readers of the task pump's
    regions read driver-executed stages too. Never open across a yield:
    the loop drives the iterator with next() inside the region."""
    from auron_tpu.utils.profiling import EngineCounters

    counters = EngineCounters._installed
    out: list[Batch] = []
    batches = iter(op.execute(partition, ctx))
    while True:
        with obs.span("batch", cat="pump"):
            b = next(batches, None)
        if b is None:
            return out
        if counters is not None:
            counters.note_batch()
        obs.note_pump_batch()
        out.append(b)


def _partition_scoped(which: str, inner) -> bool:
    """Nodes whose output depends on seeing a WHOLE partition: splitting a
    partition into slices changes their result (regrouping aggs, windows,
    per-partition limits/top-k)."""
    if which == "hash_agg" and inner.mode != pb.AGG_PARTIAL:
        return True
    if which in ("window", "window_group_limit", "limit"):
        return True
    if which == "sort" and inner.has_fetch:
        return True  # per-partition top-k
    return False


#: nodes allowed BETWEEN the SMJ and its exchange leaf on a split side —
#: strictly per-row (or whole-input sorts feeding the merge join)
_SLICE_SAFE_BELOW = {"sort", "project", "filter", "ipc_reader", "rename_columns"}


def _find_single_smj(plan: pb.PhysicalPlanNode):
    """The stage's sort_merge_join node, when the stage is skew-splittable:
    exactly one SMJ; no partition-scoped node above it (its result would
    change when a partition runs as several slices); the SMJ's subtrees
    contain only slice-safe nodes down to their leaves."""
    found: list = []
    blocked: list = []

    def rec(node, above_scoped: bool):
        which = node.WhichOneof("plan")
        inner = getattr(node, which)
        if which == "sort_merge_join":
            found.append(inner)
            if above_scoped:
                blocked.append("partition-scoped ancestor")
            for side in ("left", "right"):
                if not _slice_safe(getattr(inner, side)):
                    blocked.append(f"{side} subtree not slice-safe")
            return  # subtrees validated by _slice_safe
        if _partition_scoped(which, inner):
            above_scoped = True
        if which == "union":
            for c in inner.children:
                rec(c, above_scoped)
            return
        for f in ("child", "left", "right"):
            try:
                present = inner.HasField(f)
            except ValueError:
                continue
            if present:
                rec(getattr(inner, f), above_scoped)

    def _slice_safe(node) -> bool:
        which = node.WhichOneof("plan")
        inner = getattr(node, which)
        if which not in _SLICE_SAFE_BELOW:
            return False
        if which == "sort" and inner.has_fetch:
            return False
        if which == "ipc_reader":
            return True
        return _slice_safe(inner.child)

    rec(plan, False)
    if len(found) != 1 or blocked:
        return None
    return found[0]


def _group_maps_by_bytes(per_map: list[int], target: float) -> list[tuple[int, int]]:
    """Contiguous map ranges each totalling ~target bytes (>=1 map per
    range; ranges cover [0, n_maps)). A small tail folds into the last
    range — every extra slice re-reads the other side."""
    groups: list[tuple[int, int]] = []
    lo = 0
    acc = 0.0
    for m, b in enumerate(per_map):
        acc += b
        if acc >= target:
            groups.append((lo, m + 1))
            lo = m + 1
            acc = 0.0
    if lo < len(per_map):
        if groups and acc < target / 2:
            groups[-1] = (groups[-1][0], len(per_map))
        else:
            groups.append((lo, len(per_map)))
    if not groups:
        groups.append((0, len(per_map)))
    return groups


def _local_shard(arr: jax.Array, p: int):
    """Shard p of a leading-axis-sharded global array (must be local)."""
    for s in arr.addressable_shards:
        idx = s.index[0]
        if (idx.start or 0) == p:
            return s.data[0]
    raise KeyError(f"partition {p} not addressable on this process")


def _devices_of(batches) -> set:
    """The devices the batches' planes lie on (their selection planes:
    a batch's planes travel together)."""
    return {d for b in batches for d in b.device.sel.devices()}


def _n_devices(batches) -> int:
    return len(_devices_of(batches))


def _one_device(batches) -> int | None:
    """The id of the ONE device the batches lie on; -1 where they lie on
    several (a replicated or sharded input: R-a3's fault), None of none."""
    devs = _devices_of(batches)
    if not devs:
        return None
    return next(iter(devs)).id if len(devs) == 1 else -1


def _stage_inputs(scans: list[str], resources: dict,
                  partition: int) -> list[Batch]:
    """The resident batches a stage's partition scans: its memory-scan
    leaves' (``scans``, their resource ids) entries for that partition (a
    file exchange's blocks, read from disk, are no device input)."""
    out: list[Batch] = []
    for rid in scans:
        source = resources.get(rid)
        if isinstance(source, dict):
            source = source.get(partition)
        elif isinstance(source, list) and partition < len(source):
            source = source[partition]
        else:
            continue
        out.extend(b for b in source or () if isinstance(b, Batch))
    return out


@partial(jax.jit, static_argnames=("n_parts",))
def _live_pid_counts(sel: jnp.ndarray, pid: jnp.ndarray, n_parts: int) -> jnp.ndarray:
    """int32[n_parts] live rows per destination: a compare-and-reduce per
    destination (XLA fuses it into one pass; no scatter). Ids outside
    [0, n_parts) and dead rows fall out of every bucket."""
    dst = jnp.arange(n_parts, dtype=jnp.int32)[:, None]
    hit = (pid.astype(jnp.int32)[None, :] == dst) & sel[None, :]
    return jnp.sum(hit, axis=1, dtype=jnp.int32)


def _row_width_bytes(schema: T.Schema) -> int:
    """Rough per-row device byte width (values + validity) for stats."""
    width = 1  # sel
    for f in schema:
        width += np.dtype(f.dtype.physical_dtype().name).itemsize + 1
    return width
