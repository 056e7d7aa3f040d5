"""Host-schedulable stage splitting + shuffle-manager contract.

The engine's own ``MeshQueryDriver`` resolves ``mesh_exchange`` nodes
internally (ICI all_to_all or file shuffle) — but a host engine like Spark
schedules stages ITSELF: the reference integrates by making stage N's plan
end in a native shuffle writer whose map output is committed to the host's
shuffle tracker, and stage N+1 start with a reader fed by the host's
shuffle fetch (AuronShuffleManager.scala:14-37,
NativeShuffleExchangeBase.scala:124-296, Shims.scala:249 MapStatus commit).

``split_stages`` performs the same decomposition on a converted plan:

    stage k   = subtree below a mesh_exchange, wrapped in shuffle_writer
                (one task per map partition; .data/.index file paths are
                filled per task by the host via ``stage_task``)
    stage k+1 = the consumer, with the exchange spliced into an ipc_reader
                whose resource id is the exchange id

``ShuffleManager`` is the host-side contract: map tasks register their
(map_partition -> data/index) outputs per exchange (the MapStatus commit
analog); reduce tasks fetch a block provider that serves exactly those
files. A JSON *manifest* form of the registration crosses the C ABI for
out-of-process hosts (see ``manifest``/``provider_from_manifest`` and
bridge/api.put_resource_shuffle).
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass, field

from auron_tpu.plan.protowalk import child_nodes, rewrite_children
from auron_tpu.proto import plan_pb2 as pb

DATA_TEMPLATE = "{work_dir}/{exchange_id}_map{partition}.data"
INDEX_TEMPLATE = "{work_dir}/{exchange_id}_map{partition}.index"


@dataclass
class StageSpec:
    """One host-schedulable stage of a split plan."""

    stage_id: int
    plan: pb.PhysicalPlanNode  # shuffle_writer root for producer stages
    exchange_id: str | None  # exchange this stage PRODUCES (None = final)
    num_output_partitions: int | None  # reduce width of the produced exchange
    input_exchange_ids: list[str] = field(default_factory=list)

    @property
    def is_final(self) -> bool:
        return self.exchange_id is None

    @property
    def data_template(self) -> str | None:
        """Shuffle data-file path template with {work_dir}/{partition}
        placeholders — the host computes task file paths by plain string
        substitution, never touching the plan proto (TaskDefs contract)."""
        if self.exchange_id is None:
            return None
        return DATA_TEMPLATE.replace("{exchange_id}", self.exchange_id)

    @property
    def index_template(self) -> str | None:
        if self.exchange_id is None:
            return None
        return INDEX_TEMPLATE.replace("{exchange_id}", self.exchange_id)


def ffi_reader_ids(plan: pb.PhysicalPlanNode) -> list[str]:
    """Resource ids of every ffi_reader in a plan subtree (dedup, in
    tree order) — tells a host which segment inputs feed which stage."""
    out: list[str] = []

    def rec(node: pb.PhysicalPlanNode) -> None:
        if node.WhichOneof("plan") == "ffi_reader":
            rid = node.ffi_reader.resource_id
            if rid not in out:
                out.append(rid)
        for c in child_nodes(node):
            rec(c)

    rec(plan)
    return out


def split_stages(
    plan: pb.PhysicalPlanNode, namespace: str = ""
) -> list[StageSpec]:
    """Decompose a plan with mesh_exchange nodes into host-schedulable
    stages, producers before consumers (post-order). ``namespace``
    prefixes every exchange id (writer paths AND reader resource ids) so
    concurrent conversions in one engine process can't collide on
    executor-side resource keys."""
    stages: list[StageSpec] = []
    counter = [0]

    def rewrite(node: pb.PhysicalPlanNode, inputs: list[str]) -> pb.PhysicalPlanNode:
        which = node.WhichOneof("plan")
        if which == "mesh_exchange":
            ex = node.mesh_exchange
            if ex.broadcast:
                raise NotImplementedError(
                    "a broadcast mesh_exchange has no shuffle-file form: a "
                    "host engine schedules it as its own BroadcastExchange")
            child_inputs: list[str] = []
            child = rewrite(ex.child, child_inputs)
            ex_id = namespace + (
                ex.exchange_id or f"__stage_exchange_{counter[0]}"
            )
            counter[0] += 1
            writer = pb.PhysicalPlanNode(
                shuffle_writer=pb.ShuffleWriterNode(
                    child=child,
                    partitioning=ex.partitioning,
                    output_data_file=DATA_TEMPLATE.replace(
                        "{exchange_id}", ex_id
                    ),
                    output_index_file=INDEX_TEMPLATE.replace(
                        "{exchange_id}", ex_id
                    ),
                )
            )
            stages.append(
                StageSpec(
                    stage_id=len(stages),
                    plan=writer,
                    exchange_id=ex_id,
                    num_output_partitions=int(ex.partitioning.num_partitions),
                    input_exchange_ids=child_inputs,
                )
            )
            inputs.append(ex_id)
            schema = _plan_schema(child)
            return pb.PhysicalPlanNode(
                ipc_reader=pb.IpcReaderNode(schema=schema, resource_id=ex_id)
            )
        return rewrite_children(node, lambda c: rewrite(c, inputs))

    final_inputs: list[str] = []
    final = rewrite(plan, final_inputs)
    stages.append(
        StageSpec(
            stage_id=len(stages),
            plan=final,
            exchange_id=None,
            num_output_partitions=None,
            input_exchange_ids=final_inputs,
        )
    )
    return stages


def _plan_schema(node: pb.PhysicalPlanNode) -> pb.Schema:
    """Output schema of a plan subtree (instantiates operators, no exec)."""
    from auron_tpu.plan.planner import plan_from_proto, schema_to_proto

    return schema_to_proto(plan_from_proto(node).schema)


def stage_task(
    spec: StageSpec,
    partition: int,
    work_dir: str,
    conf: dict | None = None,
) -> pb.TaskDefinition:
    """Instantiate one task of a stage: clone the stage plan, fill this
    task's shuffle output file paths (the host owns file placement, like
    Spark's shuffle block resolver), stamp stage/partition ids."""
    plan = pb.PhysicalPlanNode()
    plan.CopyFrom(spec.plan)
    _fill_paths(plan, partition, work_dir)
    t = pb.TaskDefinition(
        plan=plan, stage_id=spec.stage_id, partition_id=partition
    )
    for k, v in (conf or {}).items():
        t.conf[k] = str(v)
    return t


def _fill_paths(node: pb.PhysicalPlanNode, partition: int, work_dir: str) -> None:
    which = node.WhichOneof("plan")
    if which == "shuffle_writer":
        inner = node.shuffle_writer
        inner.output_data_file = inner.output_data_file.format(
            work_dir=work_dir, partition=partition
        )
        inner.output_index_file = inner.output_index_file.format(
            work_dir=work_dir, partition=partition
        )
    for c in child_nodes(node):
        _fill_paths(c, partition, work_dir)


# ---------------------------------------------------------------------------
# shuffle-manager contract (AuronShuffleManager / MapStatus analog)
# ---------------------------------------------------------------------------


class ShuffleManager:
    """Tracks committed map outputs per exchange and serves block providers
    to reduce tasks. In-process hosts use the object directly; out-of-process
    hosts ship the JSON manifest over the C ABI."""

    def __init__(self):
        self._outputs: dict[str, dict[int, tuple[str, str]]] = {}

    def register_map_output(
        self, exchange_id: str, map_partition: int, data_file: str, index_file: str
    ) -> None:
        """MapStatus commit: a map task's shuffle files become visible."""
        self._outputs.setdefault(exchange_id, {})[map_partition] = (
            data_file, index_file,
        )

    def map_outputs(self, exchange_id: str) -> list[tuple[str, str]]:
        by_part = self._outputs.get(exchange_id, {})
        return [by_part[p] for p in sorted(by_part)]

    def block_provider(self, exchange_id: str):
        from auron_tpu.exec.shuffle.reader import MultiMapBlockProvider

        return MultiMapBlockProvider(self.map_outputs(exchange_id))

    def manifest(self, exchange_id: str) -> bytes:
        """JSON manifest of an exchange's map outputs — the cross-process
        form of ``block_provider`` (shipped through put_resource_shuffle)."""
        return json.dumps(
            [
                {"data": d, "index": i}
                for d, i in self.map_outputs(exchange_id)
            ]
        ).encode()


def provider_from_manifest(payload: bytes | str):
    """Rebuild a reduce-side block provider from a JSON manifest."""
    from auron_tpu.exec.shuffle.reader import MultiMapBlockProvider

    entries = json.loads(payload)
    pairs = [(e["data"], e["index"]) for e in entries]
    for d, i in pairs:
        if not (os.path.exists(d) and os.path.exists(i)):
            raise FileNotFoundError(f"missing shuffle files {d} / {i}")
    return MultiMapBlockProvider(pairs)
