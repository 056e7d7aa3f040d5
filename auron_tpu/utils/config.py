"""Typed, self-documenting configuration system.

Analog of the reference's three-tier config stack:
- typed ``ConfigOption`` builder with categories / defaults / alt keys
  (reference: auron-core/.../configuration/ConfigOption.java,
  AuronConfiguration.java:26-65),
- engine bindings such as SparkAuronConfiguration's 72 ``spark.auron.*``
  keys (reference: spark-extension/.../SparkAuronConfiguration.java:42+),
- engine-pulled native conf accessors (reference:
  auron-jni-bridge/src/conf.rs:20-64).

Here a single ``Configuration`` object backs all three roles: options are
declared once with type+default, values are resolved from (1) an explicit
session dict (set by the host-engine bridge when a task ships its
TaskDefinition), (2) process environment ``AURON_TPU_<NAME>``, (3) the
default. A doc table can be generated from the registry (analog of
SparkAuronConfigurationDocGenerator.java).
"""

from __future__ import annotations

import os
import threading
from dataclasses import dataclass
from typing import Any, Callable, Generic, TypeVar

T = TypeVar("T")

_REGISTRY: dict[str, "ConfigOption"] = {}


@dataclass(frozen=True)
class ConfigOption(Generic[T]):
    key: str
    default: T
    parse: Callable[[str], T]
    category: str = "general"
    doc: str = ""

    def __post_init__(self):
        _REGISTRY[self.key] = self

    def get(self, conf: "Configuration | None" = None) -> T:
        c = conf if conf is not None else active_conf()
        return c.get(self)


def _parse_bool(s: str) -> bool:
    return s.strip().lower() in ("1", "true", "yes", "on")


def env_key_for(key: str) -> str:
    """THE conf-key -> env-var derivation (``a.b.c`` ->
    ``AURON_TPU_A_B_C``) — one definition so get()/has() (and any future
    alt-key scheme) cannot silently disagree on which variable they
    read."""
    return "AURON_TPU_" + key.upper().replace(".", "_")


def int_conf(key: str, default: int, category: str = "general", doc: str = "") -> ConfigOption[int]:
    return ConfigOption(key, default, int, category, doc)


def float_conf(key: str, default: float, category: str = "general", doc: str = "") -> ConfigOption[float]:
    return ConfigOption(key, default, float, category, doc)


def bool_conf(key: str, default: bool, category: str = "general", doc: str = "") -> ConfigOption[bool]:
    return ConfigOption(key, default, _parse_bool, category, doc)


def str_conf(key: str, default: str, category: str = "general", doc: str = "") -> ConfigOption[str]:
    return ConfigOption(key, default, str, category, doc)


class Configuration:
    """Resolved key->value store with session overrides."""

    def __init__(self, values: dict[str, Any] | None = None):
        self._values: dict[str, Any] = dict(values or {})

    def set(self, opt: ConfigOption[T] | str, value: Any) -> "Configuration":
        key = opt if isinstance(opt, str) else opt.key
        self._values[key] = value
        return self

    def get(self, opt: ConfigOption[T]) -> T:
        if opt.key in self._values:
            v = self._values[opt.key]
            return opt.parse(v) if isinstance(v, str) else v
        env_key = env_key_for(opt.key)
        if env_key in os.environ:
            return opt.parse(os.environ[env_key])
        return opt.default

    def has(self, opt: ConfigOption[T] | str,
            include_env: bool = True) -> bool:
        """True when the option is EXPLICITLY set in this configuration
        (session value — or process env unless ``include_env=False``),
        i.e. get() would not return the declared default. Lets appliers
        act only on deliberate settings. ``include_env=False`` is for
        per-task appliers of process-wide state (obs.apply_conf): an env
        value already took effect at import, and re-asserting it on
        every task would clobber later programmatic changes."""
        key = opt if isinstance(opt, str) else opt.key
        if key in self._values:
            return True
        return include_env and env_key_for(key) in os.environ

    def copy(self) -> "Configuration":
        return Configuration(self._values)

    def as_dict(self) -> dict[str, Any]:
        return dict(self._values)


_local = threading.local()
_GLOBAL = Configuration()


def active_conf() -> Configuration:
    return getattr(_local, "conf", None) or _GLOBAL


def resolve_tri(mode: str, auto: bool) -> bool:
    """THE resolution rule for on|off|auto backend-policy knobs
    (exec.agg.incremental.*, exec.agg.dense.host.scatter, the host-sort
    fork): explicit on/off win, auto defers to the caller's backend
    predicate. One definition so a grammar change (or a new mode) cannot
    silently diverge between the forks."""
    if mode == "on":
        return True
    if mode == "off":
        return False
    return auto


class conf_scope:
    """Context manager installing a Configuration for the current thread.

    The task runtime wraps each task's execution in the configuration
    shipped with its TaskDefinition (analog of the reference pulling conf
    lazily over JNI per key, conf.rs:32-64).
    """

    def __init__(self, conf: Configuration):
        self.conf = conf

    def __enter__(self):
        self._prev = getattr(_local, "conf", None)
        _local.conf = self.conf
        return self.conf

    def __exit__(self, *exc):
        _local.conf = self._prev
        return False


def generate_doc() -> str:
    """Markdown doc table of all registered options (analog of
    SparkAuronConfigurationDocGenerator.java)."""
    rows = ["| key | default | category | doc |", "|---|---|---|---|"]
    for key in sorted(_REGISTRY):
        o = _REGISTRY[key]
        rows.append(f"| `{o.key}` | `{o.default!r}` | {o.category} | {o.doc} |")
    return "\n".join(rows)


# ---------------------------------------------------------------------------
# Core engine options (subset mirroring auron-jni-bridge/src/conf.rs:20-64 and
# SparkAuronConfiguration; grows as features land).
# ---------------------------------------------------------------------------

BATCH_SIZE = int_conf(
    "batch.size", 131072, "exec",
    "target rows per columnar device batch. Much larger than the "
    "reference's 8192 (conf.rs BATCH_SIZE) on purpose: one fused XLA "
    "program per batch amortizes dispatch over rows, and accelerator "
    "lanes want long arrays — per-batch host overhead is the engine's "
    "per-row cost floor",
)
MEMORY_FRACTION = float_conf(
    "memory.fraction", 0.6, "memory", "fraction of HBM budget usable by consumers"
)
HBM_BUDGET_BYTES = int_conf(
    "memory.hbm.budget.bytes", 0, "memory",
    "total HBM bytes the memory manager may hand out (analog of native "
    "memory = overhead * fraction, which the reference derives from the "
    "executor's provisioned memory). 0 = auto: 8GB on accelerators "
    "(HBM-sized), half of physical RAM on the CPU backend (device arrays "
    "ARE host memory there)",
)
SPILL_COMPRESSION_CODEC = str_conf(
    "spill.compression.codec", "lz4", "memory",
    "codec for spill files and shuffle runs (zstd|lz4|none). lz4 by "
    "default: local-disk shuffle/spill is codec-throughput-bound, not "
    "size-bound (the reference likewise defaults lz4 for IPC compression "
    "and reserves zstd for when bytes cross a network)",
)
HOST_SPILL_BUDGET_BYTES = int_conf(
    "memory.host.spill.budget.bytes", 2 << 30, "memory",
    "host-RAM bytes the spill ledger may keep resident before demoting the "
    "coldest HostSpills to disk (the host tier of HBM -> RAM -> disk)",
)
MEM_WAIT_TIMEOUT_S = float_conf(
    "memory.wait.timeout.seconds", 10.0, "memory",
    "how long a below-fair-share consumer waits for siblings to release "
    "memory before it is forced to spill (auron-memmgr lib.rs WAIT_TIME)",
)
TRANSFER_WINDOW_DEPTH = int_conf(
    "runtime.transfer.window.depth", 4, "runtime",
    "depth k of the async device->host transfer window: residual scalar "
    "reads (compaction live counts, dense-agg fold flags) are harvested k "
    "batches after their transfer starts, overlapping device compute "
    "(runtime/transfer.py). 1 = classic one-deep pipeline",
)
HOST_SORT_MODE = str_conf(
    "exec.host.sort", "auto", "exec",
    "compute order permutations host-side via a callback lexsort instead of "
    "lax.sort (XLA:CPU lowers lax.sort to a comparator sort ~100x slower "
    "than a radix/lexicographic sort): auto = on for the CPU backend, off "
    "on accelerators where data is HBM-resident",
)
DEVICE_SORT_IMPL = str_conf(
    "exec.device.sort.impl", "auto", "exec",
    "cluster-sort implementation when sorting on-device (host sort off): "
    "lax = multi-operand lax.sort; jnp = jitted bitonic merge network; "
    "pallas = VMEM-resident bitonic Pallas kernel; auto = lax on every "
    "backend until a chip run has compared them (ops/bitonic.py)",
)
# auronlint: disable=R14 -- upstream-parity surface (conf.rs:53): SMJ fallback is not implemented in this engine yet; the key must exist so ported configs round-trip
SMJ_FALLBACK_ENABLE = bool_conf(
    "smj.fallback.enable", True, "join",
    "fall back from hash join to sort-merge when the build side exceeds budget (SMJ_FALLBACK_* in conf.rs:53-55)",
)
# auronlint: disable=R14 -- upstream-parity surface (conf.rs:54): read only by the unimplemented SMJ fallback
SMJ_FALLBACK_ROWS_THRESHOLD = int_conf(
    "smj.fallback.rows.threshold", 10_000_000, "join", ""
)
# auronlint: disable=R14 -- upstream-parity surface (conf.rs:55): read only by the unimplemented SMJ fallback
SMJ_FALLBACK_MEM_SIZE_THRESHOLD = int_conf(
    "smj.fallback.mem.threshold.bytes", 1 << 30, "join", ""
)
PARTIAL_AGG_SKIPPING_ENABLE = bool_conf(
    "partial.agg.skipping.enable", True, "agg",
    "skip partial aggregation when observed cardinality ratio is high (conf.rs:38-41)",
)
PARTIAL_AGG_SKIPPING_RATIO = float_conf(
    "partial.agg.skipping.ratio", 0.8, "agg", ""
)
PARTIAL_AGG_SKIPPING_MIN_ROWS = int_conf(
    "partial.agg.skipping.min.rows", 20480, "agg", ""
)
AGG_INCREMENTAL_ENABLE = bool_conf(
    "exec.agg.incremental.enable", True, "agg",
    "umbrella for incremental grouped aggregation (docs/agg.md): "
    "fingerprint-sort segmentation, sorted-state probe/scatter and "
    "merge-path state merges. False = the legacy full-word "
    "sort-segmentation path everywhere (bit-identical results either way)",
)
AGG_INCREMENTAL_FINGERPRINT = str_conf(
    "exec.agg.incremental.fingerprint", "auto", "agg",
    "sort (dead, fingerprint64, iota) — 3 fixed operands — instead of the "
    "K+2 key-word operands, verifying true key equality per fingerprint "
    "segment; collision batches are exact (word-compare boundaries), "
    "counted (fp_collision_batches) and excluded from the probe/merge-path "
    "fast paths. on | off | auto = on for accelerators, off on the CPU "
    "backend (where the host lexsort already wins and the extra hashing "
    "loses — measured on the q93-class bool-key agg)",
)
AGG_INCREMENTAL_PROBE = str_conf(
    "exec.agg.incremental.probe", "auto", "agg",
    "binary-search each incoming row into the fingerprint-sorted state "
    "batch and scatter-add rows whose group already exists straight into "
    "the state accumulators — repeating-key steady state pays O(n log S) + "
    "one scatter, no sort; only miss rows flow to sort-segmentation. "
    "on | off | auto = accelerators only (XLA:CPU lowers the scatter to a "
    "serial loop that costs more than the sort it replaces)",
)
AGG_INCREMENTAL_MERGEPATH = str_conf(
    "exec.agg.incremental.mergepath", "auto", "agg",
    "merge fingerprint-sorted state and staged runs with a binsearch "
    "merge-rank permutation instead of concat-and-re-sort (the q5-class "
    "merge_time blowup); falls back to the full re-sort whenever a run is "
    "not confirmed collision-free. on | off | auto = accelerators only "
    "(the merge-rank permutation build is a scatter — serial on XLA:CPU)",
)
AGG_INCREMENTAL_FP_BITS = int_conf(
    "exec.agg.incremental.fp.bits", 64, "agg",
    "fingerprint width; < 64 truncates to the low bits. A TEST hook: tiny "
    "widths force deterministic fingerprint collisions so the "
    "collision-detection/fallback machinery is exercisable — production "
    "stays at 64",
)
AGG_DENSE_HOST_SCATTER = str_conf(
    "exec.agg.dense.host.scatter", "auto", "agg",
    "fold dense-agg batches with host np.bincount (sums/counts) and "
    "np.minimum/maximum.at (min/max) instead of on-device segment "
    "scatters: on | off | auto = on for the CPU backend, where XLA lowers "
    "segment scatters to serial loops ~8x slower (the hostsort fork, "
    "applied to scatter-reduce). Accelerators keep the fused device "
    "scatter",
)
# auronlint: disable=R14 -- upstream-parity surface (agg_ctx.rs:611): spilled-agg merge is single-pass here, bucketed merge not ported yet
AGG_SPILL_BUCKETS = int_conf(
    "agg.spill.buckets", 64, "agg",
    "number of hash buckets for spilled aggregation merge (agg/agg_ctx.rs:611)",
)
SHUFFLE_COMPRESSION_TARGET_BUF_SIZE = int_conf(
    "shuffle.compression.target.buf.size", 4 << 20, "shuffle", ""
)
EXCHANGE_MODE = str_conf(
    "exchange.mode", "auto", "shuffle",
    "transport for planned mesh_exchange nodes: mesh (ICI all_to_all) | "
    "file (durable compacted shuffle files) | auto (mesh when the payload "
    "fits exchange.mesh.max.bytes per shard)",
)
EXCHANGE_COALESCE_ENABLE = bool_conf(
    "exchange.coalesce.enable", True, "shuffle",
    "AQE post-shuffle coalescing: group small reduce partitions from "
    "map-output statistics (CoalesceShufflePartitions analog)",
)
EXCHANGE_COALESCE_TARGET_BYTES = int_conf(
    "exchange.coalesce.target.bytes", 64 << 20, "shuffle",
    "target bytes per coalesced reduce partition",
)
EXCHANGE_SKEW_ENABLE = bool_conf(
    "exchange.skew.join.enable", True, "shuffle",
    "AQE skew-join splitting: a reduce partition much larger than the "
    "median splits into map-range slices joined against the full other "
    "side (Spark OptimizeSkewedJoin analog)",
)
EXCHANGE_SKEW_FACTOR = float_conf(
    "exchange.skew.join.factor", 5.0, "shuffle",
    "a partition is skewed when its bytes exceed factor x median",
)
EXCHANGE_SKEW_MIN_BYTES = int_conf(
    "exchange.skew.join.min.bytes", 64 << 20, "shuffle",
    "partitions below this never count as skewed",
)
EXCHANGE_MESH_MAX_BYTES = int_conf(
    "exchange.mesh.max.bytes", 2 << 30, "shuffle",
    "auto-mode ceiling for device-resident exchange payload per shard; "
    "larger exchanges take the durable file path",
)
SCAN_ZEROCOPY = str_conf(
    "exec.scan.zerocopy", "auto", "scan",
    "zero-copy ingestion (docs/shuffle.md): validity-clean fixed-width "
    "Arrow/numpy column buffers upload by 64-byte-aligned buffer ALIAS "
    "instead of a host->device copy (XLA:CPU device_put aliases aligned "
    "host memory; accelerators still DMA but skip the intermediate numpy "
    "materialization), validity/selection planes of full clean batches "
    "come from shared cached all-true planes, and dictionary pages pass "
    "through by reference. The engine relies on Arrow/ingest buffers "
    "staying immutable while device arrays reference them (Arrow buffers "
    "are immutable by contract; Batch.from_pandas documents the same "
    "contract for user frames). on | off | auto = on. off restores the "
    "copying ingest path exactly (bit-identical results either way)",
)
SHUFFLE_ENCODING = str_conf(
    "exec.shuffle.encoding", "auto", "shuffle",
    "shuffle block format v2 (docs/shuffle.md): per-column light-weight "
    "encodings (dict pass-through, RLE, frame-of-reference bitpack, "
    "packbits) chosen per block from cheap stats, with the general codec "
    "only as fallback for incompressible planes — the writer stops paying "
    "zstd/lz4 over every byte on the hot path, and the reader decodes "
    "blocks straight into capacity-bucket device buffers instead of via "
    "an intermediate Arrow table. on | off | auto = on. off restores the "
    "compressed-IPC v1 blocks and the Arrow-table read path byte-for-byte",
)
SHUFFLE_ENCODING_DICT_MAX = int_conf(
    "exec.shuffle.encoding.dict.max", 4096, "shuffle",
    "largest dictionary (distinct values) a v2 block will carry for a "
    "dictionary-preserving column; larger dictionaries were already "
    "materialized by the writer and encode as plain value columns",
)
SHUFFLE_ENCODING_FALLBACK = str_conf(
    "exec.shuffle.encoding.fallback.codec", "auto", "shuffle",
    "general-purpose codec for planes no light-weight encoding fits "
    "(zstd|lz4|none|auto = spill.compression.codec). A codec named here "
    "but unavailable in the runtime degrades to the light-weight "
    "encodings with a single stderr warning instead of failing the write",
)
IGNORE_CORRUPTED_FILES = bool_conf(
    "files.ignore.corrupted", False, "scan", "tolerate unreadable input files (conf.rs:37)"
)
PARQUET_MAX_OVER_READ_SIZE = int_conf(
    "parquet.max.over.read.size", 16 << 20, "scan",
    "read coalescing window for remote-FS parquet reads (conf.rs:44)",
)
PARQUET_LATE_MATERIALIZATION = bool_conf(
    "parquet.late.materialization", True, "scan",
    "decode predicate columns first and skip the wide decode for row "
    "groups with zero matches (page/dictionary-check analog)",
)
CASE_SENSITIVE = bool_conf("case.sensitive", False, "sql", "identifier resolution")
SQL_SHUFFLE_PARTITIONS = int_conf(
    "sql.shuffle.partitions", 2, "sql",
    "mesh width of SQL-frontend plans: partition count of every "
    "mesh_exchange the lowering emits and of the partitioned probe scan "
    "(spark.sql.shuffle.partitions analog; the driver's AQE may coalesce "
    "below it at runtime)",
)
SQL_GATE_SF = float_conf(
    "sql.gate.sf", 4.0, "sql",
    "scale factor of the real-text differential gate (make sqlgate); the "
    "tier-1 run overrides this to a toy scale",
)
SQL_GATE_FLOAT_REL = float_conf(
    "sql.gate.float.rel", 1e-6, "sql",
    "relative float tolerance of the SQL gate's row comparator "
    "(models/compare.py; the ULP term is fixed at 4)",
)
FILTER_FUSE = bool_conf(
    "exec.filter.fuse", True, "exec",
    "compile trace-safe filter predicates into ONE jitted program per "
    "(schema, predicate, capacity-bucket) instead of eager per-op "
    "dispatch: fuses the compare/mask chain into a single pass and stops "
    "eager dispatch from serializing against concurrent jitted programs "
    "on the executor (the q5-class FilterExec misattribution). Subsumed "
    "by exec.fuse.* whole-stage fusion when a filter sits inside a fused "
    "segment; this knob still governs standalone FilterExec batches",
)
FUSE_ENABLE = str_conf(
    "exec.fuse.enable", "auto", "fusion",
    "whole-stage fusion (plan/fusion.py, docs/fusion.md): compile each "
    "maximal scan->filter->project->partial-agg-input pipeline segment "
    "between blocking boundaries into ONE jitted XLA program per "
    "(schema, segment signature, capacity bucket). on | off | auto = "
    "fuse everywhere the per-segment cost model predicts a win — always "
    "on accelerators, and on the CPU backend only for segments whose "
    "estimated eager-dispatch count reaches exec.fuse.min.ops (the "
    "PR-3-measured CPU exception: fused filter chains beat eager "
    "dispatch there too). Results are bit-identical either way",
)
FUSE_MIN_OPS = int_conf(
    "exec.fuse.min.ops", 2, "fusion",
    "cost-model threshold for fuse-vs-materialize on the CPU backend "
    "under exec.fuse.enable=auto: a segment fuses only when the eager "
    "path would cost at least this many per-batch operator dispatches "
    "(expression DAG nodes + one per constituent operator). Accelerator "
    "backends fuse every trace-safe segment regardless — dispatch "
    "round-trips dominate there",
)
FUSE_AGG_INPUTS = bool_conf(
    "exec.fuse.agg.inputs", True, "fusion",
    "extend fused segments THROUGH a partial-mode HashAggExec's input "
    "evaluation: grouping and aggregate argument expressions are "
    "compiled into the segment program and the aggregate is rewritten "
    "to consume bare column refs — the scan->filter->project->partial-"
    "agg stage shape of ROADMAP item 2 (gated by the same cost model)",
)
FUSE_PROBE = str_conf(
    "exec.fuse.probe", "auto", "fusion",
    "extend the fused stage feeding a hash join's probe side THROUGH the "
    "probe prologue: key evaluation, canonical-word packing, the unique/"
    "existence hash-map lookup and the build-row pair-gather (incl. the "
    "predicted compact-take) compile into the SAME stage program, so a "
    "probe batch costs one dispatch instead of a chain of eager per-op "
    "jits. The build side, the join's CompactionBoundary (its mispredict "
    "repair included) and finish_probe semantics are unchanged. on | off | auto "
    "= accelerators always, CPU when the segment cost model fuses "
    "(exec.fuse.min.ops). off restores the eager probe bit-identically",
)
FUSE_SHUFFLE = str_conf(
    "exec.fuse.shuffle", "auto", "fusion",
    "extend the fused stage feeding a ShuffleWriterExec THROUGH the "
    "repartition prologue: partition-id hashing and (on the device "
    "substrate) pid-clustering ride the stage program, so the writer "
    "receives already-clustered device batches. The host/device "
    "clustering substrate follows the SAME policy as the eager writer "
    "(writer.repartition_substrate), so fused and fallback repartition "
    "cannot diverge. on | off | auto = same cost-model split as "
    "exec.fuse.enable. off restores the eager repartition bit-identically",
)
SERVE_MAX_CONCURRENT = int_conf(
    "serve.admission.max.concurrent", 4, "serve",
    "queries the SQL server executes simultaneously; arrivals beyond it "
    "QUEUE (admission control) instead of piling onto the executor pool. "
    "The analog of the reference's per-task tokio runtimes is bounded "
    "here instead: lowered plans are pure jitted programs that interleave "
    "on one device, so the limit shapes memory pressure, not parallel "
    "substrate",
)
SERVE_QUEUE_TIMEOUT_S = float_conf(
    "serve.admission.queue.timeout.seconds", 60.0, "serve",
    "longest a query waits in the admission queue (for a concurrency "
    "slot or for memory headroom) before the server answers busy — the "
    "queue-don't-die escape hatch's bound",
)
SERVE_ADMIT_MEM_FRACTION = float_conf(
    "serve.admission.memory.fraction", 0.9, "serve",
    "memory-manager-aware backpressure: a query waits in the admission "
    "queue while consumer usage exceeds this fraction of the manager's "
    "budget. Admitted queries past the threshold still run — the memory "
    "manager degrades them to spilling per its per-query fair shares — "
    "but new work queues instead of deepening the overcommit",
)
SERVE_PLAN_CACHE_ENTRIES = int_conf(
    "serve.plan.cache.entries", 256, "serve",
    "bounded size of the plan-digest-keyed compiled-plan cache "
    "(serve/cache.py): a hit skips parse->bind->lower and re-enters the "
    "fusion stage cache with zero new XLA compiles; least-recently-used "
    "entries evict past the bound",
)
SERVE_GATE_SF = float_conf(
    "serve.gate.sf", 1.0, "serve",
    "scale factor of the concurrency differential gate "
    "(models/servegate.py). At toy scale per-query wall is GIL-bound "
    "Python where concurrency cannot pay; >=1 gives queries real device "
    "compute, the regime the serving claim is about. tier-1 and make "
    "servecheck override to toy scale (they gate bit-identity and "
    "zero-compile replay, not throughput)",
)
SERVE_GATE_CLIENTS = int_conf(
    "serve.gate.clients", 8, "serve",
    "concurrent clients the differential gate replays the corpus with "
    "(each client replays every corpus query once)",
)
STREAM_CALC_FUSE = str_conf(
    "stream.calc.fuse", "auto", "stream",
    "streaming Calc chains (exec/streaming.py) ride whole-stage fused "
    "programs: the per-micro-batch filter+project chain is built as an "
    "exec tree and passed through plan/fusion.py, so a long-running "
    "stream compiles once per (schema, segment signature, capacity "
    "bucket) and every subsequent event batch costs ONE dispatch. "
    "on | off | auto = on (the exec.fuse.* cost model still decides "
    "per segment). off restores the eager per-op dispatch loop "
    "bit-identically — the A/B leg make streamgate measures",
)
STREAM_POLL_MAX_RECORDS = int_conf(
    "stream.poll.max.records", 8192, "stream",
    "records per source poll = the micro-batch ceiling of a continuous "
    "pipeline (auron_tpu/stream). Determinism-relevant: resumed runs "
    "must re-poll the same micro-batch boundaries, so the checkpoint "
    "manifest records the value it ran with and the restore path "
    "refuses a mismatch instead of silently re-batching differently",
)
STREAM_CHECKPOINT_INTERVAL = int_conf(
    "stream.checkpoint.interval.batches", 8, "stream",
    "checkpoint barrier cadence of a continuous pipeline, in micro-"
    "batches: every N-th micro-batch the coordinator atomically "
    "snapshots {source offsets, window/agg state, watermark, emission "
    "seq} (temp + os.replace), the unit of exactly-once crash-resume "
    "(docs/streaming.md)",
)
STREAM_CHECKPOINT_KEEP = int_conf(
    "stream.checkpoint.keep", 2, "stream",
    "completed checkpoints retained per stream; older snapshot files "
    "are pruned after each successful barrier (the latest one is what "
    "a restore loads, the extras are crash insurance while the newest "
    "is being replaced)",
)
STREAM_SERVE_MAX_STREAMS = int_conf(
    "stream.serve.max.streams", 4, "stream",
    "continuous queries one server process will run concurrently "
    "(POST /stream register); registrations past the bound are refused "
    "loudly with 429 — long-running pipelines hold their executor "
    "threads, so admission is a hard count, not a queue",
)
UDF_FALLBACK_ENABLE = bool_conf(
    "udf.fallback.enable", True, "expr",
    "evaluate unconvertible expressions via host callback (SparkUDFWrapper analog)",
)
TOKIO_EQUIV_PREFETCH_DEPTH = int_conf(
    "runtime.prefetch.depth", 2, "runtime",
    "batches prefetched by the task pump (analog of the 1-slot sync_channel + tokio workers, rt.rs:108-140)",
)
NATIVE_LOG_LEVEL = str_conf("log.level", "info", "runtime", "engine log level (conf.rs:64)")
METRICS_ROW_COUNTS = bool_conf(
    "metrics.row.counts", False, "runtime",
    "per-operator output_rows metrics; unlike the reference (free host-side "
    "Arrow metadata) a device row count costs a reduction kernel per batch, "
    "so production runs keep it off and read row counts at task boundaries",
)
