"""Repartitioning strategies.

Analog of the reference's partitionings (shuffle/mod.rs:112-121,
auron.proto:676-704): Hash (Spark murmur3 + Pmod — bit-exact so reducers
receive exactly the rows the host engine expects), RoundRobin, Range
(host-sampled bounds + binary search on orderable key words), Single.
Each returns a per-row partition id vector on device.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import jax.numpy as jnp
import numpy as np

from auron_tpu.columnar.batch import Batch
from auron_tpu.exprs import Evaluator, ir
from auron_tpu.ops.hash_dispatch import hash_batch
from auron_tpu.ops.hashing import pmod
from auron_tpu.ops.sortkeys import SortSpec, sort_operands


class Partitioning:
    num_partitions: int

    def partition_ids(self, batch: Batch, ctx) -> jnp.ndarray:
        raise NotImplementedError

    def fuse_spec(self, schema) -> tuple | None:
        """Static hashable description for whole-stage shuffle fusion
        (plan/fusion.py `_stage_program_shuffle`), or None when this
        partitioning can't ride a fused stage program. The traced twin is
        ``partition_ids_traced`` below — BOTH must compute bit-identical
        pids (the fused writer's repartition may never diverge from the
        eager one)."""
        return None


def _hash_pids(vals, sel, n_out: int, traced: bool) -> jnp.ndarray:
    """THE Spark-exact (murmur3 + Pmod) pid computation shared by the
    eager HashPartitioning and the fused stage program. The pallas fast
    path (single int64 key on TPU, bit-identical by the kernel's contract)
    is eager-only — inside a fused trace the jnp path fuses anyway; the
    traced entry also restricts to fixed-width keys (hash_batch_fixed:
    fuse_spec guarantees it, and the dict byte-matrix host cache must
    never run at trace time)."""
    cap = sel.shape[0]
    if (
        not traced
        and len(vals) == 1
        and vals[0].dict is None
        and str(vals[0].values.dtype) == "int64"
    ):
        from auron_tpu.jaxenv import is_tpu

        # one device only: a batch downstream of a mesh exchange is
        # replicated over the mesh, and Mosaic kernels cannot be
        # partitioned automatically — the jnp hash below can
        if is_tpu() and len(vals[0].values.sharding.device_set) == 1:
            from auron_tpu.ops.pallas_kernels import partition_ids_pallas

            pids = partition_ids_pallas(vals[0].values, n_out)
            null_pid = pmod(
                jnp.full(cap, jnp.uint32(42)).view(jnp.int32), n_out
            )
            return jnp.where(vals[0].validity, pids, null_pid)
    from auron_tpu.exec.basic import batch_from_columns
    from auron_tpu.ops.hash_dispatch import hash_batch_fixed

    kb = batch_from_columns(vals, [f"k{i}" for i in range(len(vals))], sel)
    hasher = hash_batch_fixed if traced else hash_batch
    h = hasher(kb, list(range(len(vals))), "murmur3", seed=42)
    return pmod(h, n_out)


def _roundrobin_pids(sel, start, n_out: int) -> jnp.ndarray:
    """Deterministic per-task round-robin cursor (reference:
    shuffle/mod.rs RoundRobin) — the one definition behind the eager and
    traced paths. ``start`` may be a host int or a traced scalar."""
    ordinal = jnp.cumsum(sel.astype(jnp.int32)) - 1
    return ((ordinal + start) % n_out).astype(jnp.int32)


#: dtypes the murmur3 device dispatch hashes WITHOUT host dictionary
#: expansion — the fused stage's key-type gate (dict-encoded strings hash
#: through a per-vocabulary byte matrix whose trace-time caching is
#: per-object: eager only)
_FUSE_HASHABLE_KINDS = frozenset({
    "INT8", "INT16", "INT32", "INT64", "DATE32", "TIMESTAMP", "BOOL",
    "FLOAT32", "FLOAT64", "DECIMAL",
})


@dataclass
class SinglePartitioning(Partitioning):
    num_partitions: int = 1

    def partition_ids(self, batch: Batch, ctx) -> jnp.ndarray:
        return jnp.zeros(batch.capacity, jnp.int32)

    def fuse_spec(self, schema) -> tuple | None:
        return ("single",)


@dataclass
class HashPartitioning(Partitioning):
    exprs: list
    num_partitions: int

    def partition_ids(self, batch: Batch, ctx) -> jnp.ndarray:
        ev = Evaluator(batch.schema)
        vals = ev.evaluate(batch, self.exprs)
        # hot single-int64-key case: the hand-tiled pallas kernel on TPU
        # (identical spark-exact bits; jnp path everywhere else). NULL keys
        # leave the running hash at the seed, so their pid is the constant
        # pmod(seed) — blended on device, no host sync, no fallback
        return _hash_pids(
            vals, batch.device.sel, self.num_partitions, traced=False
        )

    def fuse_spec(self, schema) -> tuple | None:
        for e in self.exprs:
            try:
                dt = e.dtype_of(schema)
            except Exception:
                return None
            if dt.is_dict_encoded or dt.kind.name not in _FUSE_HASHABLE_KINDS:
                return None
        return ("hash", tuple(self.exprs))


@dataclass
class RoundRobinPartitioning(Partitioning):
    num_partitions: int

    def partition_ids(self, batch: Batch, ctx) -> jnp.ndarray:
        # deterministic start per (task partition), matching the reference's
        # per-task round-robin cursor (shuffle/mod.rs RoundRobin)
        start = (ctx.partition_id if ctx is not None else 0) % self.num_partitions
        return _roundrobin_pids(batch.device.sel, start, self.num_partitions)

    def fuse_spec(self, schema) -> tuple | None:
        return ("roundrobin",)


def partition_ids_traced(spec, schema, n_out: int, sel, values, validity,
                         rr_start) -> jnp.ndarray:
    """Traceable twin of ``Partitioning.partition_ids`` for fused stage
    programs: same Evaluator key evaluation, same ``_hash_pids`` /
    ``_roundrobin_pids`` policies (minus the eager-only pallas branch,
    whose bits are identical by contract). ``rr_start`` arrives as a
    DEVICE scalar so one compiled program serves every task partition."""
    kind = spec[0]
    cap = sel.shape[0]
    if kind == "single":
        return jnp.zeros(cap, jnp.int32)
    if kind == "roundrobin":
        return _roundrobin_pids(sel, rr_start, n_out)
    from auron_tpu.columnar.batch import Batch as _B
    from auron_tpu.columnar.batch import DeviceBatch as _DB

    b = _B(schema, _DB(sel, values, validity), (None,) * len(schema.fields))
    vals = Evaluator(schema).evaluate(b, list(spec[1]))
    return _hash_pids(vals, sel, n_out, traced=True)


@dataclass
class RangePartitioning(Partitioning):
    """bounds: host-provided list of boundary rows (one per key expr),
    computed by the exchange from a sample of the input (the engine side
    samples — NativeShuffleExchangeBase.scala:312)."""

    sort_exprs: list
    specs: list
    num_partitions: int
    bound_words: np.ndarray = field(default=None)  # [num_bounds, n_words] uint64

    def partition_ids(self, batch: Batch, ctx) -> jnp.ndarray:
        ev = Evaluator(batch.schema)
        keys = ev.evaluate(batch, self.sort_exprs)
        words = sort_operands(keys, self.specs)  # 2 words per key
        n = batch.capacity
        nb = self.bound_words.shape[0]
        pid = jnp.zeros(n, jnp.int32)
        # Spark RangePartitioner: row goes to the first partition whose bound
        # >= key, i.e. pid = #bounds strictly below the row key
        for bi in range(nb):
            lt = jnp.zeros(n, bool)
            eq = jnp.ones(n, bool)
            for wi, w in enumerate(words):
                bw = jnp.uint64(int(self.bound_words[bi, wi]))
                lt = lt | (eq & (bw < w))
                eq = eq & (bw == w)
            pid = pid + lt.astype(jnp.int32)
        return jnp.minimum(pid, self.num_partitions - 1)


def make_range_bounds(
    sample: Batch, sort_exprs: list, specs: list, num_partitions: int
) -> np.ndarray:
    """Compute range boundary key words from a sample batch (host side)."""
    import jax

    ev = Evaluator(sample.schema)
    keys = ev.evaluate(sample, sort_exprs)
    words = [np.asarray(jax.device_get(w)) for w in sort_operands(keys, specs)]
    sel = np.asarray(jax.device_get(sample.device.sel))
    live = np.nonzero(sel)[0]
    mat = np.stack([w[live] for w in words], axis=1)  # [n, n_words]
    order = np.lexsort(list(reversed([mat[:, i] for i in range(mat.shape[1])])))
    mat = mat[order]
    n = mat.shape[0]
    bounds = []
    for i in range(1, num_partitions):
        idx = min(n - 1, max(0, (i * n) // num_partitions))
        bounds.append(mat[idx])
    if not bounds:
        return np.zeros((0, len(words)), dtype=np.uint64)
    return np.stack(bounds).astype(np.uint64)
