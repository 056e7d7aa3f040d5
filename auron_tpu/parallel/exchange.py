"""ICI all-to-all repartitioning and sharded aggregation steps.

This is the on-device counterpart of the file shuffle (exec/shuffle/):
when producer and consumer stages run on the same mesh, rows move over ICI
via ``lax.all_to_all`` instead of through compacted disk runs — the
"intra-slice repartition" of SURVEY.md §7. The file shuffle remains the
durable path (AQE boundaries, retries, inter-slice DCN fallback).

SPMD layout: every array carries a leading partition axis sharded over the
mesh's ``p`` axis; inside ``shard_map`` each device sees its own rows
[cap, ...]. Repartitioning builds a fixed-capacity send matrix
[P, slot_cap, ...] (slot ranks computed with one device sort), swaps it
with ``all_to_all``, and the receiver flattens peers' blocks. Fixed
slot capacity keeps shapes static for XLA; an overflow flag (psum over
dropped rows) tells the host runtime to re-run the exchange with a larger
bucket — the static-shape analog of a grow-and-retry hash table.

Spark-exactness: partition ids use the same murmur3+pmod as the file
shuffle, so a mesh exchange and a file shuffle route rows identically.
"""

from __future__ import annotations

from functools import lru_cache, partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map

from auron_tpu.ops import hashing as H
from auron_tpu.parallel.mesh import PARTITION_AXIS


class ExchangeResult(NamedTuple):
    arrays: tuple  # exchanged row arrays, each [P*slot_cap] per shard
    sel: jnp.ndarray  # liveness of received rows
    overflow: jnp.ndarray  # int32 count of dropped rows (global)


def _slot_ranks(pids: jnp.ndarray, sel: jnp.ndarray, n_parts: int):
    """Rank of each row within its destination partition (device sort)."""
    cap = pids.shape[0]
    key = jnp.where(sel, pids, n_parts).astype(jnp.int32)
    iota = jnp.arange(cap, dtype=jnp.int32)
    s_key, order = lax.sort((key, iota), num_keys=1)
    # rank within equal-key run
    boundary = jnp.concatenate([jnp.ones(1, bool), s_key[1:] != s_key[:-1]])
    # lax.cummax, not jnp.maximum.accumulate: the ufunc .accumulate
    # methods only exist on jax >= 0.5
    run_start = lax.cummax(jnp.where(boundary, iota, 0))
    rank_sorted = iota - run_start
    ranks = jnp.zeros(cap, jnp.int32).at[order].set(rank_sorted)
    return ranks


def all_to_all_rows(
    arrays: tuple,
    sel: jnp.ndarray,
    pids: jnp.ndarray,
    n_parts: int,
    slot_cap: int,
):
    """Inside shard_map: route rows to their destination shards.

    arrays: per-row payload arrays [cap]; sel: liveness; pids: destination.
    Returns (received arrays [n_parts*slot_cap], received sel, overflow).
    """
    ranks = _slot_ranks(pids, sel, n_parts)
    keep = sel & (ranks < slot_cap)
    overflow = jnp.sum((sel & ~keep).astype(jnp.int32))

    # dead/overflow rows target an out-of-bounds slot -> dropped by scatter
    dest_p = jnp.where(keep, pids, n_parts).astype(jnp.int32)
    dest_s = jnp.where(keep, ranks, slot_cap).astype(jnp.int32)

    def scatter(a):
        send = jnp.zeros((n_parts, slot_cap), dtype=a.dtype)
        return send.at[dest_p, dest_s].set(a, mode="drop")

    send_sel = jnp.zeros((n_parts, slot_cap), bool).at[dest_p, dest_s].set(True, mode="drop")
    sent = [scatter(a) for a in arrays]

    recv = [
        lax.all_to_all(s, PARTITION_AXIS, split_axis=0, concat_axis=0, tiled=True)
        for s in sent
    ]
    recv_sel = lax.all_to_all(send_sel, PARTITION_AXIS, split_axis=0, concat_axis=0, tiled=True)
    total_overflow = lax.psum(overflow, PARTITION_AXIS)
    return tuple(r.reshape(-1) for r in recv), recv_sel.reshape(-1), total_overflow


def _group_sum_i64(keys: jnp.ndarray, vals: jnp.ndarray, sel: jnp.ndarray):
    """Per-shard sort-segmented sum of int64/float64 vals by int64 keys.
    Returns prefix-packed (keys, sums, counts, group_valid)."""
    cap = keys.shape[0]
    live = jnp.where(sel, jnp.uint64(0), jnp.uint64(1))
    kw = keys.view(jnp.uint64) if keys.dtype == jnp.int64 else keys.astype(jnp.int64).view(jnp.uint64)
    iota = jnp.arange(cap, dtype=jnp.int32)
    s_live, s_kw, order = lax.sort((live, kw, iota), num_keys=2)
    s_sel = s_live == 0
    s_keys = keys[order]
    s_vals = vals[order]
    boundary = (
        jnp.concatenate([jnp.ones(1, bool), s_kw[1:] != s_kw[:-1]]) & s_sel
    )
    seg = jnp.where(s_sel, jnp.cumsum(boundary.astype(jnp.int32)) - 1, cap)
    sums = jax.ops.segment_sum(jnp.where(s_sel, s_vals, jnp.zeros_like(s_vals)), seg, num_segments=cap + 1)[:cap]
    counts = jax.ops.segment_sum(s_sel.astype(jnp.int64), seg, num_segments=cap + 1)[:cap]
    first_pos = jax.ops.segment_min(iota, seg, num_segments=cap + 1)[:cap]
    num_groups = jnp.sum(boundary.astype(jnp.int32))
    gkeys = s_keys[jnp.clip(first_pos, 0, cap - 1)]
    gvalid = iota < num_groups
    return gkeys, sums, counts, gvalid


def batch_exchange_step(mesh: Mesh, slot_cap: int, n_hash_cols: int = 1):
    """Generic mesh repartitioner: route rows of an arbitrary column set to
    the shard owning murmur3(key columns) % P — the ICI path for ANY hash
    shuffle (values+validity of every column travel together). Columns are
    a pytree, so schemas of mixed dtypes compile into one program per
    (shapes, dtypes) signature.

    Inputs (sharded over p): key_cols tuple of int64 [P, cap]; payload
    arrays pytree of [P, cap]; sel [P, cap]. Returns exchanged (key_cols,
    payload, sel, overflow)."""
    n_parts = mesh.shape[PARTITION_AXIS]

    def step(key_cols, payload, sel):
        key_cols = tuple(k[0] for k in key_cols)
        payload = jax.tree.map(lambda a: a[0], payload)
        sel = sel[0]
        h = jnp.full(sel.shape, jnp.uint32(42))
        for k in key_cols:
            h = H.murmur3_i64(k, h)
        pid = H.pmod(h.view(jnp.int32), n_parts)
        flat, treedef = jax.tree.flatten(payload)
        arrays = tuple(key_cols) + tuple(flat)
        recv, rsel, overflow = all_to_all_rows(arrays, sel, pid, n_parts, slot_cap)
        rkeys = recv[: len(key_cols)]
        rpayload = jax.tree.unflatten(treedef, list(recv[len(key_cols):]))
        add = lambda a: a[None]
        return (
            tuple(k[None] for k in rkeys),
            jax.tree.map(add, rpayload),
            rsel[None],
            overflow,
        )

    spec = P(PARTITION_AXIS)
    fn = shard_map(
        step,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=(spec, spec, spec, P()),
    )
    return jax.jit(fn)


@lru_cache(maxsize=64)
def pid_exchange_step(mesh: Mesh, slot_cap: int):
    """Mesh repartitioner routed by PRECOMPUTED partition ids. Memoized
    per (mesh, slot_cap): a fresh jax.jit per exchange would retrace and
    re-lower the collective on every query a server replays.

    The planned-query driver computes pids host-side with the same
    ``Partitioning`` code the file shuffle writer uses (spark-exact murmur3
    incl. dictionary-string hashing, range bounds, round-robin cursors), so
    a mesh exchange and a file shuffle route rows bit-identically — this
    step only moves them. Inputs (sharded over p): ``arrays`` pytree of
    [P, cap] row arrays, ``sel`` [P, cap] liveness, ``pids`` [P, cap] int32
    destinations. Returns (arrays [P, P*slot_cap], sel, overflow)."""
    n_parts = mesh.shape[PARTITION_AXIS]

    def step(arrays, sel, pids):
        arrays = jax.tree.map(lambda a: a[0], arrays)
        sel, pids = sel[0], pids[0]
        flat, treedef = jax.tree.flatten(arrays)
        recv, rsel, overflow = all_to_all_rows(
            tuple(flat), sel, pids, n_parts, slot_cap
        )
        out = jax.tree.unflatten(treedef, list(recv))
        return (
            jax.tree.map(lambda a: a[None], out),
            rsel[None],
            overflow,
        )

    spec = P(PARTITION_AXIS)
    fn = shard_map(
        step,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=(spec, spec, P()),
    )
    return jax.jit(fn)


def sharded_agg_exchange_step(mesh: Mesh, slot_cap: int):
    """Build the jitted SPMD program: partial agg -> ICI all_to_all by key
    hash -> final agg. This is the engine's flagship distributed step — the
    device-resident equivalent of Spark stage N (partial) -> shuffle ->
    stage N+1 (final) for `SELECT k, sum(v), count(v) GROUP BY k`.

    Inputs (sharded over p): keys [P, cap] int64, vals [P, cap] float64,
    sel [P, cap] bool. Outputs (sharded): group keys/sums/counts/valid per
    shard plus a global overflow counter.
    """
    n_parts = mesh.shape[PARTITION_AXIS]

    def step(keys, vals, sel):
        # shard_map keeps the sharded leading axis with local size 1
        keys, vals, sel = keys[0], vals[0], sel[0]
        # 1. partial aggregation on local rows
        gk, gs, gc, gv = _group_sum_i64(keys, vals, sel)
        # 2. route groups to owners by spark-exact murmur3(key) % P
        h = H.murmur3_i64(gk, jnp.uint32(42)).view(jnp.int32)
        pid = H.pmod(h, n_parts)
        (rk, rs, rc), rsel, overflow = all_to_all_rows(
            (gk, gs, gc), gv, pid, n_parts, slot_cap
        )
        # 3. final aggregation of received partials (merge sums and counts)
        fk, fs, fcnt_groups, fv = _group_sum_i64(rk, rs, rsel)
        # counts must be summed too (not counted): reuse segment machinery
        _, fc, _, _ = _group_sum_i64(rk, rc.astype(jnp.float64), rsel)
        return fk[None], fs[None], fc.astype(jnp.int64)[None], fv[None], overflow

    spec = P(PARTITION_AXIS)
    fn = shard_map(
        step,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=(spec, spec, spec, spec, P()),
    )
    return jax.jit(fn)
