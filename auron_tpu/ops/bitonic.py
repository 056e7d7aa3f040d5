"""Bitonic cluster sort: the engine's sort primitive as a TPU-shaped network.

The engine is sort-shaped: grouping (ops/segments.py), ordering
(ops/sortkeys.py), and shuffle clustering all reduce to "stable ascending
sort of a tuple of uint64 key words with an int32 payload". The default
device path is a multi-operand ``lax.sort`` whose lexicographic comparator
forces XLA:TPU onto its generic (slow) sort lowering — the same hot spot
the reference attacks with a hand-written radix sort
(datafusion-ext-commons/src/algorithm/rdx_sort.rs). Radix scatters don't
vectorize on the VPU, so the TPU-native design is a **bitonic merge
network**:

- each uint64 operand splits into hi/lo uint32 planes (32-bit lane math;
  no 64-bit emulation inside the network), the int32 payload is one more
  plane; planes stack into one (planes, rows, 128) array;
- a compare-exchange between partners ``i`` and ``i ^ j`` (j a power of
  two) is TWO STATIC ROLLS + a select: for elements with bit j clear the
  partner sits at ``i + j`` (roll by -j), for the rest at ``i - j``
  (roll by +j). Lane rolls (j < 128) and sublane rolls (j >= 128) are
  native VPU data movement — the network never gathers;
- the payload plane participates as the LAST compare key, making the
  order a total order and the result bit-identical to the stable
  ``lax.sort`` it replaces (bitonic networks are not otherwise stable);
- the whole network runs in one Pallas kernel with every plane
  VMEM-resident: ~log2(P)*(log2(P)+1)/2 substages touch VMEM only,
  where the equivalent XLA sort round-trips HBM per pass.

The same network runs as plain jitted jnp (``impl="jnp"``) on any
backend (identical algorithm, XLA-scheduled). Correctness of both paths
is pinned to ``lax.sort`` in tests/test_bitonic.py (Pallas in interpret
mode off-TPU), and tests/test_chip_compile.py compiles the kernel for a
described v5e. ``device.sort.impl=auto`` resolves to ``lax`` (see
``sort_impl_for``): the network is opt-in until a chip run has compared
the two.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from auron_tpu.utils.config import DEVICE_SORT_IMPL, active_conf

_LANES = 128
# single-block kernel: x + partner + compare temps must sit in VMEM
_VMEM_GATE_BYTES = 12 << 20


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p <<= 1
    return p


def _split_planes(operands: tuple, narrow: tuple) -> list[jnp.ndarray]:
    """uint64 operands -> hi/lo uint32 planes (most-significant first);
    int32/uint32 operands -> one plane. Plane order = compare order.
    narrow[i] marks a uint64 operand whose hi word is STATICALLY ZERO
    (caller's guarantee — e.g. the 0/1 dead-rows key, or a null-bits word
    covering <= 32 key columns): it rides as its lo plane alone, cutting
    network work per substage.

    Signed operands are sign-biased (hi/only plane XOR 0x80000000) so the
    network's unsigned plane compare matches lax.sort's signed order;
    narrow is ignored for signed operands (a signed value with a
    guaranteed-zero hi word would be non-negative anyway)."""
    planes: list[jnp.ndarray] = []
    for op, nw in zip(operands, narrow):
        if op.dtype == jnp.uint64:
            if not nw:
                planes.append((op >> jnp.uint64(32)).astype(jnp.uint32))
            planes.append((op & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32))
        elif op.dtype == jnp.uint32:
            planes.append(op)
        elif op.dtype == jnp.int32:
            planes.append(op.view(jnp.uint32) ^ jnp.uint32(0x80000000))
        elif op.dtype == jnp.int64:
            u = op.view(jnp.uint64)
            planes.append(
                ((u >> jnp.uint64(32)).astype(jnp.uint32)) ^ jnp.uint32(0x80000000)
            )
            planes.append((u & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32))
        else:
            raise TypeError(f"bitonic operand dtype {op.dtype}")
    return planes


def _substage(x: jnp.ndarray, flat: jnp.ndarray, R: int, k: int, j: int) -> jnp.ndarray:
    """ONE compare-exchange substage of the bitonic network over stacked
    planes x: (NP, R, 128). want_max[i] = bit_j(i) != bit_k(i); partner by
    two static rolls + select; lexicographic uint32 compare chain across
    planes (payload plane = last key -> never equal, the order is total).
    THE single comparator core — the full network and the tiled path's
    merge stage both run exactly this code."""
    jbit = (flat & j) != 0
    kbit = (flat & k) != 0
    want_max = jbit != kbit
    if j >= _LANES:
        sh, ax = j // _LANES, 1
    else:
        sh, ax = j, 2
    partner = jnp.where(
        jbit[None], jnp.roll(x, sh, axis=ax), jnp.roll(x, -sh, axis=ax)
    )
    lt = jnp.zeros((R, _LANES), dtype=bool)
    eq = jnp.ones((R, _LANES), dtype=bool)
    for p in range(x.shape[0]):  # auronlint: disable=R5 -- unrolled loop over packed key PLANES inside the jitted network, not rows
        a, b = x[p], partner[p]
        lt = lt | (eq & (a < b))
        eq = eq & (a == b)
    take_partner = lt == want_max
    return jnp.where(take_partner[None], partner, x)


def _iota2d(P: int):
    R = P // _LANES
    rows = lax.broadcasted_iota(jnp.int32, (R, _LANES), 0)
    cols = lax.broadcasted_iota(jnp.int32, (R, _LANES), 1)
    return R, rows * _LANES + cols


def _network(x: jnp.ndarray, P: int) -> jnp.ndarray:
    """The full bitonic sort network (fully unrolled; static strides)."""
    R, flat = _iota2d(P)
    k = 2
    while k <= P:
        j = k // 2
        while j >= 1:
            x = _substage(x, flat, R, k, j)
            j //= 2
        k *= 2
    return x


@partial(jax.jit, static_argnames=("P",))
def _run_jnp(x: jnp.ndarray, P: int) -> jnp.ndarray:
    return _network(x, P)


def _bitonic_kernel(x_ref, out_ref, *, P: int):
    out_ref[:] = _network(x_ref[:], P)


@partial(jax.jit, static_argnames=("P", "interpret"))
def _run_pallas(x: jnp.ndarray, P: int, interpret: bool) -> jnp.ndarray:
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    return pl.pallas_call(
        partial(_bitonic_kernel, P=P),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY if interpret else pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY if interpret else pltpu.VMEM),
        interpret=interpret,
    )(x)


def _merge_network(x: jnp.ndarray, P: int) -> jnp.ndarray:
    """The FINAL bitonic stage only (k = P): turns one bitonic sequence of
    length P into sorted order — the compare-exchange kernel of the tiled
    path. Literally _network's last stage (k = P makes every kbit 0, so
    the shared comparator's want_max reduces to jbit)."""
    R, flat = _iota2d(P)
    j = P // 2
    while j >= 1:
        x = _substage(x, flat, R, P, j)
        j //= 2
    return x


def _merge_kernel(x_ref, out_ref, *, P: int):
    out_ref[:] = _merge_network(x_ref[:], P)


@partial(jax.jit, static_argnames=("P", "interpret"))
def _run_pallas_merge(x: jnp.ndarray, P: int, interpret: bool) -> jnp.ndarray:
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    return pl.pallas_call(
        partial(_merge_kernel, P=P),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY if interpret else pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY if interpret else pltpu.VMEM),
        interpret=interpret,
    )(x)


@partial(jax.jit, static_argnames=("B", "impl", "interpret"))
def _merge_pairs(pairs: jnp.ndarray, B: int, impl: str, interpret: bool) -> jnp.ndarray:
    """Merge-split over pairs (npairs, NP, 2*RB, 128), each pair
    [block_a ++ reversed(block_b)] (a bitonic sequence); returns the
    merged ascending pairs. impl="pallas" runs the VMEM-resident merge
    kernel per pair (lax.map: one trace, sequential grid); "jnp" vmaps
    the same network through XLA."""
    if impl == "pallas":
        return lax.map(lambda x: _run_pallas_merge(x, 2 * B, interpret), pairs)
    return jax.vmap(lambda x: _merge_network(x, 2 * B))(pairs)


def _reverse_block(x: jnp.ndarray) -> jnp.ndarray:
    """Reverse element order of a (NP, RB, 128) block (rows and lanes)."""
    return x[:, ::-1, ::-1]


def _tiled_sort(stacked: jnp.ndarray, P: int, impl: str, interpret: bool,
                block_rows: int) -> jnp.ndarray:
    """Batcher bitonic network over SORTED BLOCKS with merge-split
    compare-exchanges (the standard lift of a sorting network to sorted
    runs, 0-1-principle correct): inputs larger than one VMEM block sort
    block-by-block (each block a single-kernel network), then log^2(nb)
    merge-split passes — every kernel invocation stays VMEM-sized, so the
    Pallas path covers arbitrarily large inputs (VERDICT r4 #4; the
    reference's analog is the rdx_sort + loser-tree merge pair)."""
    NP = stacked.shape[0]
    RB = block_rows // _LANES
    nb = P // block_rows
    x = stacked.reshape(NP, nb, RB, _LANES)

    # ---- phase 1: sort each block independently (VMEM-resident network);
    # lax.map traces the kernel ONCE and runs blocks sequentially — the
    # per-block program (pallas or jnp) stays within the VMEM budget
    def sort_block(blk):
        if impl == "pallas":
            return _run_pallas(blk, block_rows, interpret)
        return _network(blk, block_rows)

    x = jnp.moveaxis(lax.map(sort_block, jnp.moveaxis(x, 1, 0)), 0, 1)

    # ---- phase 2: Batcher network over blocks; merge-split per exchange
    k = 2
    while k <= nb:
        j = k // 2
        while j >= 1:
            lo_ids = [i for i in range(nb) if not i & j]
            pairs = []
            for i in lo_ids:
                a, b = x[:, i], x[:, i ^ j]
                pairs.append(jnp.concatenate([a, _reverse_block(b)], axis=1))
            merged = _merge_pairs(jnp.stack(pairs), block_rows, impl, interpret)
            new_blocks: list = [None] * nb
            for pi, i in enumerate(lo_ids):
                lo, hi = merged[pi, :, :RB, :], merged[pi, :, RB:, :]
                # i has bit j clear: it takes the MIN half unless its
                # k-region sorts descending (bit_k set) — the block-level
                # image of the element network's want_max = bit_j != bit_k
                desc = (i & k) != 0
                new_blocks[i] = hi if desc else lo
                new_blocks[i ^ j] = lo if desc else hi
            x = jnp.stack(new_blocks, axis=1)
            j //= 2
        k *= 2
    return x.reshape(NP, P // _LANES, _LANES)


def bitonic_sort(
    operands: tuple,
    *,
    impl: str = "jnp",
    interpret: bool | None = None,
    narrow: tuple | None = None,
) -> tuple:
    """Stable ascending sort of an operand tuple; drop-in for
    ``lax.sort(operands, num_keys=len(operands)-1)`` where the last
    operand is a distinct int32 payload (iota). Requires that contract —
    the payload doubles as the stability tiebreak inside the network.
    interpret=None resolves to interpret-mode off-TPU (CPU tests exercise
    the kernel through the Pallas interpreter)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if narrow is None:
        narrow = (False,) * len(operands)
    cap = operands[0].shape[0]
    P = max(_next_pow2(cap), 8 * _LANES)
    planes = _split_planes(operands, narrow)
    # padding sorts last: all-ones exceeds every real key (dead-rows-last
    # keys are 0/1) and the payload slice below discards it anyway
    pad = jnp.full(P - cap, jnp.uint32(0xFFFFFFFF))
    stacked = jnp.stack(
        [jnp.concatenate([p, pad]).reshape(P // _LANES, _LANES) for p in planes]
    )
    n_planes = stacked.shape[0]
    single_block = n_planes * P * 4 * 3 <= _VMEM_GATE_BYTES
    if impl not in ("pallas", "jnp"):
        raise ValueError(f"bitonic impl {impl!r} (use lax.sort for 'lax')")
    if single_block:
        out = _run_pallas(stacked, P, interpret) if impl == "pallas" else _run_jnp(stacked, P)
    else:
        # tiled: per-kernel working set = one block pair; covers inputs of
        # any size (VERDICT r4 #4 — the 12MB gate no longer routes
        # perf-gate-scale partitions off the kernel path)
        block_rows = 8 * _LANES
        while n_planes * (4 * block_rows) * 4 * 3 <= _VMEM_GATE_BYTES and block_rows < P // 2:
            block_rows *= 2
        out = _tiled_sort(stacked, P, impl, interpret, block_rows)
    flat = out.reshape(out.shape[0], P)[:, :cap]
    # recombine planes -> original operand dtypes (narrow: hi is zero;
    # signed: undo the sign bias applied in _split_planes)
    result = []
    i = 0
    for op, nw in zip(operands, narrow):
        if op.dtype == jnp.uint64:
            if nw:
                w = flat[i].astype(jnp.uint64)
                i += 1
            else:
                w = (flat[i].astype(jnp.uint64) << jnp.uint64(32)) | flat[
                    i + 1
                ].astype(jnp.uint64)
                i += 2
            result.append(w)
        elif op.dtype == jnp.int64:
            hi = flat[i] ^ jnp.uint32(0x80000000)
            w = (hi.astype(jnp.uint64) << jnp.uint64(32)) | flat[i + 1].astype(
                jnp.uint64
            )
            result.append(w.view(jnp.int64))
            i += 2
        elif op.dtype == jnp.int32:
            result.append((flat[i] ^ jnp.uint32(0x80000000)).view(jnp.int32))
            i += 1
        else:
            result.append(flat[i].astype(op.dtype))
            i += 1
    return tuple(result)


def ordered_sort(
    operands: tuple,
    word_narrow: tuple | None = None,
    impl: str | None = None,
    conf=None,
) -> tuple:
    """ORDER-BY path dispatch: drop-in for
    ``lax.sort(operands, num_keys=len(operands)-1)`` over
    ``(live, *order_words, iota)`` operands (exec/sort_exec.py,
    exec/window_exec.py — both eager, so this owns the impl resolution).
    word_narrow marks order words with statically-zero hi halves (the 0/1
    null-placement words sortkeys emits — sortkeys.narrow_flags); the
    liveness key always rides narrow, the iota payload is the stability
    tiebreak."""
    n_words = len(operands) - 2
    if word_narrow is None:
        word_narrow = (False,) * n_words
    assert len(word_narrow) == n_words, (len(word_narrow), n_words)
    if impl is None:
        impl = sort_impl_for(  # auronlint: sort-payload -- generic ORDER BY: the operand planes ARE the user's sort keys, all must participate
            n_words, operands[0].shape[0], n_narrow_words=sum(word_narrow),
            conf=conf,
        )
    if impl in ("jnp", "pallas"):
        narrow = (True, *word_narrow, False)
        return bitonic_sort(operands, impl=impl, narrow=narrow)
    return lax.sort(operands, num_keys=len(operands) - 1)


def sort_impl_for(n_words: int, cap: int, n_narrow_words: int = 1, conf=None) -> str:
    """Trace-time choice of the cluster-sort implementation for a
    (dead_key, *words, iota) operand tuple: 'lax' | 'jnp' | 'pallas'.
    Resolved from config OUTSIDE jit (like hostsort.use_host_sort) —
    callers must thread it as a static argument. n_narrow_words = how many
    of the words ride as single planes (segment_by_keys narrows the
    null-bits word for <= 32 key columns). ``conf``: REQUIRED on any path
    a cross-thread spill merge can reach — active_conf() is thread-local
    and would resolve a foreign task's sort impl there (R7)."""
    mode = (conf if conf is not None else active_conf()).get(DEVICE_SORT_IMPL)
    if mode in ("lax", "jnp", "pallas"):
        return mode
    # auto: lax.sort everywhere until a chip run has compared the two
    # (bench_sort.py; ROADMAP S8). The fully-unrolled network's Mosaic
    # compile grows with log^2(P) substages — minutes at P >= 131072 — so
    # the kernel is opt-in (device.sort.impl=pallas), never the default.
    # n_words/n_narrow_words/cap stay in the signature for callers'
    # static cfg keys.
    return "lax"
