"""Device group-by primitives: key normalization, sort-segmentation, reducers.

The reference aggregates through an in-memory hash table with
cardinality-adaptive switching to sorted merge
(datafusion-ext-plans/src/agg/agg_table.rs:474-520). Pointer-chasing hash
tables don't map to the TPU's vector units, so the TPU-native design is
**sort-segmented grouping**, which is also exact (no hash collisions):

1. each group-key column is normalized to a canonical uint64 word
   (0 for NULL; a packed null-bits word distinguishes NULL from 0 and makes
   SQL GROUP BY treat NULLs as equal);
2. a sort clusters equal keys (dead rows — sel=0 — sort to the end via a
   leading liveness key). Two forms: the legacy multi-operand sort over
   every key word, and the INCREMENTAL fingerprint form (docs/agg.md) that
   sorts only ``(dead, fingerprint64(words), iota)`` — 3 fixed operands —
   and gathers the columns by the permutation;
3. segment boundaries are adjacent-difference compares over the FULL words
   (exact even when fingerprints collide — a collision is detected and
   flagged, never assumed away); segment ids are a cumsum; every aggregate
   becomes a ``jax.ops.segment_*`` reduction with a **static** segment
   count equal to the batch capacity.

Fingerprint-sorted runs additionally merge WITHOUT sorting via the
binsearch merge-rank (``merge_rank_order`` / ``segment_merged``) — the
merge-path half of the incremental design.

Output groups land in a padded batch (one slot per potential group) with a
validity prefix — shapes stay static for XLA, the dynamic group count only
matters host-side when slicing results.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
from functools import partial
import jax.numpy as jnp
from jax import lax

from auron_tpu import obs
from auron_tpu import types as T
from auron_tpu.obs.launch import ejnp
# top-level on purpose: hashing holds module-level jnp constants — a lazy
# import inside a jitted function would CREATE them under the trace and
# leak dead tracers into the module cache
from auron_tpu.ops import binsearch, hashing
from auron_tpu.ops.floatbits import f64_equality_word
from auron_tpu.exprs.eval import ColumnVal


def key_words(vals: list[ColumnVal]) -> list[jnp.ndarray]:
    """Canonical uint64 equality words for group keys: one word per column
    plus one packed null-bits word per 64 columns."""
    words: list[jnp.ndarray] = []
    null_bits = None
    for i, cv in enumerate(vals):
        w = _canonical_word(cv)
        words.append(ejnp.where(cv.validity, w, jnp.uint64(0)))
        bit = ejnp.where(cv.validity, jnp.uint64(0), jnp.uint64(1) << jnp.uint64(i % 64))
        null_bits = bit if null_bits is None else (null_bits | bit)
    if null_bits is not None:
        words.append(null_bits)
    return words


def _canonical_word(cv: ColumnVal) -> jnp.ndarray:
    dt = cv.dtype
    v = cv.values
    if dt.kind == T.TypeKind.BOOL:
        return v.astype(jnp.uint64)
    if dt.is_dict_encoded:
        # codes are equality keys within a unified-dictionary context
        # (wide decimals included — must beat the DECIMAL branch below)
        return v.astype(jnp.int64).view(jnp.uint64)
    if dt.is_integer or dt.kind in (T.TypeKind.DATE32, T.TypeKind.TIMESTAMP, T.TypeKind.DECIMAL):
        return v.astype(jnp.int64).view(jnp.uint64)
    if dt.kind == T.TypeKind.FLOAT32:
        # normalize -0.0 == 0.0 and NaNs equal (Spark group-by semantics)
        f = v.astype(jnp.float32)
        f = jnp.where(f == 0, jnp.float32(0), f)
        f = jnp.where(jnp.isnan(f), jnp.float32(jnp.nan), f)
        return f.view(jnp.uint32).astype(jnp.uint64)
    if dt.kind == T.TypeKind.FLOAT64:
        f = v.astype(jnp.float64)
        f = jnp.where(f == 0, jnp.float64(0), f)
        f = jnp.where(jnp.isnan(f), jnp.float64(jnp.nan), f)
        return f64_equality_word(f)
    if dt.is_dict_encoded:
        # codes are equality keys within a unified-dictionary context
        return v.astype(jnp.int64).view(jnp.uint64)
    raise TypeError(f"ungroupable type {dt}")


class Segmentation(NamedTuple):
    order: jnp.ndarray  # permutation clustering equal keys, dead rows last
    seg_ids: jnp.ndarray  # per sorted position; dead rows -> cap (overflow bucket)
    boundary: jnp.ndarray  # bool per sorted position: first of its segment
    group_of_slot: jnp.ndarray  # sorted position of each group's first row
    num_groups: jnp.ndarray  # dynamic scalar
    sel_sorted: jnp.ndarray  # liveness in sorted order
    # fingerprint-mode extras (None on the legacy full-word sort path):
    fp_sorted: jnp.ndarray | None = None  # uint64 fingerprints, sorted order
    collision: jnp.ndarray | None = None  # bool scalar: some fp run holds >1 key


def _finish_segmentation(
    order, sorted_words, sel_sorted, cap, fp_sorted=None
) -> Segmentation:
    """Shared segmentation tail over an ALREADY-CLUSTERED layout: boundaries
    from adjacent full-word compares (exact under fingerprint collisions —
    a colliding fp run splits at every key change instead of fusing keys),
    segment ids as a cumsum, first-row slots via segment_min.

    In fingerprint mode the collision flag marks batches where an fp run
    held more than one distinct key: such a batch's groups are correct but
    may be SPLIT (same key in two segments when a colliding key interleaves)
    and its fps are not unique — downstream (exec/agg_exec) counts it,
    excludes it from merge-path/probe fast paths, and re-reduces where a
    split group could escape to output."""
    word_change = jnp.zeros(cap, dtype=bool)
    for w in sorted_words:  # auronlint: disable=R1 -- loop over the key-word operand tuple (column count, not rows)
        word_change = word_change | jnp.concatenate(
            [jnp.zeros(1, bool), w[1:] != w[:-1]]
        )
    diff = word_change.at[0].set(True)
    boundary = diff & sel_sorted
    seg_ids_live = jnp.cumsum(boundary.astype(jnp.int32)) - 1
    seg_ids = jnp.where(sel_sorted, seg_ids_live, cap)
    num_groups = jnp.sum(boundary.astype(jnp.int32))
    group_of_slot = jax.ops.segment_min(
        jnp.arange(cap, dtype=jnp.int32), seg_ids, num_segments=cap + 1
    )[:cap]
    collision = None
    if fp_sorted is not None:
        fp_same = jnp.concatenate(
            [jnp.zeros(1, bool), fp_sorted[1:] == fp_sorted[:-1]]
        )
        live_adj = sel_sorted & jnp.concatenate(
            [jnp.zeros(1, bool), sel_sorted[:-1]]
        )
        collision = jnp.any(live_adj & fp_same & word_change)
    return Segmentation(
        order, seg_ids, boundary, group_of_slot, num_groups, sel_sorted,
        fp_sorted, collision,
    )


@partial(
    obs.jit,
    static_argnames=("host_sort", "device_impl", "n_key_cols", "fingerprint",
                     "fp_bits"),
)
def segment_by_keys(
    words: list[jnp.ndarray],
    sel: jnp.ndarray,
    order: jnp.ndarray | None = None,
    fp: jnp.ndarray | None = None,
    *,
    host_sort: bool,
    device_impl: str = "lax",
    n_key_cols: int = 0,
    fingerprint: bool = False,
    fp_bits: int = 64,
) -> Segmentation:
    """host_sort and device_impl are REQUIRED static values: callers must
    resolve them from config OUTSIDE the trace (jit caches are keyed by
    shapes, not config — a default resolved inside the trace would bake a
    stale choice into already-compiled programs). device_impl picks the
    on-device sort when host_sort is False: 'lax' | 'jnp' | 'pallas'
    (ops/bitonic.py network paths).

    With ``fingerprint`` the K+2-operand sort collapses to a fixed
    3-operand ``(dead, fingerprint64(words), iota)`` sort (iota as a key:
    fully stable, same tie order as the stable host lexsort); key/payload
    columns are gathered by the resulting permutation and segment
    boundaries still come from FULL word compares, so output is exact even
    when fingerprints collide (see _finish_segmentation). Groups emerge in
    fingerprint order, which exec/agg_exec exploits for sorted-state
    probing and merge-path merges.

    With host_sort, EVERY caller must precompute ``order`` eagerly
    (host_order / host_order_fp) and pass it as data: this function is
    itself jitted, so an order=None host_sort call compiles the
    pure_callback into an XLA:CPU program — and concurrent
    callback-bearing programs wedge the intra-op pool (runtime/task.py
    invariant). The in-trace callback is kept only as a
    single-threaded-context fallback."""
    from auron_tpu.ops import hostsort

    cap = sel.shape[0]
    dead_first_key = jnp.where(sel, jnp.uint64(0), jnp.uint64(1))
    iota = jnp.arange(cap, dtype=jnp.int32)
    # scope names (``auron.agg.*``, HLO metadata only) are what a device
    # trace names this program's operations by
    if fingerprint:
        if fp is None:
            # host-sort callers pass the fp they already computed for the
            # eager lexsort (host_order_fp) — hashing twice per batch would
            # cancel the narrower sort's savings
            with jax.named_scope("auron.agg.fingerprint"):
                fp = hashing.fingerprint64(words, fp_bits)
        with jax.named_scope("auron.agg.sort"):
            if host_sort:
                if order is None:
                    order = hostsort.order_by_words((dead_first_key, fp))
                sel_sorted = sel[order]
                fp_sorted = fp[order]
            else:
                # iota is a KEY (num_keys=3): ties resolve in batch order,
                # the same stable semantics as the host lexsort — `first`
                # and staged-run layouts stay identical across backends
                # auronlint: sort-payload -- fixed 3-operand fingerprint sort (the payload-thin form)
                s_dead, fp_sorted, order = lax.sort(
                    (dead_first_key, fp, iota), num_keys=3
                )
                # the sort already emitted the sorted planes — no re-gather
                sel_sorted = s_dead == 0
        with jax.named_scope("auron.agg.key_gather"):
            sorted_words = tuple(w[order] for w in words)
        with jax.named_scope("auron.agg.boundaries"):
            return _finish_segmentation(
                order, sorted_words, sel_sorted, cap, fp_sorted=fp_sorted
            )
    if host_sort:
        if order is None:
            with jax.named_scope("auron.agg.sort"):
                order = hostsort.order_by_words((dead_first_key, *words))
        with jax.named_scope("auron.agg.key_gather"):
            sel_sorted = sel[order]
            sorted_words = tuple(w[order] for w in words)
    else:
        operands = [dead_first_key, *words, iota]
        with jax.named_scope("auron.agg.sort"):
            if device_impl in ("jnp", "pallas"):
                from auron_tpu.ops import bitonic

                # statically-zero hi planes skip the network: the 0/1 dead
                # key always; the null-bits word (last, by key_words
                # construction) when <= 32 key columns set bits in its low
                # half only
                narrow = [True] + [False] * len(words) + [False]
                if 0 < n_key_cols <= 32 and len(words) == n_key_cols + 1:
                    narrow[len(words)] = True
                # auronlint: sort-payload -- legacy full-word grouping sort: the operand list scales with key columns by design; the fingerprint path above is the thin form
                sorted_ops = bitonic.bitonic_sort(
                    tuple(operands), impl=device_impl, narrow=tuple(narrow)
                )
            else:
                # auronlint: sort-payload -- legacy full-word grouping sort (collision-free exact fallback for the fingerprint path)
                sorted_ops = lax.sort(tuple(operands),
                                      num_keys=len(operands) - 1)
            sel_sorted = sorted_ops[0] == 0
            sorted_words = sorted_ops[1:-1]
            order = sorted_ops[-1]
    with jax.named_scope("auron.agg.boundaries"):
        return _finish_segmentation(order, sorted_words, sel_sorted, cap)


def host_order(words: list[jnp.ndarray], sel: jnp.ndarray,
               where: str = "hostsort") -> jnp.ndarray:
    """EAGER host lexsort order for segment_by_keys(host_sort=True):
    identical tie semantics to the in-trace callback (dead rows last,
    stable). Call OUTSIDE jit; pass the result as ``order``. ``where``
    names the sort in its ring event (``obs.note_sort``)."""
    import numpy as np

    obs.note_sort(int(sel.shape[0]), where, "host")

    # auronlint: sync-point(2/batch) -- documented eager host boundary ("call OUTSIDE jit"); one batched transfer
    dead_d, words_d = jax.device_get(
        (ejnp.where(sel, jnp.uint64(0), jnp.uint64(1)), tuple(words)))
    operands = [np.asarray(dead_d), *[np.asarray(w) for w in words_d]]
    return jnp.asarray(np.lexsort(tuple(reversed(operands))).astype(np.int32))


@partial(obs.jit, static_argnames=("fp_bits",))
def _fp_dead_jit(words, sel, fp_bits: int):
    return (
        jnp.where(sel, jnp.uint64(0), jnp.uint64(1)),
        hashing.fingerprint64(list(words), fp_bits),
    )


def host_order_fp(
    words: list[jnp.ndarray], sel: jnp.ndarray, fp_bits: int = 64
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """EAGER host lexsort (order, fingerprints) for the fingerprint path:
    the fingerprint computes on device (one tiny jitted program) and only
    TWO arrays cross to the host — np.lexsort cost stops scaling with
    key-column count. np.lexsort is stable, matching the device path's
    iota tie key. The device fp array is returned so the downstream jit
    consumes it as data instead of hashing the words a second time."""
    import numpy as np

    obs.note_sort(int(sel.shape[0]), "hostsort", "host")
    dead_dev, fp_dev = _fp_dead_jit(tuple(words), sel, fp_bits)
    # auronlint: sync-point(2/batch) -- fingerprint host-sort boundary: 2 fixed arrays per batch regardless of key count (vs 2+K for host_order)
    dead_d, fp_d = jax.device_get((dead_dev, fp_dev))
    order = jnp.asarray(
        np.lexsort((np.asarray(fp_d), np.asarray(dead_d))).astype(np.int32)
    )
    return order, fp_dev


def merge_rank_order(
    fp: jnp.ndarray, sel: jnp.ndarray, cap_a: int
) -> jnp.ndarray:
    """Merge-path permutation for TWO fp-sorted runs laid out back to back
    in one array (A = [0, cap_a), B = [cap_a, cap)), each a live prefix
    sorted ascending by fingerprint. Returns the stable-merge order (A
    before B on ties) computed with two binary searches — O(n log n) word
    compares against the O(n log^2 n) multi-operand re-sort it replaces —
    placing dead/pad rows after every live row.

    Call inside jit; fp must already be masked to UINT64_MAX on dead rows.
    """
    cap = fp.shape[0]
    cap_b = cap - cap_a
    fp_a, fp_b = fp[:cap_a], fp[cap_a:]
    # A[i] lands after every B < it; B[j] after every A <= it (A wins ties,
    # so equal-fingerprint groups from the two runs come out ADJACENT)
    pos_a = jnp.arange(cap_a, dtype=jnp.int32) + binsearch.lower_bound_dyn(
        [fp_b], [fp_a], jnp.int32(cap_b)
    )
    pos_b = jnp.arange(cap_b, dtype=jnp.int32) + binsearch.upper_bound_dyn(
        [fp_a], [fp_b], jnp.int32(cap_a)
    )
    return (
        jnp.zeros(cap, jnp.int32)
        .at[pos_a].set(jnp.arange(cap_a, dtype=jnp.int32))
        .at[pos_b].set(cap_a + jnp.arange(cap_b, dtype=jnp.int32))
    )


def segment_merged(
    words: list[jnp.ndarray],
    sel: jnp.ndarray,
    cap_a: int,
    fp_bits: int = 64,
    fp: jnp.ndarray | None = None,
) -> Segmentation:
    """Segmentation of two back-to-back fp-sorted runs WITHOUT a sort:
    merge-rank the fingerprints (merge_rank_order), then the standard
    word-exact segmentation tail. The collision flag reports any fp run
    holding >1 distinct key in the merged layout (cross-run fingerprint
    collisions included). Call inside jit.

    ``fp``: the runs' cached dead-masked fingerprints laid out like the
    columns (exec/agg_exec passes the concatenated ``_inc_fp`` arrays so
    every pair merge skips the O(rows x K) re-hash)."""
    if fp is None:
        fp = hashing.fingerprint64(words, fp_bits)
        fp = jnp.where(sel, fp, jnp.uint64(0xFFFFFFFFFFFFFFFF))
    order = merge_rank_order(fp, sel, cap_a)
    sel_sorted = sel[order]
    sorted_words = tuple(w[order] for w in words)
    cap = sel.shape[0]
    return _finish_segmentation(
        order, sorted_words, sel_sorted, cap, fp_sorted=fp[order]
    )


# ---------------------------------------------------------------------------
# segment reducers (operate on *sorted* value arrays)
# ---------------------------------------------------------------------------


def _masked(vals: jnp.ndarray, mask: jnp.ndarray, identity) -> jnp.ndarray:
    return jnp.where(mask, vals, jnp.asarray(identity, dtype=vals.dtype))


def seg_sum(vals, valid, seg_ids, cap):
    s = jax.ops.segment_sum(_masked(vals, valid, 0), seg_ids, num_segments=cap + 1)[:cap]
    any_valid = jax.ops.segment_max(
        valid.astype(jnp.int32), seg_ids, num_segments=cap + 1
    )[:cap].astype(bool)
    return s, any_valid


def seg_count(valid, seg_ids, cap):
    return jax.ops.segment_sum(
        valid.astype(jnp.int64), seg_ids, num_segments=cap + 1
    )[:cap]


def seg_min(vals, valid, seg_ids, cap):
    ident = _max_identity(vals.dtype)
    m = jax.ops.segment_min(_masked(vals, valid, ident), seg_ids, num_segments=cap + 1)[:cap]
    any_valid = jax.ops.segment_max(valid.astype(jnp.int32), seg_ids, num_segments=cap + 1)[
        :cap
    ].astype(bool)
    return m, any_valid


def seg_max(vals, valid, seg_ids, cap):
    ident = _min_identity(vals.dtype)
    m = jax.ops.segment_max(_masked(vals, valid, ident), seg_ids, num_segments=cap + 1)[:cap]
    any_valid = jax.ops.segment_max(valid.astype(jnp.int32), seg_ids, num_segments=cap + 1)[
        :cap
    ].astype(bool)
    return m, any_valid


def seg_first(vals, valid, seg_ids, cap, ignores_null: bool):
    n = vals.shape[0]
    pos = jnp.arange(n, dtype=jnp.int32)
    eligible = valid if ignores_null else jnp.ones_like(valid)
    pos_or_inf = jnp.where(eligible, pos, n)
    first_pos = jax.ops.segment_min(pos_or_inf, seg_ids, num_segments=cap + 1)[:cap]
    safe = jnp.clip(first_pos, 0, n - 1)
    fv = vals[safe]
    fm = valid[safe] & (first_pos < n)
    return fv, fm


def _max_identity(dtype):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.inf
    if dtype == jnp.bool_:
        return True
    return jnp.iinfo(dtype).max


def _min_identity(dtype):
    if jnp.issubdtype(dtype, jnp.floating):
        return -jnp.inf
    if dtype == jnp.bool_:
        return False
    return jnp.iinfo(dtype).min


# ---------------------------------------------------------------------------
# exact integer segment sums by int32 limbs (the dense aggregate's scatters)
# ---------------------------------------------------------------------------

# What one DEAD row of a scatter costs on the TPU (a row routed to the drop
# segment: what compaction saves; a live row is scattered on either side of
# the choice), in the unit ``columnar.batch.compaction_bucket`` counts in:
# one int32 plane gathered by a random index, 7.5 ns a row of output. A
# scatter of a 64-bit plane (an int64 or float64 ``segment_sum``, minimum or
# maximum) is SCATTER_WIDE of them, a scatter of a 32-bit or bool plane (an
# int32 / float32 sum or maximum, one LIMB of an integer sum, the
# ``segment_max`` of the present / valid flags) SCATTER_NARROW. Readings on
# the v5e (PERF.md section 5, "unit costs", PR 37; ``tools/scatter_costs.py``
# reads them again). Alone, 20 calls ending in block_until_ready at
# 4,194,304 rows into 262,144 slots: a 64-bit plane 69.0 ns a row with every
# row to the drop segment, 75.1 ns with a tenth live, 123.8 ns uniform; a
# 32-bit one 8.8 / 7.9 / 6.6 ns: a dead row is the DEAREST of a narrow
# scatter (they all hit one slot) and the cheapest of a wide one. Inside
# query 65's fold: all dead 8.8 ns a narrow plane and 69 ns the int64 sum;
# in the traced cell 8.4 ns a dead row between live ones a narrow plane.
# The constants are the least DEAD-row reading of each (8.4 and 69.0 ns),
# rounded down: where the fold is cheaper than reckoned, compacting must
# not be chosen in its place. (PR 35 held 0.5 and 4, from a traced cell's
# seconds shared out over live and dead rows: with 0.5 query 65's
# tenth-live batch stayed dense and the query read 0.04 s more, PR 37.)
SCATTER_WIDE = 9.0
SCATTER_NARROW = 1.0
#: the most int32 limbs an integer sum is split into; one that would need
#: more keeps its 64-bit scatter. k planes scattered one by one cost k
#: narrow scatters (PR 37, alone as above, 3 / 5 / 7 / 8 limbs: 19.9 / 33.2
#: / 46.6 / 53.1 ns a row uniform, 26.3 / 43.8 / 61.3 / 70.0 ns with every
#: row to one slot, against 123.8 and 69.0 ns for the 64-bit scatter; in
#: query 65's fold 3 limbs and the flags 33.6 ns a live row where the
#: int64 sum and the flags read 137.4): eight break even on a batch of dead
#: rows alone, which the arm's compaction takes first, and pay 2.3 times
#: over on live ones. An int64 needs seven up to 4,194,304 rows, eight at
#: 8,388,608. (ONE scatter of [rows, k] windows reads 16.2-17.0 ns a row
#: whatever k, but XLA lays the windows out 128 lanes wide: 2.3 GB of
#: temporaries at 4,194,304 rows and 24-27 s to compile. Not taken.)
LIMBS_PAY_UP_TO = 8


class LimbPlan(NamedTuple):
    """How an integer plane is summed by 32-bit scatters (``limb_plan``)."""

    bits: int   # b: the width of a low limb
    limbs: int  # k: the planes scattered
    cover: int  # two's-complement bits of a value the limbs hold, <= 64


def limb_plan(value_bits: int, rows: int) -> LimbPlan | None:
    """The limbs an exact segment sum of ``rows`` integers of ``value_bits``
    two's-complement bits (sign included) is taken by, or None where one
    64-bit scatter is cheaper. A rule over a type's width and a shape.

    The worst slot receives every row, and a limb's sum must fit its int32
    accumulator: a low limb is ``b`` unsigned bits with ``rows <= 2^(31-b)``
    (``rows x (2^b - 1) < 2^31``); the TOP limb is what the arithmetic shift
    leaves, sign and all, and may hold ``b + 1`` bits (``rows x 2^b <=
    2^31`` in magnitude, and -2^31 itself fits). So ``k`` limbs hold
    ``k x b + 1`` bits: ``k = ceil((value_bits - 1) / b)``. A DECIMAL(7,2)
    sum (24 bits and a sign) at 4,194,304 rows: b = 9, k = 3; a merge of
    DECIMAL(17,2) (57 bits and a sign) at 131,072 rows: b = 14, k = 5; an
    int64 at 4,194,304 rows: k = 7, recombined mod 2^64, which is the
    wrapping sum a 64-bit scatter gives."""
    b = 31 - max(rows - 1, 1).bit_length()
    if b < 1:
        return None
    k = max(-(-(value_bits - 1) // b), 1)
    if k > LIMBS_PAY_UP_TO:
        return None
    return LimbPlan(b, k, min(k * b + 1, 64))


def split_limbs(vals, b: int, k: int) -> list:
    """An integer plane as ``k`` int32 planes: ``k - 1`` unsigned limbs of
    ``b`` bits from the low end, and what the arithmetic shift leaves above
    them, sign and all (``limb_plan`` says when that fits 32 bits)."""
    v = vals.astype(jnp.int64)
    mask = jnp.int64((1 << b) - 1)
    limbs = [((v >> (i * b)) & mask).astype(jnp.int32) for i in range(k - 1)]
    return limbs + [(v >> ((k - 1) * b)).astype(jnp.int32)]


def seg_sum_limbs(vals, ids, nseg: int, value_bits: int, checked: bool):
    """Exact int64 segment sums of an integer plane through int32 scatters.

    ``vals`` holds 0 in every row that must not count (dead, NULL); ``ids``
    routes those to a drop segment or anywhere. ``value_bits`` is what the
    column's TYPE says its values occupy (sign included): the limbs follow
    from it and from the rows (``limb_plan``), each is scattered as an int32
    plane, and the limb sums are recombined at the table's width, shifted
    into place and added mod 2^64. ``checked`` says that width is a promise
    and not the plane's physical one (a DECIMAL's precision over its int64
    plane): the program then checks, in the pass that splits the values,
    that every one lies inside the bits the limbs hold, and takes the
    64-bit scatter where one does not (both arms compiled, one run), so a
    plane that breaks its declared precision is still summed exactly."""
    plan = limb_plan(value_bits, vals.shape[0])
    if plan is None:
        return jax.ops.segment_sum(vals.astype(jnp.int64), ids, num_segments=nseg)
    b, k, cover = plan

    def narrow(v):
        out = None
        for i, limb in enumerate(split_limbs(v, b, k)):
            part = jax.ops.segment_sum(limb, ids, num_segments=nseg)
            part = part.astype(jnp.int64) << (i * b)
            out = part if out is None else out + part
        return out

    if not checked or cover >= 64:
        return narrow(vals)
    v64 = vals.astype(jnp.int64)
    half = jnp.int64(1 << (cover - 1))
    fits = jnp.all((v64 >= -half) & (v64 < half))
    return lax.cond(
        fits, narrow,
        lambda v: jax.ops.segment_sum(v, ids, num_segments=nseg), v64)
