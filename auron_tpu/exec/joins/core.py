"""Equi-join core shared by sort-merge and hash joins.

Join-type semantics mirror the reference's matrix (Inner/Left/Right/Full/
LeftSemi/LeftAnti/Existence — auron.proto:508-517, tested by
datafusion-ext-plans/src/joins/test.rs). The execution strategy is
TPU-first: the build side becomes a **sorted-array map** (canonical key
words + one device sort; analog of joins/join_hash_map.rs but
vector-friendly), probes are batched branchless binary searches
(ops/binsearch.py), and pair output is a capacity-bucketed *ragged
expansion*: per-probe match counts -> cumsum offsets -> searchsorted slot
decoding, emitted in fixed-shape chunks. The only host syncs are one per
probe batch (total match count) — everything else stays on device.

SQL null semantics: a NULL in any join key never matches (probe rows with
null keys get count 0); join conditions (non-equi residual predicates)
filter candidate pairs *before* outer/semi/anti matching is decided, as in
Spark.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Iterator

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa
from jax import lax

from auron_tpu import obs
from auron_tpu import types as T
from auron_tpu.columnar.batch import (
    Batch,
    DeviceBatch,
    bucket_capacity,
    compaction_index,
    device_concat,
    lookup_compare_width,
    lookup_form,
    note_index,
)
from auron_tpu.exec.basic import batch_from_columns
from auron_tpu.exprs import Evaluator, ir
from auron_tpu.exprs.eval import ColumnVal
from auron_tpu.obs.launch import ejnp
from auron_tpu.ops import binsearch, hashmap
from auron_tpu.ops.floatbits import f64_equality_word
from auron_tpu.ops import segments as S

INNER = "inner"
LEFT = "left"
RIGHT = "right"
FULL = "full"
LEFT_SEMI = "left_semi"
LEFT_ANTI = "left_anti"
EXISTENCE = "existence"

JOIN_TYPES = (INNER, LEFT, RIGHT, FULL, LEFT_SEMI, LEFT_ANTI, EXISTENCE)

# pair slots per emitted chunk: large enough that per-chunk dispatch +
# deferred-agg flag reads amortize (a q72-scale expansion emits hundreds
# of millions of pairs; 256k chunks meant ~1300 chunk round-trips), small
# enough that a chunk's gathered columns stay modest (~8 MB/column)
_EXPAND_CHUNK = 1 << 20


def join_output_schema(
    left: T.Schema, right: T.Schema, join_type: str, exists_col: str = "exists"
) -> T.Schema:
    if join_type in (LEFT_SEMI, LEFT_ANTI):
        return left
    if join_type == EXISTENCE:
        return T.Schema(tuple(left.fields) + (T.Field(exists_col, T.BOOL, False),))
    lf = [T.Field(f.name, f.dtype, True) for f in left.fields]
    rf = [T.Field(f.name, f.dtype, True) for f in right.fields]
    return T.Schema(tuple(lf + rf))


@dataclass
class PreparedBuild:
    batch: Batch  # build rows, clustered by key (sorted), dead rows last
    words: list[jnp.ndarray]  # canonical key words, sorted order
    n_live: int  # live row count (host)
    matched: jnp.ndarray  # bool per build row, updated across probe batches
    # -- unique-key fast path (PK-like build sides) --
    # When every live build key is distinct, each probe row has at most one
    # match, so the join degenerates to one gather: no ragged expansion, no
    # per-batch host sync. Dimension-table joins (the common BHJ shape) are
    # almost always in this regime.
    unique: bool = False
    # dense direct-address table: lut[word - lut_base] = build row index
    # (or -1). Built when the single key is integer-like with a small value
    # range (surrogate-key dims); turns the probe into a single O(1) gather.
    lut: jnp.ndarray | None = None
    lut_base: int = 0  # key-value base (signed int of words.min())
    # existence-only table for duplicate-keyed builds probed by semi/anti
    # (no pair enumeration needed): exists_lut[key - lut_base] per probe row
    # replaces the binary search — and lets the build skip its sort.
    exists_lut: jnp.ndarray | None = None
    # live key list of a SMALL unique build with one key word (the width
    # is columnar.batch.lookup_compare_width's): (keys[K], rows[K]), the
    # live keys (int32 offsets of lut_base where the LUT exists, else the
    # key words) and their build rows, pads -1 in rows. The probe then
    # COMPARES a probe row against the list instead of gathering from the
    # LUT or searching the sorted words (_probe_unique_ops)
    key_list: tuple[jnp.ndarray, jnp.ndarray] | None = None
    # multi-integer-key packing: when set, ``words`` is ONE packed uint64
    # word and probes must pack their key words with the same spec
    pack: "PackSpec | None" = None
    # unique-run compression of a duplicate-keyed sorted build (CSR over
    # the sorted rows): probes do ONE binary search over DISTINCT keys
    # instead of two over all rows — the analog of the reference's one
    # hash-map entry per distinct key (join/join_hash_map.rs)
    uniq_words: list | None = None
    run_starts: jnp.ndarray | None = None  # [cap+1]; run i is rows
    # [run_starts[i], run_starts[i+1]) of the sorted build
    n_uniq: "jnp.ndarray | int" = 0  # device scalar (never synced)
    # the hash map of a unique one-word build too wide for a LUT or a key
    # list (ops/hashmap.py, where columnar.batch.lookup_form says so): the
    # build then stays unsorted, ``batch`` and ``words`` in arrival order
    hash_table: hashmap.HashTable | None = None

    @property
    def probe_table(self):
        """What the unique probe is handed beside the LUT: the hash map,
        the live key list, or None (the sorted words)."""
        return self.hash_table if self.hash_table is not None else self.key_list


def _key_columns(batch: Batch, key_exprs: list[ir.Expr]) -> list[ColumnVal]:
    return Evaluator(batch.schema).evaluate(batch, key_exprs)


def _canon_words(vals: list[ColumnVal]) -> tuple[list[jnp.ndarray], jnp.ndarray]:
    """Equality words per key + all-keys-valid mask (null keys never join)."""
    words = []
    valid = None
    for cv in vals:
        w = S._canonical_word(cv)
        words.append(ejnp.where(cv.validity, w, jnp.uint64(0)))
        valid = cv.validity if valid is None else (valid & cv.validity)
    return words, valid


def unify_key_dicts(
    build_vals: list[ColumnVal], probe_vals: list[ColumnVal]
) -> tuple[list[ColumnVal], list[ColumnVal]]:
    """Remap dict-encoded key pairs onto a joint vocabulary so codes are
    directly comparable equality words."""
    out_b, out_p = [], []
    for bv, pv in zip(build_vals, probe_vals):
        if not bv.dtype.is_dict_encoded:
            out_b.append(bv)
            out_p.append(pv)
            continue
        vocab: dict = {}
        remaps = []
        for d in (bv.dict, pv.dict):
            pl = d.to_pylist()
            m = np.empty(len(pl), dtype=np.int64)
            for i, s in enumerate(pl):
                m[i] = vocab.setdefault(s, len(vocab))
            remaps.append(m)
        nb = jnp.asarray(remaps[0])[jnp.clip(bv.values, 0, len(remaps[0]) - 1)]
        np_ = jnp.asarray(remaps[1])[jnp.clip(pv.values, 0, len(remaps[1]) - 1)]
        if bv.dtype.kind == T.TypeKind.DECIMAL:
            joint_type = bv.dtype.to_arrow()
            filler = []
        elif bv.dtype.kind == T.TypeKind.BINARY:
            joint_type, filler = pa.binary(), [b""]
        else:
            joint_type, filler = pa.string(), [""]
        joint = pa.array(list(vocab.keys()) or filler, type=joint_type)
        out_b.append(ColumnVal(nb.astype(jnp.int32), bv.validity, bv.dtype, joint))
        out_p.append(ColumnVal(np_.astype(jnp.int32), pv.validity, pv.dtype, joint))
    return out_b, out_p


@partial(obs.jit, static_argnames=("device_sort",))
def _prepare_build_jit(key_sel, row_sel, words, values, validity, order, *,
                       device_sort: bool):
    """Fused build-side preparation: cluster rows by key and compute the
    uniqueness/key-range stats in ONE compiled program (the whole build was
    previously ~40 eager primitives — each a separate unfused pass over a
    capacity-sized buffer, which is what collapsed the join-heavy perf-gate
    classes). ``order`` is the host lexsort permutation on CPU hosts
    (ops/hostsort.py rationale) and None on accelerators, where the sort
    runs in-program on device."""
    cap = key_sel.shape[0]
    if device_sort:
        live_first = jnp.where(key_sel, jnp.uint64(0), jnp.uint64(1))
        iota = jnp.arange(cap, dtype=jnp.int32)
        sorted_ops = lax.sort(  # auronlint: sort-payload -- join build clustering probes by FULL key words (binsearch equality); a fingerprint plane cannot serve lexicographic probes
            tuple([live_first, *words, iota]), num_keys=len(words) + 1
        )
        sorted_words = tuple(sorted_ops[1:-1])
        order = sorted_ops[-1]
    else:
        sorted_words = tuple(w[order] for w in words)
    from auron_tpu.columnar.batch import device_take

    # null-keyed rows stay live (outer emits them): permute row_sel, not key_sel
    taken = device_take(DeviceBatch(row_sel, values, validity), order)
    row_sel_s, values_s, validity_s = taken.sel, taken.values, taken.validity
    n_live_dev = jnp.sum(key_sel)
    live_sorted = jnp.arange(cap) < n_live_dev  # live rows are a prefix
    dup = jnp.concatenate(
        [jnp.zeros(1, bool), _adjacent_all_eq(sorted_words)])
    # adjacent ALL-columns-equal, both rows live, marks a duplicate key
    has_dup = jnp.any(
        dup & live_sorted & jnp.concatenate([jnp.zeros(1, bool), live_sorted[:-1]])
    )
    w0 = sorted_words[0]
    kmin = w0[0]
    kmax = w0[jnp.clip(n_live_dev - 1, 0, cap - 1)]
    stats = jnp.stack([
        n_live_dev.astype(jnp.uint64),
        has_dup.astype(jnp.uint64),
        kmin,
        kmax,
    ])
    return row_sel_s, sorted_words, values_s, validity_s, stats



@obs.jit
def _presorted_stats_jit(sel, words):
    """(already_clustered, stats) in one tiny program: True when key-live
    rows form a prefix AND their word tuples are lexicographically
    non-decreasing (unsigned — the binary-search comparator's order).
    SMJ build sides straight from SortExec hit this; stats match
    _prepare_build_jit's layout so the caller is branch-transparent."""
    cap = sel.shape[0]
    n_live = jnp.sum(sel)
    prefix_ok = jnp.all(sel == (jnp.arange(cap) < n_live))
    in_prefix = jnp.arange(1, cap) < n_live  # positions 1..cap-1 with prev live
    # lexicographic non-decreasing: at the first differing word, prev <= cur
    lt = jnp.zeros(cap - 1, bool)   # prev < cur at an earlier word
    eq = jnp.ones(cap - 1, bool)    # all earlier words equal
    for w in words:
        a, b = w[:-1], w[1:]
        lt = lt | (eq & (a < b))
        eq = eq & (a == b)
    all_eq = _adjacent_all_eq(words)
    nondec = jnp.all(jnp.where(in_prefix, lt | eq, True))
    has_dup = jnp.any(in_prefix & all_eq)
    w0 = words[0]
    kmin = w0[0]
    kmax = w0[jnp.clip(n_live - 1, 0, cap - 1)]
    stats = jnp.stack([
        n_live.astype(jnp.uint64),
        has_dup.astype(jnp.uint64),
        kmin,
        kmax,
    ])
    return prefix_ok & nondec, stats


@obs.jit
def _key_minmax_jit(words, sel):
    """Per-key signed (min, max) over live rows — one tiny program feeding
    the multi-key packing decision."""
    mins, maxs = [], []
    imax = jnp.iinfo(jnp.int64).max
    imin = jnp.iinfo(jnp.int64).min
    for w in words:
        s = w.view(jnp.int64)
        mins.append(jnp.min(jnp.where(sel, s, imax)))
        maxs.append(jnp.max(jnp.where(sel, s, imin)))
    return jnp.stack(mins), jnp.stack(maxs)


@dataclass(frozen=True)
class PackSpec:
    """Multi-key -> single-word packing parameters (build-side ranges)."""

    mins: tuple  # signed per-key minimum
    maxs: tuple  # signed per-key maximum
    shifts: tuple  # left-shift per key (leading key highest)


_PACKABLE_KINDS = (
    T.TypeKind.INT8, T.TypeKind.INT16, T.TypeKind.INT32, T.TypeKind.INT64,
    T.TypeKind.DATE32, T.TypeKind.TIMESTAMP, T.TypeKind.BOOL,
)


def _maybe_pack(vals, words, sel) -> PackSpec | None:
    """Decide multi-integer-key packing from build-side ranges (one sync).
    Packing halves every downstream word-tuple pass: the build sort, the
    presorted check, and each of the probe's ~2*log2(n) binary-search
    gathers."""
    if len(words) < 2:
        return None
    for cv in vals:
        if cv.dtype.kind not in _PACKABLE_KINDS or cv.dtype.is_dict_encoded:
            return None
    mins, maxs = (x.tolist() for x in jax.device_get(_key_minmax_jit(tuple(words), sel)))  # auronlint: sync-point(8/task) -- one fused min/max read decides LUT eligibility per build
    if any(mn > mx for mn, mx in zip(mins, maxs)):  # no live rows
        return None
    bits = [max(int(mx - mn).bit_length(), 1) for mn, mx in zip(mins, maxs)]
    if sum(bits) > 63:
        return None
    shifts = []
    acc = 0
    for b in reversed(bits):  # last key sits in the low bits
        shifts.append(acc)
        acc += b
    shifts = tuple(reversed(shifts))
    return PackSpec(mins=tuple(mins), maxs=tuple(maxs), shifts=shifts)


@obs.jit
def _pack_probe_words_jit(words, valid, mins, maxs, shifts):
    """Apply a build-side PackSpec to probe words in one program: rows
    whose key falls outside the build's per-key range can never match —
    masked invalid (their clamped packed word may alias a real build
    key). mins/maxs/shifts arrive as DYNAMIC scalars (one compile per
    word count, not per data-dependent key range)."""
    in_range = None
    acc = jnp.zeros(words[0].shape, jnp.uint64)
    for i, w in enumerate(words):
        s = w.view(jnp.int64)
        ok = (s >= mins[i]) & (s <= maxs[i])
        in_range = ok if in_range is None else (in_range & ok)
        off = jnp.clip(s - mins[i], 0, None).astype(jnp.uint64)
        acc = acc | (off << shifts[i])
    new_valid = in_range if valid is None else (valid & in_range)
    return acc, new_valid


def _pack_probe_jit(words, valid, spec: PackSpec):
    return _pack_probe_words_jit(
        tuple(words), valid,
        jnp.asarray(spec.mins, jnp.int64),
        jnp.asarray(spec.maxs, jnp.int64),
        jnp.asarray(spec.shifts, jnp.uint64),
    )



@obs.jit
def _key_range_jit(w0, sel):
    """(n_live, kmin, kmax) of the live signed key values — the no-sort
    pre-pass deciding whether a dense LUT can replace the sorted-array map."""
    s = w0.view(jnp.int64)
    n_live = jnp.sum(sel)
    kmin = jnp.min(jnp.where(sel, s, jnp.iinfo(jnp.int64).max))
    kmax = jnp.max(jnp.where(sel, s, jnp.iinfo(jnp.int64).min))
    return jnp.stack([n_live, kmin, kmax])


@partial(obs.jit, static_argnames=("size",))
def _scatter_luts_jit(w0, sel, kmin, size: int):
    """Dense tables straight from the unsorted build — no sort pass.
    Returns (row_lut, exists, has_dup): row_lut maps key-kmin -> original
    row index (valid only when !has_dup), exists marks occupied slots."""
    cap = w0.shape[0]
    idx = (w0.view(jnp.int64) - kmin).astype(jnp.int32)
    slot = jnp.where(sel, idx, size)
    counts = jnp.zeros(size, jnp.int32).at[slot].add(1, mode="drop")
    row_lut = (
        jnp.full(size, -1, jnp.int32)
        .at[slot]
        .set(jnp.arange(cap, dtype=jnp.int32), mode="drop")
    )
    has_dup = jnp.any(counts > 1)
    return row_lut, counts > 0, has_dup


@partial(obs.jit, static_argnames=("width",))
def _key_list_jit(w0, sel, base, *, width: int):
    """(keys[width], rows[width]) of a unique build's live keys: int32
    offsets of ``base`` (the LUT's), or the key words where ``base`` is
    None. A pad's row is -1, so whatever its key it can never match."""
    idx, live = compaction_index(sel, width)
    w = w0[idx]
    keys = w if base is None else (w.view(jnp.int64) - base).astype(jnp.int32)
    return jnp.where(live, keys, 0), jnp.where(live, idx, -1)


def _small_key_list(w0, sel, n_live: int, base):
    """The live key list of a unique one-word build, where the lookup
    policy says comparing beats its map: the LUT at ``base``, or (None)
    the binary search over the sorted words."""
    width = lookup_compare_width(
        n_live, None if base is not None else w0.shape[0])
    if width is None:
        return None
    note_index(w0.shape[0], width)
    return _key_list_jit(w0, sel, base, width=width)


def lookup_kind(build: PreparedBuild) -> str:
    """How the unique probe maps a key to its build row: ``compare``
    (the live key list), ``lut`` (one gather), ``hash`` (the hash map: two
    slots and a row gathered) or ``search`` (the sorted words: a gather for
    every bit of the build's capacity)."""
    if build.hash_table is not None:
        return "hash"
    if build.key_list is not None:
        return "compare"
    return "lut" if build.lut is not None else "search"


def prepare_build(
    batches: list[Batch],
    key_exprs: list[ir.Expr],
    schema: T.Schema,
    need_pairs: bool = True,
    conf=None,
) -> PreparedBuild:
    """``need_pairs=False`` (semi/anti probes that only test existence)
    licenses the duplicate-tolerant LUT fast path: with duplicates and no
    pair enumeration the build can stay unsorted behind an existence table."""
    from auron_tpu.ops import hostsort

    if batches:
        big = device_concat(batches)
    else:
        big = Batch.empty(schema)
    vals = _key_columns(big, key_exprs)
    words, valid = _canon_words(vals)
    sel = big.device.sel & (valid if valid is not None else True)
    cap = big.capacity
    dev = big.device

    # ---- multi-integer-key packing: one word for every downstream pass
    pack = _maybe_pack(vals, words, sel) if cap > 0 else None
    if pack is not None:
        packed, _ = _pack_probe_jit(tuple(words), None, pack)
        words = [packed]

    # ---- sort-free maps: one integer-like word (a key or a packed key),
    # by a LUT over a small value range, else by a hash map
    if (
        cap > 0
        and len(words) == 1
        and vals[0].dtype.kind
        in (T.TypeKind.INT8, T.TypeKind.INT16, T.TypeKind.INT32, T.TypeKind.INT64,
            T.TypeKind.DATE32, T.TypeKind.TIMESTAMP)
        and not vals[0].dtype.is_dict_encoded
    ):
        n_live, kmin_h, kmax_h = (int(x) for x in jax.device_get(_key_range_jit(words[0], sel)))  # auronlint: sync-point(8/task) -- one fused key-range read per build
        # pigeonhole pre-check: more live rows than distinct slots guarantees
        # duplicates, so a pairs-producing build can never be unique — skip
        # the scatter pass (and its sync) instead of building tables that the
        # duplicates+pairs fallthrough would discard
        cannot_be_unique = n_live > kmax_h - kmin_h + 1
        lut = (
            n_live > 0
            and 0 <= kmax_h - kmin_h < min(max(4 * cap, 1 << 16), 1 << 22)
            and not (need_pairs and cannot_be_unique)
        )
        # a one-word key too wide for a LUT: the hash map where its probe
        # pays (columnar.batch.lookup_form), unless duplicates are certain
        hashed = (
            not lut
            and n_live > 0
            and not cannot_be_unique
            and lookup_form(n_live, cap) == "hash"
        )
        if lut:
            size = bucket_capacity(int(kmax_h - kmin_h) + 1)
            row_lut, exists, flags_d = _scatter_luts_jit(
                words[0], sel, jnp.int64(kmin_h), size=size
            )
        elif hashed:
            table, flags_d = hashmap.build(words[0], sel, n_live)
        if lut or hashed:
            flags = jax.device_get(flags_d)  # auronlint: sync-point(8/task) -- one read per build: the LUT's duplicate flag, or the hash map's (unplaced, duplicate, rounds)
        if lut:
            if not bool(flags):
                return PreparedBuild(
                    batch=big, words=[words[0]], n_live=n_live,
                    matched=jnp.zeros(cap, bool), unique=True,
                    lut=row_lut, lut_base=kmin_h, pack=pack,
                    key_list=_small_key_list(
                        words[0], sel, n_live, jnp.int64(kmin_h)),
                )
            if not need_pairs:
                return PreparedBuild(
                    batch=big, words=[words[0]], n_live=n_live,
                    matched=jnp.zeros(cap, bool), unique=False,
                    exists_lut=exists, lut_base=kmin_h, pack=pack,
                )
            # duplicates + pair output -> fall through to the sorted map
        elif hashed:
            left, dup, rounds = (int(x) for x in flags)
            obs.note_join_hash_build(n_live, table.rows.shape[0], rounds,
                                     bool(left or dup))
            if not (left or dup):
                return PreparedBuild(
                    batch=big, words=[words[0]], n_live=n_live,
                    matched=jnp.zeros(cap, bool), unique=True,
                    hash_table=table, pack=pack,
                )
            # a duplicate key, or a row left unplaced: the sorted map
    # presorted pre-check: SMJ build sides arrive straight from SortExec,
    # already clustered with live rows in a prefix — detecting that on
    # device (one tiny sync) skips the whole sort + all-column permute
    sorted_flag, stats0 = jax.device_get(_presorted_stats_jit(sel, tuple(words)))  # auronlint: sync-point(8/task) -- one tiny sync skips the whole sort (see comment above)
    if bool(sorted_flag):
        clustered = big
        stats = stats0
        sorted_words = list(words)
    else:
        if hostsort.use_host_sort(conf, rows=cap):
            order = S.host_order(words, sel, where="join_build")
            device_sort = False
        else:
            obs.note_sort(cap, "join_build", "device")
            order, device_sort = None, True
        row_sel_s, sorted_words, values_s, validity_s, stats = _prepare_build_jit(
            sel, dev.sel, tuple(words), dev.values, dev.validity, order,
            device_sort=device_sort,
        )
        clustered = Batch(
            big.schema, DeviceBatch(row_sel_s, values_s, validity_s), big.dicts
        )
    sorted_words = list(sorted_words)
    # uniqueness stats ride ONE transfer (integer-like keys took the LUT
    # fast path above, so no dense table is built here)
    n_live, has_dup_h, _, _ = (int(x) for x in jax.device_get(stats))  # auronlint: sync-point(8/task) -- build-plan stats, one read per build
    unique = n_live > 0 and not has_dup_h
    uniq_words = run_starts = None
    n_uniq = 0
    has_dict_key = any(v.dtype.is_dict_encoded for v in vals)
    if not unique and n_live > 0 and not has_dict_key:
        # dict-encoded keys re-key per probe batch (driver rebuilds the
        # PreparedBuild on a joint vocabulary, dropping these fields), so
        # compression would be dead work there
        # n_uniq stays a DEVICE scalar: it only ever feeds traced probe
        # programs, and syncing it here would block on the compression
        uw, run_starts, n_uniq = _compress_runs_jit(
            tuple(sorted_words), jnp.int32(n_live))
        uniq_words = list(uw)
    key_list = None
    if unique and len(sorted_words) == 1 and not has_dict_key:
        # live rows are a prefix of the clustered build
        key_list = _small_key_list(
            sorted_words[0], jnp.arange(cap) < n_live, n_live, None)
    return PreparedBuild(
        batch=clustered,
        words=sorted_words,
        n_live=n_live,
        matched=jnp.zeros(cap, bool),
        unique=unique,
        key_list=key_list,
        pack=pack,
        uniq_words=uniq_words,
        run_starts=run_starts,
        n_uniq=n_uniq,
    )


def _adjacent_all_eq(words):
    """bool[cap-1]: rows (j, j+1) equal across ALL key words — the one
    definition behind dup stats, presorted detection and run compression
    (three hand-rolled copies of this scan had started to drift)."""
    eq = None
    for w in words:
        e = w[:-1] == w[1:]
        eq = e if eq is None else (eq & e)
    return eq


@obs.jit
def _compress_runs_jit(sorted_words, n_live):
    """Unique-run compression of a sorted duplicate-keyed build: compacted
    distinct key words + run start offsets (CSR over the sorted rows).
    One program at build time; every probe batch then searches the
    distinct keys once instead of running lower+upper bounds over all
    rows."""
    cap = sorted_words[0].shape[0]
    pos = jnp.arange(cap, dtype=jnp.int32)
    live = pos < n_live
    neq = jnp.concatenate(
        [jnp.ones(1, bool), ~_adjacent_all_eq(sorted_words)])
    head = live & neq
    uid = jnp.cumsum(head.astype(jnp.int32)) - 1
    n_uniq = jnp.where(n_live > 0, uid[jnp.maximum(n_live - 1, 0)] + 1, 0)
    tgt = jnp.where(head, uid, cap + 1)  # cap+1: dropped by the scatters
    starts = jnp.full(cap + 1, n_live, jnp.int32).at[tgt].set(pos, mode="drop")
    uniq = tuple(
        jnp.zeros(cap, w.dtype).at[tgt].set(w, mode="drop")
        for w in sorted_words
    )
    return uniq, starts, n_uniq


def _uniq_lookup(uniq_words, run_starts, n_uniq, probe_words):
    """Traced CSR lookup shared by the pairs and mark probes: ONE binary
    search over distinct keys -> (found, run_lo, run_hi) per probe row.
    Keep the found/clip logic HERE only — a boundary tweak applied to one
    probe flavor but not the other would silently diverge semi/anti
    results from inner-join results for the same keys."""
    u = binsearch._search(
        list(uniq_words), list(probe_words), n_uniq, binsearch._lex_less
    )
    cap = uniq_words[0].shape[0]
    ucl = jnp.clip(u, 0, cap - 1)
    found = u < n_uniq
    for uw, pw in zip(uniq_words, probe_words):
        found = found & (uw[ucl] == pw)
    lo = run_starts[ucl]
    hi = run_starts[jnp.clip(u + 1, 0, run_starts.shape[0] - 1)]
    return found, lo, hi


def _covered_fold(build_matched, hit, lo, hi):
    """Fold probe-hit build-row ranges into ``matched`` via one
    diff/cumsum pass (shared by both no-pairs probe flavors)."""
    bcap = build_matched.shape[0]
    starts = jnp.where(hit, lo, bcap)
    stops = jnp.where(hit, hi, bcap)
    diff = jnp.zeros(bcap + 1, jnp.int32)
    diff = diff.at[starts].add(1, mode="drop")
    diff = diff.at[stops].add(-1, mode="drop")
    return build_matched | (jnp.cumsum(diff[:bcap]) > 0)


@obs.jit
def _uniq_ranges_jit(uniq_words, run_starts, n_uniq, probe_words, ok):
    """(lo, count) per probe row via the shared CSR lookup."""
    found, lo, hi = _uniq_lookup(uniq_words, run_starts, n_uniq, probe_words)
    hit = ok & found
    counts = jnp.where(hit, hi - lo, 0).astype(jnp.int32)
    return jnp.where(hit, lo, 0), counts


def _compare_rows(key, keys, rows):
    """bi[r] = max over the list's slots j of (rows[j] where key[r] ==
    keys[j], else -1): the key -> row map of a small unique build by
    comparing. The list's axis is the MAJOR one, so the reduction is one
    elementwise pass a slot over row vectors held in registers: no
    cross-lane reduce and no [K, rows] array in memory."""
    hit = key[None, :] == keys[:, None]
    return jnp.max(jnp.where(hit, rows[:, None], jnp.int32(-1)), axis=0)


def _probe_unique_ops(
    probe_words, ok_base, lut, lut_base, bwords, n_live, bcap: int,
    table=None,
):
    """Traceable core of the unique-build probe (called inside jit):
    (bi, ok) by the build's hash map where ``table`` is one
    (``PreparedBuild.probe_table``), by its live key list where ``table``
    is that, else by its LUT, else by binary search. The four maps agree
    on ``ok`` and on ``bi`` wherever ``ok`` (the list and the LUT on every
    row: an unmatched row's ``bi`` is 0, the search's its insertion point,
    the map's a row or 0; nothing reads any of them)."""
    if isinstance(table, hashmap.HashTable):
        bi, found = hashmap.probe(table, probe_words[0])
        return jnp.clip(bi, 0, bcap - 1), ok_base & found
    if lut is not None:
        w = probe_words[0]
        size = lut.shape[0]
        # view, not astype: words >= 2^63 are negative keys and must
        # reinterpret bit-exactly, a value conversion would be UB-ish
        idx = w.view(jnp.int64) - lut_base
        in_range = (idx >= 0) & (idx < size)
        slot = jnp.clip(idx, 0, size - 1).astype(jnp.int32)
        # in_range stays in ok on both arms: a key clipped into the range
        # must not alias the live key at its edge
        bi = lut[slot] if table is None else _compare_rows(slot, *table)
        ok = ok_base & in_range & (bi >= 0)
        return jnp.clip(bi, 0, bcap - 1), ok
    if table is not None:
        bi = _compare_rows(probe_words[0], *table)
        return jnp.clip(bi, 0, bcap - 1), ok_base & (bi >= 0)
    lo = binsearch._search(bwords, probe_words, n_live, binsearch._lex_less)
    bi = jnp.clip(lo, 0, bcap - 1)
    eq = lo < n_live
    for bw, pw in zip(bwords, probe_words):
        eq = eq & (bw[bi] == pw)
    return bi, ok_base & eq


from functools import partial


def _canon_words_traced(key_vals, key_masks, key_kinds):
    """Canonical equality words from raw key arrays (traceable: the kind
    tags ride as static args so the whole canon+probe chain fuses into one
    program instead of per-op full-capacity passes)."""
    words = []
    valid = None
    for v, m, kind in zip(key_vals, key_masks, key_kinds):
        if kind == "bool":
            w = v.astype(jnp.uint64)
        elif kind == "f32":
            f = v.astype(jnp.float32)
            f = jnp.where(f == 0, jnp.float32(0), f)
            f = jnp.where(jnp.isnan(f), jnp.float32(jnp.nan), f)
            w = f.view(jnp.uint32).astype(jnp.uint64)
        elif kind == "f64":
            f = v.astype(jnp.float64)
            f = jnp.where(f == 0, jnp.float64(0), f)
            f = jnp.where(jnp.isnan(f), jnp.float64(jnp.nan), f)
            w = f64_equality_word(f)
        else:  # ints / date / timestamp / decimal64 / dict codes
            w = v.astype(jnp.int64).view(jnp.uint64)
        words.append(jnp.where(m, w, jnp.uint64(0)))
        valid = m if valid is None else (valid & m)
    return words, valid


def key_kind(dtype) -> str:
    if dtype.kind == T.TypeKind.BOOL:
        return "bool"
    if dtype.is_dict_encoded:
        return "int"
    if dtype.kind == T.TypeKind.FLOAT32:
        return "f32"
    if dtype.kind == T.TypeKind.FLOAT64:
        return "f64"
    return "int"


@partial(obs.jit, static_argnames=("bcap", "use_lut", "probe_outer", "key_kinds"))
def _unique_probe_jit(
    key_vals, key_masks, psel, lut, lut_base, bwords, n_live, table,
    bcap: int, use_lut: bool, probe_outer: bool, key_kinds: tuple,
):
    """Canon + probe in ONE program (no gathers): (bi, ok, sel_out, live).
    ``table`` (``PreparedBuild.probe_table``: None, or the build's two
    arrays of its key list or its hash map; the pytree's shape is the
    static half) picks the compare or the hash map."""
    probe_words, pvalid = _canon_words_traced(key_vals, key_masks, key_kinds)
    ok_base = psel & (pvalid if pvalid is not None else jnp.ones_like(psel))
    bi, ok = _probe_unique_ops(
        probe_words, ok_base, lut if use_lut else None, lut_base, bwords,
        n_live, bcap, table,
    )
    sel_out = psel if probe_outer else (psel & ok)
    return bi, ok, sel_out, jnp.sum(sel_out.astype(jnp.int32))


@obs.jit
def _gather_build_jit(build_vals, build_masks, bi, ok):
    """Build-column gathers at probe capacity (dense-output fallback)."""
    return (
        tuple(v[bi] for v in build_vals),
        tuple(m[bi] & ok for m in build_masks),
    )


@partial(obs.jit, static_argnames=("out_cap",))
def _unique_compact_take_pred_jit(
    probe_vals, probe_masks, bi, ok, build_vals, build_masks, sel, out_cap: int
):
    """Sync-free compaction at a PREDICTED static bucket: the row index is
    computed on device from the selection mask (no host flatnonzero, no
    blocking live-count read). Rows beyond ``out_cap`` are truncated — the
    caller harvests the true live count asynchronously and repairs a
    too-small bucket by re-taking (exec/selectivity.py protocol)."""
    idx, new_sel = compaction_index(sel, out_cap)
    c_pvals = tuple(v[idx] for v in probe_vals)
    c_pmasks = tuple(m[idx] & new_sel for m in probe_masks)
    c_bi = bi[idx]
    c_ok = ok[idx] & new_sel
    out_bvals = tuple(v[c_bi] for v in build_vals)
    out_bmasks = tuple(m[c_bi] & c_ok for m in build_masks)
    return c_pvals, c_pmasks, out_bvals, out_bmasks, new_sel


@partial(obs.jit, static_argnames=("bcap", "use_lut", "probe_outer", "key_kinds"))
def _unique_join_emit_jit(
    key_vals,
    key_masks,
    psel,
    lut,
    lut_base,
    bwords,
    n_live,
    table,
    build_vals,
    build_masks,
    bcap: int,
    use_lut: bool,
    probe_outer: bool,
    key_kinds: tuple = (),
):
    """One fused program: key canon + unique probe + projected build-column
    gathers + output selection. Probe-side columns never move (views)."""
    probe_words, pvalid = _canon_words_traced(key_vals, key_masks, key_kinds)
    ok_base = psel & (pvalid if pvalid is not None else jnp.ones_like(psel))
    bi, ok = _probe_unique_ops(
        probe_words, ok_base, lut if use_lut else None, lut_base, bwords,
        n_live, bcap, table,
    )
    out_vals = tuple(v[bi] for v in build_vals)
    out_masks = tuple(m[bi] & ok for m in build_masks)
    sel_out = psel if probe_outer else (psel & ok)
    return bi, ok, out_vals, out_masks, sel_out


def probe_ranges(build: PreparedBuild, probe_words, probe_valid, probe_sel):
    ok = probe_sel & (probe_valid if probe_valid is not None else True)
    if build.uniq_words is not None:
        return _uniq_ranges_jit(
            tuple(build.uniq_words), build.run_starts,
            build.n_uniq, tuple(probe_words), ok,
        )
    lo = binsearch.lower_bound(build.words, probe_words, build.n_live)
    hi = binsearch.upper_bound(build.words, probe_words, build.n_live)
    counts = jnp.where(ok, hi - lo, 0).astype(jnp.int32)
    return lo, counts


@obs.jit
def _probe_exists_jit(exists_lut, base, pword, pvalid, psel):
    """Existence probe against a duplicate-tolerant dense LUT: one gather
    per probe batch, no binary search, no build sort."""
    size = exists_lut.shape[0]
    idx = pword.view(jnp.int64) - base
    in_range = (idx >= 0) & (idx < size)
    hit = exists_lut[jnp.clip(idx, 0, size - 1).astype(jnp.int32)]
    ok = psel & (pvalid if pvalid is not None else True)
    return ok & in_range & hit


def probe_mark(build: PreparedBuild, probe_words, probe_valid, probe_sel,
               need_build_delta: bool):
    """Fused no-pairs probe (semi/anti/existence) over whichever build
    layout exists: the CSR unique-run compression when the build has
    duplicates (one search over distinct keys), else the two-search path."""
    if build.uniq_words is not None:
        return _probe_mark_uniq_jit(
            tuple(build.uniq_words), build.run_starts, build.n_uniq,
            build.matched, tuple(probe_words), probe_valid, probe_sel,
            need_build_delta=need_build_delta,
        )
    return _probe_mark_jit(
        tuple(build.words), jnp.int32(build.n_live), build.matched,
        tuple(probe_words), probe_valid, probe_sel,
        need_build_delta=need_build_delta,
    )


@partial(obs.jit, static_argnames=("need_build_delta",))
def _probe_mark_uniq_jit(
    uniq_words, run_starts, n_uniq, build_matched, probe_words, probe_valid,
    probe_sel, *, need_build_delta: bool,
):
    ok = probe_sel & (probe_valid if probe_valid is not None else True)
    found, lo, hi = _uniq_lookup(uniq_words, run_starts, n_uniq, probe_words)
    probe_matched = ok & found
    if not need_build_delta:
        return probe_matched, build_matched
    return probe_matched, _covered_fold(build_matched, probe_matched, lo, hi)


@partial(obs.jit, static_argnames=("need_build_delta",))
def _probe_mark_jit(
    build_words, n_live, build_matched, probe_words, probe_valid, probe_sel,
    *, need_build_delta: bool,
):
    """Fused no-pairs probe (semi/anti/existence): binary-search ranges,
    per-probe matched flags, and — when the build side owns the mark — the
    range-covered build flags folded into ``matched``, all in one program
    (per-batch eager dispatch was a measured q95-class sink)."""
    lo = binsearch._search(build_words, probe_words, n_live, binsearch._lex_less)
    hi = binsearch._search(build_words, probe_words, n_live, binsearch._lex_less_eq)
    ok = probe_sel & (probe_valid if probe_valid is not None else True)
    counts = jnp.where(ok, hi - lo, 0).astype(jnp.int32)
    probe_matched = (counts > 0) & probe_sel
    if not need_build_delta:
        return probe_matched, build_matched
    cap = build_words[0].shape[0]
    hit = counts > 0
    starts = jnp.where(hit, lo, cap)
    stops = jnp.where(hit, lo + counts, cap)
    diff = jnp.zeros(cap + 1, jnp.int32)
    diff = diff.at[starts].add(1, mode="drop")
    diff = diff.at[stops].add(-1, mode="drop")
    covered = jnp.cumsum(diff[:cap]) > 0
    return probe_matched, build_matched | covered


def expand_pairs(
    probe_batch: Batch,
    build: PreparedBuild,
    lo: jnp.ndarray,
    counts: jnp.ndarray,
    condition,  # None | (combined_schema, expr, swapped)
    track_probe_matched: bool,
) -> tuple[list[tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]], jnp.ndarray, jnp.ndarray]:
    """Produce per-chunk (probe_idx, build_idx, pair_ok) index triples.

    Returns (chunks, probe_matched, build_matched_delta). Gathering into
    output batches is the caller's job (it knows the column order).
    """
    offsets = jnp.cumsum(counts)
    total = int(jax.device_get(offsets[-1])) if counts.shape[0] else 0  # auronlint: sync-point(1/batch) -- ragged join-pair total, one per batch (ARCHITECTURE.md contract)
    pcap = probe_batch.capacity
    bcap = build.batch.capacity
    probe_matched = counts > 0
    build_matched_delta = jnp.zeros(bcap, bool)
    chunks = []
    if total == 0:
        return chunks, probe_matched & probe_batch.device.sel, build_matched_delta

    starts = offsets - counts
    for cstart in range(0, total, _EXPAND_CHUNK):
        ccap = bucket_capacity(min(_EXPAND_CHUNK, total - cstart))
        li, ri, ok = _decode_chunk(
            offsets, starts, lo, jnp.int32(cstart), jnp.int32(total),
            ccap=ccap, pcap=pcap, bcap=bcap,
        )
        chunks.append((li, ri, ok))

    if condition is not None:
        comb_schema, expr, assemble = condition
        new_chunks = []
        probe_matched = jnp.zeros(pcap, bool)
        for li, ri, ok in chunks:
            pair_batch = assemble(probe_batch, build.batch, li, ri, ok)
            cv = Evaluator(comb_schema).evaluate(pair_batch, [expr])[0]
            ok2 = ok & cv.validity & cv.values.astype(bool)
            new_chunks.append((li, ri, ok2))
            probe_matched = probe_matched.at[li].max(ok2, mode="drop")
        chunks = new_chunks
        probe_matched = probe_matched & probe_batch.device.sel

    for li, ri, ok in chunks:
        build_matched_delta = build_matched_delta.at[ri].max(ok, mode="drop")

    return chunks, probe_matched, build_matched_delta


from functools import partial


@partial(obs.jit, static_argnames=("ccap", "pcap", "bcap"))
def _decode_chunk(offsets, starts, lo, cstart, total, ccap: int, pcap: int, bcap: int):
    """Ragged-expansion slot decode for one output chunk (fused)."""
    t = jnp.arange(ccap, dtype=jnp.int32) + cstart
    ok = t < total
    li = jnp.clip(jnp.searchsorted(offsets, t, side="right").astype(jnp.int32), 0, pcap - 1)
    within = t - starts[li]
    ri = jnp.clip(lo[li] + within, 0, bcap - 1)
    return li, ri, ok


@obs.jit
def gather_pair_arrays(probe_vals, probe_masks, build_vals, build_masks, li, ri, ok):
    """One fused program gathering all pair columns (both sides)."""
    pv = tuple(v[li] for v in probe_vals)
    pm = tuple(m[li] & ok for m in probe_masks)
    bv = tuple(v[ri] for v in build_vals)
    bm = tuple(m[ri] & ok for m in build_masks)
    return pv, pm, bv, bm


def gather_columns(batch: Batch, idx: jnp.ndarray, row_ok: jnp.ndarray) -> list[ColumnVal]:
    out = []
    for i, f in enumerate(batch.schema):
        v = batch.col_values(i)[idx]
        m = batch.col_validity(i)[idx] & row_ok
        out.append(ColumnVal(v, m, f.dtype, batch.dicts[i]))
    return out


def null_columns(schema: T.Schema, cap: int, dicts) -> list[ColumnVal]:
    out = []
    for i, f in enumerate(schema):
        out.append(
            ColumnVal(
                jnp.zeros(cap, f.dtype.physical_dtype()),
                jnp.zeros(cap, bool),
                f.dtype,
                dicts[i],
            )
        )
    return out
