"""Order-preserving sort-key encoding.

The reference sorts with a key-prefix row format + comparators
(sort_exec.rs key-prefix compare, ext-commons eq_comparator). The TPU-native
equivalent encodes every sort key into uint64 words whose *unsigned* order
equals the SQL order, so a single multi-operand ``lax.sort`` implements any
(asc/desc, nulls first/last) lexicographic sort:

- signed ints/date/timestamp/decimal: XOR the sign bit;
- floats: IEEE total-order trick (negative -> ~bits, positive -> bits|sign),
  which also places NaN above +inf — Spark's NaN-greatest semantics;
- strings: rank through the (host-)sorted unified dictionary — UTF-8 byte
  order, matching Spark's unicode-code-point comparisons;
- descending inverts the word; null placement is a leading 0/1 word per key.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax.numpy as jnp
import numpy as np

from auron_tpu import types as T
from auron_tpu.exprs.eval import ColumnVal
from auron_tpu.ops.floatbits import f64_orderable_word


@dataclass(frozen=True)
class SortSpec:
    asc: bool = True
    nulls_first: bool = True  # Spark default: nulls first for asc, last for desc


def orderable_word(cv: ColumnVal) -> jnp.ndarray:
    """uint64 whose unsigned order == SQL ascending order (nulls excluded)."""
    dt = cv.dtype
    v = cv.values
    sign = jnp.uint64(1) << jnp.uint64(63)
    if dt.kind == T.TypeKind.BOOL:
        return v.astype(jnp.uint64)
    if dt.is_dict_encoded:
        # incl. wide decimals: order via the (numeric/lexicographic) rank
        rank = _dict_rank(cv.dict)
        return jnp.asarray(rank)[jnp.clip(v, 0, len(rank) - 1)].astype(jnp.uint64)
    if dt.is_integer or dt.kind in (T.TypeKind.DATE32, T.TypeKind.TIMESTAMP, T.TypeKind.DECIMAL):
        return v.astype(jnp.int64).view(jnp.uint64) ^ sign
    if dt.kind == T.TypeKind.FLOAT32:
        f = v.astype(jnp.float32)
        f = jnp.where(f == 0, jnp.float32(0), f)  # -0.0 == 0.0
        f = jnp.where(jnp.isnan(f), jnp.float32(jnp.nan), f)  # canonical NaN
        b = f.view(jnp.uint32).astype(jnp.uint64) << jnp.uint64(32)
        neg = (b & sign) != 0
        return jnp.where(neg, ~b, b | sign)
    if dt.kind == T.TypeKind.FLOAT64:
        f = v.astype(jnp.float64)
        f = jnp.where(f == 0, jnp.float64(0), f)
        f = jnp.where(jnp.isnan(f), jnp.float64(jnp.nan), f)
        return f64_orderable_word(f)
    raise TypeError(f"unsortable type {dt}")


# Bounded memo of per-dictionary rank tables: consecutive batches usually
# share the identical dictionary object, and the Python sort is O(d log d)
# host work on the per-batch hot path. Keyed by id() with the dictionary
# kept referenced so ids can't be recycled; FIFO-evicted at _RANK_CACHE_MAX.
_RANK_CACHE: dict[int, tuple] = {}
_RANK_CACHE_MAX = 64


def _dict_rank(d) -> np.ndarray:
    hit = _RANK_CACHE.get(id(d))
    if hit is not None and hit[0] is d:
        return hit[1]
    import decimal as pydec

    entries = d.to_pylist()
    if any(isinstance(e, pydec.Decimal) for e in entries):
        # wide-decimal dictionaries order numerically, not by bytes
        keyed = [e if e is not None else pydec.Decimal(0) for e in entries]
    else:
        keyed = [
            (e.encode("utf-8") if isinstance(e, str) else (e if e is not None else b""))
            for e in entries
        ]
    order = sorted(range(len(keyed)), key=lambda i: keyed[i])
    rank = np.empty(len(keyed), dtype=np.uint64)
    for r, i in enumerate(order):
        rank[i] = r
    if len(_RANK_CACHE) >= _RANK_CACHE_MAX:
        _RANK_CACHE.pop(next(iter(_RANK_CACHE)))  # auronlint: disable=R10 -- deliberate trace-time memo eviction: bounded cache of deterministic values, replay-safe
    # auronlint: disable=R10 -- deliberate trace-time memo: ranks are a pure function of the dictionary object, replay-safe on cache hits
    _RANK_CACHE[id(d)] = (d, rank)
    return rank


def dict_rank_maps(d) -> tuple[np.ndarray, np.ndarray]:
    """(rank, inv) for a dictionary: ``rank[code]`` is the code's
    lexicographic (UTF-8 byte order) rank, ``inv[rank]`` recovers the code.

    min/max reductions over dictionary codes must run in rank space — codes
    are in first-occurrence order, which has no relation to SQL string order.

    Both arrays are zero-padded to a power-of-two capacity bucket so jitted
    consumers see a stable shape signature across batches with different
    dictionary cardinalities (real codes/ranks never index the padding).
    """
    rank = _dict_rank(d).astype(np.int64)
    n = len(rank)
    inv = np.empty_like(rank)
    inv[rank] = np.arange(n, dtype=np.int64)
    cap = max(8, 1 << (n - 1).bit_length()) if n else 8
    if cap > n:
        pad = np.zeros(cap - n, dtype=np.int64)
        rank = np.concatenate([rank, pad])
        inv = np.concatenate([inv, pad])
    return rank, inv


def sort_operands(
    keys: list[ColumnVal], specs: list[SortSpec]
) -> list[jnp.ndarray]:
    """Build the lax.sort key operands: per key a null-placement word then the
    (direction-adjusted) value word."""
    ops: list[jnp.ndarray] = []
    for cv, spec in zip(keys, specs):
        nf = spec.nulls_first
        null_word = jnp.where(
            cv.validity,
            jnp.uint64(1) if nf else jnp.uint64(0),
            jnp.uint64(0) if nf else jnp.uint64(1),
        )
        w = orderable_word(cv)
        if not spec.asc:
            w = ~w
        w = jnp.where(cv.validity, w, jnp.uint64(0))
        ops.append(null_word)
        ops.append(w)
    return ops


def narrow_flags(n_keys: int) -> tuple[bool, ...]:
    """Per-operand narrow markers for sort_operands' output: the 0/1
    null-placement words have statically-zero hi halves (bitonic network
    single-plane ride); the direction-adjusted value words use all 64
    bits (descending inverts)."""
    return (True, False) * n_keys
