"""A unique-key hash map for the unique-build join probe, built by scatters.

The build side of a join whose key is one 64-bit word (a single integer key
or several packed into one, ``exec/joins/core.py _maybe_pack``) and whose
value range is too wide for a dense LUT was sorted and binary-searched: a
gathered 64-bit element a probe row for every bit of the build's capacity.
This map answers the same ``(row, found)`` with a fixed number of gathers a
probe row whatever the build's size: two slots' words and one row index.

Layout: two-choice cuckoo hashing, one key a slot, in a power-of-two table
of at least ``LOAD`` slots a live key. A key word ``w`` is mixed by a
bijection (``mix``: splitmix64's finalizer), and the mixed word ``z`` names
the key's two slots, its high and its low 32 bits under the table's mask.
A slot holds the ``z`` of the key placed there (equal ``z`` is equal ``w``),
as two 32-bit halves, and the key's build row; an empty slot holds the row
-1 and a ``z`` whose high half names the NEXT slot, so no key whose first
choice is that slot reads it as its own.

Build: live rows are placed in rounds. In each round every unplaced row
scatters its row id to its target slot (the larger id wins), reads back who
won, and a winner writes its word there; the slot's former occupant, if any,
is evicted, and it and every loser try their other slot the next round
(a row's first target is its first slot). The first round runs at the build's capacity into an empty
table; the unplaced rows are then compacted into narrower static widths,
one round each, and the last width runs up to ``TAIL_ROUNDS`` more rounds
until none is left. At a load ``a`` (live keys over slots) the first round
leaves about ``n a / 2`` of ``n`` rows unplaced (two rows met in a slot),
and every later round leaves unplaced the share of rows whose target was
occupied, about ``a`` of them: so the k-th width is the bucket of
``n a^k``, at least twice what should reach it (``stage_widths``, from the
live count the host holds). A build that ends with a row unplaced, or whose
unplaced rows outgrow a width, is not a map: the caller keeps its sorted
path.

Duplicates are detected during placement: a winner that evicts its own key,
or whose other slot holds its key after the round's writes, is a duplicate
(two equal keys can only both be placed in each other's other slot, and the
later of the two placements sees the earlier; in the first round all rows
target their first slot, so equal keys cannot both win it). A duplicate
sends the caller to its sorted path as well.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax.numpy as jnp
from jax import lax

from auron_tpu import obs
from auron_tpu.columnar.batch import bucket_capacity, compaction_index, note_index

#: slots a live key, at least: the table is the power of two above
#: ``LOAD x n_live``, so its load lies in (1/8, 1/4]
LOAD = 4
#: the narrowest width of the stages after the first round
MIN_WIDTH = 1024
#: the most rounds the last width runs; at a load of a quarter the unplaced
#: rows shrink about four-fold a round, so a 2^21-row build is placed in
#: about a dozen rounds in all (8 to 15 on the chip at 2^20 to 2^21 keys)
TAIL_ROUNDS = 32

_M1 = 0xBF58476D1CE4E5B9
_M2 = 0x94D049BB133111EB


class HashTable(NamedTuple):
    """The probe's pytree: ``hi`` and ``lo`` uint32[size], the halves of the
    mixed word placed in a slot, and ``rows`` int32[size], its build row (-1
    where empty). The word is kept as two 32-bit planes because XLA:TPU
    scatters a 64-bit plane about twenty times slower than a 32-bit one
    (PERF.md section 5), and every round of the build scatters it."""

    hi: jnp.ndarray
    lo: jnp.ndarray
    rows: jnp.ndarray


def table_size(n_live: int) -> int:
    """The slots of a table for ``n_live`` keys: a power of two."""
    return bucket_capacity(max(LOAD * n_live, 2))


def stage_widths(cap: int, n_live: int) -> tuple[int, ...]:
    """The static widths of the build's stages: the capacity, then the
    buckets of ``n_live x a^k`` (``a`` the table's load) down to
    ``MIN_WIDTH``."""
    load = n_live / table_size(n_live)
    out, want = [cap], n_live * load
    while out[-1] > MIN_WIDTH:
        w = min(bucket_capacity(max(int(want), MIN_WIDTH)), out[-1])
        if w == out[-1]:
            break
        out.append(w)
        want *= load
    return tuple(out)


def mix(w: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """splitmix64's finalizer, a bijection of uint64 (equal mixed words are
    equal words), as its high and low uint32 halves."""
    z = w ^ (w >> 30)
    z = z * jnp.uint64(_M1)
    z = z ^ (z >> 27)
    z = z * jnp.uint64(_M2)
    z = z ^ (z >> 31)
    return (z >> 32).astype(jnp.uint32), z.astype(jnp.uint32)


def _slots(hi, lo, size: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    m = jnp.uint32(size - 1)
    return (hi & m).astype(jnp.int32), (lo & m).astype(jnp.int32)


def _round(table, homeless, dup, size: int, first: bool):
    """One round of placement. ``homeless`` is (row, hi, lo, last): the
    unplaced rows' ids (-1 none), mixed words' halves, and the slot each
    last targeted (-1 none: it targets its first slot), at the stage's
    width."""
    hi, lo, rows = table
    hrow, zh, zl, hlast = homeless
    valid = hrow >= 0
    h1, h2 = _slots(zh, zl, size)
    t = jnp.where(h1 != hlast, h1, h2)
    claims = jnp.full(size, -1, jnp.int32).at[jnp.where(valid, t, size)].max(
        hrow, mode="drop")
    won = valid & (claims[t] == hrow)
    put = jnp.where(won, t, size)
    if first:
        # an empty table: the claims are its rows, nothing is evicted, and
        # equal keys share their first slot, so at most one has won it
        hi = hi.at[put].set(zh, mode="drop")
        lo = lo.at[put].set(zl, mode="drop")
        return (hi, lo, claims), (jnp.where(won, -1, hrow), zh, zl, t), dup
    old_row, old_hi, old_lo = rows[t], hi[t], lo[t]
    rows = rows.at[put].set(hrow, mode="drop")
    hi = hi.at[put].set(zh, mode="drop")
    lo = lo.at[put].set(zl, mode="drop")
    other = jnp.where(t == h1, h2, h1)
    dup = dup | jnp.any(won & (
        ((old_row >= 0) & (old_hi == zh) & (old_lo == zl))
        | ((other != t) & (hi[other] == zh) & (lo[other] == zl))))
    homeless = (jnp.where(won, old_row, hrow), jnp.where(won, old_hi, zh),
                jnp.where(won, old_lo, zl), jnp.where(valid, t, hlast))
    return (hi, lo, rows), homeless, dup


def _narrow(homeless, width: int):
    """The unplaced rows compacted into ``width`` slots, and whether they
    outgrew it."""
    hrow, zh, zl, hlast = homeless
    live = hrow >= 0
    idx, keep = compaction_index(live, width)
    over = jnp.sum(live.astype(jnp.int32)) > width
    return (jnp.where(keep, hrow[idx], -1), zh[idx], zl[idx],
            jnp.where(keep, hlast[idx], -1)), over


@partial(obs.jit, static_argnames=("size", "widths"))
def _build_jit(word, sel, *, size: int, widths: tuple):
    cap = word.shape[0]
    # an empty slot s holds the halves (s + 1) mod size: no word whose first
    # slot is s has that high half
    nxt = (jnp.arange(size, dtype=jnp.uint32) + 1) & jnp.uint32(size - 1)
    table = (nxt, nxt, jnp.full(size, -1, jnp.int32))
    homeless = (jnp.where(sel, jnp.arange(cap, dtype=jnp.int32), -1), *mix(word),
                jnp.full(cap, -1, jnp.int32))
    dup = jnp.bool_(False)
    over = jnp.bool_(False)
    table, homeless, dup = _round(table, homeless, dup, size, first=True)
    for width in widths[1:]:
        homeless, o = _narrow(homeless, width)
        over = over | o
        table, homeless, dup = _round(table, homeless, dup, size, first=False)

    def more(c):
        return (c[3] < TAIL_ROUNDS) & jnp.any(c[1][0] >= 0)

    def step(c):
        table, homeless, dup, n = c
        table, homeless, dup = _round(table, homeless, dup, size, first=False)
        return table, homeless, dup, n + 1

    table, homeless, dup, tail = lax.while_loop(
        more, step, (table, homeless, dup, jnp.int32(0)))
    left = jnp.any(homeless[0] >= 0) | over
    stats = jnp.stack([left.astype(jnp.int32), dup.astype(jnp.int32),
                       tail + len(widths)])
    return HashTable(*table), stats


def build(word: jnp.ndarray, sel: jnp.ndarray, n_live: int):
    """``(table, stats)`` for the live rows (``sel``) of a one-word build,
    in one program and no read: ``stats`` int32[3] on the device, (a row
    left unplaced, a duplicate key met, the rounds run). The table is a map
    only where the first two are 0."""
    cap = word.shape[0]
    widths = stage_widths(cap, n_live)
    for a, b in zip(widths, widths[1:]):
        note_index(a, b)
    return _build_jit(word, sel, size=table_size(n_live), widths=widths)


def probe(table: HashTable, word: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Traceable lookup: ``(row, found)`` for each probe word; ``row`` is
    meaningful where ``found``."""
    zh, zl = mix(word)
    h1, h2 = _slots(zh, zl, table.rows.shape[0])
    at1 = (table.hi[h1] == zh) & (table.lo[h1] == zl)
    at2 = (table.hi[h2] == zh) & (table.lo[h2] == zl)
    row = table.rows[jnp.where(at1, h1, h2)]
    return row, (at1 | at2) & (row >= 0)
