"""The unique-build join's hash map (ops/hashmap.py): its lookup answers as
the binary search over the sorted words does, at every join type the unique
path serves; a duplicate key sends the build back to its sorted path; and
``columnar.batch.lookup_form`` picks the map only where the chip's gathers
make it pay. The chip's rules are asked for on XLA:CPU by patching
``_gather_bound``, as the compaction rules' tests do."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pandas as pd
import pyarrow as pa
import pytest

from auron_tpu import obs
from auron_tpu import types as T
from auron_tpu.columnar import Batch
from auron_tpu.columnar import batch as batch_mod
from auron_tpu.columnar.batch import bucket_capacity, lookup_form
from auron_tpu.exec.basic import MemoryScanExec
from auron_tpu.exec.joins import SortMergeJoinExec, core
from auron_tpu.exec.joins.core import (
    EXISTENCE, INNER, LEFT, LEFT_ANTI, LEFT_SEMI,
)
from auron_tpu.exprs.eval import ColumnVal
from auron_tpu.exprs.ir import col
from auron_tpu.ops import hashmap


@pytest.fixture()
def rings():
    """The recorder on, so the builds and lookups leave their events."""
    before = obs.mode()
    obs.set_mode(obs.MODE_RECORDER)
    yield time.perf_counter()
    obs.set_mode(before)


# ---- the lookup itself: (bi, ok) by the map and by the search ---------------


def _key_words(kind: str, n: int, cap: int, rng):
    """(build words, build sel, probe words, probe ok) as prepare_build and
    the probe make them: ``packed`` two int64 keys packed into one word by
    the build's ranges, ``negative`` one int64 key over both signs,
    ``null`` one int64 key with NULLs on both sides."""
    n_probe = 4 * n + 64
    if kind == "packed":
        item = rng.integers(1, 18_001, 8 * n)
        ticket = rng.integers(1, 1 << 22, 8 * n)
        pairs = np.unique(np.stack([item, ticket], 1), axis=0)[:n]
        rng.shuffle(pairs)
        pi = np.concatenate([pairs[rng.integers(0, n, n_probe // 2)],
                             np.stack([rng.integers(1, 18_001, n_probe // 2),
                                       rng.integers(1, 1 << 22, n_probe // 2)], 1)])
        bvals = [ColumnVal(jnp.asarray(np.pad(pairs[:, j], (0, cap - n))),
                           jnp.arange(cap) < n, T.INT64) for j in (0, 1)]
        words, valid = core._canon_words(bvals)
        sel = valid & (jnp.arange(cap) < n)
        pack = core._maybe_pack(bvals, words, sel)
        bword, _ = core._pack_probe_jit(tuple(words), None, pack)
        pvals = [ColumnVal(jnp.asarray(pi[:, j]), jnp.ones(n_probe, bool), T.INT64)
                 for j in (0, 1)]
        pw, pv = core._canon_words(pvals)
        pword, pok = core._pack_probe_jit(tuple(pw), pv, pack)
        return bword, sel, pword, pok
    keys = np.unique(rng.integers(-(1 << 40), 1 << 40, 2 * n))[:n]
    rng.shuffle(keys)
    probe = np.concatenate([keys[rng.integers(0, n, n_probe // 2)],
                            rng.integers(-(1 << 40), 1 << 40, n_probe // 2)])
    bvalid = np.arange(cap) < n
    pvalid = np.ones(n_probe, bool)
    if kind == "null":
        bvalid[rng.random(cap) < 0.1] = False
        pvalid[rng.random(n_probe) < 0.1] = False
    (bword,), bv = core._canon_words([ColumnVal(
        jnp.asarray(np.pad(keys, (0, cap - n))), jnp.asarray(bvalid), T.INT64)])
    (pword,), pv = core._canon_words([ColumnVal(
        jnp.asarray(probe), jnp.asarray(pvalid), T.INT64)])
    return bword, bv, pword, pv


@pytest.mark.parametrize("kind", ["packed", "negative", "null"])
@pytest.mark.parametrize("n", [1, 1023, 1 << 17])
def test_hash_lookup_answers_as_the_search(n, kind):
    """Where the search finds a probe's key the map finds the same build row,
    and neither finds a key the build lacks or a NULL one."""
    rng = np.random.default_rng(n + len(kind))
    cap = bucket_capacity(n)
    bword, sel, pword, pok = _key_words(kind, n, cap, rng)
    n_live = int(jnp.sum(sel))
    table, stats = hashmap.build(bword, sel, n_live)
    left, dup, rounds = (int(x) for x in jax.device_get(stats))
    assert not left and not dup and rounds >= 1
    # the search's build: live words sorted, original rows beside them
    w, s = np.asarray(bword), np.asarray(sel)
    order = np.flatnonzero(s)[np.argsort(w[s], kind="stable")]
    sorted_words = jnp.asarray(np.pad(w[order], (0, cap - n_live)))
    probe = jax.jit(core._probe_unique_ops, static_argnums=(6,))
    bi_h, ok_h = probe([pword], pok, None, 0, [sorted_words], jnp.int32(n_live),
                       cap, table)
    bi_s, ok_s = probe([pword], pok, None, 0, [sorted_words], jnp.int32(n_live),
                       cap, None)
    ok_h, ok_s = np.asarray(ok_h), np.asarray(ok_s)
    assert ok_s.any() and (~ok_s).any()
    np.testing.assert_array_equal(ok_h, ok_s)
    np.testing.assert_array_equal(np.asarray(bi_h)[ok_h], order[np.asarray(bi_s)[ok_s]])


# ---- through the sort-merge join, at every join type it serves --------------


def _scan(df, chunk: int):
    return MemoryScanExec.single([
        Batch.from_arrow(pa.RecordBatch.from_pandas(df.iloc[i:i + chunk],
                                                    preserve_index=False))
        for i in range(0, len(df), chunk)])


def _frames(rng, n_build: int = 2000, dup: bool = False):
    """A fact side keyed by (item, ticket) with NULLs and a unique-keyed
    returns side over a wide range: no LUT serves it, and it has more keys
    than the widest key list."""
    item = rng.integers(1, 18_001, 4 * n_build)
    ticket = rng.integers(1, 1 << 22, 4 * n_build)
    keys = np.unique(np.stack([item, ticket], 1), axis=0)[:n_build]
    rng.shuffle(keys)
    if dup:
        keys[7] = keys[3]
    returns = pd.DataFrame({
        "sr_item": pd.array(keys[:, 0], dtype="Int64"),
        "sr_ticket": pd.array(keys[:, 1], dtype="Int64"),
        "sr_qty": pd.array(rng.integers(1, 100, n_build), dtype="Int64")})
    n = 6 * n_build
    hit = rng.random(n) < 0.3
    pick = keys[rng.integers(0, n_build, n)]
    sales = pd.DataFrame({
        "ss_item": pd.array(np.where(hit, pick[:, 0], rng.integers(1, 18_001, n)),
                            dtype="Int64"),
        "ss_ticket": pd.array(np.where(hit, pick[:, 1], rng.integers(1, 1 << 22, n)),
                              dtype="Int64"),
        "ss_row": np.arange(n, dtype=np.int64)})
    sales.loc[rng.random(n) < 0.05, "ss_ticket"] = pd.NA
    return sales, returns


def _smj(sales, returns, jt):
    op = SortMergeJoinExec(_scan(sales, 2048), _scan(returns, 512),
                           [col(0), col(1)], [col(0), col(1)], jt)
    out = op.collect().to_pandas()
    return out.sort_values(list(out.columns)).reset_index(drop=True)


@pytest.mark.parametrize("jt", [INNER, LEFT, LEFT_SEMI, LEFT_ANTI, EXISTENCE])
def test_join_types_answer_as_under_the_search(monkeypatch, rings, jt):
    sales, returns = _frames(np.random.default_rng(41))
    monkeypatch.setattr(batch_mod, "_gather_bound", lambda: False)
    want = _smj(sales, returns, jt)
    t_search = time.perf_counter()
    monkeypatch.setattr(batch_mod, "_gather_bound", lambda: True)
    got = _smj(sales, returns, jt)
    pd.testing.assert_frame_equal(got, want)
    before = obs.window_summary(rings, t_search)
    after = obs.window_summary(t_search, time.perf_counter())
    assert "hash" not in before["join_lookup_rows"]
    assert after["join_lookup_rows"]["hash"] > 0
    assert after["join_hash_build"]["n"] == 1
    assert after["join_hash_build"]["fallbacks"] == 0
    assert after["join_hash_build"]["rows"] == len(returns)


def test_a_duplicate_key_sends_the_build_to_its_sorted_path(monkeypatch, rings):
    """One key twice among 2,000: the map meets it while placing, says so in
    its event, and the build sorts and answers every pair as before."""
    monkeypatch.setattr(batch_mod, "_gather_bound", lambda: True)
    sales, returns = _frames(np.random.default_rng(43), dup=True)
    got = _smj(sales, returns, INNER)
    s = obs.window_summary(rings, time.perf_counter())
    assert s["join_hash_build"]["n"] == 1 and s["join_hash_build"]["fallbacks"] == 1
    assert "hash" not in s["join_lookup_rows"]
    assert s["sort_rows"]["join_build"]["host"] == 2048
    want = sales.dropna().merge(returns, left_on=["ss_item", "ss_ticket"],
                                right_on=["sr_item", "sr_ticket"])
    want = want.sort_values(list(want.columns)).reset_index(drop=True)
    assert len(got) == len(want) > 0
    np.testing.assert_array_equal(got.to_numpy(dtype=float), want.to_numpy(dtype=float))


def test_the_map_is_not_built_where_a_key_repeats_by_its_range(monkeypatch):
    """More live keys than values in their range: a duplicate for certain, so
    no map is tried and the pigeonhole sends the build to its sort."""
    monkeypatch.setattr(batch_mod, "_gather_bound", lambda: True)
    built = []
    monkeypatch.setattr(hashmap, "build", lambda *a: built.append(a) or 1 / 0)
    df = pd.DataFrame({"k": np.arange(3000, dtype=np.int64) % 2000})
    b = core.prepare_build([Batch.from_pandas(df)], [col(0)], T.Schema.from_arrow(
        pa.Schema.from_pandas(df, preserve_index=False)))
    assert not b.unique and b.hash_table is None and not built


# ---- the rule ---------------------------------------------------------------


@pytest.mark.parametrize("chip, n_live, cap, want", [
    # query 93's build side a partition, and the unit costs' shapes
    (True, 1_150_000, 1 << 21, "hash"),
    (True, 1 << 20, 1 << 20, "hash"),
    (True, 1 << 21, 1 << 21, "hash"),
    # past the key list's widest width, the map at once
    (True, 1025, 2048, "hash"),
    # a small build keeps its live key list
    (True, 1024, 1 << 21, "compare"),
    (True, 15, 128, "compare"),
    # XLA:CPU keeps the search at every one of the cell's shapes
    (False, 1_150_000, 1 << 21, "search"),
    (False, 1 << 20, 1 << 20, "search"),
    (False, 1 << 21, 1 << 21, "search"),
    (False, 1025, 2048, "search"),
    (False, 1024, 1 << 21, "search"),
])
def test_lookup_form_over_shapes(monkeypatch, chip, n_live, cap, want):
    monkeypatch.setattr(batch_mod, "_gather_bound", lambda: chip)
    assert lookup_form(n_live, cap) == want


def test_stage_widths_hold_twice_what_should_reach_them():
    """The widths fall by the table's load from the capacity to the least
    width; every width is at least twice the rows the round before it should
    leave unplaced (n a / 2 after the first round, times a after each)."""
    for n, cap in ((1_150_000, 1 << 21), (1 << 20, 1 << 20), (1 << 17, 1 << 17)):
        load = n / hashmap.table_size(n)
        widths = hashmap.stage_widths(cap, n)
        assert widths[0] == cap and widths[-1] == hashmap.MIN_WIDTH
        assert list(widths) == sorted(widths, reverse=True)
        left = n * load / 2
        for w in widths[1:]:
            assert w >= min(2 * left, cap) or w == hashmap.MIN_WIDTH
            left *= load
