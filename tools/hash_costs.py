"""The unique-build join's lookups, timed on the chip: what
``columnar/batch.py lookup_form`` and ``ops/hashmap.py`` rest on.

    python tools/hash_costs.py            (on a TPU host)

Times, by the host clock around calls that end in ``block_until_ready``
(best of three sets, ``tools/scatter_costs.py``'s ``timed``):

- the hash map's build (``ops/hashmap.py _build_jit``) of 1,048,576 and
  2,097,152 distinct 40-bit keys at a capacity of their own, and of
  1,150,000 keys in a capacity of 2,097,152 (query 93's build side a
  partition), at the module's table size;
- its probe of 4,194,304 words, a tenth of them build keys, against each of
  those tables: the lookup alone, as ``_probe_unique_ops``' hash arm runs it;
- the binary search over the same keys sorted (``ops/binsearch.py
  _search`` plus the equality check, the search arm), at 4,194,304 probe
  words.

Runs on a TPU only; prints a line a reading and writes them all to
``chiprun_out/hash_costs.json``. PERF.md section 5 ("unit costs") holds the
readings."""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

import auron_tpu  # noqa: F401  (x64)
from auron_tpu.ops import binsearch, hashmap

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from scatter_costs import OUT, note, timed  # noqa: E402

PROBE_ROWS = 1 << 22
#: (live keys, capacity)
BUILDS = ((1 << 20, 1 << 20), (1 << 21, 1 << 21), (1_150_000, 1 << 21))


def build_in(size: int, widths: tuple):
    return jax.jit(lambda w, s: hashmap._build_jit.__wrapped__(
        w, s, size=size, widths=widths))


@jax.jit
def hash_probe(table, words):
    return hashmap.probe(table, words)


@jax.jit
def search_probe(sorted_words, n, words):
    lo = binsearch._search([sorted_words], [words], n, binsearch._lex_less)
    bi = jnp.clip(lo, 0, sorted_words.shape[0] - 1)
    return bi, (lo < n) & (sorted_words[bi] == words)


def main() -> None:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"needs a TPU, found {dev.platform}: a CPU time is no unit cost")
    print(f"device: {dev.device_kind}", flush=True)
    rng = np.random.default_rng(11)
    for n, cap in BUILDS:
        keys = rng.choice(1 << 40, n, replace=False).astype(np.uint64)
        w = np.zeros(cap, np.uint64)
        w[:n] = keys
        sel = np.arange(cap) < n
        perm = rng.permutation(cap)
        word, live = jnp.asarray(w[perm]), jnp.asarray(sel[perm])
        hits = rng.random(PROBE_ROWS) < 0.1
        probe = np.where(hits, keys[rng.integers(0, n, PROBE_ROWS)],
                         rng.integers(0, 1 << 40, PROBE_ROWS).astype(np.uint64))
        pw = jnp.asarray(probe)
        widths = hashmap.stage_widths(cap, n)
        tag = f"n{n}.cap{cap}"
        size = hashmap.table_size(n)
        f = build_in(size, widths)
        table, stats = f(word, live)
        left, dup, rounds = (int(x) for x in stats)
        print(f"{tag}.size{size}: placed {not (left or dup)}, rounds {rounds}",
              flush=True)
        note(f"build.{tag}.size{size}", timed(f, word, live), cap)
        row, found = hash_probe(table, pw)
        ok = bool(np.array_equal(np.asarray(found), np.isin(probe, keys)))
        print(f"probe.{tag}.size{size}: found as the keys say: {ok}", flush=True)
        note(f"probe.{tag}.size{size}", timed(hash_probe, table, pw), PROBE_ROWS)
        sorted_w = jnp.asarray(np.concatenate(
            [np.sort(keys), np.zeros(cap - n, np.uint64)]))
        note(f"search.{tag}", timed(search_probe, sorted_w, jnp.int32(n), pw),
             PROBE_ROWS)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/hash_costs.json", "w") as fh:
        json.dump({"device_kind": dev.device_kind, "seconds": OUT}, fh, indent=1)


if __name__ == "__main__":
    main()
