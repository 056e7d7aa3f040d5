"""Randomized join differential testing across the full matrix.

Random tables (duplicate keys, NULL keys, multiple batches) x random join
type x random exec kind x random build side, against SQL-semantics pandas
oracles — the fuzzing extension of the fixed matrix in test_joins.py.
"""

from collections import Counter

import numpy as np
import pandas as pd
import pyarrow as pa
import pytest

from auron_tpu.columnar import Batch
from auron_tpu.exec.basic import MemoryScanExec
from auron_tpu.exec.joins import BroadcastHashJoinExec, SortMergeJoinExec
from auron_tpu.exprs.ir import col


def _mk(df, chunk):
    bs = [
        Batch.from_arrow(
            pa.RecordBatch.from_pandas(df.iloc[i : i + chunk], preserve_index=False)
        )
        for i in range(0, max(len(df), 1), chunk)
    ]
    if not bs:
        bs = [Batch.from_arrow(pa.RecordBatch.from_pandas(df, preserve_index=False))]
    return MemoryScanExec.single(bs)


def _table(rng, n, key_range, null_frac):
    k = rng.integers(0, key_range, n).astype(float)
    k[rng.random(n) < null_frac] = np.nan
    return pd.DataFrame({
        "k": pd.array([None if np.isnan(x) else int(x) for x in k], dtype="Int64"),
        "p": rng.integers(0, 1000, n),
    })


def _rows(df, cols):
    out = []
    for _, r in df[cols].iterrows():
        out.append(tuple(None if pd.isna(v) else int(v) for v in r))
    return Counter(out)


@pytest.mark.parametrize("seed", range(12))
def test_join_fuzz(seed):
    _fuzz(seed)


@pytest.mark.parametrize("seed", range(12, 24))
def test_join_fuzz_through_the_compare_map(seed, lookups_compare,
                                           lookup_events):
    """The same matrix over tables of distinct keys (every build unique)
    with the lookup rule patched: each unique probe reads its build's
    live key list, never a LUT or the sorted words."""
    with lookups_compare():
        probed, evs = lookup_events(lambda: _fuzz(seed, distinct=True))
    assert {kind for kind, _ in evs} == ({"compare"} if probed else set())


def _fuzz(seed, distinct=False) -> bool:
    """One random join against its oracle. Returns whether both sides held
    a row with a key (so that a unique build was probed)."""
    rng = np.random.default_rng(seed + 100)
    ldf = _table(rng, int(rng.integers(0, 120)), int(rng.integers(1, 25)), 0.1)
    rdf = _table(rng, int(rng.integers(0, 120)), int(rng.integers(1, 25)), 0.1)
    if distinct:
        ldf = ldf[~ldf.k.duplicated() | ldf.k.isna()].reset_index(drop=True)
        rdf = rdf[~rdf.k.duplicated() | rdf.k.isna()].reset_index(drop=True)
    rdf = rdf.rename(columns={"k": "k2", "p": "q"})
    jt = str(rng.choice(["inner", "left", "right", "full", "left_semi",
                         "left_anti", "existence"]))
    kind = str(rng.choice(["smj", "bhj_left", "bhj_right"]))
    chunk = int(rng.integers(16, 64))

    left = _mk(ldf, chunk)
    right = _mk(rdf, chunk)
    if kind == "smj":
        op = SortMergeJoinExec(left, right, [col(0)], [col(0)], jt)
    else:
        op = BroadcastHashJoinExec(
            left, right, [col(0)], [col(0)], jt,
            build_side="left" if kind == "bhj_left" else "right",
        )
    got = op.collect().to_pandas()

    lnn = ldf[ldf.k.notna()]
    rnn = rdf[rdf.k2.notna()]
    rkeys = set(rnn.k2)
    if jt == "inner":
        want = lnn.merge(rnn, left_on="k", right_on="k2")
        assert _rows(got, ["k", "p", "k2", "q"]) == _rows(want, ["k", "p", "k2", "q"])
    elif jt == "left":
        want = ldf.merge(rnn, left_on="k", right_on="k2", how="left")
        assert _rows(got, ["k", "p", "k2", "q"]) == _rows(want, ["k", "p", "k2", "q"])
    elif jt == "right":
        want = lnn.merge(rdf, left_on="k", right_on="k2", how="right")
        assert _rows(got, ["k", "p", "k2", "q"]) == _rows(want, ["k", "p", "k2", "q"])
    elif jt == "full":
        left_part = ldf.merge(rnn, left_on="k", right_on="k2", how="left")
        matched = set(lnn.k) & rkeys
        right_un = rdf[~rdf.k2.isin(matched) | rdf.k2.isna()]
        pad = pd.DataFrame({"k": [None] * len(right_un), "p": [None] * len(right_un)})
        pad.index = right_un.index
        want = pd.concat([left_part, pd.concat([pad, right_un], axis=1)],
                         ignore_index=True)
        assert _rows(got, ["k", "p", "k2", "q"]) == _rows(want, ["k", "p", "k2", "q"])
    elif jt == "left_semi":
        want = ldf[ldf.k.isin(rkeys)]
        assert _rows(got, ["k", "p"]) == _rows(want, ["k", "p"])
    elif jt == "left_anti":
        want = ldf[~ldf.k.isin(rkeys) | ldf.k.isna()]
        assert _rows(got, ["k", "p"]) == _rows(want, ["k", "p"])
    else:  # existence
        assert len(got) == len(ldf)
        for _, r in got.iterrows():
            expect = (not pd.isna(r.k)) and int(r.k) in rkeys
            assert bool(r["exists"]) == expect
    return len(lnn) > 0 and len(rnn) > 0
