"""Bitonic cluster-sort network vs the stable lax.sort it replaces.

Both implementations (jitted jnp network, Pallas kernel via interpreter on
CPU) must be bit-identical to ``lax.sort(operands, num_keys=n-1)`` with an
iota payload — including stability, dead-row clustering, and non-power-of-2
capacities (padding must never leak into the real slots).
"""

import numpy as np
import jax.numpy as jnp
import pytest
from jax import lax

from auron_tpu.ops import bitonic


def _operands(cap, n_words, n_distinct, seed, dead_frac=0.0):
    rng = np.random.default_rng(seed)
    sel = rng.random(cap) >= dead_frac
    dead_first = jnp.where(jnp.asarray(sel), jnp.uint64(0), jnp.uint64(1))
    words = [
        jnp.asarray(rng.integers(0, n_distinct, cap).astype(np.uint64))
        for _ in range(n_words)
    ]
    if n_words:
        # exercise high-plane bits too
        words[0] = words[0] | (words[0] << jnp.uint64(33))
    iota = jnp.arange(cap, dtype=jnp.int32)
    return (dead_first, *words, iota)


@pytest.mark.parametrize("impl", ["jnp", "pallas"])
@pytest.mark.parametrize(
    "cap,n_words,n_distinct,dead_frac",
    [
        (1024, 1, 37, 0.0),
        (1024, 1, 5, 0.3),
        (2048, 2, 400, 0.1),
        (1500, 2, 64, 0.2),  # non-power-of-2 capacity
        (4096, 3, 11, 0.5),  # many duplicates -> stability visible
        (1024, 1, 1, 0.0),  # single group
    ],
)
def test_matches_stable_lax_sort(impl, cap, n_words, n_distinct, dead_frac):
    ops = _operands(cap, n_words, n_distinct, seed=cap + n_words, dead_frac=dead_frac)
    want = lax.sort(ops, num_keys=len(ops) - 1)
    got = bitonic.bitonic_sort(ops, impl=impl, interpret=True)
    for w, g in zip(want, got):
        np.testing.assert_array_equal(np.asarray(w), np.asarray(g))


@pytest.mark.parametrize("impl", ["jnp", "pallas"])
def test_signed_operands_match_lax(impl):
    """int64/int32 key operands compare signed (sign-biased planes)."""
    rng = np.random.default_rng(21)
    cap = 1024
    k = jnp.asarray(rng.integers(-(2**62), 2**62, cap).astype(np.int64))
    v = jnp.asarray(rng.integers(-(2**30), 2**30, cap).astype(np.int32))
    iota = jnp.arange(cap, dtype=jnp.int32)
    ops = (k, v, iota)
    want = lax.sort(ops, num_keys=2)
    got = bitonic.bitonic_sort(ops, impl=impl, interpret=True)
    for w, g in zip(want, got):
        np.testing.assert_array_equal(np.asarray(w), np.asarray(g))


@pytest.mark.parametrize("impl", ["jnp", "pallas"])
def test_narrow_planes_match(impl):
    """narrow=True operands (statically-zero hi words) sort identically."""
    ops = _operands(2048, 2, 100, seed=9, dead_frac=0.25)
    # dead key (0/1) and second word masked to 32 bits -> narrowable
    ops = (ops[0], ops[1], ops[2] & jnp.uint64(0xFFFFFFFF), ops[3])
    want = lax.sort(ops, num_keys=len(ops) - 1)
    got = bitonic.bitonic_sort(
        ops, impl=impl, interpret=True, narrow=(True, False, True, False)
    )
    for w, g in zip(want, got):
        np.testing.assert_array_equal(np.asarray(w), np.asarray(g))


@pytest.mark.parametrize("impl", ["jnp", "pallas"])
def test_segment_by_keys_device_impl(impl):
    from auron_tpu.exprs.eval import ColumnVal
    from auron_tpu import types as T
    from auron_tpu.ops import segments as S

    rng = np.random.default_rng(7)
    cap = 2048
    vals = jnp.asarray(rng.integers(-50, 50, cap).astype(np.int64))
    validity = jnp.asarray(rng.random(cap) > 0.1)
    sel = jnp.asarray(rng.random(cap) > 0.2)
    words = S.key_words([ColumnVal(vals, validity, T.INT64, None)])

    ref = S.segment_by_keys(words, sel, host_sort=False, device_impl="lax")
    got = S.segment_by_keys(words, sel, host_sort=False, device_impl=impl)
    for name in ("order", "seg_ids", "boundary", "group_of_slot", "sel_sorted"):
        np.testing.assert_array_equal(
            np.asarray(getattr(ref, name)), np.asarray(getattr(got, name)), err_msg=name
        )
    assert int(ref.num_groups) == int(got.num_groups)


@pytest.mark.parametrize("impl", ["jnp", "pallas"])
def test_agg_end_to_end_with_bitonic(impl):
    """A grouped aggregation with the bitonic sort forced stays exact."""
    import pandas as pd
    import pyarrow as pa

    from auron_tpu.columnar import Batch
    from auron_tpu.exec.agg_exec import AggExpr, HashAggExec
    from auron_tpu.exec.base import ExecutionContext
    from auron_tpu.exec.basic import MemoryScanExec
    from auron_tpu.exprs.ir import col
    from auron_tpu.utils.config import (
        DEVICE_SORT_IMPL,
        HOST_SORT_MODE,
        Configuration,
        conf_scope,
    )

    rng = np.random.default_rng(11)
    df = pd.DataFrame({
        "g": rng.integers(0, 40, 6000).astype(np.int64),
        "v": rng.integers(-100, 100, 6000).astype(np.int64),
    })
    scan = MemoryScanExec.single([
        Batch.from_arrow(pa.RecordBatch.from_pandas(
            df.iloc[i : i + 1500], preserve_index=False))
        for i in range(0, len(df), 1500)
    ])
    partial = HashAggExec(
        scan, [(col(0), "g")],
        [(AggExpr("sum", col(1)), "s"), (AggExpr("count", col(1)), "c")],
        "partial",
    )
    agg = HashAggExec(
        partial, [(col(0), "g")],
        [(AggExpr("sum", col(1)), "s"), (AggExpr("count", col(2)), "c")],
        "final",
    )
    # host sort owns CPU by default — force it off so the device impl runs
    conf = Configuration().set(HOST_SORT_MODE, "off").set(DEVICE_SORT_IMPL, impl)
    with conf_scope(conf):
        got = (
            agg.collect(0, ExecutionContext()).to_pandas()
            .sort_values("g").reset_index(drop=True)
        )
    want = (
        df.groupby("g").agg(s=("v", "sum"), c=("v", "count")).reset_index()
        .sort_values("g").reset_index(drop=True)
    )
    import pandas.testing as pdt

    pdt.assert_frame_equal(got, want, check_dtype=False)


@pytest.mark.parametrize("impl", ["jnp", "pallas"])
def test_order_by_and_window_with_bitonic(impl):
    """The ORDER BY and window paths produce identical results with the
    network forced (exec/sort_exec.py + exec/window_exec.py wiring)."""
    import pandas as pd
    import pyarrow as pa

    from auron_tpu.columnar import Batch
    from auron_tpu.exec.base import ExecutionContext
    from auron_tpu.exec.basic import MemoryScanExec
    from auron_tpu.exec.sort_exec import SortExec
    from auron_tpu.exec.window_exec import WindowExec, WindowFunc
    from auron_tpu.exprs.ir import col
    from auron_tpu.ops.sortkeys import SortSpec
    from auron_tpu.utils.config import (
        DEVICE_SORT_IMPL,
        HOST_SORT_MODE,
        Configuration,
        conf_scope,
    )

    rng = np.random.default_rng(31)
    df = pd.DataFrame({
        "g": rng.integers(0, 9, 4000).astype(np.int64),
        "v": rng.standard_normal(4000),
    })
    df.loc[df.index % 11 == 0, "v"] = np.nan
    scan = MemoryScanExec.single([Batch.from_arrow(
        pa.RecordBatch.from_pandas(df.iloc[i:i+1000], preserve_index=False))
        for i in range(0, len(df), 1000)])

    conf = Configuration().set(HOST_SORT_MODE, "off").set(DEVICE_SORT_IMPL, impl)
    ref_conf = Configuration().set(HOST_SORT_MODE, "off").set(DEVICE_SORT_IMPL, "lax")

    def run_sort(c):
        op = SortExec(scan, [col(1), col(0)],
                      [SortSpec(asc=False, nulls_first=False), SortSpec()])
        with conf_scope(c):
            return op.collect(0, ExecutionContext(conf=c)).to_pandas()

    pd.testing.assert_frame_equal(run_sort(conf), run_sort(ref_conf))

    def run_window(c):
        op = WindowExec(scan, [col(0)], [(col(1), SortSpec())],
                        [(WindowFunc("row_number"), "rn")])
        with conf_scope(c):
            out = op.collect(0, ExecutionContext(conf=c)).to_pandas()
        return out.sort_values(["g", "rn"]).reset_index(drop=True)

    pd.testing.assert_frame_equal(run_window(conf), run_window(ref_conf))


def test_sort_impl_for_gates():
    from auron_tpu.utils.config import DEVICE_SORT_IMPL, Configuration, conf_scope

    # explicit override wins regardless of backend
    with conf_scope(Configuration().set(DEVICE_SORT_IMPL, "jnp")):
        assert bitonic.sort_impl_for(2, 1 << 16) == "jnp"
    # auto -> lax on every backend (the kernel is opt-in until a chip
    # run has compared the two)
    with conf_scope(Configuration().set(DEVICE_SORT_IMPL, "auto")):
        assert bitonic.sort_impl_for(2, 1 << 16) == "lax"


# ---------------------------------------------------------------------------
# tiled multi-block path (VERDICT r4 #4)
# ---------------------------------------------------------------------------


def test_tiled_sort_matches_lax_sort_multiblock():
    """Force multi-block tiling (shrunken VMEM gate) and pin the tiled
    network bit-exactly to the stable lax.sort across block-count regimes."""
    from auron_tpu.ops import bitonic as BT

    rng = np.random.default_rng(17)
    old_gate = BT._VMEM_GATE_BYTES
    BT._VMEM_GATE_BYTES = 64 << 10  # tiny: every case below tiles
    try:
        for n in (3000, 8192, 20000, 65536):
            w0 = jnp.asarray(rng.integers(0, 1 << 60, n, dtype=np.uint64))
            w1 = jnp.asarray(rng.integers(0, 50, n, dtype=np.uint64))
            iota = jnp.arange(n, dtype=jnp.int32)
            ops = (w1, w0, iota)  # duplicate-heavy leading key
            want = lax.sort(ops, num_keys=2)
            got = BT.bitonic_sort(ops, impl="jnp")
            for g, w in zip(got, want):
                np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    finally:
        BT._VMEM_GATE_BYTES = old_gate


def test_tiled_sort_block_boundary_values():
    """Adversarial block patterns: presorted, reverse-sorted, constant, and
    alternating runs must all merge-split to global order."""
    from auron_tpu.ops import bitonic as BT

    old_gate = BT._VMEM_GATE_BYTES
    BT._VMEM_GATE_BYTES = 64 << 10
    try:
        n = 16384
        cases = [
            np.arange(n, dtype=np.uint64),
            np.arange(n, dtype=np.uint64)[::-1].copy(),
            np.full(n, 7, dtype=np.uint64),
            np.tile(np.array([5, 1, 9, 3], dtype=np.uint64), n // 4),
        ]
        for arr in cases:
            ops = (jnp.asarray(arr), jnp.arange(n, dtype=jnp.int32))
            want = lax.sort(ops, num_keys=1)
            got = BT.bitonic_sort(ops, impl="jnp")
            for g, w in zip(got, want):
                np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    finally:
        BT._VMEM_GATE_BYTES = old_gate


def test_tiled_sort_pallas_matches_lax_sort():
    from auron_tpu.ops import bitonic as BT

    old_gate = BT._VMEM_GATE_BYTES
    BT._VMEM_GATE_BYTES = 64 << 10
    try:
        rng = np.random.default_rng(5)
        n = 8192
        ops = (jnp.asarray(rng.integers(0, 1 << 40, n, dtype=np.uint64)),
               jnp.arange(n, dtype=jnp.int32))
        want = lax.sort(ops, num_keys=1)
        got = BT.bitonic_sort(ops, impl="pallas")
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    finally:
        BT._VMEM_GATE_BYTES = old_gate
