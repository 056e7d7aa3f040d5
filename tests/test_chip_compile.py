"""Ask the chip's compiler, without the chip: the Pallas kernels and the main
path's stage programs compiled for a DESCRIBED TPU v5e at the shapes the
main path gives them (on-chip-measurement guide, section 2).

What interpret mode cannot show, this does: Mosaic refuses 64-bit values
inside a kernel, a block that outgrows VMEM, a program that cannot be
partitioned. Nothing runs, so nothing here is a result or a time on the
device — only "the chip's compiler accepts it, quickly".

The topology is described inside a module-scoped fixture, never at import
(only one process may hold the TPU library; every xdist worker imports
every test file). Keep every such test in THIS file: a second file could
go to another worker, whose fixture would then skip.
"""

import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

#: a kernel compiles in a second or two and the flagship stage in about six;
#: the bound only has to catch a compile that has grown to minutes (the
#: fully-unrolled sort network did), with room for six busy xdist workers
MAX_COMPILE_S = 60.0


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else it logs under /tmp
    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe = no compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described device is written to the persistent cache
    # but cannot be read back without a chip (the next one warns): keep
    # these out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    from auron_tpu.parallel.mesh import PARTITION_AXIS

    return Mesh(np.array(topo.devices[:4]), (PARTITION_AXIS,))


def _compile(fn, *args, **static):
    """Lower + compile for the described device, within the bound."""
    t0 = time.perf_counter()
    compiled = fn.lower(*args, **static).compile()
    secs = time.perf_counter() - t0
    assert secs < MAX_COMPILE_S, f"compiled in {secs:.1f}s (bound {MAX_COMPILE_S}s)"
    return compiled


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# ---------------------------------------------------------------------------
# Pallas kernels
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("rows", [1000, 1 << 16, 1 << 20, 1 << 22])
def test_partition_ids_kernel_compiles(one_chip, rows):
    """The shuffle pid kernel up to bench.py's accelerator batch (1<<22
    rows): the row-block grid keeps VMEM use independent of the batch."""
    from auron_tpu.ops.pallas_kernels import partition_ids_pallas

    compiled = _compile(
        partition_ids_pallas, _sds((rows,), jnp.int64, one_chip), n_parts=200)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("planes,P_", [(4, 2048), (3, 4096), (6, 2048)])
def test_bitonic_sort_kernel_compiles(one_chip, planes, P_):
    """The opt-in (exec.device.sort.impl=pallas) single-block network."""
    from auron_tpu.ops import bitonic

    x = _sds((planes, P_ // 128, 128), jnp.uint32, one_chip)
    compiled = _compile(bitonic._run_pallas, x, P=P_, interpret=False)
    assert "tpu_custom_call" in compiled.as_text()


def test_bitonic_merge_kernel_compiles(one_chip):
    """The tiled path's merge-split kernel over one block pair."""
    from auron_tpu.ops import bitonic

    x = _sds((4, 8192 // 128, 128), jnp.uint32, one_chip)
    compiled = _compile(bitonic._run_pallas_merge, x, P=8192, interpret=False)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("cap", [1 << 11, 1 << 17, 1 << 20, 1 << 22])
def test_auto_sort_impl_is_lax_on_tpu(monkeypatch, cap):
    """No Pallas sort shape is ``auto``'s pick: the unrolled network's
    compile runs to minutes from P = 131072 up, so on a TPU too ``auto``
    is lax.sort until a chip run has compared them (ROADMAP S8)."""
    from auron_tpu.ops import bitonic
    from auron_tpu.utils.config import DEVICE_SORT_IMPL, Configuration

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    conf = Configuration().set(DEVICE_SORT_IMPL, "auto")
    assert bitonic.sort_impl_for(2, cap, conf=conf) == "lax"


# ---------------------------------------------------------------------------
# the main path's jitted programs
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("rows,n_parts", [(1 << 20, 2), (1 << 22, 8)])
def test_routing_counts_compile(one_chip, rows, n_parts):
    """The exchange's on-device routing histogram (plain jnp, int32)."""
    from auron_tpu.parallel.mesh_driver import _live_pid_counts

    compiled = _compile(
        _live_pid_counts, _sds((rows,), jnp.bool_, one_chip),
        _sds((rows,), jnp.int32, one_chip), n_parts=n_parts)
    (out,) = jax.tree.leaves(compiled.out_info)
    assert out.shape == (n_parts,) and out.dtype == jnp.int32


def test_float64_key_words_compile(monkeypatch, one_chip):
    """A TPU carries float64 as a float32 pair and refuses every bitcast
    between float64 and 64-bit integers: the IEEE-bits words are refused,
    the pair-built words of ops/floatbits.py compile."""
    from auron_tpu.ops import floatbits

    f = _sds((1 << 20,), jnp.float64, one_chip)
    with pytest.raises(Exception, match="X64 element types"):
        jax.jit(lambda v: v.view(jnp.uint64)).lower(f).compile()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for word in (floatbits.f64_orderable_word, floatbits.f64_equality_word):
        compiled = _compile(jax.jit(word), f)
        (out,) = jax.tree.leaves(compiled.out_info)
        assert out.dtype == jnp.uint64


def _scatter_results(hlo: str) -> list[list[str]]:
    """The element types every scatter of a compiled program's text
    accumulates into, one list a scatter: ``["s32"]`` a 32-bit one,
    ``["u32", "u32"]`` the variadic scatter the TPU's 64-bit rewrite leaves
    of an int64 one (low and high words scattered as a pair)."""
    import re

    out = []
    for line in hlo.splitlines():
        m = re.search(r"= (\(.*?\)|\S+) scatter\(", line)
        if m:
            out.append(re.findall(r"\b([a-z]+\d+|pred)\[", m.group(1)))
    return out


@pytest.mark.parametrize("rows, raw, limbs", [
    (1 << 22, True, 3),     # the map side: DECIMAL(7,2) prices, 9-bit limbs
    (1 << 17, False, 5),    # the reduce side: a merge of DECIMAL(17,2) sums
])
def test_dense_aggregate_fold_compiles_at_query_65s_batch(one_chip, rows, raw, limbs):
    """The dense table's fold (``agg_exec._dense_update_jit``) as query 65's
    sums give it: a batch of 4,194,304 rows (the partial sum) or 131,072
    (the final sum's merge), two int64 keys packed into 13 x 18,001 lanes of
    a 262,144-slot table, one DECIMAL sum. One scatter program: seconds to
    compile where a sort of that width would take minutes (ops/hostsort.py
    DEVICE_SORT_MAX_ROWS). The sum goes by int32 limbs: no scatter of the
    program has a 64-bit operand (the v5e has no 64-bit integer scatter; its
    rewrite shows as one scatter of a PAIR of 32-bit words),
    except the one the map side's guard keeps for a plane that breaks its
    declared precision, in the other arm of its conditional."""
    from auron_tpu import types as T
    from auron_tpu.exec import agg_exec
    from auron_tpu.ops.segments import limb_plan

    size = 1 << 18
    col = _sds((rows,), jnp.int64, one_chip)
    ok = _sds((rows,), jnp.bool_, one_chip)
    in_t = T.decimal(7, 2)
    plan = limb_plan(agg_exec._sum_bits(raw, in_t)[0], rows)
    assert plan.limbs == limbs
    guarded = plan.cover < 64
    compiled = _compile(
        agg_exec._dense_update_jit,
        (_sds((size,), jnp.int64, one_chip),), (_sds((size,), jnp.bool_, one_chip),),
        _sds((size,), jnp.bool_, one_chip),
        _sds((2,), jnp.int64, one_chip), _sds((2,), jnp.int64, one_chip),
        (col, col), (ok, ok), ok, (((col, ok),),),
        cfg=(raw, (("sum", in_t),), (14, 18002)), size=size)
    assert compiled.memory_analysis().temp_size_in_bytes < (1 << 28)
    scatters = _scatter_results(compiled.as_text())
    wide = [ts for ts in scatters
            if len(ts) > 1 or ts[0] in ("s64", "u64", "f64")]
    # the limbs and the two flag scatters, one 32-bit accumulator each
    assert len(scatters) - len(wide) == limbs + 2, scatters
    assert len(wide) == (1 if guarded else 0), scatters
    assert ("conditional(" in compiled.as_text()) == guarded


@pytest.mark.parametrize("cap, out_cap", [
    (1 << 21, 1 << 18),     # query 65's map-side drain of its dense table
    (1 << 22, 1 << 19),     # its tenth-live batch through the dense boundary
])
def test_compaction_by_scatter_compiles_at_query_65s_shapes(
        monkeypatch, one_chip, cap, out_cap):
    """``_compact_dev`` as the TPU builds it at query 65's widest takes: the
    index by ONE scatter (``compaction_index_form``), no search loop left,
    four int64 columns gathered at the bucket. The temporaries stay under
    one int32 plane of the capacity: no windowed layout 128 lanes wide."""
    from auron_tpu.columnar import batch as batch_mod

    monkeypatch.setattr(batch_mod, "_gather_bound", lambda: True)
    assert batch_mod.compaction_index_form(cap, out_cap) == "scatter"
    # a jit of its own: no trace another test left under XLA:CPU's form
    compact = jax.jit(batch_mod._compact_dev.__wrapped__,
                      static_argnames=("out_cap", "cols"))
    planes = range(4)
    dev = batch_mod.DeviceBatch(
        _sds((cap,), jnp.bool_, one_chip),
        tuple(_sds((cap,), jnp.int64, one_chip) for _ in planes),
        tuple(_sds((cap,), jnp.bool_, one_chip) for _ in planes))
    compiled = _compile(compact, dev, out_cap=out_cap)
    text = compiled.as_text()
    assert len(_scatter_results(text)) == 1 and " while(" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * cap


def test_flagship_stage_program_compiles(one_chip):
    """``__graft_entry__.entry()``'s fused filter + project + group
    aggregation (a 3-operand lax.sort inside) at its own example shapes."""
    from auron_tpu.models.flagship import example_args, fused_filter_agg_step

    args = [_sds(a.shape, a.dtype, one_chip) for a in example_args(cap=8192)]
    compiled = _compile(jax.jit(fused_filter_agg_step), *args)
    assert compiled.memory_analysis().temp_size_in_bytes < (1 << 30)


@pytest.mark.parametrize("rows, width, use_lut", [
    (1 << 20, 1024, True),      # the chain's batch, int32 offsets of the LUT
    (1 << 22, 256, True),       # the bridge path's batch
    (1 << 20, 1024, False),     # a build without a LUT: whole 64-bit words
])
def test_compare_probe_keeps_no_key_by_row_temporary(one_chip, rows, width,
                                                     use_lut):
    """The unique probe against a small build's live key list
    (``core._compare_rows``): the reduction over the list's slots is fused
    with the compare and select, so the program's temporaries stay a few
    row vectors and never a ``[slots, rows]`` array."""
    from auron_tpu.exec.joins import core

    bcap = 32768
    key_list = (_sds((width,), jnp.int32 if use_lut else jnp.uint64, one_chip),
                _sds((width,), jnp.int32, one_chip))
    compiled = _compile(
        core._unique_probe_jit,
        (_sds((rows,), jnp.int64, one_chip),),
        (_sds((rows,), jnp.bool_, one_chip),), _sds((rows,), jnp.bool_, one_chip),
        _sds((bcap,), jnp.int32, one_chip) if use_lut else None,
        _sds((), jnp.int64, one_chip) if use_lut else None,
        [_sds((bcap,), jnp.uint64, one_chip)], _sds((), jnp.int32, one_chip),
        key_list, bcap=bcap, use_lut=use_lut, probe_outer=False,
        key_kinds=("int",))
    assert compiled.memory_analysis().temp_size_in_bytes < 16 * rows


def test_hash_map_build_and_probe_compile_at_query_93s_shapes(one_chip):
    """The unique build's hash map (ops/hashmap.py) at a partition of query
    93: its build of 1,048,576 live keys in as many rows (every placement
    stage and the tail's loop), and the unique probe's program looking up
    4,194,304 probe rows in it. The map's arrays are planes, never a
    ``[rows, k]`` window laid out 128 lanes wide: the build's temporaries
    stay within its table and a few row planes, the probe's within a few
    planes of its rows."""
    from auron_tpu.exec.joins import core
    from auron_tpu.ops import hashmap

    n = 1 << 20
    size = hashmap.table_size(n)
    compiled = _compile(
        hashmap._build_jit, _sds((n,), jnp.uint64, one_chip),
        _sds((n,), jnp.bool_, one_chip), size=size,
        widths=hashmap.stage_widths(n, n))
    assert compiled.memory_analysis().temp_size_in_bytes < 12 * size + 64 * n

    rows = 1 << 22
    table = hashmap.HashTable(_sds((size,), jnp.uint32, one_chip),
                              _sds((size,), jnp.uint32, one_chip),
                              _sds((size,), jnp.int32, one_chip))
    compiled = _compile(
        core._unique_probe_jit,
        (_sds((rows,), jnp.int64, one_chip),),
        (_sds((rows,), jnp.bool_, one_chip),), _sds((rows,), jnp.bool_, one_chip),
        None, None, [_sds((n,), jnp.uint64, one_chip)],
        _sds((), jnp.int32, one_chip), table, bcap=n, use_lut=False,
        probe_outer=False, key_kinds=("int",))
    # 28 B a row on the compiler of this writing: the mixed word, its two
    # slots and the two 64-bit slots read; a window 128 lanes wide would be
    # hundreds
    assert compiled.memory_analysis().temp_size_in_bytes < 32 * rows


def test_exchange_steps_compile_for_four_chips(mesh4):
    """The ICI shuffle as ONE program across four chips: both mesh
    programs must partition, and the collective must really be there."""
    from auron_tpu.parallel.exchange import (
        pid_exchange_step,
        sharded_agg_exchange_step,
    )

    rows = NamedSharding(mesh4, P("p"))
    cap = 1 << 17
    cols = (_sds((4, cap), jnp.int64, rows), _sds((4, cap), jnp.float64, rows))
    masks = (_sds((4, cap), jnp.bool_, rows),) * 2
    compiled = _compile(
        pid_exchange_step(mesh4, slot_cap=1 << 16), (cols, masks),
        _sds((4, cap), jnp.bool_, rows), _sds((4, cap), jnp.int32, rows))
    assert "all-to-all" in compiled.as_text()

    compiled = _compile(
        sharded_agg_exchange_step(mesh4, slot_cap=128),
        _sds((4, 128), jnp.int64, rows), _sds((4, 128), jnp.float64, rows),
        _sds((4, 128), jnp.bool_, rows))
    assert "all-to-all" in compiled.as_text()
