"""Pallas TPU kernels for hot host-independent primitives.

The engine's default device path is XLA-compiled jnp (which already fuses
elementwise chains well); these Pallas kernels exist for the hot spots
where hand control over VMEM tiling pays: the murmur3 partition-id pass
over shuffle batches is the first (every shuffled row pays it). The kernel
computes Spark-exact murmur3(int64) + Pmod in one VMEM-resident pass:
uint32 lane math on the VPU, 2D (rows, 128) tiling.

Usage is gated by the caller (``exec/shuffle/partitioning.py`` picks the
kernel on a TPU and the jnp hash elsewhere); CPU tests run it in interpret
mode, and ``tests/test_chip_compile.py`` compiles it for a described v5e.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

_LANES = 128


def _murmur3_pmod_kernel(lo_ref, hi_ref, out_ref, *, seed: int, n_parts: int):
    c1 = jnp.uint32(0xCC9E2D51)
    c2 = jnp.uint32(0x1B873593)

    def rotl(x, r):
        return (x << r) | (x >> (32 - r))

    def mix(h1, k1):
        k1 = k1 * c1
        k1 = rotl(k1, 15)
        k1 = k1 * c2
        h1 = h1 ^ k1
        h1 = rotl(h1, 13)
        return h1 * jnp.uint32(5) + jnp.uint32(0xE6546B64)

    lo = lo_ref[:]
    hi = hi_ref[:]
    h1 = jnp.full(lo.shape, jnp.uint32(seed))
    h1 = mix(h1, lo)
    h1 = mix(h1, hi)
    h1 = h1 ^ jnp.uint32(8)
    h1 = h1 ^ (h1 >> 16)
    h1 = h1 * jnp.uint32(0x85EBCA6B)
    h1 = h1 ^ (h1 >> 13)
    h1 = h1 * jnp.uint32(0xC2B2AE35)
    h1 = h1 ^ (h1 >> 16)
    signed = h1.astype(jnp.int32)
    p = signed % jnp.int32(n_parts)
    out_ref[:] = jnp.where(p < 0, p + jnp.int32(n_parts), p)


# rows of 128 lanes per grid step: 3 uint32/int32 blocks of 512 KiB,
# double-buffered = 3 MiB of VMEM whatever the batch size
_BLOCK_ROWS = 1024


@partial(jax.jit, static_argnames=("n_parts", "seed", "interpret"))
def partition_ids_pallas(
    values_i64: jnp.ndarray, n_parts: int, seed: int = 42, interpret: bool = False
) -> jnp.ndarray:
    """Spark Pmod(murmur3(long), n) as a Pallas kernel. 1-D input.
    A 1-D grid walks (_BLOCK_ROWS, 128) row blocks, so VMEM use does not
    grow with the batch."""
    from jax.experimental import pallas as pl

    n = values_i64.shape[0]
    rows = -(-n // _LANES)
    block = min(_BLOCK_ROWS, -(-rows // 8) * 8)
    rows = -(-rows // block) * block
    u = jnp.pad(values_i64, (0, rows * _LANES - n)).view(jnp.uint64)
    lo = (u & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32).reshape(rows, _LANES)
    hi = (u >> jnp.uint64(32)).astype(jnp.uint32).reshape(rows, _LANES)
    # x64 is on globally and Mosaic refuses 64-bit values: a bare Python 0
    # in the index map would be traced as an int64 constant
    spec = pl.BlockSpec((block, _LANES), lambda i: (i, jnp.int32(0)))
    out = pl.pallas_call(
        partial(_murmur3_pmod_kernel, seed=seed, n_parts=n_parts),
        out_shape=jax.ShapeDtypeStruct((rows, _LANES), jnp.int32),
        grid=(rows // block,),
        in_specs=[spec, spec],
        out_specs=spec,
        interpret=interpret,
    )(lo, hi)
    return out.reshape(-1)[:n]
