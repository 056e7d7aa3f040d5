"""uint64 key words of a float64 column — on a device that has no float64.

The engine keys everything on uint64 words (ops/sortkeys.py orders by them,
ops/segments.py and exec/joins/core.py test them for equality), and for a
float64 column the word is its IEEE bit pattern, taken with a bitcast. A TPU
has no 64-bit float: XLA:TPU carries a float64 as a PAIR of float32
(``hi + lo``: about 48 mantissa bits, float32's exponent range) and its
compiler refuses every bitcast between float64 and a 64-bit integer —
``UNIMPLEMENTED: While rewriting computation to not contain X64 element
types`` (so do ``jnp.signbit`` and ``jnp.frexp``, which bitcast inside).
That is how every ORDER BY over a double first failed on the v5e (PR 22).

On a TPU the words are therefore built from the pair itself, with float32
bitcasts only: ``hi = float32(f)`` and ``lo = float32(f - hi)`` recover the
two halves exactly, ``(hi, lo)`` compares lexicographically like ``f``, and
two values are equal iff both halves are. Elsewhere the IEEE bits stay.
The Spark-exact hashes of a double (ops/hashing.py murmur3_f64,
xxhash64_f64) need the true IEEE bits and still bitcast: hashing a float64
key on a TPU raises XLA's UNIMPLEMENTED, loudly, until someone needs it.

Callers canonicalize first (-0.0 -> 0.0, one NaN), as they always did.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_SIGN64 = 1 << 63


def _has_f64_bitcast() -> bool:
    return jax.default_backend() != "tpu"


def _halves(f: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(hi, lo) float32 halves of a canonicalized float64: lo is zero where
    hi is not finite, zeros are +0.0 and NaN is the one float32 NaN."""
    hi = f.astype(jnp.float32)
    lo = jnp.where(jnp.isfinite(hi), f - hi.astype(jnp.float64), 0.0)
    lo = lo.astype(jnp.float32)
    hi = jnp.where(jnp.isnan(hi), jnp.float32(jnp.nan), hi)
    hi = jnp.where(hi == 0, jnp.float32(0), hi)
    lo = jnp.where(lo == 0, jnp.float32(0), lo)
    return hi, lo


def _orderable32(f32: jnp.ndarray) -> jnp.ndarray:
    """uint32 whose unsigned order is the float32's numeric order."""
    b = f32.view(jnp.uint32)
    sign = jnp.uint32(1 << 31)
    return jnp.where((b & sign) != 0, ~b, b | sign)


def _pack(hi32: jnp.ndarray, lo32: jnp.ndarray) -> jnp.ndarray:
    return (hi32.astype(jnp.uint64) << jnp.uint64(32)) | lo32.astype(jnp.uint64)


def f64_equality_word(f: jnp.ndarray) -> jnp.ndarray:
    """uint64 equal iff the (canonicalized) float64 values are equal."""
    if _has_f64_bitcast():
        return f.view(jnp.uint64)
    hi, lo = _halves(f)
    return _pack(hi.view(jnp.uint32), lo.view(jnp.uint32))


def f64_orderable_word(f: jnp.ndarray) -> jnp.ndarray:
    """uint64 whose unsigned order is the (canonicalized) float64's SQL
    ascending order, NaN greatest."""
    if _has_f64_bitcast():
        b = f.view(jnp.uint64)
        sign = jnp.uint64(_SIGN64)
        return jnp.where((b & sign) != 0, ~b, b | sign)
    hi, lo = _halves(f)
    return _pack(_orderable32(hi), _orderable32(lo))
