"""ctypes bindings for the native runtime helpers (native/auron_native.cpp).

Loads ``native/libauron_native.so``, building it from the source beside it
on first use. Every entry has a numpy twin so the engine runs where the
library is not packaged at all (mirrors the reference's
is_jni_bridge_inited() branching that lets kernels run without a JVM,
spill.rs:90-101) — but a source that does not build, or a library that
does not load, raises: which twin ran is never a silent matter.
"""

from __future__ import annotations

import ctypes
import os
import subprocess

import numpy as np

_LIB = None
_TRIED = False


def _lib():
    global _LIB, _TRIED
    if _TRIED:
        return _LIB
    _TRIED = True
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    so = os.path.join(here, "native", "libauron_native.so")
    if not os.path.exists(so):
        if not os.path.exists(os.path.join(here, "native", "auron_native.cpp")):
            return None  # not packaged: the numpy twins are the engine
        r = subprocess.run(
            ["make", "-C", os.path.join(here, "native"), "libauron_native.so"],
            capture_output=True, text=True, timeout=120,
        )
        if r.returncode != 0:
            raise RuntimeError(
                f"native helper library failed to build:\n{r.stderr[-2000:]}"
            )
    lib = ctypes.CDLL(so)
    # one literal `lib.<sym>.argtypes/.restype =` statement per export —
    # auronlint R15 cross-checks these bindings against the C signatures
    # in native/auron_native.cpp, so they must stay statically visible
    # (no getattr loops) and every void kernel pins restype = None
    # (ctypes' default c_int return on a void function reads garbage).
    lib.murmur3_i32.argtypes = [
        ctypes.POINTER(ctypes.c_int32), ctypes.c_int64, ctypes.c_int32,
        ctypes.POINTER(ctypes.c_int32),
    ]
    lib.murmur3_i32.restype = None
    lib.murmur3_i64.argtypes = [
        ctypes.POINTER(ctypes.c_int64), ctypes.c_int64, ctypes.c_int32,
        ctypes.POINTER(ctypes.c_int32),
    ]
    lib.murmur3_i64.restype = None
    lib.murmur3_bytes.argtypes = [
        ctypes.POINTER(ctypes.c_uint8), ctypes.POINTER(ctypes.c_int64),
        ctypes.c_int64, ctypes.c_int32, ctypes.POINTER(ctypes.c_int32),
    ]
    lib.murmur3_bytes.restype = None
    lib.radix_partition.argtypes = [
        ctypes.POINTER(ctypes.c_int32), ctypes.c_int64, ctypes.c_int32,
        ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64),
    ]
    lib.radix_partition.restype = None
    lib.loser_tree_merge.argtypes = [
        ctypes.POINTER(ctypes.c_void_p), ctypes.POINTER(ctypes.c_int64),
        ctypes.c_int32, ctypes.c_int32, ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_int64),
    ]
    lib.loser_tree_merge.restype = None
    try:
        lib.crc32c_hash.argtypes = [
            ctypes.POINTER(ctypes.c_uint8), ctypes.c_int64, ctypes.c_uint32,
        ]
        lib.crc32c_hash.restype = ctypes.c_uint32
    except AttributeError:
        pass  # stale .so without the symbol: callers fall back
    try:
        lib.scaled_probe_f64.argtypes = [
            ctypes.POINTER(ctypes.c_double), ctypes.c_int64, ctypes.c_double,
            ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64),
        ]
        lib.scaled_probe_f64.restype = ctypes.c_int
        lib.scaled_probe_f32.argtypes = [
            ctypes.POINTER(ctypes.c_float), ctypes.c_int64, ctypes.c_float,
            ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64),
        ]
        lib.scaled_probe_f32.restype = ctypes.c_int
        lib.scaled_pack_f64.argtypes = [
            ctypes.POINTER(ctypes.c_double), ctypes.c_int64, ctypes.c_double,
            ctypes.c_int64, ctypes.c_int32, ctypes.POINTER(ctypes.c_uint8),
        ]
        lib.scaled_pack_f64.restype = None
        lib.scaled_pack_f32.argtypes = [
            ctypes.POINTER(ctypes.c_float), ctypes.c_int64, ctypes.c_float,
            ctypes.c_int64, ctypes.c_int32, ctypes.POINTER(ctypes.c_uint8),
        ]
        lib.scaled_pack_f32.restype = None
        lib.scaled_unpack_f64.argtypes = [
            ctypes.POINTER(ctypes.c_uint8), ctypes.c_int64, ctypes.c_double,
            ctypes.c_int64, ctypes.c_int32, ctypes.POINTER(ctypes.c_double),
        ]
        lib.scaled_unpack_f64.restype = None
        lib.scaled_unpack_f32.argtypes = [
            ctypes.POINTER(ctypes.c_uint8), ctypes.c_int64, ctypes.c_float,
            ctypes.c_int64, ctypes.c_int32, ctypes.POINTER(ctypes.c_float),
        ]
        lib.scaled_unpack_f32.restype = None
    except AttributeError:
        pass  # stale .so without the scaled kernels: callers fall back
    _LIB = lib
    return lib


def available() -> bool:
    return _lib() is not None


def _ptr(a: np.ndarray, ctype):
    return a.ctypes.data_as(ctypes.POINTER(ctype))


def murmur3_i32_host(v: np.ndarray, seed: int = 42) -> np.ndarray:
    v = np.ascontiguousarray(v, dtype=np.int32)
    out = np.empty(len(v), dtype=np.int32)
    lib = _lib()
    if lib is None:  # numpy fallback via the device kernel on host arrays
        import jax.numpy as jnp

        from auron_tpu.ops.hashing import murmur3_i32

        return np.asarray(murmur3_i32(jnp.asarray(v), jnp.uint32(seed)).view(jnp.int32))
    lib.murmur3_i32(_ptr(v, ctypes.c_int32), len(v), seed, _ptr(out, ctypes.c_int32))
    return out


def murmur3_i64_host(v: np.ndarray, seed: int = 42) -> np.ndarray:
    v = np.ascontiguousarray(v, dtype=np.int64)
    out = np.empty(len(v), dtype=np.int32)
    lib = _lib()
    if lib is None:  # numpy fallback via the device kernel on host arrays
        import jax.numpy as jnp

        from auron_tpu.ops.hashing import murmur3_i64

        return np.asarray(murmur3_i64(jnp.asarray(v), jnp.uint32(seed)).view(jnp.int32))
    lib.murmur3_i64(_ptr(v, ctypes.c_int64), len(v), seed, _ptr(out, ctypes.c_int32))
    return out


def murmur3_bytes_host(data: bytes | np.ndarray, offsets: np.ndarray,
                       seed: int = 42) -> np.ndarray:
    buf = np.frombuffer(data, dtype=np.uint8) if isinstance(data, (bytes, bytearray)) else np.ascontiguousarray(data, np.uint8)
    offsets = np.ascontiguousarray(offsets, dtype=np.int64)
    n = len(offsets) - 1
    out = np.empty(n, dtype=np.int32)
    lib = _lib()
    if lib is None:
        from auron_tpu.ops.hashing import murmur3_bytes as dev_m3
        import jax.numpy as jnp

        lens = (offsets[1:] - offsets[:-1]).astype(np.int32)
        max_len = int(((lens.max() if n else 0) + 3) & ~3) or 4
        mat = np.zeros((n, max_len), np.uint8)
        for i in range(n):
            mat[i, : lens[i]] = buf[offsets[i] : offsets[i + 1]]
        return np.asarray(
            dev_m3(jnp.asarray(mat), jnp.asarray(lens), jnp.uint32(seed)).view(jnp.int32)
        )
    lib.murmur3_bytes(_ptr(buf, ctypes.c_uint8), _ptr(offsets, ctypes.c_int64),
                      n, seed, _ptr(out, ctypes.c_int32))
    return out


def radix_partition_host(pids: np.ndarray, n_parts: int) -> tuple[np.ndarray, np.ndarray]:
    """Returns (counts[n_parts], order[n]) clustering rows by partition."""
    pids = np.ascontiguousarray(pids, dtype=np.int32)
    n = len(pids)
    counts = np.empty(n_parts, dtype=np.int64)
    order = np.empty(n, dtype=np.int64)
    lib = _lib()
    if lib is None:
        counts[:] = np.bincount(pids, minlength=n_parts)
        order[:] = np.argsort(pids, kind="stable")
        return counts, order
    lib.radix_partition(_ptr(pids, ctypes.c_int32), n, n_parts,
                        _ptr(counts, ctypes.c_int64), _ptr(order, ctypes.c_int64))
    return counts, order


def loser_tree_merge_host(
    run_words: list[list[np.ndarray]],
) -> tuple[np.ndarray, np.ndarray]:
    """Merge sorted runs keyed by uint64 word lists.

    run_words[r][w]: w-th key array of run r (all runs same n_words).
    Returns (out_run, out_idx) in globally sorted order.
    """
    n_runs = len(run_words)
    n_words = len(run_words[0])
    lens = np.array([len(r[0]) for r in run_words], dtype=np.int64)
    total = int(lens.sum())
    out_run = np.empty(total, dtype=np.int32)
    out_idx = np.empty(total, dtype=np.int64)
    lib = _lib()
    if lib is None:
        words = [
            np.concatenate([np.ascontiguousarray(r[w], np.uint64) for r in run_words])
            for w in range(n_words)
        ]
        runs = np.concatenate(
            [np.full(len(r[0]), i, np.int32) for i, r in enumerate(run_words)]
        )
        idxs = np.concatenate([np.arange(len(r[0]), dtype=np.int64) for r in run_words])
        order = np.lexsort(list(reversed(words)) + [idxs * 0])  # keys only; stable
        return runs[order], idxs[order]
    arrs = []  # keep references alive
    ptrs = (ctypes.c_void_p * (n_runs * n_words))()
    for r in range(n_runs):
        for w in range(n_words):
            a = np.ascontiguousarray(run_words[r][w], dtype=np.uint64)
            arrs.append(a)
            ptrs[r * n_words + w] = a.ctypes.data
    lib.loser_tree_merge(ptrs, _ptr(lens, ctypes.c_int64), n_runs, n_words,
                         _ptr(out_run, ctypes.c_int32), _ptr(out_idx, ctypes.c_int64))
    return out_run, out_idx


def crc32c_host(data: bytes, crc: int = 0) -> int | None:
    """CRC-32C via the native slice-by-8 kernel; None = library absent or
    stale (caller uses its table-loop fallback)."""
    lib = _lib()
    if lib is None or not hasattr(lib, "crc32c_hash"):
        return None
    buf = (ctypes.c_uint8 * len(data)).from_buffer_copy(data)
    return int(lib.crc32c_hash(buf, len(data), ctypes.c_uint32(crc)))


def scaled_probe_host(a: np.ndarray, s: float):
    """Fused verify + int-range pass for the shuffle v2 scaled encoding
    (docs/shuffle.md): returns (lo, hi) when EVERY lane of ``a`` survives
    round(v*s) -> int -> float -> /s bitwise, None when any lane refuses,
    or False when the library lacks the kernel (caller runs the numpy
    twin)."""
    lib = _lib()
    fn = getattr(lib, f"scaled_probe_{'f64' if a.dtype == np.float64 else 'f32'}", None) if lib else None
    if fn is None:
        return False
    a = np.ascontiguousarray(a)
    lo = ctypes.c_int64()
    hi = ctypes.c_int64()
    fp = ctypes.c_double if a.dtype == np.float64 else ctypes.c_float
    ok = fn(_ptr(a, fp), len(a), a.dtype.type(s), ctypes.byref(lo),
            ctypes.byref(hi))
    return (lo.value, hi.value) if ok else None


def scaled_pack_host(a: np.ndarray, s: float, lo: int,
                     width: int) -> np.ndarray | None:
    """Fused pack for a scaled_probe_host-verified plane: one read pass
    emitting the FOR-narrowed offsets (width in {1,2,4}; 8 = int64
    passthrough with lo ignored). None = kernel unavailable."""
    lib = _lib()
    fn = getattr(lib, f"scaled_pack_{'f64' if a.dtype == np.float64 else 'f32'}", None) if lib else None
    if fn is None:
        return None
    a = np.ascontiguousarray(a)
    out = np.empty(len(a) * width, dtype=np.uint8)
    fp = ctypes.c_double if a.dtype == np.float64 else ctypes.c_float
    fn(_ptr(a, fp), len(a), a.dtype.type(s), lo, width,
       _ptr(out, ctypes.c_uint8))
    return out


def scaled_unpack_host(payload: np.ndarray, n: int, s: float, lo: int,
                       width: int, dtype) -> np.ndarray | None:
    """Fused decode of a scaled plane straight to floats (one pass);
    None = kernel unavailable (caller runs the numpy twin)."""
    lib = _lib()
    dt = np.dtype(dtype)
    fn = getattr(lib, f"scaled_unpack_{'f64' if dt == np.float64 else 'f32'}", None) if lib else None
    if fn is None:
        return None
    src = np.ascontiguousarray(payload)
    out = np.empty(n, dtype=dt)
    fp = ctypes.c_double if dt == np.float64 else ctypes.c_float
    fn(_ptr(src, ctypes.c_uint8), n, dt.type(s), lo, width, _ptr(out, fp))
    return out
