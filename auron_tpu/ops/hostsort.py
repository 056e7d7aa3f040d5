"""Backend-adaptive order-permutation primitive.

Every grouping/ordering kernel here reduces to "stable ascending sort of a
tuple of uint64 key words" (ops/segments.py segment_by_keys, ops/sortkeys.py
sort operands, exec/sort_exec.py runs). On accelerators that is one
multi-operand ``lax.sort`` over HBM-resident data — the right call. XLA:CPU
however lowers ``lax.sort`` to a generic comparator sort, measured ~50-100x
slower than a lexicographic host sort for these word tuples; on the CPU
backend the permutation is therefore computed by a ``pure_callback``
``np.lexsort`` (stable, identical tie semantics to the stable ``lax.sort``),
and the surrounding program stays jitted — only the argsort leaves the
device, the gathers it feeds remain fused XLA.

The reference hits the same fork: its CPU engine sorts with a hand-written
radix sort (datafusion-ext-commons rdx_sort), not a comparison sort.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from auron_tpu.utils.config import HOST_SORT_MODE, active_conf, resolve_tri


#: the widest sort an accelerator runs as one ``lax.sort`` under ``auto``.
#: XLA:TPU compiles a sort by its width, not its operands' bytes: the
#: fingerprint sort's three keys compiled in 6 s at 8,192 rows, 42 s at
#: 16,384, 157 s at 32,768 and 181 s at 131,072, and one 32-bit key in 7 /
#: 25 / 32 s (a described v5e, PR 32; PR 22 met 6-7 minutes at 524,288 on
#: the chip's machine). Past this width the permutation comes from the host
#: (two arrays down, one up, ``np.lexsort``: milliseconds at 131,072 rows),
#: as on XLA:CPU, until a device sort whose compile is bounded takes its
#: place (ROADMAP S8).
DEVICE_SORT_MAX_ROWS = 1 << 14


def use_host_sort(conf=None, rows: int | None = None) -> bool:
    """Trace-time decision: host lexsort or device lax.sort. Under ``auto``
    the host sorts on the CPU backend, and on an accelerator where the
    caller names a width (``rows``) over ``DEVICE_SORT_MAX_ROWS``.

    ``conf``: pass the task's own Configuration on any path a
    cross-thread spill can reach — active_conf() is thread-local, so the
    spilling thread would otherwise resolve a foreign task's knob."""
    return resolve_tri(
        (conf if conf is not None else active_conf()).get(HOST_SORT_MODE),
        jax.default_backend() == "cpu"
        or (rows is not None and rows > DEVICE_SORT_MAX_ROWS),
    )


def _lexsort_cb(*words):
    # primary key first in our convention; np.lexsort wants primary LAST
    return np.lexsort(tuple(reversed(words))).astype(np.int32)


def order_by_words(operands: tuple) -> jnp.ndarray:
    """Stable ascending order permutation (int32) of the operand tuple;
    operands[0] is the primary key. Host path — call only under
    use_host_sort()."""
    cap = operands[0].shape[0]
    return jax.pure_callback(
        _lexsort_cb,
        jax.ShapeDtypeStruct((cap,), jnp.int32),
        *operands,
    )
