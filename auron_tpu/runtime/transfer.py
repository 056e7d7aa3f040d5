"""k-deep asynchronous device->host transfer window.

The engine's residual host reads (compaction live counts, dense-agg fold
flags, spill/metrics counters) are one-scalar transfers whose *cost* is not
the bytes but the stall: a blocking ``device_get`` waits for the device
computation producing the value AND the round-trip of the link. The window
removes both from the critical path:

- ``start_host_transfer`` kicks off a non-blocking device->host copy
  (``copy_to_host_async``) the moment the producing program is dispatched;
- the value is *harvested* k batches later (``TransferWindow``), by which
  time the copy has ridden behind k batches of device compute — the read
  returns from the runtime's host-side landing buffer without stalling.

Harvests run under ``profiling.async_read_scope`` so engine counters
account them as ``async_reads``, not host syncs; a harvest that still
blocks (window too shallow) is attributed to its call site like any other
stall. This is the host-coordination half of the sync-free steady-state
pipeline (docs/pipeline.md); the prediction half lives in
``exec/selectivity.py``.
"""

from __future__ import annotations

from collections import deque
from typing import Any, Iterator

import numpy as np

from auron_tpu import obs
from auron_tpu.utils.profiling import async_read_scope


def start_host_transfer(*arrays) -> None:
    """Begin non-blocking device->host copies. Best-effort: backends or
    array types without ``copy_to_host_async`` (numpy scalars, tracers in
    tests) simply skip — the later harvest then pays the transfer, which
    is exactly the pre-window behavior."""
    for a in arrays:
        copy = getattr(a, "copy_to_host_async", None)
        if copy is not None:
            try:
                copy()
            except Exception:  # noqa: BLE001  # auronlint: disable=R12 -- async-copy probe: an unsupported backend/layout degrades to the harvest paying the transfer, the documented pre-window behavior
                pass


def harvest(*arrays) -> tuple[np.ndarray, ...]:  # auronlint: thread-root(foreign) -- window harvests run on whichever thread drains (incl. cross-thread spill drains)
    """Resolve previously started transfers to host numpy values,
    accounted as async reads (see module docstring). Goes through
    jax.device_get (not np.asarray) so the read is visible to the
    profiling hook — the C++ ``__array__`` fast path bypasses it."""
    import jax

    with obs.span("harvest", cat="wait") as sp, async_read_scope():
        if sp is not None:
            sp.arg = {"n": len(arrays)}
        return tuple(
            np.asarray(x) for x in jax.device_get(arrays)  # auronlint: sync-point(1/batch) -- async-window harvest: transfer started k batches earlier, accounted as async_reads
        )


class TransferWindow:
    """FIFO of in-flight (arrays, payload) entries, at most ``depth`` deep.

    ``push`` starts the transfers and returns the entries that fell out of
    the window (resolved, oldest-first); ``drain`` resolves the rest at end
    of stream. Depth 1 degenerates to the classic one-deep software
    pipeline (dispatch i+1, then finish i)."""

    def __init__(self, depth: int):
        self.depth = max(1, int(depth))
        self._q: deque = deque()

    def __len__(self) -> int:
        return len(self._q)

    def push(self, arrays: tuple, payload: Any) -> list[tuple[tuple, Any]]:
        start_host_transfer(*arrays)
        # capture the pushing thread's span: harvests may run on whichever
        # thread drains (cross-thread spill drains) and must attribute the
        # read to the OWNING task's trace (docs/observability.md). Mode
        # off keeps this per-batch path bare (no contextvar read).
        sp = (obs.current_span()
              if obs.core._mode != obs.MODE_OFF else None)
        self._q.append((arrays, payload, sp))
        out = []
        while len(self._q) > self.depth:
            out.append(self._pop())
        return out

    def _pop(self) -> tuple[tuple, Any]:
        arrays, payload, sp = self._q.popleft()
        if obs.core._mode == obs.MODE_OFF:
            return harvest(*arrays), payload
        with obs.use_span(sp):
            return harvest(*arrays), payload

    def drain(self) -> Iterator[tuple[tuple, Any]]:
        while self._q:
            yield self._pop()
