"""Host-engine bridge: the 4-entry-point task ABI + resource map.

Analog of the reference's JNI surface (auron-core JniBridge.java:49-80):
``callNative / nextBatch / finalizeNative / onExit`` plus the resource map
(putResource/getResource) that hands scan providers, shuffle-block readers,
UDF contexts and FS openers to tasks. A JVM front-end binds these through
the C ABI exported by native/bridge (see native/), a python front-end calls
them directly. Batches cross the boundary as Arrow (in-process objects or
IPC bytes — the C-data-interface analog).
"""

from __future__ import annotations

import itertools
import threading
from typing import Any

import pyarrow as pa

from auron_tpu import obs
from auron_tpu.runtime.task import TaskRuntime

_lock = threading.Lock()
_resources: dict[str, Any] = {}
_runtimes: dict[int, TaskRuntime] = {}
_next_handle = itertools.count(1)


# ---- resource map (JniBridge.putResource/getResource analog) ----


def put_resource(key: str, value: Any) -> None:
    with _lock:
        _resources[key] = value


def put_resource_ipc(key: str, payload: bytes) -> None:
    """C-ABI batch-resource entry: the payload MUST be an Arrow IPC
    stream; it registers as a list of RecordBatches (consumable by
    ffi_reader / scan providers). Raw opaque payloads go through
    ``auron_put_resource_bytes`` -> plain put_resource instead — an
    explicit type split, no content sniffing."""
    import io

    with pa.ipc.open_stream(io.BytesIO(payload)) as r:
        batches = list(r)
    put_resource(key, batches)


def put_resource_c_stream(key: str, stream_ptr: int) -> None:
    """Arrow C-FFI batch-resource entry (auron_put_resource_arrow): the
    host hands an ``ArrowArrayStream*`` and batches cross the boundary by
    POINTER — no IPC serialization, no copy (the reference's L4 boundary
    design: JNI hands Arrow C-data structs, not bytes). The stream is
    imported lazily; the registered provider is one-shot, like a host
    engine's per-task scan handoff."""
    reader = pa.RecordBatchReader._import_from_c(int(stream_ptr))
    put_resource(key, reader)


def next_batch_c(handle: int, array_ptr: int, schema_ptr: int) -> int:
    """Arrow C-FFI batch export (auron_next_batch_arrow): writes the next
    batch into host-allocated ``ArrowArray*`` / ``ArrowSchema*`` structs
    (release callbacks transfer ownership per the C data interface spec).
    Returns 1 on a batch, 0 at end of stream. The batch's buffers are
    handed off by reference — the serde-free twin of next_batch_ipc."""
    with obs.span("next_batch_c", cat="entry"):
        rb = next_batch(handle)
        if rb is None:
            return 0
        rb._export_to_c(int(array_ptr), int(schema_ptr))
        return 1


def put_resource_shuffle(key: str, manifest: bytes) -> None:
    """C-ABI shuffle-fetch entry: the payload is a ShuffleManager JSON
    manifest ([{data,index},...]); it registers as a reduce-side block
    provider (the host shuffle fetch handing blocks to IpcReaderExec,
    AuronBlockStoreShuffleReaderBase analog)."""
    from auron_tpu.convert.stages import provider_from_manifest

    put_resource(key, provider_from_manifest(manifest))


def get_resource(key: str) -> Any:
    with _lock:
        return _resources.get(key)


def remove_resource(key: str) -> None:
    with _lock:
        _resources.pop(key, None)
        # engine-built clients cached against the resource (e.g. the kafka
        # wire client under "<rid>.client") die with it
        client = _resources.pop(f"{key}.client", None)
    if client is not None and hasattr(client, "close"):
        try:
            client.close()
        except Exception:  # noqa: BLE001 — removal must not raise
            pass
    # broadcast-build locks are keyed by resource id; evict with the
    # resource so executors don't accumulate one lock per broadcast
    from auron_tpu.exec.joins.bhj import evict_build_lock

    evict_build_lock(key)


def install_udf_callback(fn_ptr: int) -> None:
    """C-ABI entry (auron_register_udf_callback): install the host's UDF
    evaluator; __hive:<token> expressions route through it."""
    from auron_tpu.bridge import udf

    udf.install_c_callback(int(fn_ptr))


# ---- task entry points ----


def call_native(task_bytes: bytes, extra_resources: dict | None = None) -> int:
    """Start a task from a serialized TaskDefinition; returns a handle.

    ``extra_resources`` overlay the global map for THIS task only — the
    in-process serving path's isolation primitive: two concurrent queries
    each hand their own stage output under the same rid without racing on
    put_resource/remove_resource (the C ABI keeps using the global map)."""
    with obs.span("call_native", cat="entry"):
        with _lock:
            resources = dict(_resources)
        if extra_resources:
            resources.update(extra_resources)
        # session-set obs knobs apply inside TaskRuntime.__init__, BEFORE its
        # pump thread starts (a post-start apply would race the task's own
        # span installation); only the HTTP service starts lazily here
        rt = TaskRuntime(task_bytes, resources=resources, shared=_resources)
        try:
            # conf-gated observability service (auron/src/http analog)
            from auron_tpu.utils.httpsvc import maybe_start_from_conf

            maybe_start_from_conf(rt.ctx.conf)
            h = next(_next_handle)
            with _lock:
                _runtimes[h] = rt
        except BaseException:
            # the runtime's pump thread is already running: a failure before
            # the handle is published must cancel/join it, or it leaks for
            # the life of the process (R11 task-runtime protocol)
            try:
                rt.finalize()
            except Exception:  # noqa: BLE001  # auronlint: disable=R12 -- unwind: the original failure is the error; finalize's own is secondary
                pass
            raise
        return h


def native_task(task_bytes: bytes, extra_resources: dict | None = None):
    """Context manager around one task's lifecycle: ``call_native`` on
    entry, ``finalize_native`` on EVERY exit — the R11-clean shape for
    drain loops (the PR-12 lesson: a failing drain must not leak its
    runtime's handle and pump thread)::

        with api.native_task(task.SerializeToString()) as h:
            while (rb := api.next_batch(h)) is not None:
                ...

    On an exceptional exit the finalize error (if any) is swallowed —
    the propagating error is the primary one."""
    return _NativeTask(task_bytes, extra_resources)


class _NativeTask:
    __slots__ = ("_task_bytes", "_extra", "handle")

    def __init__(self, task_bytes: bytes, extra_resources: dict | None):
        self._task_bytes = task_bytes
        self._extra = extra_resources
        self.handle: int | None = None

    def __enter__(self) -> int:
        self.handle = call_native(self._task_bytes, self._extra)
        return self.handle

    def __exit__(self, exc_type, exc, tb) -> bool:
        if self.handle is None:
            return False
        if exc_type is None:
            finalize_native(self.handle)
        else:
            try:
                finalize_native(self.handle)
            except Exception:  # noqa: BLE001  # auronlint: disable=R12 -- unwind: the propagating task error is primary; finalize's own is secondary
                pass
        return False


def next_batch(handle: int) -> pa.RecordBatch | None:
    """The Arrow materialisation is entry work: the span's self time is
    what the host spends on it once the queue wait and the device reads
    inside it are taken out."""
    rt = _runtimes[handle]
    with obs.span("next_batch", cat="entry"):
        return rt.next_arrow()


def next_batch_ipc(handle: int) -> bytes | None:
    """IPC-serialized variant for out-of-process hosts."""
    import io

    with obs.span("next_batch_ipc", cat="entry"):
        rb = next_batch(handle)
        if rb is None:
            return None
        sink = io.BytesIO()
        with pa.ipc.new_stream(sink, rb.schema) as w:
            w.write_batch(rb)
        return sink.getvalue()


_metrics_sink = None


def set_metrics_sink(fn) -> None:
    """Install a callable receiving every finalized task's metric-tree
    snapshot (the in-process analog of the reference pushing each task's
    MetricNode tree into Spark's SQLMetric registry at finalize,
    native-engine/auron/src/metrics.rs:7-35). Pass None to uninstall.
    Used by perf_gate.py to build per-class operator-time breakdowns."""
    global _metrics_sink
    _metrics_sink = fn


def finalize_native(handle: int) -> dict:
    with _lock:
        rt = _runtimes.pop(handle, None)
    if rt is None:
        return {}
    with obs.span("finalize_native", cat="entry"):
        snap = rt.finalize()
    if _metrics_sink is not None:
        try:
            _metrics_sink(snap)
        except Exception:  # noqa: BLE001  # auronlint: disable=R12 -- observability sink isolation: a broken metrics consumer must not fail the task it observes
            pass
    return snap


def finalize_native_json(handle: int) -> bytes:
    """C-ABI variant: metrics tree serialized as JSON bytes."""
    import json

    return json.dumps(finalize_native(handle)).encode("utf-8")


def convert_plan_json(payload: bytes) -> bytes:
    """Conversion service entry (C ABI auron_convert_plan): host-plan JSON
    in, segmentation response JSON out (convert/service.py)."""
    from auron_tpu.convert.service import convert_host_plan_json

    return convert_host_plan_json(payload)


def on_exit() -> None:
    with _lock:
        handles = list(_runtimes)
    for h in handles:
        try:
            finalize_native(h)
        except Exception:
            pass
