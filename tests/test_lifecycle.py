"""Regression tests for the R11/R12 engine fixes (auronlint v3).

Each test reproduces the failure path the new static rules surfaced and
pins the fixed behavior: no leaked task runtimes, no leaked memory-
manager registrations, no stranded RSS attempts, no wedged consumers.
"""

import threading

import pytest

from auron_tpu import types as T  # noqa: F401 — parity with sibling suites
from auron_tpu.bridge import api
from auron_tpu.columnar import Batch
from auron_tpu.exprs.ir import ScalarFunc, col
from auron_tpu.memory.memmgr import MemManager
from auron_tpu.plan import builders as B


def _task_bytes(plan, **kw):
    return B.task(plan, **kw).SerializeToString()


def _runtimes_snapshot():
    with api._lock:
        return set(api._runtimes)


# ---------------------------------------------------------------------------
# bridge: call_native unwind + native_task context manager
# ---------------------------------------------------------------------------


def test_call_native_unwinds_runtime_on_post_start_failure(monkeypatch):
    """R11 find: a failure AFTER TaskRuntime construction (the lazy HTTP
    service start) previously leaked the runtime — pump thread running,
    handle never published, finalize never reachable."""
    from auron_tpu.utils import httpsvc

    def boom(conf):
        raise RuntimeError("injected post-start failure")

    monkeypatch.setattr(httpsvc, "maybe_start_from_conf", boom)
    b = Batch.from_pydict({"x": [1, 2, 3]})
    api.put_resource("lc_src", [[b]])
    before = _runtimes_snapshot()
    threads_before = threading.active_count()
    try:
        with pytest.raises(RuntimeError, match="injected post-start"):
            api.call_native(_task_bytes(B.memory_scan(b.schema, "lc_src")))
        assert _runtimes_snapshot() == before
        # the pump thread must be joined by the unwinding finalize, not
        # left alive behind an unreachable handle
        for _ in range(100):
            if threading.active_count() <= threads_before:
                break
            import time

            time.sleep(0.02)
        assert threading.active_count() <= threads_before
    finally:
        api.remove_resource("lc_src")


def test_native_task_finalizes_on_failing_drain():
    """The PR-12 leak class, pinned at the helper level: a drain loop
    that raises must still finalize (handle gone, no error masking)."""
    b = Batch.from_pydict({"x": [1, 0]})
    api.put_resource("lc_src2", [[b]])
    plan = B.project(B.memory_scan(b.schema, "lc_src2"),
                     [(ScalarFunc("nope", (col(0),)), "y")])
    before = _runtimes_snapshot()
    try:
        with pytest.raises(RuntimeError, match="failed"):
            with api.native_task(_task_bytes(plan)) as h:
                while api.next_batch(h) is not None:
                    pass
        assert _runtimes_snapshot() == before
    finally:
        api.remove_resource("lc_src2")


def test_native_task_finalizes_on_consumer_error():
    """An error raised by the CONSUMER (not the task) also finalizes."""
    b = Batch.from_pydict({"x": [1, 2]})
    api.put_resource("lc_src3", [[b]])
    before = _runtimes_snapshot()
    try:
        with pytest.raises(ValueError, match="consumer"):
            with api.native_task(
                _task_bytes(B.memory_scan(b.schema, "lc_src3"))
            ) as h:
                api.next_batch(h)
                raise ValueError("consumer bailed")
        assert _runtimes_snapshot() == before
    finally:
        api.remove_resource("lc_src3")


# ---------------------------------------------------------------------------
# agg setup window: no leaked memory-manager registrations
# ---------------------------------------------------------------------------


def test_agg_setup_failure_leaks_no_consumers(monkeypatch):
    """R11 find: ~300 lines of setup ran between mm.register(table) and
    the protecting try — a failure there (here: TransferWindow
    construction, the deferred-counts arm) leaked registered consumers
    in the process-wide manager for the life of the process."""
    from auron_tpu.runtime import transfer

    def boom(depth):
        raise RuntimeError("injected window failure")

    monkeypatch.setattr(transfer, "TransferWindow", boom)
    b = Batch.from_pydict({"k": [1, 2, 1], "v": [1.0, 2.0, 3.0]})
    api.put_resource("lc_agg", [[b]])
    plan = B.hash_agg(
        B.memory_scan(b.schema, "lc_agg"),
        [(col(0), "k")], [("sum", col(1), "s")], "partial",
    )
    mm = MemManager.get()
    with mm._lock:
        consumers_before = list(mm._consumers)
    try:
        with pytest.raises(RuntimeError, match="failed"):
            with api.native_task(_task_bytes(plan)) as h:
                while api.next_batch(h) is not None:
                    pass
        with mm._lock:
            leaked = [c for c in mm._consumers if c not in consumers_before]
        assert not leaked, [c.name for c in leaked]
    finally:
        api.remove_resource("lc_agg")


# ---------------------------------------------------------------------------
# spill containers: demote failure releases the disk tier
# ---------------------------------------------------------------------------


def test_hostspill_demote_failure_releases_disk_and_keeps_blocks(
    monkeypatch, tmp_path
):
    """R11 find: a failed demotion write leaked the DiskSpill temp file
    and lost the in-RAM blocks' consistency."""
    import pyarrow as pa

    from auron_tpu.memory import memmgr
    from auron_tpu.utils.config import Configuration

    conf = Configuration()
    sp = memmgr.HostSpill(str(tmp_path), conf=conf)
    sp.write_table(pa.table({"x": [1, 2, 3]}))
    released = []

    class FailingDisk:
        def __init__(self, spill_dir=None, *, conf):
            self.path = str(tmp_path / "no-such-dir" / "spill")

        def release(self):
            released.append(True)

    monkeypatch.setattr(memmgr, "DiskSpill", FailingDisk)
    with pytest.raises(OSError):
        sp._demote()
    assert released == [True]
    # blocks stayed resident and readable
    assert not sp.demoted
    tables = list(sp.read_tables())
    assert sum(t.num_rows for t in tables) == 3
    sp.release()


# ---------------------------------------------------------------------------
# pump boundary: context installation failure relays instead of hanging
# ---------------------------------------------------------------------------


def test_pump_context_failure_relays_not_hangs(monkeypatch):
    """R12 find: set_task_context ran BEFORE the pump's try — a failure
    there killed the pump without enqueueing _END, so next_batch blocked
    forever."""
    from auron_tpu.utils import logging as tlog

    def boom(stage, part):
        raise RuntimeError("injected context failure")

    monkeypatch.setattr(tlog, "set_task_context", boom)
    b = Batch.from_pydict({"x": [1]})
    from auron_tpu.runtime.task import TaskRuntime

    rt = TaskRuntime(
        _task_bytes(B.memory_scan(b.schema, "unused")),
        resources={"unused": [[b]]},
    )
    with pytest.raises(RuntimeError, match="failed"):
        # must raise promptly (the relay), not deadlock on an empty queue
        rt.next_batch()


# ---------------------------------------------------------------------------
# RSS: a failing writer attempt aborts its staged blocks
# ---------------------------------------------------------------------------


def test_rss_writer_aborts_attempt_on_failure():
    """R11/R12 find (the named rss_net suspect): a failing RSS map task
    left its uncommitted attempt's pushed blocks staged in the service
    forever (local RAM, or the remote daemon's)."""
    from auron_tpu.exec.shuffle.rss import (
        LocalRssService, RssPartitionWriterClient,
    )

    svc = LocalRssService()
    inner = RssPartitionWriterClient(svc, "s1", 0)

    class FlakyWriter:
        """First push lands (the attempt has staged bytes to leak — the
        assertion below must not pass vacuously); the second fails."""

        def __init__(self):
            self.pushes = 0

        def write(self, partition, block):
            self.pushes += 1
            if self.pushes >= 2:
                raise RuntimeError("injected push failure")
            inner.write(partition, block)

        def abort(self):
            inner.abort()

    writer = FlakyWriter()
    api.put_resource("lc_rss", writer)
    b = Batch.from_pydict({"x": list(range(16))})
    api.put_resource("lc_rss_src", [[b]])
    plan = B.rss_shuffle_writer(
        B.memory_scan(b.schema, "lc_rss_src"),
        B.hash_partitioning([col(0)], 2), "lc_rss",
    )
    try:
        with pytest.raises(RuntimeError, match="failed"):
            with api.native_task(_task_bytes(plan)) as h:
                while api.next_batch(h) is not None:
                    pass
        assert writer.pushes >= 2, "fixture never pushed — vacuous"
        with svc._lock:
            staged = dict(svc._staging)
        assert not staged, "failed attempt left staged blocks in the service"
    finally:
        api.remove_resource("lc_rss")
        api.remove_resource("lc_rss_src")
