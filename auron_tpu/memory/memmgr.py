"""HBM budget manager with spillable consumers.

Analog of the reference's memory manager (native-engine/auron-memmgr/src/
lib.rs): a global budget (total = overhead * memory_fraction, set at session
init — exec.rs:80-88), consumers register and report usage
(MemConsumer trait, lib.rs:46,202), per-consumer fair share drives who
spills (mem_used_percent, lib.rs:213-225), and growth beyond the managed
pool either self-spills or WAITS for siblings to release memory
(Operation::Spill/Wait, lib.rs:330-410). The reference spills to JVM-heap
blocks or local files (spill.rs:90-101); the TPU-native tiers are:

    HBM (device arrays) -> host RAM (``HostSpill``: compressed blocks in
                           RAM, demoted when the host ledger fills)
                        -> local disk (``DiskSpill``: zstd-compressed
                           Arrow IPC files)

Stateful operators (sort runs, agg states, shuffle staging, join builds)
register as consumers. Unspillable consumers (e.g. a hash-join build that
must stay resident for probing) still register so their usage shrinks the
managed pool others fair-share — the reference's mem_unspillable
accounting (lib.rs:355-364).

Two growth protocols coexist:

- ``update_mem_used(consumer, new_used)`` — the reference's protocol:
  fair-share limits (consumer_mem_max = managed/num_spillables, min =
  max/8), self-spill when over, condition-variable wait (with timeout →
  forced spill) when under min share.
- ``acquire(consumer, additional)`` — cascade protocol used by streaming
  operators: spill the largest *other* spillable consumers first, the
  requester last, so small consumers can grow at dominant ones' expense.

The budget is ONE CHIP's. A consumer belongs to the chip its owner's work
lands on — JAX's default device on the registering thread, which the mesh
driver sets to a partition's chip around its pump (parallel/mesh_driver.py)
— and every share, shortfall and victim list is taken among the consumers
of that chip alone: a partition that fills its chip spills its own state,
never a sibling's on a chip with room. Outside a mesh pump (a bridge task,
the collect stage) a consumer is on the process's first device, so on one
chip there is the one ledger the manager always had.
"""

from __future__ import annotations

import os
import tempfile
import threading
import time
from typing import Protocol

from auron_tpu import obs
from auron_tpu.utils.config import (
    HBM_BUDGET_BYTES,
    HOST_SPILL_BUDGET_BYTES,
    MEM_WAIT_TIMEOUT_S,
    MEMORY_FRACTION,
    active_conf,
)

# growth below this never triggers spill/wait (reference MIN_TRIGGER_SIZE)
_MIN_TRIGGER_BYTES = 1 << 20


def _auto_budget() -> int:
    """Hardware-shaped default (conf 0 = auto): accelerators get an
    HBM-sized 8GB; on the CPU backend device arrays live in host RAM, so
    half the physical memory is the faithful analog of the reference's
    executor-memory-derived budget."""
    import jax

    if jax.default_backend() != "cpu":
        return 8 << 30
    try:
        phys = os.sysconf("SC_PHYS_PAGES") * os.sysconf("SC_PAGE_SIZE")
        return phys // 2  # the documented behavior, no floor: a small
        # host must spill, not OOM
    except (ValueError, OSError):
        return 8 << 30


def _calling_device():
    """The chip the calling thread's arrays land on: ``jax.default_device``'s
    value on this thread, the process's first device where none is named."""
    import jax

    dev = jax.config.jax_default_device
    return dev if isinstance(dev, jax.Device) else jax.local_devices()[0]


class MemConsumer(Protocol):
    name: str

    def mem_used(self) -> int: ...

    def spill(self) -> int:
        """Release memory; returns bytes freed."""
        ...


class MemManager:
    _instance: "MemManager | None" = None

    def __init__(self, budget_bytes: int | None = None):
        # the process-wide singleton is DELIBERATELY built from the
        # ambient conf: init() runs at session setup under the session's
        # scope, and a lazy get() from a service thread sees the global —
        # both are the intended process-level budget source
        conf = active_conf()  # auronlint: disable=R7 -- process singleton: session-setup scope or the global conf IS the budget source
        # 0 = auto applies to the CONF default only; an explicit
        # budget_bytes=0 is an intentional always-spill manager
        total = (
            budget_bytes
            if budget_bytes is not None
            else (conf.get(HBM_BUDGET_BYTES) or _auto_budget())
        )
        self.budget = int(total * conf.get(MEMORY_FRACTION))
        self._lock = threading.RLock()
        self._released = threading.Condition(self._lock)
        self._consumers: list[MemConsumer] = []
        self._spillable: dict[int, bool] = {}
        # the chip whose ledger each consumer is on
        self._device: dict[int, object] = {}
        # owning span captured at register(): registration happens on the
        # owning task's thread, so a spill dispatched LATER by a foreign
        # thread still attributes to the owner's trace (obs/span.py)
        self._owner_spans: dict[int, object] = {}
        self.num_spills = 0
        self.num_waits = 0
        self._wait_timeout = float(conf.get(MEM_WAIT_TIMEOUT_S))

    # ---- lifecycle ----

    @classmethod
    def init(cls, budget_bytes: int | None = None) -> "MemManager":
        cls._instance = MemManager(budget_bytes)
        return cls._instance

    @classmethod
    def get(cls) -> "MemManager":
        if cls._instance is None:
            cls._instance = MemManager()
        return cls._instance

    # ---- consumer API ----

    def register(self, consumer: MemConsumer, spillable: bool = True) -> None:
        with self._lock:
            self._consumers.append(consumer)
            self._spillable[id(consumer)] = spillable
            self._device[id(consumer)] = _calling_device()
            self._owner_spans[id(consumer)] = obs.current_span()

    def unregister(self, consumer: MemConsumer) -> None:
        with self._lock:
            if consumer in self._consumers:
                self._consumers.remove(consumer)
            self._spillable.pop(id(consumer), None)
            self._device.pop(id(consumer), None)
            self._owner_spans.pop(id(consumer), None)
            # freed capacity: wake waiters blocked on the managed pool
            self._released.notify_all()

    def notify_released(self) -> None:
        """Consumers call this after shrinking (spill, drain, finish) so
        waiters blocked in update_mem_used can re-check the pool."""
        with self._lock:
            self._released.notify_all()

    def _beside(self, consumer: MemConsumer) -> list:
        """The registered consumers on ``consumer``'s chip."""
        dev = self._device.get(id(consumer))
        if dev is None:     # not registered: the chip its caller works on
            dev = _calling_device()
        return [c for c in self._consumers
                if self._device.get(id(c)) == dev]

    def total_used(self, consumer: MemConsumer | None = None) -> int:
        """Bytes in use on ``consumer``'s chip; with none named, on the
        fullest chip (what admission holds against the one-chip budget)."""
        with self._lock:
            if consumer is not None:
                return sum(c.mem_used() for c in self._beside(consumer))
            by_dev: dict = {}
            for c in self._consumers:
                dev = self._device.get(id(c))
                by_dev[dev] = by_dev.get(dev, 0) + c.mem_used()
            return max(by_dev.values(), default=0)

    def mem_snapshot(self) -> dict:
        """THE manager snapshot both observability surfaces render
        (httpsvc /metrics JSON and /metrics.prom): budget, spill count,
        per-consumer usage — taken under the lock, one definition so a
        new field can't land on one endpoint and silently miss the
        other."""
        with self._lock:
            return {
                "budget_bytes": self.budget,
                "num_spills": self.num_spills,
                "consumers": [
                    {"name": c.name, "mem_used": c.mem_used(),
                     "device": getattr(self._device.get(id(c)), "id", None)}
                    for c in self._consumers
                ],
            }

    def _pool_state(self, consumer: MemConsumer) -> tuple[int, int, int]:
        """(total_used, managed_pool, num_spillables) of ``consumer``'s chip
        — managed pool = budget minus unspillable usage (lib.rs:355-364)."""
        total_used = 0
        unspillable = 0
        n_spillables = 0
        for c in self._beside(consumer):
            u = c.mem_used()
            total_used += u
            if self._spillable.get(id(c), True):
                n_spillables += 1
            else:
                unspillable += u
        return total_used, max(self.budget - unspillable, 0), max(n_spillables, 1)

    def mem_used_percent(self, consumer: MemConsumer) -> float:
        """Consumer's share of its fair-share maximum (lib.rs:213-225)."""
        with self._lock:
            _, managed, n = self._pool_state(consumer)
            return consumer.mem_used() / max(managed / n, 1)

    def _dispatch_spill(self, consumer: MemConsumer) -> int:
        """Run ``consumer.spill()`` under the OWNING task's span (captured
        at register()): spill enter/exit land on the owner's trace
        timeline even when the memory manager dispatches the spill from a
        foreign task's thread. Owner-less consumers record untraced —
        NEVER against the executing thread's ambient span."""
        if obs.core._mode == obs.MODE_OFF:  # keep the no-obs path bare
            return consumer.spill()
        owner = self._owner_spans.get(id(consumer))
        t0 = time.perf_counter_ns()
        # parent=owner (or None) is EXPLICIT: the spill's own span is what
        # rides the executing thread while consumer.spill() runs
        arg = {"consumer": consumer.name}
        with obs.span("spill", cat="spill", parent=owner, arg=arg):
            freed = consumer.spill()
            arg["bytes"] = int(freed)
        # freed==0 attempts are not spills: num_spills skips them, and the
        # two exported counts must agree (/metrics.prom vs /queries)
        trace = owner.trace if owner is not None else None
        if freed and trace is not None and obs.core._mode == obs.MODE_TRACE:
            trace.note_spill(time.perf_counter_ns() - t0, freed)
        return freed

    def update_mem_used(self, consumer: MemConsumer, old_used: int, new_used: int) -> None:
        """Reference growth protocol (lib.rs:330-410): growing past the
        managed pool or the consumer's fair share triggers a self-spill;
        consumers under min share (fair/8) wait for siblings to release
        before spilling tiny states, with a timeout escape."""
        if new_used <= old_used or new_used < _MIN_TRIGGER_BYTES:
            if new_used < old_used:
                self.notify_released()
            return
        with self._lock:
            spillable = self._spillable.get(id(consumer), True)
            total_used, managed, n = self._pool_state(consumer)
            consumer_max = managed // n
            consumer_min = consumer_max // 8
            over = total_used > managed or new_used > consumer_max
            if not over:
                return
            if spillable and new_used > consumer_min:
                pass  # self-spill below (outside the wait path)
            else:
                # below min share (or unspillable): wait for the pool
                self.num_waits += 1

                def pool_has_room() -> bool:
                    used, managed, _ = self._pool_state(consumer)
                    return used <= managed

                ok = self._released.wait_for(pool_has_room,
                                             timeout=self._wait_timeout)
                if ok or not spillable:
                    return
        # self-spill without holding the manager lock (consumer locks are
        # ordered manager -> consumer; spill takes the consumer lock)
        freed = self._dispatch_spill(consumer)
        if freed:
            with self._lock:
                # R8: concurrent growers from different task threads race
                # on this counter (the acquire() path already locks it)
                self.num_spills += 1
            self.notify_released()

    def acquire(self, consumer: MemConsumer, additional: int) -> None:
        """Cascade protocol: declare intent to grow; spills largest other
        spillable consumers first, the requester last.

        Lock order invariant: the manager lock is NEVER held across a
        consumer's spill() (consumer locks wrap device compute that can
        take seconds — and on the CPU backend a blocked chain through a
        callback-bearing computation can wedge outright). Victims are
        chosen under the lock, spilled outside it, and the shortfall
        re-checked per victim."""
        with self._lock:
            needed = self.total_used(consumer) + additional - self.budget
            if needed <= 0:
                return
            others = sorted(
                (
                    c
                    for c in self._beside(consumer)
                    if c is not consumer and self._spillable.get(id(c), True)
                ),
                key=lambda c: c.mem_used(),
                reverse=True,
            )
            victims = others + (
                [consumer] if self._spillable.get(id(consumer), True) else []
            )
        for c in victims:
            with self._lock:
                # re-check live pool state per victim: concurrent spills/
                # releases may have already covered the shortfall — and
                # membership: a victim that finished and unregistered in
                # the meantime must not be spilled (its spill would write
                # a temp file nothing ever unlinks, ADVICE r4)
                needed = self.total_used(consumer) + additional - self.budget
                gone = c is not consumer and c not in self._consumers
            if needed <= 0:
                break
            if gone or c.mem_used() == 0:
                continue
            if self._dispatch_spill(c):
                with self._lock:
                    self.num_spills += 1
        self.notify_released()


# ---------------------------------------------------------------------------
# spill containers (host-RAM and disk tiers)
# ---------------------------------------------------------------------------


def _conf_trace_id(conf) -> int:
    """Owning trace id carried by a spill container's conf (obs.trace.id,
    threaded exactly like the compression codec: the executing thread may
    be a foreign task's, its ambient context is NOT the owner's)."""
    if conf is None:
        return 0
    try:
        return int(conf.get(obs.OBS_TRACE_ID))
    except Exception:
        return 0


def _container_span(what: str, container: str, conf):
    """A spill container's ``spill:<what>`` span, attributed to the OWNING
    trace by the conf-carried id (the trace itself may have closed)."""
    return obs.span(what, cat="spill", arg={"consumer": container},
                    parent=None, trace_id=_conf_trace_id(conf))


class DiskSpill:
    """Disk tier: zstd-compressed Arrow IPC blocks in a temp file (analog of
    the reference's compressed file spills, spill.rs:40-56).

    ``conf``: the owning task's Configuration — spills run on whichever
    thread the memory manager dispatches, so the compression codec must
    be threaded, not read from the spilling thread's active_conf() (R7)."""

    def __init__(self, spill_dir: str | None = None, *, conf):
        fd, self.path = tempfile.mkstemp(
            suffix=".spill", dir=spill_dir or tempfile.gettempdir()
        )
        os.close(fd)
        self._offsets: list[int] = [0]
        self._conf = conf

    def write_table(self, tbl) -> None:
        from auron_tpu.exec.shuffle.format import encode_block

        with _container_span("write", "DiskSpill", self._conf) as sp:
            blk = encode_block(tbl, conf=self._conf)
            with open(self.path, "ab") as f:
                f.write(blk)
            self._offsets.append(self._offsets[-1] + len(blk))
            if sp is not None:
                sp.arg["bytes"] = len(blk)

    def read_tables(self):
        from auron_tpu.exec.shuffle.format import decode_blocks

        with open(self.path, "rb") as f:
            data = f.read()
        yield from decode_blocks(data)

    def release(self) -> None:
        try:
            os.unlink(self.path)
        except OSError:
            pass


class _HostLedger:
    """Process-wide accounting of host-RAM spill bytes. When the ledger
    would exceed the configured host budget, the OLDEST resident HostSpills
    demote to disk first (they are the coldest; the reference's analog is
    the JVM on-heap spill manager handing blocks to the block manager when
    heap runs short, SparkOnHeapSpillManager.scala:37-199)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._resident: list["HostSpill"] = []
        self._bytes = 0

    def admit(self, spill: "HostSpill", nbytes: int, conf=None) -> list["HostSpill"]:
        """Record bytes; returns the demotion victims WITHOUT demoting —
        the caller runs them after releasing its own spill lock (admission
        happens under the admitting spill's lock so it can never interleave
        with a concurrent demotion of that same spill, ADVICE r4).

        ``conf``: threaded from the admitting spill — admissions happen on
        spill-dispatch threads where active_conf() is a foreign task's."""
        budget = int(
            (conf if conf is not None else active_conf()).get(HOST_SPILL_BUDGET_BYTES)
        )
        to_demote: list[HostSpill] = []
        with self._lock:
            self._bytes += nbytes
            if spill not in self._resident:
                self._resident.append(spill)
            # pick only enough victims to clear the shortfall: their bytes
            # leave the ledger later (each victim's forget), so track a
            # running remainder here instead of re-reading self._bytes —
            # otherwise ONE pressure event demotes every resident spill
            remaining = self._bytes
            while remaining > budget and self._resident:
                victim = self._resident.pop(0)
                to_demote.append(victim)
                remaining -= victim._admitted
        return to_demote

    def forget(self, spill: "HostSpill", nbytes: int) -> None:
        with self._lock:
            self._bytes -= nbytes
            if spill in self._resident:
                self._resident.remove(spill)

    def resident_bytes(self) -> int:
        with self._lock:
            return self._bytes


_host_ledger = _HostLedger()


class HostSpill:
    """Host-RAM tier: compressed blocks kept in RAM (device -> host is one
    transfer; re-reading skips the disk round trip). Demotes itself to a
    DiskSpill when the process host ledger fills. Interface-compatible
    with DiskSpill (write_table / read_tables / release)."""

    def __init__(self, spill_dir: str | None = None, *, conf):
        self._blocks: list[bytes] | None = []
        self._nbytes = 0
        self._admitted = 0  # bytes this spill currently holds in the ledger
        self._disk: DiskSpill | None = None
        self._spill_dir = spill_dir
        self._conf = conf  # owning task's conf (codec + ledger budget, R7)
        self._lock = threading.Lock()

    def write_table(self, tbl) -> None:
        from auron_tpu.exec.shuffle.format import encode_block

        with _container_span("write", "HostSpill", self._conf) as sp:
            blk = encode_block(tbl, conf=self._conf)
            if sp is not None:
                sp.arg["bytes"] = len(blk)
            with self._lock:
                if self._disk is not None:
                    with open(self._disk.path, "ab") as f:
                        f.write(blk)
                    return
                self._blocks.append(blk)
                self._nbytes += len(blk)
                self._admitted += len(blk)
                # admission under OUR lock: a concurrent demotion of this
                # spill must take this lock first, so it always sees these
                # bytes and forgets exactly _admitted — the ledger can't
                # drift (ADVICE r4: the post-release admit re-added bytes a
                # demotion had already forgotten and re-inserted a demoted
                # spill as resident)
                victims = _host_ledger.admit(self, len(blk), conf=self._conf)
        for v in victims:  # demote OUTSIDE our lock (lock order spill->ledger)
            v._demote()

    def _demote(self) -> None:  # auronlint: thread-root(foreign) -- ledger pressure demotes victims on whichever thread admitted the last block
        """Move resident blocks to disk (ledger pressure)."""
        with _container_span("demote", "HostSpill", self._conf) as sp:
            with self._lock:
                if self._disk is not None or self._blocks is None:
                    return
                disk = DiskSpill(self._spill_dir, conf=self._conf)
                try:
                    with open(disk.path, "ab") as f:
                        for blk in self._blocks:
                            f.write(blk)
                except BaseException:
                    # a failed demotion write (disk full) must not leak the
                    # temp file; the blocks stay resident in RAM (R11)
                    disk.release()
                    raise
                freed = self._admitted
                self._blocks, self._nbytes, self._admitted = [], 0, 0
                self._disk = disk
            _host_ledger.forget(self, freed)
            if sp is not None:
                sp.arg["bytes"] = freed

    @property
    def demoted(self) -> bool:
        with self._lock:
            return self._disk is not None

    def read_tables(self):
        from auron_tpu.exec.shuffle.format import decode_blocks

        with self._lock:
            disk, blocks = self._disk, list(self._blocks or ())
        if disk is not None:
            yield from disk.read_tables()
            return
        yield from decode_blocks(b"".join(blocks))

    def release(self) -> None:
        with self._lock:
            disk, freed = self._disk, self._admitted
            self._blocks, self._nbytes, self._disk = None, 0, None
            self._admitted = 0
        if disk is not None:
            disk.release()
        if freed:
            _host_ledger.forget(self, freed)


def make_spill(spill_dir: str | None = None, *, conf):
    """Spill container for operator state: host-RAM tier first, demoting
    to disk under ledger pressure (the promised HBM -> host RAM -> disk
    cascade). ``conf``: REQUIRED — the OWNING task's Configuration.
    Spill writes and ledger demotions run on memory-manager dispatch
    threads, where the ambient active_conf() is a FOREIGN task's;
    keyword-only with no default so a forgotten conf is a TypeError at
    construction, not a silent cross-thread codec/budget leak (R7).
    Pass None deliberately only for conf-independent scratch (tests)."""
    return HostSpill(spill_dir, conf=conf)
