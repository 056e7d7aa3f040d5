"""Test harness: force the CPU backend with 8 virtual devices.

Multi-chip behavior (shuffle exchange over a Mesh, sharded aggregation) is
tested on a virtual 8-device CPU mesh — mirroring how the reference tests
"multi-node" behavior on a single JVM with local task scheduling
(reference: BaseAuronSQLSuite.scala:38-50). Real-TPU runs happen in
bench.py / __graft_entry__.py, not in unit tests.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from auron_tpu.jaxenv import force_cpu_backend  # noqa: E402

force_cpu_backend(8)

import auron_tpu  # noqa: F401,E402  (enables x64)


import pytest  # noqa: E402


@pytest.fixture()
def enable_row_metrics(monkeypatch):
    """Turn on per-operator output_rows metrics (conf-gated, default off)."""
    from auron_tpu.utils.config import METRICS_ROW_COUNTS

    env_key = "AURON_TPU_" + METRICS_ROW_COUNTS.key.upper().replace(".", "_")
    monkeypatch.setenv(env_key, "true")


@pytest.fixture()
def joins_stay_dense(monkeypatch):
    """A context manager under which ``compaction_bucket``'s rule never
    pays at a join's output boundary: every batch is gathered at its
    capacity. The dense twin that row-exactness tests compare the
    compacting joins with; a rule patched in a test, not an option of the
    program."""
    import contextlib

    from auron_tpu.exec.joins import chain, driver

    @contextlib.contextmanager
    def scope():
        with monkeypatch.context() as m:
            for mod in (chain, driver):
                m.setattr(mod, "compaction_bucket", lambda *a, **k: None)
            yield

    return scope


@pytest.fixture()
def lookups_compare(monkeypatch):
    """A context manager under which the lookup policy
    (``columnar.batch.lookup_compare_width``) finds comparing free: every
    unique one-word build of up to the ladder's widest width carries its
    live key list and is probed by comparing. XLA:CPU's own break-even is
    "never", so the tests reach the compare map by patching the rule's
    unit costs, not through an option of the program."""
    import contextlib

    from auron_tpu.columnar import batch as batch_mod

    @contextlib.contextmanager
    def scope():
        with monkeypatch.context() as m:
            m.setattr(batch_mod, "_lookup_costs", lambda: (1.0, 0.0, 0.0))
            yield

    return scope


@pytest.fixture()
def lookup_events():
    """``lookup_events(run)`` -> (``run()``'s result, the ``lookup`` events
    it noted under the flight recorder as (kind, rows), in call order)."""
    import time

    from auron_tpu import obs
    from auron_tpu.obs import core

    def capture(run):
        saved = obs.mode()
        obs.set_mode("recorder")
        try:
            t0 = time.perf_counter_ns()
            out = run()
            t1 = time.perf_counter_ns()
            evs = sorted((ev for _r, evs in core.snapshot_events()
                          for ev in evs
                          if ev[2] == "lookup" and t0 <= ev[0] < t1),
                         key=lambda ev: ev[0])
        finally:
            obs.set_mode(saved)
        return out, [(ev[7]["kind"], ev[7]["rows"]) for ev in evs]

    return capture


@pytest.fixture(scope="module")
def leak_canary():
    """Tier-1 leak canary (R11's dynamic twin): a suite that drives whole
    queries must leave the process registries as it found them —
    ``api._runtimes`` (a failing request leaked one per query before
    PR 12), the global resource map, and the obs ring registry (a ring
    owned by a suite-spawned thread that never exited = a stuck waiter).
    Autoused by the serving and sqlgate suites; teardown asserts the
    baselines restored."""
    import threading
    import time

    from auron_tpu.bridge import api
    from auron_tpu.obs import core as obs_core

    with api._lock:
        base_rt = set(api._runtimes)
        base_res = set(api._resources)
    base_threads = {t.ident for t in threading.enumerate()}

    yield

    with api._lock:
        leaked_rt = {h: type(rt).__name__ for h, rt in api._runtimes.items()
                     if h not in base_rt}
        leaked_res = sorted(set(api._resources) - base_res)
    assert not leaked_rt, (
        f"suite leaked task runtimes {leaked_rt} — every call_native "
        "needs its finalize_native on every path (R11)")
    assert not leaked_res, (
        f"suite leaked resource-map entries {leaked_res} — every "
        "put_resource needs its remove_resource")

    # obs rings: suite-spawned threads must have exited (their rings go
    # dead and prune); a STILL-LIVE post-baseline thread owning a ring is
    # the stuck-waiter shape. Short grace: handler/pump threads may be
    # mid-exit when the last test returns.
    deadline = time.monotonic() + 5.0
    while True:
        live_now = {t.ident for t in threading.enumerate()}
        with obs_core._reg_lock:
            stuck = [r.tname for r in obs_core._rings
                     if r.ident in live_now and r.ident not in base_threads]
        if not stuck or time.monotonic() > deadline:
            break
        time.sleep(0.05)
    assert not stuck, (
        f"suite-spawned threads still alive with obs rings: {stuck} — "
        "a waiter was never released (R11 inflight-event shape)")
    # and the registry prunes dead rings once retention lapses — the
    # eviction path the /trace endpoint's memory bound rests on
    with obs_core._reg_lock:
        obs_core._prune_locked(
            time.perf_counter_ns() + obs_core._RETENTION_NS)
        live_now = {t.ident for t in threading.enumerate()}
        undead = [r.tname for r in obs_core._rings
                  if r.ident not in live_now]
    assert not undead, f"dead-thread rings survived a forced prune: {undead}"
