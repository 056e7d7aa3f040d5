"""Concurrency differential gate: N clients vs serial, bit-identical.

The serving layer's proof (ISSUE 12, docs/serving.md): the corpus the
sqlgate already verifies against pandas oracles is replayed through
:class:`~auron_tpu.serve.server.SqlServer` in three legs —

1. WARM: every corpus query once, serially. Plans compile and cache
   (plan-digest cache + fusion stage cache + jit caches); results are
   recorded as the reference output.
2. SERIAL REPLAY: the corpus again, serially, on the warm server. This
   is the throughput baseline (serial queries/s) AND the replay
   contract: every result must be bit-identical to leg 1 and the leg
   must add ZERO new XLA compiles (the program cache did its job).
3. CONCURRENT: ``serve.gate.clients`` clients each replay the corpus
   once, simultaneously (each client starts at a rotated corpus offset
   so the mix is heterogeneous, like real tenants). Every result must
   again be bit-identical to leg 1, the leg must add zero compiles, and
   every query must carry its own distinct trace id (no cross-query
   attribution bleed).

The gate FAILS on: any result divergence, any new compile in legs 2-3,
duplicated trace ids, concurrent/serial throughput below the speedup
floor, or a queries/s regression below 0.9x the best recorded in
PERF_RATCHET.json (key ``serve_qps@sf<SF>x<N>``; the same ratchet
discipline as the per-class perf floors — new bests persist only from
passing runs). p50/p99 latency is recorded per leg.

The speedup floor is SUBSTRATE-RESOLVED, the same measured split as
every ``auto`` backend knob (``SERVEGATE_MIN_SPEEDUP`` overrides both
tiers): 2.0 on accelerator backends, 1.4 on the CPU backend. Measured
basis (24-core box, sf=1, 8 clients — the full trail is in
docs/serving.md): concurrent XLA executions scale
near-linearly when query work is device-resident (a 6-thread
device-program A/B scales ~5.6x, and forcing the device sort/fold
substrates lifts this gate's ratio to 2.73x — at 26% LOWER absolute
queries/s, so it is not the shipped config); the CPU-optimal config
keeps PR-3's host sort/fold substrates, whose per-row numpy holds the
GIL and caps multi-query scaling at ~1.6-1.7x. The 2x claim is an
accelerator-regime property; the CPU tier gates against regression in
the regime the box actually has, and the ABSOLUTE queries/s ratchet is
the stronger guard on both.

Run ``python -m auron_tpu.models.servegate`` (make servegate); tier-1
and ``make servecheck`` run the same machinery at toy scale.
"""

from __future__ import annotations

import os
import time
from typing import Optional

import numpy as np

if __name__ == "__main__" and os.environ.get("JAX_PLATFORMS") == "cpu":
    # a CPU run lands on a 1-device host; the mesh wants
    # sql.shuffle.partitions devices (same bootstrap as models/sqlgate)
    from auron_tpu.jaxenv import force_cpu_backend
    from auron_tpu.utils.config import Configuration, SQL_SHUFFLE_PARTITIONS

    force_cpu_backend(max(2, SQL_SHUFFLE_PARTITIONS.get(Configuration())))

from auron_tpu.utils.config import (
    SERVE_GATE_CLIENTS,
    SERVE_GATE_SF,
    SQL_SHUFFLE_PARTITIONS,
    Configuration,
)

RATCHET_SLACK = 0.9


def _percentiles(lat_s: list[float]) -> dict:
    if not lat_s:
        return {"p50_ms": None, "p99_ms": None}
    arr = np.asarray(lat_s, dtype=np.float64) * 1e3
    return {"p50_ms": round(float(np.percentile(arr, 50)), 2),
            "p99_ms": round(float(np.percentile(arr, 99)), 2)}


def _frames_identical(a, b) -> bool:
    """Bit-identity for result frames: same dtypes, same values, same
    row order (executions are deterministic; any reorder is a finding)."""
    if list(a.columns) != list(b.columns) or len(a) != len(b):
        return False
    if list(a.dtypes) != list(b.dtypes):
        return False
    return a.equals(b)


def build_server(sf: Optional[float] = None, n_parts: Optional[int] = None,
                 frames: Optional[dict] = None, conf=None):
    """A SqlServer over the sqlgate's catalog + TPC-DS frames."""
    from auron_tpu.models import sqlgate, tpcds
    from auron_tpu.serve import SqlServer
    from auron_tpu.sql.catalog import build_tables

    base = conf if conf is not None else Configuration()
    sf = sf if sf is not None else SERVE_GATE_SF.get(base)
    n_parts = (n_parts if n_parts is not None
               else SQL_SHUFFLE_PARTITIONS.get(base))
    if frames is None:
        data = tpcds.generate(sf=sf, seed=42)
        frames = build_tables(data, seed=42)
    return SqlServer(sqlgate.gate_catalog(), frames, conf=base,
                     n_parts=n_parts), sf


def run_gate(sf: Optional[float] = None, clients: Optional[int] = None,
             names: Optional[list[str]] = None,
             frames: Optional[dict] = None,
             min_speedup: Optional[float] = None,
             server=None) -> dict:
    """The three-leg differential; returns the summary record (``ok``
    plus every failure listed in ``failures``)."""
    import threading

    from auron_tpu.models import sqlgate
    from auron_tpu.utils.profiling import EngineCounters

    counters = EngineCounters.install()
    conf = Configuration()
    clients = clients if clients is not None else SERVE_GATE_CLIENTS.get(conf)
    if min_speedup is None:
        env = os.environ.get("SERVEGATE_MIN_SPEEDUP")
        if env is not None:
            min_speedup = float(env)
        else:
            import jax

            # substrate-resolved floor (module docstring): accelerators
            # claim the 2x; the CPU backend's host sort/fold substrates
            # hold the GIL and cap multi-query scaling
            min_speedup = 2.0 if jax.default_backend() != "cpu" else 1.4
    if server is None:
        server, sf = build_server(sf=sf, frames=frames, conf=conf)
    elif sf is None:
        sf = SERVE_GATE_SF.get(conf)
    cases = [c for c in sqlgate.CASES
             if names is None or c.name in names]
    failures: list[str] = []

    # ---- leg 1: warm (compile + cache; reference results)
    reference: dict[str, object] = {}
    t0 = time.perf_counter()
    for c in cases:
        df, rec = server.submit(c.sql, tenant="warm")
        reference[c.name] = df
        if rec["cache_hit"]:
            failures.append(f"warm leg unexpectedly hit the cache: {c.name}")
    warm_s = time.perf_counter() - t0
    compiles_warm = counters.compiles

    # ---- leg 2: serial replay on the warm server
    serial_lat: list[float] = []
    trace_ids: list[int] = []
    t0 = time.perf_counter()
    for c in cases:
        df, rec = server.submit(c.sql, tenant="serial")
        serial_lat.append(rec["wall_s"])
        if "trace_id" in rec:
            trace_ids.append(rec["trace_id"])
        if not rec["cache_hit"]:
            failures.append(f"serial replay missed the plan cache: {c.name}")
        if not _frames_identical(reference[c.name], df):
            failures.append(f"serial replay diverged: {c.name}")
    serial_s = time.perf_counter() - t0
    serial_qps = len(cases) / serial_s if serial_s else 0.0
    replay_compiles = counters.compiles - compiles_warm
    if replay_compiles:
        failures.append(
            f"serial replay added {replay_compiles} XLA compiles "
            "(program cache failed)")

    # ---- leg 3: N clients replay concurrently, rotated offsets
    conc_lat: list[float] = []
    conc_failures: list[str] = []
    lock = threading.Lock()

    def client(i: int) -> None:
        try:
            order = cases[i % len(cases):] + cases[:i % len(cases)]
            for c in order:
                try:
                    df, rec = server.submit(c.sql, tenant=f"client{i}")
                except Exception as e:  # noqa: BLE001 — the gate records
                    with lock:
                        conc_failures.append(
                            f"client{i} {c.name}: {type(e).__name__}: {e}")
                    continue
                with lock:
                    conc_lat.append(rec["wall_s"])
                    if "trace_id" in rec:
                        trace_ids.append(rec["trace_id"])
                    if not rec["cache_hit"]:
                        conc_failures.append(
                            f"client{i} missed the plan cache: {c.name}")
                    if not _frames_identical(reference[c.name], df):
                        conc_failures.append(
                            f"client{i} diverged from serial: {c.name}")
        except BaseException as e:  # noqa: BLE001
            # the comparison code above runs on this client thread too: an
            # escaping error would kill the thread silently and the gate
            # would under-count — record it as a failure instead (R12)
            with lock:
                conc_failures.append(
                    f"client{i} crashed: {type(e).__name__}: {e}")

    threads = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(clients)]
    compiles_before = counters.compiles
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    conc_s = time.perf_counter() - t0
    failures.extend(conc_failures)
    conc_queries = clients * len(cases)
    conc_qps = conc_queries / conc_s if conc_s else 0.0
    conc_compiles = counters.compiles - compiles_before
    if conc_compiles:
        failures.append(
            f"concurrent leg added {conc_compiles} XLA compiles")
    # every query ran as its OWN trace: duplicated ids = attribution bleed
    if len(trace_ids) != len(set(trace_ids)):
        failures.append("duplicated trace ids across queries (trace bleed)")

    speedup = conc_qps / serial_qps if serial_qps else 0.0
    if speedup < min_speedup:
        failures.append(
            f"concurrent/serial queries/s {speedup:.2f}x < required "
            f"{min_speedup:.2f}x")

    # ---- ratchet (shared PERF_RATCHET.json discipline)
    rkey = f"serve_qps@sf{sf:g}x{clients}"
    ratchet_on = os.environ.get("SERVEGATE_RATCHET", "1") != "0"
    best = None
    if ratchet_on:
        from perf_gate import _load_ratchet, _save_ratchet

        ratchet = _load_ratchet()
        best = ratchet.get(rkey)
        if best is not None and conc_qps < RATCHET_SLACK * best:
            failures.append(
                f"queries/s {conc_qps:.2f} < ratchet floor "
                f"{RATCHET_SLACK * best:.2f} (best {best:.2f})")
        if not failures and conc_qps > (best or 0.0):
            ratchet[rkey] = round(conc_qps, 3)
            _save_ratchet(ratchet)

    return {
        "metric": "servegate", "sf": sf, "clients": clients,
        "queries": len(cases),
        "warm_s": round(warm_s, 3),
        "serial_s": round(serial_s, 3),
        "serial_qps": round(serial_qps, 3),
        "serial": _percentiles(serial_lat),
        "concurrent_s": round(conc_s, 3),
        "concurrent_qps": round(conc_qps, 3),
        "concurrent": _percentiles(conc_lat),
        "speedup": round(speedup, 3),
        "min_speedup": min_speedup,
        "replay_compiles": replay_compiles,
        "concurrent_compiles": conc_compiles,
        "ratchet_key": rkey, "ratchet_best": best,
        "server": server.stats(),
        "failures": failures,
        "ok": not failures,
    }


def main() -> None:
    import json
    import sys

    sf = float(os.environ.get("SERVEGATE_SF", "0") or 0) or None
    clients = int(os.environ.get("SERVEGATE_CLIENTS", "0") or 0) or None
    names = [n for n in os.environ.get("SERVEGATE_QUERIES", "").split(",")
             if n] or None
    rec = run_gate(sf=sf, clients=clients, names=names)
    print(json.dumps(rec), flush=True)
    if not rec["ok"]:
        sys.exit(1)


if __name__ == "__main__":
    main()
