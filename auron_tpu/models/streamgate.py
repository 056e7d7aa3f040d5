"""Streaming throughput + exactly-once gate (docs/streaming.md).

Four legs over one deterministic event corpus (seeded JSON records,
two partitions) and one calc-heavy CREATE STREAMING VIEW:

1. FUSED: the pipeline with ``stream.calc.fuse=on`` — the Calc chain
   rides whole-stage fused programs. Best-of-``STREAMGATE_REPS`` wall
   clock becomes the sustained ``stream_events_s`` figure; the
   emissions are recorded as the reference output.
2. EAGER: the same corpus with ``stream.calc.fuse=off`` (per-expression
   Evaluator). Emissions must be bit-identical to leg 1, and fused
   events/s must beat eager by ``STREAMGATE_MIN_FUSED_SPEEDUP``
   (default 1.05x) — the fusion knob must EARN its default.
3. REPLAY STABILITY: a second fused run must add ZERO new XLA compiles
   (the per-(schema, segment, bucket) program cache did its job — same
   contract make perfcheck enforces at toy scale).
4. CRASH-RESUME: the fused pipeline again with checkpointing on, hard-
   stopped mid-run (a step cap landing between barriers), then resumed
   via StreamPipeline.restore. The stitched emission log must be
   bit-identical to leg 1 — the kill-at-every-seam fuzz
   (tests/test_stream_exactly_once.py) at gate scale.

The gate FAILS on: emission divergence in any leg, a fused speedup
below the floor, any replay compile, or fused events/s below 0.9x the
best recorded in PERF_RATCHET.json (key ``stream_events_s``; same
ratchet discipline as every other perf floor — new bests persist only
from passing runs).

Run ``python -m auron_tpu.models.streamgate`` (make streamgate).
"""

from __future__ import annotations

import json
import os
import time

import numpy as np

if __name__ == "__main__" and os.environ.get("JAX_PLATFORMS") == "cpu":
    from auron_tpu.jaxenv import force_cpu_backend

    force_cpu_backend(2)

from auron_tpu import types as T
from auron_tpu.utils.config import (
    STREAM_CALC_FUSE,
    STREAM_CHECKPOINT_INTERVAL,
    STREAM_POLL_MAX_RECORDS,
    Configuration,
)

RATCHET_SLACK = 0.9
RATCHET_KEY = "stream_events_s"

SCHEMA = T.Schema.of(T.Field("k", T.STRING), T.Field("v", T.FLOAT64),
                     T.Field("ts", T.INT64))

#: calc-heavy on purpose: three WHERE conjuncts and arithmetic in every
#: aggregate argument, so the Calc chain carries real per-batch work for
#: the fused-vs-eager differential (a bare column passthrough measures
#: only json.loads)
VIEW = """
CREATE STREAMING VIEW streamgate_1s
  WATERMARK FOR ts AS ts - INTERVAL '2' SECOND
AS SELECT k, window_start, window_end,
          SUM(v * 2.0 + 1.0) AS total, COUNT(*) AS n,
          AVG(v * v) AS mean, MIN(v - 3.0) AS lo, MAX(v + 3.0) AS hi
   FROM events
   WHERE v >= 0 AND v < 9.5 AND ts >= 0
   GROUP BY k, TUMBLE(ts, INTERVAL '1' SECOND)
"""


def _corpus(n: int, seed: int = 7) -> list[list[bytes]]:
    rng = np.random.default_rng(seed)
    keys = np.array(list("abcdefgh"))[rng.integers(0, 8, n)]
    vals = np.round(rng.random(n) * 10 - 0.5, 3)
    ts = np.arange(n) * 3 + rng.integers(0, 5, n)
    recs = [json.dumps({"k": k, "v": float(v), "ts": int(t)}).encode()
            for k, v, t in zip(keys, vals, ts)]
    return [recs[: n // 2], recs[n // 2:]]


def _conf(fuse: bool, poll: int) -> Configuration:
    c = Configuration()
    c.set(STREAM_CALC_FUSE, "on" if fuse else "off")
    c.set(STREAM_POLL_MAX_RECORDS, poll)
    c.set(STREAM_CHECKPOINT_INTERVAL, 8)
    return c


def _run_once(plan, parts, conf, checkpoint_dir=None, max_steps=None):
    """One full (or capped) pipeline run; returns (events/s, emissions,
    steps)."""
    from auron_tpu.exec.streaming import JsonRowDeserializer, MockKafkaSource
    from auron_tpu.stream import CollectSink, StreamPipeline

    sink = CollectSink()
    p = StreamPipeline(plan, MockKafkaSource(parts),
                       JsonRowDeserializer(SCHEMA), sink, conf=conf,
                       checkpoint_dir=checkpoint_dir)
    t0 = time.perf_counter()
    steps = p.run(max_steps=max_steps, drain=max_steps is None)
    wall = time.perf_counter() - t0
    events = p.metrics["events_in"]
    p.close()
    return (events / wall if wall else 0.0,
            [e.to_json() for e in sink.emissions], steps)


def run_gate(events: int | None = None, reps: int | None = None,
             poll: int = 512,
             min_fused_speedup: float | None = None) -> dict:
    """The four-leg differential; returns the summary record."""
    import tempfile

    from auron_tpu.exec.streaming import JsonRowDeserializer, MockKafkaSource
    from auron_tpu.stream import (
        CollectSink,
        StreamPipeline,
        lower_streaming_view,
    )
    from auron_tpu.utils.profiling import EngineCounters

    counters = EngineCounters.install()
    events = events or int(os.environ.get("STREAMGATE_EVENTS", "60000"))
    reps = reps or int(os.environ.get("STREAMGATE_REPS", "3"))
    if min_fused_speedup is None:
        min_fused_speedup = float(
            os.environ.get("STREAMGATE_MIN_FUSED_SPEEDUP", "1.05"))
    parts = _corpus(events)
    plan = lower_streaming_view(VIEW, SCHEMA)
    failures: list[str] = []

    # ---- leg 1: fused (warm-up rep compiles; best rep is the figure)
    fused_eps, reference = 0.0, None
    _run_once(plan, parts, _conf(True, poll))  # warm: compile + caches
    compiles_warm = counters.compiles
    for _ in range(reps):
        eps, ems, _ = _run_once(plan, parts, _conf(True, poll))
        fused_eps = max(fused_eps, eps)
        if reference is None:
            reference = ems
        elif ems != reference:
            failures.append("fused reruns diverged (nondeterminism)")

    # ---- leg 3 folded in: the timed fused reps must not compile
    replay_compiles = counters.compiles - compiles_warm
    if replay_compiles:
        failures.append(
            f"fused replay added {replay_compiles} XLA compiles "
            "(stream program cache failed)")

    # ---- leg 2: eager differential
    eager_eps = 0.0
    for _ in range(reps):
        eps, ems, _ = _run_once(plan, parts, _conf(False, poll))
        eager_eps = max(eager_eps, eps)
        if ems != reference:
            failures.append("eager emissions diverged from fused")
            break
    speedup = fused_eps / eager_eps if eager_eps else 0.0
    if speedup < min_fused_speedup:
        failures.append(
            f"fused/eager events/s {speedup:.3f}x < required "
            f"{min_fused_speedup:.2f}x")

    # ---- leg 4: crash-resume bit-identity at gate scale
    with tempfile.TemporaryDirectory() as ckdir:
        conf = _conf(True, poll)
        _, partial, steps = _run_once(
            plan, parts, conf, checkpoint_dir=ckdir,
            max_steps=max(3, (events // poll) // 2) + 1)
        sink = CollectSink()  # the crashed run's sink is gone; fresh one
        p = StreamPipeline.restore(
            plan, lambda mode, off: MockKafkaSource(
                parts, startup_mode=mode, start_offsets=off),
            JsonRowDeserializer(SCHEMA), sink, ckdir, conf=conf)
        committed = p.emit_seq
        p.run(drain=True)
        p.close()
        resumed = (partial[:committed]
                   + [e.to_json() for e in sink.emissions])
        if resumed != reference:
            failures.append(
                f"crash-resume diverged after step cap {steps} "
                f"(committed seq {committed})")

    # ---- ratchet (shared PERF_RATCHET.json discipline)
    best = None
    if os.environ.get("STREAMGATE_RATCHET", "1") != "0":
        from perf_gate import _load_ratchet, _save_ratchet

        ratchet = _load_ratchet()
        best = ratchet.get(RATCHET_KEY)
        if best is not None and fused_eps < RATCHET_SLACK * best:
            failures.append(
                f"events/s {fused_eps:.0f} < ratchet floor "
                f"{RATCHET_SLACK * best:.0f} (best {best:.0f})")
        if not failures and fused_eps > (best or 0.0):
            ratchet[RATCHET_KEY] = round(fused_eps, 1)
            _save_ratchet(ratchet)

    return {
        "metric": "streamgate", "events": events, "poll": poll,
        "reps": reps,
        "fused_events_s": round(fused_eps, 1),
        "eager_events_s": round(eager_eps, 1),
        "speedup": round(speedup, 3),
        "min_fused_speedup": min_fused_speedup,
        "replay_compiles": replay_compiles,
        "emissions": len(reference or ()),
        "ratchet_key": RATCHET_KEY, "ratchet_best": best,
        "failures": failures,
        "ok": not failures,
    }


def main() -> None:
    import sys

    rec = run_gate()
    print(json.dumps(rec), flush=True)
    if not rec["ok"]:
        sys.exit(1)


if __name__ == "__main__":
    main()
