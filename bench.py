"""Benchmark: flagship q3-class TPC-DS pipeline throughput.

Runs the full engine path (protobuf plans -> planner -> runtime -> device
compute -> file shuffle -> final agg -> top-k) on the available accelerator
and compares against a pandas single-thread baseline of the same query.

Phases:
  1. generate synthetic TPC-DS star schema (BENCH_SF, default 8 ~ 23M rows)
  2. pandas single-thread oracle (the baseline; data already in RAM)
  3. ingest: host -> device upload of the fact/dim columns, timed separately
     (the pandas baseline starts with data in RAM; the engine's comparable
     starting point is data in HBM — ingest bandwidth is reported, not
     folded into the query time)
  4. warm-up run (compiles; persistent XLA cache makes this cheap after the
     first process, see auron_tpu/jaxenv.py)
  5. two timed runs (best-of), identical plan, device-resident input

Prints ONE JSON line:
    {"metric": ..., "value": N, "unit": ..., "vs_baseline": N,
     "backend": ..., "device_kind": ..., "sf": N,
     "engine_s": N, "baseline_s": N, "ingest_s": N, "ingest_gb_s": N,
     "fact_gb_per_s": N}

It runs on whatever backend JAX gives this process and says which in the
record; it never looks for another. The cluster-sort microbench is its own
script (``bench_sort.py``), run in its own process.

Env knobs: BENCH_SF, BENCH_PARTS (map partitions; default = one per
device), BENCH_BATCH_ROWS.

``ingest_gb_s`` RATCHETS like the gate speedups (BENCH_RATCHET=0 opts
out): the best value per (sf, backend) persists in PERF_RATCHET.json
(key ``ingest_gb_s@sf<N>[:backend]``, seeded from BENCH_r05's 1.245
GB/s at sf=8) and a correct run whose ingest throughput falls below
0.9 x best exits nonzero — zero-copy-ingest gains (ROADMAP item 3) are
held the same way query speedups are.

``--trace-out=PATH`` (or AURON_TRACE_OUT) raises obs to full-trace mode
and writes the timed runs' span timeline as Chrome/Perfetto JSON
(docs/observability.md). Without the flag the runs still execute under
a query trace (ring attribution + /queries summary).
"""

import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

def main() -> None:
    import threading

    import auron_tpu  # noqa: F401
    from auron_tpu import obs
    from auron_tpu.bridge import api
    from auron_tpu.exec.metrics import MetricNode
    from auron_tpu.models import tpcds
    from auron_tpu.utils.profiling import EngineCounters

    # engine-level sync accounting rides the BENCH record so the
    # trajectory catches sync regressions, not just throughput
    counters = EngineCounters.install()

    # per-operator rollup (same sink shape as perf_gate.py) so the BENCH
    # record carries a top_ops section — op-level regressions show in the
    # BENCH_r* trajectory even when end-to-end throughput still passes
    op_totals: dict[str, dict[str, int]] = {}
    flat_totals: dict[str, int] = {}
    sink_lock = threading.Lock()

    def sink(snap: dict) -> None:
        with sink_lock:
            MetricNode.accumulate_op_totals(snap, op_totals)
            for k, v in MetricNode.flat_totals(snap).items():
                flat_totals[k] = flat_totals.get(k, 0) + int(v)

    api.set_metrics_sink(sink)

    sf = float(os.environ.get("BENCH_SF", "8"))
    # one map/reduce partition per device; multi-partition execution is
    # covered by perf_gate.py and the mesh tests
    parts_env = os.environ.get("BENCH_PARTS")
    if parts_env:
        n_parts = int(parts_env)
    else:
        import jax

        n_parts = max(1, len(jax.devices()))
    data = tpcds.generate(sf=sf, seed=42)
    n_rows = data.fact_rows()
    n_bytes = int(data.store_sales.memory_usage(index=False, deep=False).sum())

    # --- pandas baseline (single-thread CPU, data in RAM; best-of-2 like
    # the engine's timed runs, so neighbor noise hits both sides equally) ---
    baseline_s = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        want = tpcds.q3_class_oracle(data)
        baseline_s = min(baseline_s, time.perf_counter() - t0)

    # --- ingest: RAM -> HBM, timed separately ---
    import jax

    backend = jax.devices()[0].platform
    device_kind = jax.devices()[0].device_kind
    # accelerator runs favor big batches: per-batch host syncs ride a
    # high-latency link, and device compute amortizes over larger shapes
    batch_rows = int(
        os.environ.get("BENCH_BATCH_ROWS", str(1 << 22 if backend != "cpu" else 1 << 20))
    )
    t0 = time.perf_counter()
    ingested = tpcds.ingest_q3(data, n_map=n_parts, batch_rows=batch_rows)
    ingest_s = time.perf_counter() - t0

    # --- engine: warm-up (compile) then best-of-2 timed runs ---
    with tempfile.TemporaryDirectory(prefix="auron_bench_") as wd0:
        tpcds.run_q3_class(
            data, n_map=n_parts, n_reduce=n_parts, work_dir=wd0, ingested=ingested
        )
    counters.reset()  # attribute syncs to the timed runs only, not warmup
    with sink_lock:
        op_totals.clear()  # attribute top_ops to the timed runs only
        flat_totals.clear()
    from auron_tpu.obs.export import trace_out_arg

    trace_out = trace_out_arg(sys.argv[1:], "AURON_TRACE_OUT")
    if trace_out:
        obs.set_mode("trace")
    engine_s = float("inf")
    with obs.query_trace("bench.q3class") as qt:
        for _ in range(2):
            with tempfile.TemporaryDirectory(prefix="auron_bench_") as wd:
                t0 = time.perf_counter()
                got = tpcds.run_q3_class(
                    data, n_map=n_parts, n_reduce=n_parts, work_dir=wd, ingested=ingested
                )
                engine_s = min(engine_s, time.perf_counter() - t0)
    sync_snap = counters.snapshot()  # covers BOTH timed runs

    # result check (differential gate, tolerance like the reference's
    # QueryResultComparator double tolerance)
    assert len(got) == len(want), (len(got), len(want))
    for g, w in zip(got["s"], want["s"]):
        assert abs(float(g) - float(w)) <= 1e-6 * max(1.0, abs(float(w))), (g, w)

    rows_per_s = n_rows / engine_s
    baseline_rows_per_s = n_rows / baseline_s
    fact_gb_per_s = n_bytes / engine_s / 1e9

    record = {
        "metric": "tpcds_q3_class_throughput",
        "value": round(rows_per_s, 1),
        "unit": "fact_rows/s",
        "vs_baseline": round(rows_per_s / baseline_rows_per_s, 4),
        "backend": backend,
        "device_kind": device_kind,
        "sf": sf,
        "engine_s": round(engine_s, 3),
        "baseline_s": round(baseline_s, 3),
        "ingest_s": round(ingest_s, 3),
        "ingest_gb_s": round(n_bytes / ingest_s / 1e9, 3),
        "fact_gb_per_s": round(fact_gb_per_s, 3),
        # host-coordination profile of the two timed runs (the cost class
        # the sync-free pipeline attacks; see docs/pipeline.md)
        "host_syncs": sync_snap["host_syncs"],
        "host_sync_s": sync_snap["host_sync_s"],
        "async_reads": sync_snap["async_reads"],
        "sync_sites": sync_snap["sync_sites"],
        # op -> elapsed compute seconds over BOTH timed runs, top 5
        "top_ops": {
            k: round(MetricNode.op_seconds(tot), 3)
            for k, tot in sorted(
                op_totals.items(),
                key=lambda kv: -MetricNode.op_seconds(kv[1]),
            )[:5]
        },
        # op -> blocking sync-wait seconds (stall attribution to the
        # operator actually waiting — a consumer's stalls can't masquerade
        # as a producer's compute; see profiling.EngineCounters.op_sync)
        "top_ops_sync": {
            k: [v[0], v[1]] for k, v in sync_snap.get("op_sync", {}).items()
        },
    }
    # data-plane breakdown (ISSUE 11): shuffle write/read GB/s, bytes and
    # the per-column-block encoding histogram, from the same flat rollup
    # perf_gate emits per class — encoding regressions show per run
    from perf_gate import shuffle_breakdown

    with sink_lock:
        shuf = shuffle_breakdown(flat_totals)
    if shuf is not None:
        record["shuffle"] = shuf
    if trace_out:
        if qt.trace is not None:
            from auron_tpu.obs import export

            export.write_chrome_trace(trace_out, trace_id=qt.trace.id)
            record["trace_out"] = trace_out
        else:
            # an explicitly requested artifact must never vanish silently
            sys.stderr.write(
                "bench.py: --trace-out requested but obs recording is "
                "disabled (AURON_TPU_OBS_KILL?); no trace written\n"
            )
    # ---- ingest-throughput ratchet (ROADMAP item 3): ingest_gb_s rides
    # PERF_RATCHET.json like the gate speedups — best passing value per
    # (scale factor, backend), and a later run fails below 0.9 x best
    # (seeded from BENCH_r05's 1.245 GB/s). Only a CORRECT run records
    # (the differential assert above already gated that).
    from perf_gate import RATCHET_SLACK, _load_ratchet, _save_ratchet

    # %g keeps fractional scale factors distinct (sf=0.5 -> "sf0.5";
    # int() would collide 0.5/0.1 on "sf0" and 8.5 on "sf8")
    ingest_key = f"ingest_gb_s@sf{sf:g}" + (
        f":{backend}" if backend != "cpu" else ""
    )
    # the shuffle data plane ratchets alongside ingest (ROADMAP item 2:
    # "add a shuffle GB/s ratchet so both gains hold"): raw staged bytes
    # per second of encode+write work, per (sf, backend)
    shuffle_key = f"shuffle_gb_s@sf{sf:g}" + (
        f":{backend}" if backend != "cpu" else ""
    )
    ratchet = _load_ratchet()
    ingest_best = ratchet.get(ingest_key)
    shuffle_best = ratchet.get(shuffle_key)
    ratchet_ok = os.environ.get("BENCH_RATCHET", "1") != "0"
    if ratchet_ok and ingest_best is not None:
        record["ingest_floor"] = round(RATCHET_SLACK * ingest_best, 3)
    if ratchet_ok and shuffle_best is not None:
        record["shuffle_floor"] = round(RATCHET_SLACK * shuffle_best, 3)
    print(json.dumps(record))
    if ratchet_ok:
        failed = False
        gbs = record["ingest_gb_s"]
        if ingest_best is not None and gbs < RATCHET_SLACK * ingest_best:
            sys.stderr.write(
                f"bench.py: ingest throughput {gbs} GB/s regressed below "
                f"{RATCHET_SLACK} x best {ingest_best} ({ingest_key})\n"
            )
            failed = True
        shuf_gbs = (record.get("shuffle") or {}).get("shuffle_write_gb_s")
        if (
            shuffle_best is not None
            and shuf_gbs is not None
            and shuf_gbs < RATCHET_SLACK * shuffle_best
        ):
            sys.stderr.write(
                f"bench.py: shuffle write throughput {shuf_gbs} GB/s "
                f"regressed below {RATCHET_SLACK} x best {shuffle_best} "
                f"({shuffle_key})\n"
            )
            failed = True
        if failed:
            sys.exit(1)
        # only a CORRECT, PASSING run records new bests (the PR-4/PR-5
        # ratchet lesson: a broken run must never move a floor)
        changed = False
        if gbs > (ingest_best or 0.0):
            ratchet[ingest_key] = gbs
            changed = True
        if shuf_gbs is not None and shuf_gbs > (shuffle_best or 0.0):
            ratchet[shuffle_key] = shuf_gbs
            changed = True
        if changed:
            _save_ratchet(ratchet)


if __name__ == "__main__":
    main()
