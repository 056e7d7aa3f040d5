"""Large-scale differential gate: shuffle/join-heavy classes at SF>=100.

BASELINE.md's configs call for sf=100/1000 on the join/shuffle-heavy
shapes; the unit gate (tests/test_tpcds.py) runs every class at toy scale,
this script runs the heavy subset at real scale as a combined
perf + correctness gate (the in-process analog of dev/auron-it's
QueryRunner over the big scale factors).

Each class runs in its OWN subprocess with a timeout: a wedged query
gets a SIGUSR1 stack dump (forensics on stderr) and a kill, and the gate
moves on — one stall can't eat the remaining classes or the summary.

This is a gate, not a log (the reference's result-check AND plan-check are
both hard gates, dev/auron-it QueryResultComparator.scala:39-110): a class
FAILS when rows mismatch, when it exceeds the wall-clock budget, or when
its speedup vs the single-thread pandas oracle is below the per-class
minimum — and the process exits nonzero when any class fails.

Per class, one JSON line:
    {"class": ..., "sf": N, "ok": bool, "engine_s": N, "oracle_s": N,
     "speedup": N, "backend": ..., "error": str|null}
plus a "breakdown" line with the per-operator metric rollup (the metric
tree every task hands back at finalize — metrics.rs:7-35 analog) and the
engine-level compile/host-sync counters; the full tree is also written to
PERF_BREAKDOWN_SF{N}.json next to this script.

Env: PERF_GATE_SF (default 100), PERF_GATE_CLASSES (comma list, default
the heavy subset), BENCH_PARTS (default 2), PERF_GATE_CLASS_TIMEOUT
(seconds per class, default 2700), PERF_GATE_BUDGET_S (wall-clock budget
per class, default 900 — a correct-but-slow class fails), and
PERF_GATE_MIN_SPEEDUP (default 0.5; q3/q18/q93/q14 default 1.0).

``--trace-out=DIR`` (or PERF_GATE_TRACE_OUT=DIR) raises children to
full-trace mode and writes one Chrome/Perfetto span-timeline artifact
per class (``trace_<class>_sf<N>.json``; docs/observability.md).
Without the flag each class still runs under a query trace (ring
attribution). Trace-mode runs skip the ratchet (enforcement AND persistence):
the accounting overhead inside the timed dispatch must neither fail a
class hovering at 0.9×best nor pollute the recorded bests.

The floor RATCHETS (PERF_GATE_RATCHET=0 disables): PERF_RATCHET.json
records each class's best passing speedup per scale factor, and a later
run fails below max(class_floor, 0.9 * best) — the discounted 0.5x tiers
stop a class from shipping slow, the ratchet stops a class that once ran
at 1.2x from quietly sliding back toward its floor. New bests rewrite
the file as they land (kill-safe, like the breakdown merge).

The gate is RESUMABLE: PERF_GATE_RESUME=<path to a previous .out file>
(or "auto" for PERF_GATE_SF{N}.out next to this script) re-emits the
classes that already passed there and runs only the rest — a gate killed
at class 3 of 8 finishes the remaining 5 on the next invocation instead
of repaying the whole run (the SF=100 run only ever recorded 2 of 8).
The per-class breakdown file is MERGED with its previous content and
rewritten after every class, and the final summary line is emitted even
when the gate itself dies mid-class.

The parent never touches JAX, so each class child gets the device to
itself and runs on whatever backend JAX gives it (its record says which).
"""

import faulthandler
import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, ROOT)

HEAVY = ["q3", "q18", "q72", "q95", "q65", "q5", "q93", "q14"]
CLASS_TIMEOUT_S = int(os.environ.get("PERF_GATE_CLASS_TIMEOUT", "2700"))
BUDGET_S = float(os.environ.get("PERF_GATE_BUDGET_S", "900"))
# agg/scan-dominated classes must BEAT one pandas thread; the join/shuffle
# classes (where the oracle skips the exchange entirely) must reach half.
# An explicit PERF_GATE_MIN_SPEEDUP overrides BOTH tiers.
_ENV_MIN_SPEEDUP = os.environ.get("PERF_GATE_MIN_SPEEDUP")
DEFAULT_MIN_SPEEDUP = float(_ENV_MIN_SPEEDUP or "0.5")
MIN_SPEEDUP = (
    {}
    if _ENV_MIN_SPEEDUP
    else {"q3": 1.0, "q18": 1.0, "q93": 1.0, "q14": 1.0}
)


def run_one(name: str, ws: str) -> None:
    """Child mode: generate data, run ONE class + oracle, print its record."""
    faulthandler.register(signal.SIGUSR1, all_threads=True)

    from auron_tpu.utils.profiling import EngineCounters

    counters = EngineCounters.install()
    # PERF_GATE_ALL_SITES=1: attribute every blocking sync (not just >1ms
    # stalls) — the forensic mode for chasing sub-ms per-batch reads
    counters.record_all_sites = os.environ.get("PERF_GATE_ALL_SITES") == "1"

    import jax

    from auron_tpu.bridge import api
    from auron_tpu.exec.metrics import MetricNode
    from auron_tpu.models import tpcds

    import threading

    # per-operator rollup across every task of the class; tasks finalize
    # from concurrent pump threads, so the read-modify-write is locked
    op_totals: dict[str, dict[str, int]] = {}
    flat_totals: dict[str, int] = {}
    trees: list[dict] = []
    sink_lock = threading.Lock()

    def sink(snap: dict) -> None:
      with sink_lock:
        trees.append(snap)
        for k, v in MetricNode.flat_totals(snap).items():
            flat_totals[k] = flat_totals.get(k, 0) + int(v)

        MetricNode.accumulate_op_totals(snap, op_totals)

    api.set_metrics_sink(sink)

    sf = float(os.environ.get("PERF_GATE_SF", "100"))
    n_parts = int(os.environ.get("BENCH_PARTS", "2"))
    backend = jax.devices()[0].platform

    t0 = time.perf_counter()
    data = tpcds.generate(sf=sf, seed=42)
    sys.stderr.write(
        f"perf_gate[{name}]: generated sf={sf} ({data.fact_rows():,} rows) "
        f"in {time.perf_counter() - t0:.1f}s; backend={backend}\n"
    )
    work = os.path.join(ws, name)

    # Warm the jit traces + persistent-compile cache on a small dataset
    # first (PERF_GATE_WARMUP=0 disables). Batches cap at 128k rows, so a
    # small-SF run exercises the same bucket shapes / compiled programs the
    # big run uses; the timed number then measures the engine, not Python
    # tracing — the analog of the reference's warmed JVM+native session
    # (dev/auron-it runs queries on a long-lived session, not one process
    # per query). The warmup wall time is reported, not hidden.
    def dispatch(run_data, run_work):
        """One name->runner dispatch shared by warmup and the timed run
        (a class added to HEAVY only needs a runner here once)."""
        if name == "q72":
            return tpcds.run_q72_class(
                run_data, n_map=n_parts, n_reduce=n_parts, work_dir=run_work)
        if name == "q3":
            return tpcds.run_q3_class(
                run_data, n_map=n_parts, n_reduce=n_parts, work_dir=run_work)
        runs = {"q18": tpcds.run_q18_class, "q95": tpcds.run_q95_class,
                "q65": tpcds.run_q65_class, "q5": tpcds.run_q5_class,
                "q93": tpcds.run_q93_class, "q14": tpcds.run_q14_class}
        return runs[name](run_data, work_dir=run_work)

    warmup_s = 0.0
    if os.environ.get("PERF_GATE_WARMUP", "1") != "0" and sf > 4:
        t0 = time.perf_counter()
        wdata = tpcds.generate(sf=4.0, seed=11)
        wwork = os.path.join(ws, name + "_warm")
        try:
            dispatch(wdata, wwork)
        finally:
            shutil.rmtree(wwork, ignore_errors=True)
            del wdata
        warmup_s = time.perf_counter() - t0
        sys.stderr.write(f"perf_gate[{name}]: warmup {warmup_s:.1f}s\n")
        # the warmup ran under the same metrics sink and engine counters;
        # zero everything so the breakdown attributes ONLY the timed run
        with sink_lock:
            trees.clear()
            flat_totals.clear()
            op_totals.clear()
        counters.reset()

    from auron_tpu import obs

    trace_dir = os.environ.get("PERF_GATE_TRACE_OUT") or None
    if trace_dir:
        os.makedirs(trace_dir, exist_ok=True)
        obs.set_mode("trace")
    t0 = time.perf_counter()
    with obs.query_trace(f"perf_gate.{name}") as qt:
        res = dispatch(data, work)
    eng = time.perf_counter() - t0
    if trace_dir:
        if qt.trace is not None:
            from auron_tpu.obs import export

            export.write_chrome_trace(
                os.path.join(trace_dir, f"trace_{name}_sf{int(sf)}.json"),
                trace_id=qt.trace.id,
            )
        else:
            # an explicitly requested artifact must never vanish silently
            sys.stderr.write(
                f"perf_gate[{name}]: --trace-out requested but obs "
                "recording is disabled (AURON_TPU_OBS_KILL?); no trace "
                "written\n"
            )
    t0 = time.perf_counter()
    if name == "q72":
        got, sr = res
        want = tpcds.q72_class_oracle(data, sr)
    else:
        got = res
        oracles = {"q3": tpcds.q3_class_oracle,
                   "q18": tpcds.q18_class_oracle, "q95": tpcds.q95_class_oracle,
                   "q65": tpcds.q65_class_oracle, "q5": tpcds.q5_class_oracle,
                   "q93": tpcds.q93_class_oracle, "q14": tpcds.q14_class_oracle}
        want = oracles[name](data)
    orc = time.perf_counter() - t0

    err = tpcds._cmp_frames(got, want)
    print(json.dumps({
        "class": name, "sf": sf, "ok": err is None,
        "engine_s": round(eng, 3), "oracle_s": round(orc, 3),
        "speedup": round(orc / eng, 3) if eng else None,
        "warmup_s": round(warmup_s, 3),
        "backend": backend, "error": err,
    }), flush=True)
    # second line: where the time went (op rollup sorted by compute time)
    op_seconds = MetricNode.op_seconds
    ranked = sorted(op_totals.items(), key=lambda kv: -op_seconds(kv[1]))
    counter_snap = counters.snapshot()
    brk = {
        "breakdown": name, "sf": sf, "tasks": len(trees),
        "counters": counter_snap,
        # op -> elapsed compute seconds, top 5: the trajectory-diffable
        # shape (BENCH_r*/PERF_BREAKDOWN_*) that catches an op-level
        # regression even when the end-to-end speedup still passes
        "top_ops": {k: round(op_seconds(v), 3) for k, v in ranked[:5]},
        # op -> [stalls, blocking sync-wait seconds]: attribution to the
        # operator actually waiting, so a downstream sync drain can never
        # read as upstream compute again (the PR-3/PR-10 q93 hunt:
        # probe_time absorbed agg_exec.py:427's 38s across a suspended
        # generator's open timer)
        "top_ops_sync": counter_snap.get("op_sync", {}),
        "flat": {k: flat_totals[k] for k in sorted(flat_totals)},
        "ops": {k: v for k, v in ranked},
    }
    shuf = shuffle_breakdown(flat_totals)
    if shuf is not None:
        # data-plane visibility (ISSUE 11): throughputs, bytes and the
        # per-block encoding histogram ride every gate run
        brk["shuffle"] = shuf
    print(json.dumps(brk), flush=True)


def shuffle_breakdown(flat: dict) -> dict | None:
    """Data-plane rollup from a flat metric-total dict (shared by bench.py
    and the per-class breakdown line): write/read throughput, bytes, and
    the per-column-block encoding histogram — encoding regressions show in
    every gate run, next to top_ops (docs/shuffle.md). Returns None when
    the run shuffled nothing.

    write GB/s is RAW bytes staged per second of encode+write work (the
    number compacted encodings move); read GB/s is FILE bytes decoded per
    second of block-decode + bucket-assembly work. Both use ns timers, so
    bytes/ns == GB/s exactly."""
    raw = flat.get("shuffle_bytes_raw", 0)
    written = flat.get("shuffle_bytes_written", 0) or flat.get("data_size", 0)
    read = flat.get("shuffle_bytes_read", 0)
    enc_ns = flat.get("compress_time", 0) + flat.get("write_time", 0)
    dec_ns = flat.get("decode_time", 0)
    if not (raw or written or read):
        return None
    out = {
        "bytes_raw": raw,
        "bytes_written": written,
        "bytes_read": read,
        "encodings": {
            k[len("shuffle_enc_"):]: v
            for k, v in sorted(flat.items()) if k.startswith("shuffle_enc_")
        },
    }
    if raw and enc_ns:
        out["shuffle_write_gb_s"] = round(raw / enc_ns, 3)
    if read and dec_ns:
        out["shuffle_read_gb_s"] = round(read / dec_ns, 3)
    return out


RATCHET_PATH = os.path.join(ROOT, "PERF_RATCHET.json")
RATCHET_SLACK = 0.9  # a class may regress at most 10% below its best


def _load_ratchet() -> dict:
    """{f"{class}@sf{N}": best passing speedup}. Missing/corrupt = empty."""
    try:
        with open(RATCHET_PATH) as f:
            d = json.load(f)
        return {k: float(v) for k, v in d.items()}
    except (OSError, ValueError, TypeError):
        return {}


def _save_ratchet(d: dict) -> None:
    # temp + atomic replace: a kill mid-write must not truncate the file
    # (a corrupt ratchet silently resets every class's floor)
    tmp = RATCHET_PATH + ".tmp"
    with open(tmp, "w") as f:
        json.dump({k: d[k] for k in sorted(d)}, f, indent=1)
        f.write("\n")
    os.replace(tmp, RATCHET_PATH)


def _load_resume(path: str, sf: float) -> dict:
    """Passing per-class records from a previous gate's .out file (one
    JSON object per line): {class: record}. Only ok=true records at the
    SAME scale factor count — a failed class re-runs."""
    done = {}
    try:
        with open(path) as f:
            lines = f.read().splitlines()
    except OSError:
        return done
    for ln in lines:
        if not ln.startswith("{"):
            continue
        try:
            rec = json.loads(ln)
        except json.JSONDecodeError:
            continue
        if (
            rec.get("class") in HEAVY
            and rec.get("ok") is True
            and float(rec.get("sf", -1)) == sf
        ):
            done[rec["class"]] = rec
    return done


def _merge_breakdowns(out_path: str, breakdowns: dict) -> None:
    """Rewrite the breakdown file as (previous content <- this run):
    classes not re-run this time keep their prior evidence."""
    merged = {}
    try:
        with open(out_path) as f:
            merged = json.load(f)
    except (OSError, ValueError):
        pass
    merged.update(breakdowns)
    with open(out_path, "w") as f:
        json.dump(merged, f, indent=1)


def main() -> None:
    from auron_tpu.obs.export import trace_out_arg

    trace_dir = trace_out_arg(sys.argv[1:], "PERF_GATE_TRACE_OUT")
    if trace_dir:
        # children read it from the env (each class runs in a subprocess)
        os.environ["PERF_GATE_TRACE_OUT"] = trace_dir
    sf = float(os.environ.get("PERF_GATE_SF", "100"))
    names = [n.strip() for n in
             os.environ.get("PERF_GATE_CLASSES", ",".join(HEAVY)).split(",")
             if n.strip() in HEAVY]
    out_path = os.path.join(ROOT, f"PERF_BREAKDOWN_SF{int(sf)}.json")
    resume = os.environ.get("PERF_GATE_RESUME", "")
    if resume == "auto":
        resume = os.path.join(ROOT, f"PERF_GATE_SF{int(sf)}.out")
    resumed = _load_resume(resume, sf) if resume else {}
    # a --trace-out run carries full-trace accounting overhead inside the
    # timed dispatch: a diagnostic rerun must neither fail a class on the
    # tight ratcheted floor (0.9 x best) nor RECORD its slowed speedup as
    # a best — static class floors still apply
    ratchet_on = (os.environ.get("PERF_GATE_RATCHET", "1") != "0"
                  and not trace_dir)
    ratchet = _load_ratchet()
    ws = tempfile.mkdtemp(prefix="auron_perf_gate_")
    results = []
    breakdowns = {}
    try:
      for name in names:
        if name in resumed:
            rec = dict(resumed[name])
            rec["resumed"] = True
            results.append(rec)
            print(json.dumps(rec), flush=True)
            continue
        env = dict(os.environ)
        env["PERF_GATE_CHILD"] = name
        env["PERF_GATE_WS"] = ws
        rec = None
        proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        )
        try:
            out, err_txt = proc.communicate(timeout=CLASS_TIMEOUT_S)
        except subprocess.TimeoutExpired:
            # forensics: stack dump to the child's stderr, then kill
            proc.send_signal(signal.SIGUSR1)
            time.sleep(3)
            proc.kill()
            out, err_txt = proc.communicate()
            rec = {"class": name, "sf": sf, "ok": False, "engine_s": None,
                   "oracle_s": None, "speedup": None, "backend": None,
                   "error": f"timeout after {CLASS_TIMEOUT_S}s"}
            sys.stderr.write(
                f"perf_gate[{name}]: TIMEOUT; child stacks:\n{err_txt[-4000:]}\n"
            )
        if rec is None:
            lines = [ln for ln in out.splitlines() if ln.startswith("{")]
            recs = []
            for ln in lines:
                try:
                    recs.append(json.loads(ln))
                except json.JSONDecodeError:
                    pass  # child killed mid-print; keep what parsed
            main_recs = [r for r in recs if "class" in r]
            brk = [r for r in recs if "breakdown" in r]
            if brk:
                breakdowns[name] = brk[-1]
            if proc.returncode == 0 and main_recs:
                rec = main_recs[-1]
            else:
                rec = {"class": name, "sf": sf, "ok": False, "engine_s": None,
                       "oracle_s": None, "speedup": None, "backend": None,
                       "error": f"child rc={proc.returncode}: {err_txt[-300:]}"}
        # ---- the teeth: wall budget + minimum speedup are hard failures.
        # The floor RATCHETS: once a class has passed at speedup B, it must
        # stay above max(class_floor, 0.9*B) — a class hovering at its 0.5x
        # discounted floor can't hide a regression from a better past self.
        if rec["ok"]:
            floor = MIN_SPEEDUP.get(name, DEFAULT_MIN_SPEEDUP)
            rkey = f"{name}@sf{int(sf)}"
            best = ratchet.get(rkey)
            eff_floor = floor
            if ratchet_on and best is not None:
                eff_floor = max(floor, round(RATCHET_SLACK * best, 3))
            rec["floor"] = eff_floor
            if rec["engine_s"] is not None and rec["engine_s"] > BUDGET_S:
                rec["ok"] = False
                rec["error"] = (
                    f"wall budget exceeded: {rec['engine_s']:.1f}s > {BUDGET_S:.0f}s"
                )
            elif rec["speedup"] is not None and rec["speedup"] < eff_floor:
                rec["ok"] = False
                rec["error"] = f"speedup {rec['speedup']} < required {eff_floor}" + (
                    f" (ratchet: best {best})"
                    if eff_floor > floor else "")
            elif (
                ratchet_on
                and rec["speedup"] is not None
                and rec["speedup"] > (best or 0.0)
            ):
                ratchet[rkey] = rec["speedup"]
                _save_ratchet(ratchet)
        shutil.rmtree(os.path.join(ws, name), ignore_errors=True)
        results.append(rec)
        print(json.dumps(rec), flush=True)
        # evidence survives a mid-gate kill: merge + rewrite after EVERY
        # class (classes not re-run keep their previous breakdown)
        _merge_breakdowns(out_path, breakdowns)
    finally:
        # the summary line is the gate's contract with the trajectory —
        # emit it even when a class blew up the gate process itself
        passed = sum(bool(r.get("ok")) for r in results)
        print(json.dumps({
            "metric": "perf_gate", "sf": sf, "classes": len(results),
            "passed": passed, "requested": len(names),
            "resumed": sorted(resumed),
        }), flush=True)
    if passed < len(names):
        sys.exit(1)


if __name__ == "__main__":
    child = os.environ.get("PERF_GATE_CHILD")
    if child:
        run_one(child, os.environ["PERF_GATE_WS"])
    else:
        main()
