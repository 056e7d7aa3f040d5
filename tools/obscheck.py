"""Observability overhead gate (`make obscheck`).

The flight recorder's promise is "cheap enough to leave on in
production"; this gate is the teeth. It replays the same q3-class
pipeline tools/perfcheck.py uses, in three subprocess configurations:

- ``base``     — ``AURON_TPU_OBS_KILL=1``: the no-obs baseline. The obs
  facade is rebound to true no-ops at import, so instrumentation sites
  cost one no-op call — the closest a built tree can get to "the code
  without the instrumentation".
- ``off``      — ``obs.mode=off``: the dynamic kill path every site pays
  when tracing is disabled (one module-global check per event site).
  Budget: <=2%% wall over base.
- ``recorder`` — ``obs.mode=recorder``: the always-on flight recorder
  (per-thread ring appends). Budget: <=5%% wall over base.

A ``trace``-mode run also executes (full tracing + per-query summary):
its wall is REPORTED, and its exported artifact is sanity-checked —
Chrome-trace JSON loads, carries op events and the regions of the task,
pump and sync layers, and ``obs.window_summary`` of the timed replay is
complete and holds the batch path's layers (docs/observability.md).

Methodology: each mode runs OBSCHECK_REPS times interleaved and the
MINIMUM wall is compared — min-of-N measures the systematic cost, not
scheduler noise — plus a small absolute slack (OBSCHECK_SLACK_S) so a
sub-second replay on a noisy 2-core box doesn't flake the gate.

Env: OBSCHECK_SF (default 1.0), OBSCHECK_PARTS (default 2),
OBSCHECK_REPS (default 3), OBSCHECK_SLACK_S (default 0.25).
Exits nonzero on a budget breach or a broken trace artifact.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

OFF_BUDGET = 1.02       # mode=off wall vs no-obs base
RECORDER_BUDGET = 1.05  # flight-recorder wall vs no-obs base


def child(trace_out: str | None) -> None:
    """One replay: generate, warm up, run timed; print a JSON record."""
    import time

    from auron_tpu import obs
    from auron_tpu.models import tpcds
    from auron_tpu.utils.profiling import EngineCounters

    EngineCounters.install()
    sf = float(os.environ.get("OBSCHECK_SF", "1.0"))
    n_parts = int(os.environ.get("OBSCHECK_PARTS", "2"))
    data = tpcds.generate(sf=sf, seed=7)
    ws = tempfile.mkdtemp(prefix="auron_obscheck_")
    tpcds.run_q3_class(data, n_map=n_parts, n_reduce=n_parts,
                       work_dir=os.path.join(ws, "warm"))
    rec: dict = {"mode": obs.mode_name(), "kill": obs.core.KILLED}
    t0 = time.perf_counter()
    if trace_out:
        from auron_tpu.obs import export

        with obs.query_trace("obscheck.q3") as qt:
            tpcds.run_q3_class(data, n_map=n_parts, n_reduce=n_parts,
                               work_dir=os.path.join(ws, "run"))
        rec["wall_s"] = round(time.perf_counter() - t0, 4)
        export.write_chrome_trace(trace_out, trace_id=qt.trace.id)
        rec["trace_out"] = trace_out
        rec["window"] = obs.window_summary(t0, time.perf_counter())
        # whether the version-dependent EngineCounters sync hook is live:
        # the artifact check requires sync events only when it is
        rec["host_syncs"] = EngineCounters._installed.snapshot()["host_syncs"]
        rec["summary"] = qt.summary
    else:
        tpcds.run_q3_class(data, n_map=n_parts, n_reduce=n_parts,
                           work_dir=os.path.join(ws, "run"))
        rec["wall_s"] = round(time.perf_counter() - t0, 4)
    print(json.dumps(rec), flush=True)


def _run_child(env_extra: dict, trace_out: str | None = None) -> dict:
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    env.pop("AURON_TPU_OBS_KILL", None)
    env.pop("AURON_TPU_OBS_MODE", None)
    env.update(env_extra)
    env["OBSCHECK_CHILD"] = "1"
    if trace_out:
        env["OBSCHECK_TRACE_OUT"] = trace_out
    r = subprocess.run(
        [sys.executable, os.path.abspath(__file__)],
        env=env, capture_output=True, text=True, timeout=1200,
    )
    if r.returncode != 0:
        raise RuntimeError(
            f"obscheck child failed rc={r.returncode}: {r.stderr[-800:]}"
        )
    line = [ln for ln in r.stdout.splitlines() if ln.startswith("{")][-1]
    return json.loads(line)


def _check_trace_artifact(path: str, rec: dict) -> list[str]:
    problems = []
    try:
        with open(path) as f:
            ct = json.load(f)
    except (OSError, ValueError) as e:
        return [f"trace artifact unreadable: {e!r}"]
    xs = [e for e in ct.get("traceEvents", []) if e.get("ph") == "X"]
    kinds = {e.get("cat") for e in xs}
    # op events and the task/pump regions come from our own
    # instrumentation and must exist; sync regions depend on the
    # version-sensitive EngineCounters hook (profiling.py degrades to
    # "counter absent" by design) — require them only when the child
    # actually observed syncs
    required = ["op", "task", "pump"]
    if rec.get("host_syncs", 0) > 0:
        required.append("sync")
    for want in required:
        if want not in kinds:
            problems.append(f"trace artifact missing '{want}' events")
    if not all(
        isinstance(e.get("ts"), (int, float)) and "name" in e for e in xs
    ):
        problems.append("trace artifact has malformed X events")
    window = rec.get("window") or {}
    if not window.get("complete", False):
        problems.append("window_summary of the timed replay is incomplete")
    missing = {"entry", "plan", "task", "pump", "wait"} - set(
        window.get("layers", {}))
    if missing:
        problems.append(f"window_summary lacks layers {sorted(missing)}")
    return problems


def main() -> int:
    reps = int(os.environ.get("OBSCHECK_REPS", "3"))
    slack = float(os.environ.get("OBSCHECK_SLACK_S", "0.25"))
    modes = {
        "base": {"AURON_TPU_OBS_KILL": "1"},
        "off": {"AURON_TPU_OBS_MODE": "off"},
        "recorder": {"AURON_TPU_OBS_MODE": "recorder"},
    }
    walls: dict[str, list[float]] = {m: [] for m in modes}
    for i in range(reps):  # interleave so drift hits every mode equally
        for m, env in modes.items():
            rec = _run_child(env)
            walls[m].append(rec["wall_s"])
            print(json.dumps({**rec, "mode": m, "rep": i}), flush=True)
    trace_file = os.path.join(tempfile.mkdtemp(prefix="auron_obscheck_"),
                              "trace.json")
    trec = _run_child({"AURON_TPU_OBS_MODE": "trace"}, trace_out=trace_file)
    print(json.dumps({"mode": "trace", **{k: v for k, v in trec.items()
                                          if k not in ("summary", "window")}}),
          flush=True)

    base = min(walls["base"])
    failures = list(_check_trace_artifact(trace_file, trec))
    verdict = {}
    for m, budget in (("off", OFF_BUDGET), ("recorder", RECORDER_BUDGET)):
        w = min(walls[m])
        limit = base * budget + slack
        ok = w <= limit
        verdict[m] = {"wall_s": w, "limit_s": round(limit, 4), "ok": ok,
                      "overhead_pct": round(100.0 * (w / base - 1.0), 2)}
        if not ok:
            failures.append(
                f"{m} wall {w:.3f}s exceeds {limit:.3f}s "
                f"(base {base:.3f}s x {budget} + {slack}s slack)"
            )
    print(json.dumps({
        "metric": "obscheck", "base_wall_s": base, **verdict,
        "trace_wall_s": trec["wall_s"],
        "trace_overhead_pct": round(100.0 * (trec["wall_s"] / base - 1.0), 2),
        "failures": failures,
    }), flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    if os.environ.get("OBSCHECK_CHILD"):
        child(os.environ.get("OBSCHECK_TRACE_OUT") or None)
    else:
        sys.exit(main())
