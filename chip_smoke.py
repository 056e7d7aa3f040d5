"""First-run proof on the chip: the engine's main paths on a TPU v5e.

    python chip_smoke.py              # one chip: the batch and sql phases
    python chip_smoke.py --chips 4    # four chips: the mesh exchange only

One process, no children, no CPU mode and no size option. It fails at once,
non-zero and without a result line, when ``jax.devices()[0].platform`` is
not ``"tpu"`` or there are fewer devices than asked for. Every phase is
compared with the plain pandas reference the repo already has; a mismatch
or an exception ends the run non-zero — nothing is caught and reported as
a field. Earlier lines are evidence (one JSON object per line: the knobs'
resolved values, per-phase seconds, compile seconds and cache traffic);
the LAST line is exactly

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

Phases (plain functions that take their size, so a scratch script can
rehearse them at a tiny size on the CPU):

- ``batch``: the q3-class pipeline through the bridge API exactly as
  ``bench.py`` drives it — sf=8 (~23M fact rows), accelerator batch of
  1<<22 rows, two map and two reduce partitions so hash partitioning and
  the file shuffle really run on the one chip; warm-up + one timed run.
- ``sql``: ``servegate.build_server(sf=1)`` behind the real HTTP service,
  with the partition count one chip allows (1: every exchange is routed,
  counted and spliced, but the ICI collective is one shard wide — the
  four-chip phase is where the exchange is wide); three corpus texts POSTed
  and compared with ``sqlgate``'s oracles.
- ``exchange`` (``--chips 4`` only): ``__graft_entry__._dryrun_body(4)``'s
  sharded step, then one shuffle-bearing SQL text at
  ``sql.shuffle.partitions=4`` under ``exchange.mode=mesh`` and again under
  ``file``, both compared with the oracle and with each other, asserting
  that the exchanged arrays really live on four devices.

The compile cache goes where ``JAX_COMPILATION_CACHE_DIR`` says, else to
``<checkout>/.jax_cache`` (auron_tpu/jaxenv.py).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
import urllib.error
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

#: the sqlgate corpus texts the sql phase POSTs: a scan+aggregate over one
#: join (q1a), two joins + shuffle + ORDER BY/LIMIT (q3), an outer join +
#: shuffle + ORDER BY (q93a)
SQL_TEXTS = ("q1a", "q3", "q93a")
#: the four-chip phase's shuffle-bearing text: two exchanges, the first
#: ~420k rows wide at sf=1, the second fed by the first's output
EXCHANGE_TEXT = "q65"


def say(**rec) -> None:
    print(json.dumps(rec), flush=True)


def check(ok: bool, why) -> None:
    """The script's comparisons: not ``assert``, which ``python -O`` drops."""
    if not ok:
        raise AssertionError(why)


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling (or fetching from
    the persistent cache), the cache's hit/miss counts and the slowest
    programs, from jax.monitoring's own events. ``take()`` returns the
    totals since the last take."""

    _DUR = {
        "/jax/core/compile/jaxpr_trace_duration": "trace_s",
        "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
        "/jax/core/compile/backend_compile_duration": "compile_s",
    }
    _CNT = {
        "/jax/compilation_cache/cache_hits": "cache_hits",
        "/jax/compilation_cache/cache_misses": "cache_misses",
    }

    def __init__(self) -> None:
        import jax.monitoring as mon

        self._tot = dict.fromkeys([*self._DUR.values(), *self._CNT.values()], 0)
        self._tot["programs"] = self._tot["pallas_programs"] = 0
        self._slow: list = []
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, fun_name: str = "?",
                     **_kw) -> None:
        key = self._DUR.get(event)
        if key is not None:
            self._tot[key] += secs
            if key == "compile_s":
                self._tot["programs"] += 1
                self._slow.append((round(secs, 2), fun_name))
                self._tot["pallas_programs"] += "pallas" in fun_name

    def _on_event(self, event: str, **_kw) -> None:
        key = self._CNT.get(event)
        if key is not None:
            self._tot[key] += 1

    def take(self) -> dict:
        out = {k: (round(v, 3) if isinstance(v, float) else v)
               for k, v in self._tot.items()}
        out["slowest"] = sorted(self._slow, reverse=True)[:4]
        for k in self._tot:
            self._tot[k] = 0
        self._slow = []
        return out


def require_tpu(n_chips: int):
    """The device JAX gives this process, or exit: no CPU mode."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform={devs[0].platform!r}); "
                 "this script has no CPU mode")
    if len(devs) < n_chips:
        sys.exit(f"chip_smoke: need {n_chips} chips, JAX reports {len(devs)}")
    return devs


def resolved_knobs() -> dict:
    """What each backend-dependent ``auto`` resolves to in this process."""
    from auron_tpu import native
    from auron_tpu.columnar import batch
    from auron_tpu.exec.agg_exec import HashAggExec
    from auron_tpu.jaxenv import is_tpu
    from auron_tpu.memory import memmgr
    from auron_tpu.ops import bitonic, hostscatter, hostsort
    from auron_tpu.plan import fusion
    from auron_tpu.utils import config as C

    conf = C.Configuration()
    return {
        "native.available": native.available(),
        "shuffle.pid.kernel": "pallas" if is_tpu() else "jnp",
        "exec.device.sort.impl": bitonic.sort_impl_for(2, 1 << 22, conf=conf),
        "exec.host.sort": hostsort.use_host_sort(conf),
        "exec.agg.dense.host.scatter": hostscatter.use_host_scatter(),
        "exec.agg.incremental.fingerprint": HashAggExec._tri(
            C.AGG_INCREMENTAL_FINGERPRINT, conf),
        "exec.agg.incremental.probe": HashAggExec._tri(
            C.AGG_INCREMENTAL_PROBE, conf),
        "exec.agg.incremental.mergepath": HashAggExec._tri(
            C.AGG_INCREMENTAL_MERGEPATH, conf),
        "exec.fuse.enable": fusion._should_fuse(0, conf, C.FUSE_ENABLE),
        "exec.fuse.probe": fusion._should_fuse(0, conf, C.FUSE_PROBE),
        "exec.fuse.shuffle": fusion._should_fuse(0, conf, C.FUSE_SHUFFLE),
        "join.compact.rule": ("gathered elements" if batch._gather_bound()
                              else "a quarter of capacity"),
        "exchange.mode": conf.get(C.EXCHANGE_MODE),
        "memory.budget.bytes": memmgr._auto_budget(),
    }


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def phase_batch(sf: float, batch_rows: int, n_parts: int, seed: int,
                clock: CompileClock) -> None:
    """bench.py's drive of the q3-class pipeline, compared with its oracle
    to bench.py's tolerance."""
    import jax

    from auron_tpu.models import tpcds

    t0 = time.perf_counter()
    data = tpcds.generate(sf=sf, seed=seed)
    n_rows = data.fact_rows()
    n_bytes = int(data.store_sales.memory_usage(index=False, deep=False).sum())
    gen_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    want = tpcds.q3_class_oracle(data)
    oracle_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    ingested = tpcds.ingest_q3(data, n_map=n_parts, batch_rows=batch_rows)
    ingest_s = time.perf_counter() - t0
    clock.take()

    runs = []
    for label in ("warmup", "timed"):
        with tempfile.TemporaryDirectory(prefix="auron_smoke_") as wd:
            t0 = time.perf_counter()
            got = tpcds.run_q3_class(data, n_map=n_parts, n_reduce=n_parts,
                                     work_dir=wd, ingested=ingested)
            runs.append({"run": label,
                         "seconds": round(time.perf_counter() - t0, 3),
                         **clock.take()})

    check(len(want) > 0, "oracle produced no rows: the comparison would be vacuous")
    check(len(got) == len(want), (len(got), len(want)))
    for c in ("d_year", "i_brand_id"):
        check(got[c].tolist() == want[c].tolist(), f"batch: column {c} differs")
    for g, w in zip(got["s"], want["s"]):
        check(abs(float(g) - float(w)) <= 1e-6 * max(1.0, abs(float(w))), (g, w))

    peak = jax.devices()[0].memory_stats() or {}
    say(phase="batch", sf=sf, fact_rows=n_rows, fact_bytes=n_bytes,
        batch_rows=batch_rows, n_map=n_parts, n_reduce=n_parts,
        result_rows=len(got), generate_s=round(gen_s, 3),
        oracle_s=round(oracle_s, 3), ingest_s=round(ingest_s, 3), runs=runs,
        peak_device_bytes=peak.get("peak_bytes_in_use"), matches_oracle=True)


def _post_sql(port: int, body: dict, timeout: float) -> dict:
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/sql", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"}, method="POST")
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return json.loads(r.read())
    except urllib.error.HTTPError as e:
        raise RuntimeError(
            f"POST /sql answered {e.code}: {e.read().decode(errors='replace')[:2000]}"
        ) from None


def _check_rows(name: str, resp: dict, frames: dict, float_rel: float) -> None:
    """An HTTP /sql answer against the text's oracle: the same comparison
    ``sqlgate.run_case`` makes, over the JSON rows."""
    import pandas as pd

    from auron_tpu.models import sqlgate
    from auron_tpu.models.compare import compare_frames

    case = sqlgate.case_by_name(name)
    got = pd.DataFrame(resp["rows"], columns=resp["columns"])
    want = sqlgate.oracle_head(case.oracle(frames), case)
    check(len(want) > 0, f"{name}: oracle produced no rows")
    err = compare_frames(got, want, float_rel, sorted_rows=True)
    check(err is None, f"{name}: {err}")


def phase_sql(sf: float, n_parts: int, names: tuple, seed: int,
              clock: CompileClock, timeout: float = 900.0) -> None:
    """The serving stack over real HTTP, each text compared with its
    oracle; each text is POSTed twice (the second must hit the plan cache
    and answer identically)."""
    from auron_tpu.models import servegate, sqlgate, tpcds
    from auron_tpu.sql.catalog import build_tables
    from auron_tpu.utils import config as C
    from auron_tpu.utils import httpsvc

    t0 = time.perf_counter()
    frames = build_tables(tpcds.generate(sf=sf, seed=seed), seed=seed)
    server, _ = servegate.build_server(sf=sf, n_parts=n_parts, frames=frames)
    float_rel = C.SQL_GATE_FLOAT_REL.get(C.Configuration())
    setup_s = time.perf_counter() - t0
    port = httpsvc.start(0)
    httpsvc.install_sql_server(server)
    clock.take()
    texts = []
    try:
        for name in names:
            case = sqlgate.case_by_name(name)
            t0 = time.perf_counter()
            first = _post_sql(port, {"sql": case.sql, "tenant": "smoke"}, timeout)
            first_s = time.perf_counter() - t0
            compiled = clock.take()
            _check_rows(name, first, frames, float_rel)
            t0 = time.perf_counter()
            again = _post_sql(port, {"sql": case.sql, "tenant": "smoke"}, timeout)
            again_s = time.perf_counter() - t0
            check(again["cache_hit"] is True, f"{name}: plan cache missed")
            check(again["rows"] == first["rows"], f"{name}: replay diverged")
            texts.append({"text": name, "rows": len(first["rows"]),
                          "first_s": round(first_s, 3),
                          "again_s": round(again_s, 3), **compiled,
                          "again_programs": clock.take()["programs"]})
    finally:
        httpsvc.stop()
    if n_parts == 1:
        say(note="sql phase runs with sql.shuffle.partitions=1 (one chip = one "
                 "mesh device): each exchange is routed, counted and spliced but "
                 "is one shard wide; the wide exchange is the --chips 4 phase")
    say(phase="sql", sf=sf, n_parts=n_parts,
        fact_rows=len(frames["store_sales"]), setup_s=round(setup_s, 3),
        texts=texts, stats=server.stats(), matches_oracle=True)


def phase_exchange(n_chips: int, sf: float, name: str, seed: int,
                   clock: CompileClock) -> None:
    """The ICI all_to_all as the shuffle: the sharded step, then one SQL
    text over an n-wide mesh under exchange.mode=mesh and =file."""
    import __graft_entry__ as entry
    from auron_tpu.models import sqlgate, tpcds
    from auron_tpu.models.compare import compare_frames
    from auron_tpu.parallel.mesh import make_mesh
    from auron_tpu.parallel.mesh_driver import MeshQueryDriver
    from auron_tpu.sql import compile_text
    from auron_tpu.sql.catalog import build_tables
    from auron_tpu.utils import config as C

    t0 = time.perf_counter()
    entry._dryrun_body(n_chips)
    say(phase="exchange.step", n_devices=n_chips,
        seconds=round(time.perf_counter() - t0, 3), **clock.take())

    frames = build_tables(tpcds.generate(sf=sf, seed=seed), seed=seed)
    case = sqlgate.case_by_name(name)
    want = sqlgate.oracle_head(case.oracle(frames), case)
    check(len(want) > 0, f"{name}: oracle produced no rows")
    float_rel = C.SQL_GATE_FLOAT_REL.get(C.Configuration())
    mesh = make_mesh(n_chips)
    lq = compile_text(case.sql, sqlgate.gate_catalog(), n_parts=n_chips)
    got = {}
    for mode in ("mesh", "file"):
        conf = C.Configuration().set(C.EXCHANGE_MODE, mode)
        driver = MeshQueryDriver(mesh, conf=conf)
        t0 = time.perf_counter()
        df = sqlgate.execute(lq, frames, mesh, driver=driver)
        secs = time.perf_counter() - t0
        err = compare_frames(df, want, float_rel, sorted_rows=True)
        check(err is None, f"{name} under exchange.mode={mode}: {err}")
        check(bool(driver.stats), f"{name}: no exchange ran")
        for s in driver.stats:
            check(s.mode == mode, f"exchange {s.exchange_id} took {s.mode}")
            check(mode == "file" or s.n_devices == n_chips,
                  f"exchange {s.exchange_id}: exchanged arrays live on "
                  f"{s.n_devices} device(s), not {n_chips}")
        got[mode] = df
        say(phase="exchange.sql", text=name, mode=mode, n_parts=n_chips,
            seconds=round(secs, 3), rows=len(df),
            exchanges=[{"id": s.exchange_id, "mode": s.mode,
                        "rows": int(s.rows.sum()), "devices": s.n_devices}
                       for s in driver.stats],
            **clock.take(), matches_oracle=True)
    err = compare_frames(got["mesh"], got["file"], float_rel, sorted_rows=True)
    check(err is None, f"{name}: mesh and file transports disagree: {err}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = run only the mesh-exchange phase, on four chips")
    ap.add_argument("--seed", type=int, default=42,
                    help="seed of the generated tables")
    args = ap.parse_args()

    t_start = time.perf_counter()
    import auron_tpu  # noqa: F401  (x64 + compile cache, before any backend use)
    import jax

    devs = require_tpu(args.chips)
    clock = CompileClock()
    say(device={"platform": devs[0].platform, "kind": devs[0].device_kind,
                "count": len(devs)},
        jax=jax.__version__,
        compile_cache_dir=jax.config.jax_compilation_cache_dir,
        compile_cache_from_env=bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")),
        knobs=resolved_knobs())

    if args.chips == 4:
        phase_exchange(4, sf=1.0, name=EXCHANGE_TEXT, seed=args.seed, clock=clock)
    else:
        phase_batch(sf=8.0, batch_rows=1 << 22, n_parts=2, seed=args.seed,
                    clock=clock)
        phase_sql(sf=1.0, n_parts=1, names=SQL_TEXTS, seed=args.seed, clock=clock)

    say(total_s=round(time.perf_counter() - t_start, 3))
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)


if __name__ == "__main__":
    main()
