"""The harness: what every run does, whatever its cell.

Finds a cell's files by the names in ``BENCHMARK.json`` (its configuration
under ``configs/``, its traffic mix under ``traffic/``, its driver under
``drivers/``, its queries under ``queries/``, a reader for each per-layer
metric under ``metrics/``), and drives one run: set-up, window, peak memory,
the comparison with the reference, the metrics. ``run.py`` is the command;
tests call ``run_cell`` with the devices they have.

A test's fixture deployment, which is no cell of ``BENCHMARK.json``, keeps its
traffic mix under a root of its own: ``load_cell`` reads it from there, and
the mix names the package of its queries (``query_package``).
"""

from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import os
import shutil
import statistics
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def say(**rec) -> None:
    """An evidence line: one JSON object, before the result line."""
    print(json.dumps(rec, default=str), flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``benchmark/<kind>/<name>.py``, found by name. A metric that is split
    by path (``x.batch``, ``x.sql``) shares the reader ``metrics/x.py``."""
    for stem in (name, name.split(".", 1)[0]):
        path = os.path.join(HERE, kind, stem + ".py")
        if os.path.exists(path):
            mod_name = f"benchmark_{kind}_{stem.replace('.', '_')}"
            if mod_name in sys.modules:
                return sys.modules[mod_name]
            spec = importlib.util.spec_from_file_location(mod_name, path)
            mod = importlib.util.module_from_spec(spec)
            sys.modules[mod_name] = mod
            spec.loader.exec_module(mod)
            return mod
    raise FileNotFoundError(f"no benchmark/{kind}/{name}.py")


def load_cell(workload: str, spec: dict | None = None, root: str = HERE) -> dict:
    """The cell's entry of ``BENCHMARK.json`` (or of ``spec``) with its
    configuration and its traffic mix read from their own files, the mix
    from ``<root>/traffic/``."""
    if spec is None:
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            spec = json.load(f)
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"run.py: no workload {workload!r} in BENCHMARK.json; "
                         f"it has {sorted(cells)}")
    cell = dict(cells[workload])
    files = {c["name"]: c["file"] for c in spec["configs"]}
    with open(os.path.join(ROOT, files[cell["config"]])) as f:
        cell["config_file"] = json.load(f)
    with open(os.path.join(root, "traffic", cell["traffic"] + ".json")) as f:
        cell["traffic_file"] = json.load(f)

    def reports(m: dict) -> bool:
        return "workloads" not in m or workload in m["workloads"]

    cell["end_to_end"] = [m for m in spec["end_to_end"] if reports(m)]
    e2e = {m["name"] for m in cell["end_to_end"]}
    cell["per_layer"] = [m for m in spec["per_layer"]
                         if reports(m) and m["moves"] in e2e]
    return cell


def require_tpu(n_chips: int):
    """The devices JAX gives this process, or exit: there is no CPU mode."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"run.py: JAX found no TPU (platform={devs[0].platform!r}); "
                 "the benchmark has no CPU mode")
    if len(devs) < n_chips:
        sys.exit(f"run.py: the cell needs {n_chips} chips, JAX reports {len(devs)}")
    return devs[:n_chips]


def chip_peaks(device_kind: str) -> dict:
    table = load_json("peaks.json")
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       "benchmark/peaks.json: add a row with its source")
    return table[device_kind]


def span(name: str):
    """A host span on the profiler's clock; costs next to nothing while no
    trace is being taken."""
    import jax

    return jax.profiler.TraceAnnotation(name)


class Tracer:
    """The profiler around a steady sub-window, started and stopped by the
    driver at the points its traffic file names. Off in a ``--trace 0`` run:
    every method is then a no-op."""

    def __init__(self, on: bool, keep: str | None = None) -> None:
        self.on = on
        self.keep = keep
        self.dir = None
        self.t_start = self.t_stop = None
        self._stack = contextlib.ExitStack()

    def start(self) -> None:
        if not self.on or self.t_start is not None:
            return
        import jax

        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(self.dir)
        self._stack.enter_context(span("bench:window"))
        self.t_start = time.perf_counter()

    def stop(self) -> None:
        if not self.on or self.t_start is None or self.t_stop is not None:
            return
        import jax

        self.t_stop = time.perf_counter()
        self._stack.close()
        jax.profiler.stop_trace()

    def reduce(self) -> dict | None:
        """The trace's reduction; the trace's files are removed (a run
        writes little to disk), the ``.xplane.pb`` first copied to the
        directory that ``--keep-trace`` names, if it names one."""
        if self.dir is None or self.t_stop is None:
            return None
        from benchmark import trace_reduce

        try:
            path = trace_reduce.find_xplane(self.dir)
            if self.keep:
                os.makedirs(self.keep, exist_ok=True)
                shutil.copy(path, self.keep)
            return trace_reduce.reduce_file(path)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


class Gen2Clock:
    """The collector's generation-2 passes while it is entered, each with
    its pause: a full collection holds every thread of the program for about
    a tenth of a second (PERF.md section 2), one slow query of a window."""

    def __init__(self) -> None:
        self.pauses: list = []
        self._t0 = None

    def _note(self, phase: str, info: dict) -> None:
        if info["generation"] != 2:
            return
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.pauses.append(time.perf_counter() - self._t0)
            self._t0 = None

    def __enter__(self) -> "Gen2Clock":
        gc.callbacks.append(self._note)
        return self

    def __exit__(self, *exc) -> None:
        gc.callbacks.remove(self._note)


def latency_summary(records: list) -> dict:
    """Count, quartiles (``statistics.quantiles``, as the bound's spread is
    taken) and maximum of the window's latencies."""
    lat = sorted(r["t1"] - r["t0"] for r in records)
    if len(lat) < 2:            # no quartiles of fewer than two
        only = lat[0] if lat else None
        return {"n": len(lat), "median": only, "max": only}
    q1, median, q3 = statistics.quantiles(lat, n=4)
    return {"n": len(lat), "q1": q1, "median": median, "q3": q3, "max": lat[-1]}


def memory_peak_bytes(devs) -> int:
    """The peak on the fullest chip, as the backend reports it."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devs]
    return int(max(peaks))


def run_cell(cell: dict, seed: int, seconds: float, trace: bool, devs,
             t_start: float, keep_trace: str | None = None) -> dict:
    """Everything of a run after the look for a chip: set-up, window, the
    comparison, the metrics. Returns the result line as a dict."""
    from benchmark.compile_clock import CompileClock

    config, traffic = cell["config_file"], cell["traffic_file"]
    driver = load_module("drivers", config["driver"])
    clock = CompileClock()
    counters = None
    if trace:
        # patches ArrayImpl._value for the whole process: never on in the
        # run that reports the end-to-end metrics
        from auron_tpu.utils.profiling import EngineCounters

        counters = EngineCounters.install()
    tracer = Tracer(trace, keep_trace)
    kind = devs[0].device_kind
    peaks = chip_peaks(kind) if devs[0].platform == "tpu" else None

    state = driver.setup(config, traffic, seed, span=span, say=say)
    setup_compile = clock.take()
    syncs0 = counters.syncs if counters else 0
    batches0 = counters.batches if counters else 0
    setup_s = time.perf_counter() - t_start
    try:
        with Gen2Clock() as gen2:
            records, window_s = driver.window(state, seconds, tracer)
    finally:
        tracer.stop()
    window_compile = clock.take()
    syncs = counters.syncs - syncs0 if counters else None
    batches = counters.batches - batches0 if counters else None
    peak = memory_peak_bytes(devs)
    driver.finish(state)           # the program's state is freed here
    reduction = tracer.reduce()
    compared = driver.check(state, records, config["limits"])

    failed = sum(1 for r in records if not r["ok"])
    compared = {"failed": {"value": failed, "limit": config["limits"]["failed"]},
                **compared}
    correct = all(c["value"] <= c["limit"] for c in compared.values())

    say(setup={"seconds": setup_s, **setup_compile},
        window={"seconds": window_s, "requests": len(records),
                "compiles": window_compile, "host_syncs": syncs,
                "batches": batches},
        latency_s=latency_summary(records),
        gc2={"count": len(gen2.pauses), "seconds": sum(gen2.pauses)},
        hash_seed=os.environ.get("PYTHONHASHSEED", "random"))

    facts = {"records": records, "window_s": window_s, "trace": reduction,
             "traced": [tracer.t_start, tracer.t_stop],
             "compiles_in_window": window_compile["programs"],
             "host_syncs": syncs, "batches": batches, "peak_bytes": peak,
             "setup_s": setup_s, "peaks": peaks,
             "scan_bytes": state.get("scan_bytes", {})}
    metrics = {}
    for m in cell["per_layer"] if trace else cell["end_to_end"]:
        # a reader that finds nothing to read returns None: the metric is
        # then left out of the line, never reported as 0
        value = load_module("metrics", m["name"]).read(facts)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    device = {"platform": devs[0].platform, "kind": kind, "count": len(devs),
              "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": len(records), "failed": failed,
              "metrics": metrics, "device": device}
    if trace and reduction is not None:
        device["busy_s"] = reduction["busy_s"]
        device["window_s"] = reduction["window_s"]
        result["breakdown"] = {"device_ops": reduction["device_ops"],
                               "idle_gaps": reduction["idle_gaps"]}
    result["compared"] = compared
    return result
