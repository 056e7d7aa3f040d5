"""The benchmark's one command: one cell, one process, one result line.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads the cell's files by the names in ``BENCHMARK.json``, makes its data from
``--seed``, warms the cell's own shapes (all of that is ``setup_s``), measures
for ``--seconds``, reads the device's peak memory, frees the program's state,
compares what the window produced with the plain reference, and prints the
result as the last line of standard output. ``--trace 0`` reports the cell's
end-to-end metrics; ``--trace 1`` switches on the program's counters and a
profiler trace of a steady sub-window and reports the per-layer metrics.

It runs on the machine it is started on, fails non-zero and without a result
line where JAX gives it no TPU or fewer chips than the cell asks for, and
takes no notice of ``BENCH_RUN``. README.md says how the files fit together.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", metavar="DIR", default=None,
                    help="with --trace 1: keep the profiler's .xplane.pb in DIR "
                         "(to read by hand, or with trace_reduce.py)")
    args = ap.parse_args()

    from benchmark import harness

    cell = harness.load_cell(args.workload)
    import auron_tpu  # noqa: F401  (x64 and the compile cache, before any backend use)

    devs = harness.require_tpu(cell["chips"])
    result = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                              devs, T_START, args.keep_trace)
    for name, c in result["compared"].items():
        print(f"compared {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
