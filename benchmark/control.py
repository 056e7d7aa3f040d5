"""The control of a cell's comparison: it has to come out as not correct.

    python3 benchmark/control.py --workload <cell> --seeds 1 2 3

The control is the plain reference put in the program's place with money in
the lower precision that the cell's traffic file names (``control_money``),
where the configuration promises exact DECIMAL sums. Its answers go through
the same ``check`` as the window's answers. It needs no chip and the
benchmark's own runs do not run it; PERF.md section 2 gives the readings that
each limit was set from.

An answer that no lower precision moves (integers and strings, read from
columns that hold no money) states so in its traffic file: ``control_money``
is ``null``, ``control_why`` gives the reason, and ``control_halved`` names
the table of which the control drops every other row, a control that breaks
the guarantee that every row is read. ``compare.control_frames`` refuses the
null where a reference's answer holds money or where money in bfloat16 moves
it. Either control has to come out not correct.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def run_control(cell: dict, seed: int) -> dict:
    from benchmark import harness

    config, traffic = cell["config_file"], cell["traffic_file"]
    driver = harness.load_module("drivers", config["driver"])
    state, records = driver.control(config, traffic, seed)
    compared = driver.check(state, records, config["limits"])
    named = (f"money in {traffic['control_money']}"
             if traffic["control_money"] is not None
             else f"every other row of {traffic['control_halved']} dropped")
    return {"seed": seed, "control": named, "compared": compared,
            "correct": all(c["value"] <= c["limit"] for c in compared.values())}


def main() -> None:
    from benchmark import harness

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    cell = harness.load_cell(args.workload)
    if cell["traffic_file"]["control_money"] is None:
        print(f"control.py: no precision control for {args.workload}: "
              f"{cell['traffic_file']['control_why']}", file=sys.stderr)
    passed = []
    for seed in args.seeds:
        out = run_control(cell, seed)
        print(json.dumps(out), flush=True)
        passed.append(out["correct"])
    if any(passed):
        sys.exit("control.py: a control came out correct: the comparison "
                 "cannot tell it from the exact references")


if __name__ == "__main__":
    main()
