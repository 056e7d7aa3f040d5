"""Kernels: the least HBM time of the traced queries over the device's busy
seconds in the traced sub-window. Bound: memory.

The least time is the bytes of the columns each query's text must read once
(``SCAN_COLUMNS`` of its query file over the cell's own frames: a function of
schema and row counts, whatever plan the engine builds) over the chip's peak
HBM bytes/s (``peaks.json``). A query that lies partly inside the traced
sub-window counts by the share of its time that does.
"""


def read(facts: dict):
    trace, (lo, hi) = facts["trace"], facts["traced"]
    if trace is None or lo is None or hi is None or facts["peaks"] is None:
        return None
    if trace["busy_s"] <= 0:
        return None
    need = 0.0
    for r in facts["records"]:
        inside = min(r["t1"], hi) - max(r["t0"], lo)
        if r["ok"] and inside > 0:
            need += facts["scan_bytes"][r["name"]] * inside / (r["t1"] - r["t0"])
    if need <= 0:
        return None
    least_s = need / facts["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / trace["busy_s"]
