"""Kernels: the least HBM time of the hash maps' probes in the traced
sub-window over the probe program's device seconds there. Bound: memory.

What a probe row must read: its key planes once (query 93's two int64 keys,
``ss_item_sk`` and ``ss_ticket_number``, each with its validity) and one
slot of the map (a 64-bit mixed key and an int32 row: ``ops/hashmap.py``),
``probe_bytes``; the rows are those the program counted (``join_lookup_rows
["hash"]`` of ``obs.window_summary`` over the traced sub-window), the peak
HBM bytes/s ``peaks.json``'s. The device seconds are the ``device_ops`` of
the trace's reduction whose program is the unique probe's
(``PROBE_PROGRAM``), a ``while`` op left out because its body's ops carry
the same seconds (PERF.md section 3); they include the small key-list probe
of the join with ``reason``, which runs in the same program. None where the
program is not among the trace's top ten ops, no hash lookup ran in the
sub-window, or the program has no such counter.
"""

#: the program the driver's unique probe runs in (``exec/joins/core.py
#: _unique_probe_jit``), as the trace names its ops
PROBE_PROGRAM = "jit__unique_probe_jit/"


def probe_bytes(rows: int, key_planes: int = 2) -> int:
    """Bytes the hash probe of ``rows`` rows must read: ``key_planes``
    int64 keys and their validities, and one map slot a row."""
    return rows * (key_planes * (8 + 1) + 8 + 4)


def read(facts: dict):
    trace, (lo, hi) = facts["trace"], facts["traced"]
    if trace is None or lo is None or hi is None or facts["peaks"] is None:
        return None
    secs = sum(s for name, s in trace["device_ops"]
               if name.startswith(PROBE_PROGRAM) and "/_while" not in name)
    if secs <= 0:
        return None
    try:
        from auron_tpu import obs

        if obs.mode() == obs.MODE_OFF:
            return None
        summary = obs.window_summary(lo, hi)
    except (ImportError, AttributeError):
        return None
    if "join_hash_build" not in summary or not summary["complete"]:
        return None
    rows = summary["join_lookup_rows"].get("hash", 0)
    if rows <= 0:
        return None
    least_s = probe_bytes(rows) / facts["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / secs
