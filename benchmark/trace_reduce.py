"""From a profiler trace (``.xplane.pb``) to device busy/idle seconds.

``jax.profiler.ProfileData`` reads the file with nothing but JAX: planes,
their lines, and events with a start and a duration in nanoseconds. On a TPU
v5e (PERF.md section 5, read by hand in PR 25) the device is the plane
``/device:TPU:<n>``; its line ``XLA Ops`` holds one event per executed
operation, and ``XLA Modules`` one per whole program, which spans its own
operations: only the ops line goes into the union, so nothing counts twice,
and nested events on that line (a ``while`` spans its body) are merged by the
union itself.

The traced sub-window is the benchmark's own ``bench:window`` span, written
with ``jax.profiler.TraceAnnotation`` on the host's plane; device and host
events share the trace's clock. Busy is the union of the device-op intervals
cut to that window, averaged over the device planes; an idle gap is a
stretch of the window in which no device op ran, and is labelled by the
``bench:`` span of the benchmark that overlaps it most.

    python benchmark/trace_reduce.py <file.xplane.pb>     # dump, to read by hand
"""

from __future__ import annotations

import bisect
import glob
import os
import sys

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench:"
WINDOW_SPAN = "bench:window"


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def union(intervals: list) -> list:
    """Sorted, merged ``[start, end)`` intervals."""
    out: list = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def clip(intervals: list, lo: float, hi: float) -> list:
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if min(e, hi) > max(s, lo)]


def gaps(busy: list, lo: float, hi: float) -> list:
    """The stretches of ``[lo, hi)`` that the merged ``busy`` leaves open."""
    out, at = [], lo
    for s, e in busy:
        if s > at:
            out.append([at, s])
        at = max(at, e)
    if hi > at:
        out.append([at, hi])
    return out


def label_gap(gap: list, spans: list) -> str:
    """The name of the span that overlaps ``gap`` most; ties go to the
    shortest span (the innermost)."""
    best, best_key = "unlabelled", (0.0, 0.0)
    for name, s, e in spans:
        ov = min(e, gap[1]) - max(s, gap[0])
        if ov <= 0:
            continue
        key = (ov, -(e - s))
        if key > best_key:
            best, best_key = name, key
    return best


def short_op(module: str, op: str) -> str:
    """``<program>/<op>``: the program's name without its fingerprint and
    the op's own name without the HLO text behind it."""
    return f"{module.split('(', 1)[0]}/{op.split(' = ', 1)[0].strip()}"[:160]


def name_ops(ops: list, modules: list) -> list:
    """Each op event named by the program whose event on the modules line
    holds its start."""
    modules = sorted(modules, key=lambda m: m[1])
    starts = [m[1] for m in modules]
    out = []
    for name, s, e in ops:
        i = bisect.bisect_right(starts, s) - 1
        inside = i >= 0 and s < modules[i][2]
        out.append((short_op(modules[i][0] if inside else "?", name), s, e))
    return out


def extract(profile) -> dict:
    """Plain lists out of a ``ProfileData``: per device plane the op events
    ``(name, start_ns, end_ns)``, and the benchmark's spans from every other
    plane."""
    devices, spans = {}, []
    for plane in profile.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            by_line = {line.name: [(ev.name, ev.start_ns,
                                    ev.start_ns + ev.duration_ns)
                                   for ev in line.events]
                       for line in plane.lines
                       if line.name in (OPS_LINE, MODULES_LINE)}
            if by_line.get(OPS_LINE):
                devices[plane.name] = name_ops(by_line[OPS_LINE],
                                               by_line.get(MODULES_LINE, []))
        else:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
    return {"devices": devices, "spans": spans}


def reduce_events(devices: dict, spans: list, top: int = 10) -> dict | None:
    """Busy and idle of the ``bench:window`` span. None where the trace holds
    no device plane with ops, or no window span: nothing to read."""
    windows = [(s, e) for name, s, e in spans if name == WINDOW_SPAN]
    if not windows or not any(devices.values()):
        return None
    lo, hi = min(s for s, _ in windows), max(e for _, e in windows)
    inner = [sp for sp in spans if sp[0] != WINDOW_SPAN]
    busy_ns, op_ns, gap_ns = [], {}, {}
    longest: list = []
    for evs in devices.values():
        merged = union(clip([[s, e] for _, s, e in evs], lo, hi))
        busy_ns.append(sum(e - s for s, e in merged))
        for name, s, e in evs:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                op_ns[name] = op_ns.get(name, 0) + d
        for g in gaps(merged, lo, hi):
            name = label_gap(g, inner)
            gap_ns[name] = gap_ns.get(name, 0) + (g[1] - g[0])
            longest.append((g[1] - g[0], name))
    n = len(devices)
    rank = lambda d: [[k, v / n / 1e9] for k, v in
                      sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy_ns) / n / 1e9,
        "n_devices": n,
        "device_ops": rank(op_ns),
        "idle_gaps": rank(gap_ns),
        "longest_gap_s": max(longest)[0] / 1e9 if longest else 0.0,
        "window_ns": [lo, hi],
    }


def reduce_file(path: str) -> dict | None:
    ex = extract(load(path))
    return reduce_events(ex["devices"], ex["spans"])


def dump(path: str, n_events: int = 6) -> str:
    """What a person reads before trusting the reduction: every plane, its
    lines, how many events each holds, their span and a few names."""
    out = []
    for plane in load(path).planes:
        out.append(f"plane {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            if not evs:
                out.append(f"  line {line.name!r}: 0 events")
                continue
            lo = min(e.start_ns for e in evs)
            hi = max(e.start_ns + e.duration_ns for e in evs)
            tot = sum(e.duration_ns for e in evs)
            out.append(f"  line {line.name!r}: {len(evs)} events, "
                       f"start {lo} ns, span {(hi - lo) / 1e9:.6f} s, "
                       f"summed {tot / 1e9:.6f} s")
            names: dict = {}
            for e in evs:
                names[e.name] = names.get(e.name, 0) + e.duration_ns
            for k, v in sorted(names.items(), key=lambda kv: -kv[1])[:n_events]:
                out.append(f"      {v / 1e9:10.6f} s  {k[:120]}")
    return "\n".join(out)


if __name__ == "__main__":
    print(dump(sys.argv[1]))
    print(reduce_file(sys.argv[1]))
