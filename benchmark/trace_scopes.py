"""By hand: what the program's own names say about a kept trace.

The program names its work twice on the profiler's clock (PERF.md section 3):
``jax.named_scope`` names inside the two hot programs (``auron.agg.*``,
``auron.probe.*``, ``auron.stage.*``, ``auron.shuffle.*``) reach the device
trace as the ``op_name`` path of each operation, and every span and host read
of the engine is a host region ``auron:<layer>:<name>``. From a trace kept with
``run.py --trace 1 --keep-trace <dir>`` this prints, for the ``bench:window``
span:

- device seconds by scope: each executed operation's self time (a ``while``
  spans its body: the body's ops are taken out of it) booked to the
  ``auron.`` component of its ``op_name``; an operation with no scope is booked
  under its program's name. A fusion takes the scope of its root, so a fusion
  that crosses scopes is booked to one of them: with a scope map (below) the
  table marks those fusions and names the scopes they cross;
- idle seconds by the innermost ``auron:`` (or ``bench:``) span over each
  instant of each gap of the device (a gap is cut where a span of any thread
  starts or ends; the shortest span over a piece names it), and the ten longest
  gaps with the host read (``auron:sync:``) that overlaps each most;
- seconds the host's threads spent inside jit dispatch calls (the runtime's own
  ``PjitFunction(<program>)`` regions): a dispatch that takes seconds is the
  runtime holding the enqueue while the device's queue is full, which no hook
  of the program sees.

    python benchmark/trace_scopes.py <file.xplane.pb> [scope_map.json] [--stats]

Where the scope comes from (found on the v5e, PR 26): an op event of a TPU
trace holds only its times; the ``op_name`` path is the stat ``tf_op`` of the
event's METADATA entry, which ``jax.profiler.ProfileData`` does not hand out,
so ``metadata_stats`` reads it from the file's bytes (a few lines of protobuf
wire format, nothing but the standard library). It is the path the program was
COMPILED with: an executable fetched from the persistent compile cache carries
the names of the commit that compiled it (metadata is not in the cache's key),
so take the trace with ``JAX_COMPILATION_CACHE_DIR`` set to an empty directory
when the names have changed. ``--stats`` prints a few events' stats. A backend
whose events hold only ``hlo_module`` and ``hlo_op`` (the CPU's) needs the map
``{"<program>/<hlo_op>": {"scope": ..., "crosses": [...]}}`` that
``scope_map(text, program)`` builds from a compiled program's text
(``.lower(...).compile().as_text()``). The harness does not call this file.
"""

from __future__ import annotations

import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.trace_reduce import (DEVICE_PREFIX, MODULES_LINE,  # noqa: E402
                                    OPS_LINE, WINDOW_SPAN, clip, gaps,
                                    label_gap, name_ops, union)

SCOPE = "auron."
REGION = "auron:"
SPAN_PREFIXES = (REGION, "bench:")
SYNC = "auron:sync:"
DISPATCH = "PjitFunction("
OP_NAME_STAT = "tf_op"


def scope_of(op_name: str) -> str | None:
    """``jit(f)/jit(main)/auron.agg.sort/sort`` -> ``auron.agg.sort``: the
    innermost component that starts with ``auron.``."""
    parts = [p for p in op_name.split("/") if p.startswith(SCOPE)]
    return parts[-1] if parts else None


def scope_map(text: str, program: str) -> dict:
    """From a compiled program's HLO text: ``{"<program>/<instruction>":
    {"scope": the scope of its own op_name, "crosses": the other scopes of
    the instructions it fuses}}`` for every instruction with a name."""
    inside: dict[str, set] = {}      # computation -> scopes of its instructions
    calls: dict[str, str] = {}       # instruction -> the computation it calls
    own: dict[str, str | None] = {}
    comp = None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\{\s*$", line)
        if head:
            comp = head.group(1)
            inside[comp] = set()
            continue
        m = re.match(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s*=", line)
        if not m or comp is None:
            continue
        name = re.search(r'op_name="([^"]*)"', line)
        scope = scope_of(name.group(1)) if name else None
        if scope:
            inside[comp].add(scope)
        own[m.group(1)] = scope
        called = re.search(r"calls=%?([\w.\-]+)", line)
        if called:
            calls[m.group(1)] = called.group(1)
    out = {}
    for instr, scope in own.items():
        fused = inside.get(calls.get(instr, ""), set())
        if scope is None and len(fused) == 1:
            scope = next(iter(fused))
        out[f"{program}/{instr}"] = {"scope": scope,
                                     "crosses": sorted(fused - {scope})}
    return out


def _fields(buf: bytes):
    """``(field number, value)`` of one protobuf message: varints as ints,
    length-delimited fields as bytes; fixed-width fields are skipped."""
    i, n = 0, len(buf)

    def varint() -> int:
        nonlocal i
        value = shift = 0
        while True:
            b = buf[i]
            i += 1
            value |= (b & 0x7F) << shift
            shift += 7
            if not b & 0x80:
                return value

    while i < n:
        key = varint()
        field, wire = key >> 3, key & 7
        if wire == 0:
            yield field, varint()
        elif wire == 2:
            size = varint()
            yield field, buf[i:i + size]
            i += size
        else:
            i += 8 if wire == 1 else 4


def metadata_stats(data: bytes, stat: str = OP_NAME_STAT) -> dict:
    """``{plane name: {event metadata name: value of the stat}}`` out of a
    serialized XSpace: XSpace.planes=1; XPlane.name=2, .event_metadata=4 and
    .stat_metadata=5 (map entries: key=1, value=2); XEventMetadata.name=2,
    .stats=5; XStat.metadata_id=1, .str_value=5, .ref_value=7 (the id of a
    stat-metadata entry whose name is the string); XStatMetadata.name=2."""
    out = {}
    for field, plane in _fields(data):
        if field != 1:
            continue
        name, events, stat_names = "", [], {}
        for f, v in _fields(plane):
            if f == 2:
                name = v.decode()
            elif f == 4:
                events.append(dict(_fields(v))[2])
            elif f == 5:
                entry = dict(_fields(v))
                stat_names[entry[1]] = dict(_fields(entry[2])).get(2, b"").decode()
        found = {}
        for ev in events:
            ev_name = None
            for f, v in _fields(ev):
                if f == 2:
                    ev_name = v.decode()
                elif f == 5:
                    st = dict(_fields(v))
                    if stat_names.get(st.get(1)) != stat:
                        continue
                    if 5 in st:
                        found[ev_name] = st[5].decode()
                    elif 7 in st:
                        found[ev_name] = stat_names.get(st[7], "")
        if found:
            out[name] = found
    return out


def region_name(name: str) -> str:
    """A region's name without the ``#key=value,...#`` arguments that the
    profiler may leave on it."""
    return name.split("#", 1)[0]


def extract(profile, op_names: dict | None = None) -> dict:
    """Per device plane the op events ``(program/op, start_ns, end_ns,
    scope)``, the host's ``auron:`` and ``bench:`` spans, and the runtime's
    dispatch regions by thread. ``op_names`` is ``metadata_stats`` of the same file."""
    devices, spans, dispatch = {}, [], {}
    for plane in profile.planes:
        names = (op_names or {}).get(plane.name, {})
        if plane.name.startswith(DEVICE_PREFIX):
            lines = {ln.name: list(ln.events) for ln in plane.lines
                     if ln.name in (OPS_LINE, MODULES_LINE)}
            ops = lines.get(OPS_LINE, [])
            if not ops:
                continue
            mods = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in lines.get(MODULES_LINE, [])]
            named = name_ops([(e.name, e.start_ns, e.start_ns + e.duration_ns)
                              for e in ops], mods)
            devices[plane.name] = [
                (n, s, e, scope_of(names.get(ev.name, "")))
                for (n, s, e), ev in zip(named, ops)]
        else:
            for i, line in enumerate(plane.lines):     # one line per thread
                for ev in line.events:
                    span = (region_name(ev.name), ev.start_ns,
                            ev.start_ns + ev.duration_ns)
                    if ev.name.startswith(SPAN_PREFIXES):
                        spans.append(span)
                    elif ev.name.startswith(DISPATCH):
                        dispatch.setdefault((plane.name, i), []).append(span)
    return {"devices": devices, "spans": spans, "dispatch": dispatch}


def self_ns(events: list, lo: float, hi: float) -> list:
    """``(event, self nanoseconds inside [lo, hi))``: each event's clipped
    duration minus what the events nested directly in it cover; the
    program's own stack pass (``auron_tpu.obs.export.self_ns``) over the
    events ``(name, start, end, ...)`` of one line of the trace."""
    from auron_tpu.obs.export import self_ns as nested

    clipped = [(max(ev[1], lo), min(ev[2], hi), ev) for ev in events
               if min(ev[2], hi) > max(ev[1], lo)]
    return [(reg[2], ns) for reg, ns in nested(clipped)]


def label_pieces(gap: list, spans: list) -> list:
    """``[(label, ns), ...]`` for one gap, cut wherever a span starts or ends
    inside it; each piece is named by the shortest span over it (the
    innermost, whichever thread it runs on), or ``unlabelled``."""
    over = [sp for sp in spans if sp[1] < gap[1] and sp[2] > gap[0]]
    cuts = sorted({gap[0], gap[1]}
                  | {t for _, s, e in over for t in (s, e) if gap[0] < t < gap[1]})
    out = []
    for a, b in zip(cuts, cuts[1:]):
        covering = [(e - s, name) for name, s, e in over if s <= a and e >= b]
        out.append((min(covering)[1] if covering else "unlabelled", b - a))
    return out


def tables(devices: dict, spans: list, scopes: dict | None = None,
           dispatch: dict | None = None, top: int = 10) -> dict | None:
    """The tables of the ``bench:window`` span, device seconds averaged over
    the device planes. None where there is no window span or no device op."""
    scopes = scopes or {}
    windows = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if not windows or not any(devices.values()):
        return None
    lo, hi = min(s for s, _ in windows), max(e for _, e in windows)
    inner = [sp for sp in spans if sp[0] != WINDOW_SPAN]
    syncs = [sp for sp in inner if sp[0].startswith(SYNC)]
    n = len(devices)
    by_scope: dict = {}              # (program, scope or None) -> ns
    crossing: dict = {}              # program/op -> [scope, crosses, ns]
    idle: dict = {}
    longest = []
    for evs in devices.values():
        for (name, _s, _e, scope), ns in self_ns(evs, lo, hi):
            known = scopes.get(name, {})
            scope = scope or known.get("scope")
            program = name.split("/", 1)[0]
            by_scope[(program, scope)] = by_scope.get((program, scope), 0) + ns
            if known.get("crosses"):
                ent = crossing.setdefault(name, [scope, known["crosses"], 0])
                ent[2] += ns
        busy = union(clip([[s, e] for _, s, e, _ in evs], lo, hi))
        for g in gaps(busy, lo, hi):
            within: dict = {}
            for label, ns in label_pieces(g, inner):
                within[label] = within.get(label, 0) + ns
                idle[label] = idle.get(label, 0) + ns
            longest.append((g[1] - g[0], max(within, key=within.get),
                            label_gap(g, syncs)))
    programs: dict = {}
    for (program, scope), ns in by_scope.items():
        ent = programs.setdefault(program, {"total_s": 0.0, "scoped_s": 0.0,
                                            "scopes": {}})
        ent["total_s"] += ns / n / 1e9
        if scope:
            ent["scoped_s"] += ns / n / 1e9
        ent["scopes"][scope or "(no scope)"] = ns / n / 1e9
    idle_s = {k: v / n / 1e9 for k, v in idle.items()}
    total_idle = sum(idle_s.values())
    dispatch_s: dict = {}        # self time: a dispatch may nest in another
    for thread in (dispatch or {}).values():
        for (name, _s, _e), ns in self_ns(thread, lo, hi):
            dispatch_s[name] = dispatch_s.get(name, 0.0) + ns / 1e9
    return {
        "window_s": (hi - lo) / 1e9,
        "programs": programs,
        "crossing": sorted(([k, sc, cr, ns / n / 1e9]
                            for k, (sc, cr, ns) in crossing.items()),
                           key=lambda x: -x[3])[:top],
        "idle_s": idle_s,
        "idle_under_auron_share": (
            sum(v for k, v in idle_s.items() if k.startswith(REGION))
            / total_idle if total_idle else None),
        "longest_gaps": [[d / 1e9, label, sync]
                         for d, label, sync in sorted(longest, reverse=True)[:top]],
        "dispatch_s": sorted(dispatch_s.items(), key=lambda kv: -kv[1])[:top],
    }


def render(t: dict) -> str:
    out = [f"bench:window {t['window_s']:.6f} s", "",
           "device seconds by scope (self time of each op):"]
    for program, ent in sorted(t["programs"].items(),
                               key=lambda kv: -kv[1]["total_s"]):
        share = 100.0 * ent["scoped_s"] / ent["total_s"] if ent["total_s"] else 0.0
        out.append(f"  {program}: {ent['total_s']:.6f} s, "
                   f"{share:.1f} % under an auron. scope")
        for scope, s in sorted(ent["scopes"].items(), key=lambda kv: -kv[1]):
            out.append(f"      {s:12.6f} s  {scope}")
    if t["crossing"]:
        out += ["", "fusions that cross scopes (booked to the first):"]
        for name, scope, crosses, s in t["crossing"]:
            out.append(f"  {s:12.6f} s  {name}: {scope} + {', '.join(crosses)}")
    out += ["", "idle seconds by the innermost host span over each gap:"]
    for label, s in sorted(t["idle_s"].items(), key=lambda kv: -kv[1]):
        out.append(f"  {s:12.6f} s  {label}")
    if t["idle_under_auron_share"] is not None:
        out.append(f"  under an auron: span: "
                   f"{100.0 * t['idle_under_auron_share']:.1f} %")
    out += ["", "longest gaps (seconds, span over most of it, host read):"]
    for d, label, sync in t["longest_gaps"]:
        out.append(f"  {d:12.6f} s  {label}  [{sync}]")
    if t["dispatch_s"]:
        out += ["", "thread-seconds inside jit dispatch calls, by program:"]
        for name, secs in t["dispatch_s"]:
            out.append(f"  {secs:12.6f} s  {name}")
    return "\n".join(out)


def dump_stats(profile, op_names: dict, n_events: int = 3) -> str:
    """The stats of the longest device-op events (with the ``op_name`` path
    of their metadata) and of a few ``auron:`` regions: where the scope and a
    region's arguments are to be found."""
    out = []
    for plane in profile.planes:
        for line in plane.lines:
            if plane.name.startswith(DEVICE_PREFIX):
                if line.name != OPS_LINE:
                    continue
                evs = sorted(line.events, key=lambda e: -e.duration_ns)
            else:
                evs = [e for e in line.events if e.name.startswith(REGION)]
            for ev in evs[:n_events]:
                out.append(f"{plane.name} / {line.name} / {ev.name[:100]}")
                for k, v in dict(ev.stats).items():
                    out.append(f"      {k} = {str(v)[:300]}")
                path = op_names.get(plane.name, {}).get(ev.name)
                if path is not None:
                    out.append(f"      metadata {OP_NAME_STAT} = {path[:300]}")
    return "\n".join(out)


def main(argv: list) -> None:
    from jax.profiler import ProfileData

    args = [a for a in argv if not a.startswith("--")]
    with open(args[0], "rb") as f:
        data = f.read()
    profile = ProfileData.from_serialized_xspace(data)
    op_names = metadata_stats(data)
    if "--stats" in argv:
        print(dump_stats(profile, op_names))
    scopes = None
    if len(args) > 1:
        with open(args[1]) as f:
            scopes = json.load(f)
    ex = extract(profile, op_names)
    t = tables(ex["devices"], ex["spans"], scopes, ex["dispatch"])
    print(render(t) if t else "no bench:window span or no device op: nothing to read")


if __name__ == "__main__":
    main(sys.argv[1:])
