"""Per-operator metric tree.

Analog of the reference's MetricNode mirror between native and JVM
(native-engine/auron/src/metrics.rs:7-35 pushing into the engine's
SQLMetric registry, NativeHelper.scala:168-213): every operator owns a node
with named counters/nanos-timers; the tree mirrors the plan and is harvested
by the task runtime at finalize and handed to the host-engine bridge.
"""

from __future__ import annotations

import time
from contextlib import contextmanager

from auron_tpu import obs


class MetricNode:
    def __init__(self, name: str = "", children: list["MetricNode"] | None = None):
        self.name = name
        self.values: dict[str, int] = {}
        self.children: list[MetricNode] = children or []

    def child(self, i: int) -> "MetricNode":
        while len(self.children) <= i:
            self.children.append(MetricNode(f"{self.name}.{len(self.children)}"))
        return self.children[i]

    def add(self, metric: str, value: int) -> None:
        self.values[metric] = self.values.get(metric, 0) + int(value)

    def set(self, metric: str, value: int) -> None:
        self.values[metric] = int(value)

    #: metric-name suffixes that mean "wall nanos from timer()" — shared
    #: with the bench/perf_gate top_ops rollups so a newly named timer
    #: (e.g. merge_path_s) can't silently fall out of the time rankings.
    #: "elapsed_compute" predates the suffix convention and is matched by
    #: name (endswith makes that uniform).
    TIME_SUFFIXES = ("_time", "_nanos", "_s", "elapsed_compute")

    #: timers that run NESTED inside another timer above (merge_path_s
    #: ticks inside merge_time): rendered normally, but excluded from
    #: per-op time totals or their nanos would count twice
    NESTED_TIMERS = frozenset({"merge_path_s"})

    @staticmethod
    def op_seconds(metrics: dict) -> float:
        """Total timer seconds for one operator's metric dict — THE shared
        definition behind bench.py/perf_gate.py top_ops rankings (nested
        sub-timers excluded exactly once, here)."""
        return sum(
            v for m, v in metrics.items()
            if m.endswith(MetricNode.TIME_SUFFIXES)
            and m not in MetricNode.NESTED_TIMERS
        ) / 1e9

    @contextmanager
    def timer(self, metric: str, count: bool = False):
        """Accumulate wall nanos into ``metric`` (name it with a
        TIME_SUFFIXES suffix); with ``count`` also bump
        ``{metric}_n`` — hot loops use it so breakdowns can express
        per-batch multiplicities (sync-budget checks divide site counts by
        these), not just totals.

        The SAME dt is handed to the flight recorder (obs.note_op) as an
        ``op`` event: the recorder's per-operator segments and this
        metric tree are two renderings of one measurement. That
        measurement is DISPATCH time, and a timer that stays open across
        a ``yield`` also bills the consumer's time to the producer
        (docs/observability.md): where the host's time went is read from
        the regions, ``obs.window_summary``."""
        t0 = time.perf_counter_ns()
        try:
            yield
        finally:
            dt = time.perf_counter_ns() - t0
            self.add(metric, dt)
            if count:
                self.add(metric + "_n", 1)
            obs.note_op(self.name, metric, dt)

    def snapshot(self) -> dict:
        """Flatten to {name: {metric: value}, children: [...]} for the bridge.

        Tolerant of concurrent mutation: operator threads add()/child()
        while observers (httpsvc /metrics, /metrics.prom) snapshot a LIVE
        task's tree. The contract is "snapshot never raises": the
        retry-then-degrade guards the ``RuntimeError: dictionary changed
        size during iteration`` class of failure. (On today's CPython a
        C-level ``dict(d)`` copy of a str-keyed dict is GIL-atomic, so
        the retry is defense-in-depth — the contract must hold on
        interpreters/subclasses where the copy re-enters Python, not
        just on the current fast path.)"""
        vals = None
        for _ in range(1000):
            try:
                vals = dict(self.values)
                break
            except RuntimeError:
                continue
        if vals is None:  # pragma: no cover — 1000 straight collisions
            vals = {}
        # (list copies don't need the retry: concurrent child() appends
        # cannot raise during list(); the racing child is simply in or out)
        return {
            "name": self.name,
            "values": vals,
            "children": [c.snapshot() for c in list(self.children)],
        }

    def total(self, metric: str) -> int:
        return self.values.get(metric, 0) + sum(c.total(metric) for c in self.children)

    @staticmethod
    def flat_totals(snapshot: dict) -> dict[str, int]:
        """Per-metric totals across a snapshot() tree — the rollup shape
        the host engine's SQLMetric registry consumes (the JVM twin is
        NativeMetrics.flatTotals in jvm/.../NativeMetrics.scala; both
        sides must agree on this definition)."""
        out: dict[str, int] = {}

        def rec(node: dict) -> None:
            for k, v in node.get("values", {}).items():
                out[k] = out.get(k, 0) + int(v)
            for c in node.get("children", ()):
                rec(c)

        rec(snapshot)
        return out

    @staticmethod
    def accumulate_op_totals(snapshot: dict, into: dict) -> None:
        """Fold a snapshot() tree into a per-OPERATOR metric rollup (op
        name = node name with the per-instance ``.N`` suffix stripped) —
        THE shared walker behind the bench.py/perf_gate.py top_ops
        sections, kept next to op_seconds so a change to node naming or
        rollup shape can't make the two trajectories silently diverge."""

        def rec(node: dict) -> None:
            op = (node.get("name") or "<node>").split(".")[0]
            tot = into.setdefault(op, {})
            for k, v in node.get("values", {}).items():
                tot[k] = tot.get(k, 0) + int(v)
            for c in node.get("children", ()):
                rec(c)

        rec(snapshot)

    def render(self, indent: int = 0) -> str:
        """Human-readable metric tree (the engine-side analog of the
        reference's Spark-UI metric surfacing, auron-spark-ui)."""

        def fmt(k: str, v: int) -> str:
            if k.endswith(MetricNode.TIME_SUFFIXES):
                return f"{k}={v / 1e6:.1f}ms"
            return f"{k}={v}"

        vals = " ".join(fmt(k, v) for k, v in sorted(self.values.items()))
        lines = ["  " * indent + (self.name or "<node>") + (": " + vals if vals else "")]
        for c in self.children:
            lines.append(c.render(indent + 1))
        return "\n".join(lines)
