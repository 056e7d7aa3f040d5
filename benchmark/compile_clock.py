"""Seconds JAX spent tracing, lowering and compiling, from ``jax.monitoring``.

A copy of ``chip_smoke.py``'s ``CompileClock``: trace/lower/compile seconds
(a fetch from the persistent cache counts as a compile, a short one), the
cache's hits and misses, the program count and the slowest programs.
``take()`` returns the totals since the last take, so the harness reads one
account for set-up and one for the window.
"""

from __future__ import annotations


class CompileClock:
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
        self._tot["programs"] = 0
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
