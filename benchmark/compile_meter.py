"""Counts JAX's own compile events (copied from chip_smoke.py's CompileMeter,
which is not imported): trace / lowering / backend-compile seconds and the
persistent cache's hits and misses between two `take()` calls, plus the bare
number of such events — the window must see none."""

from __future__ import annotations

import threading

_DUR = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "compile_s",
}
_EVT = {
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}
KEYS = (*_DUR.values(), *_EVT.values(), "events")


class CompileMeter:
    def __init__(self):
        import jax.monitoring as mon

        self._lock = threading.Lock()
        self._acc = dict.fromkeys(KEYS, 0)
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _add(self, key, amount) -> None:
        if key:
            with self._lock:
                self._acc[key] += amount
                self._acc["events"] += 1

    def _on_duration(self, event, duration, **_):
        self._add(_DUR.get(event), duration)

    def _on_event(self, event, **_):
        self._add(_EVT.get(event), 1)

    def take(self) -> dict:
        """Totals since the last take, over all threads."""
        with self._lock:
            out, self._acc = self._acc, dict.fromkeys(KEYS, 0)
        return out
