"""Device telemetry: XLA/runtime gauges + on-demand profiler capture.

The verification plane's health questions ("is the chip compiling mid-run?",
"is device memory growing?", "how deep is the dispatch queue?") have no
monitor-plane answer — the CSV lands after the run. This collector samples
them live into the metrics registry (core/metrics.py, plane "device"):

    handel_device_xla_compile_ct        jax.monitoring compile events
    handel_device_live_arrays           jax.live_arrays() count
    handel_device_live_array_bytes      total nbytes of live arrays
    handel_device_mem_bytes_in_use      runtime memory_stats (TPU; 0 on CPU)
    handel_device_dispatch_queue_depth  BatchVerifierService pending lane
    handel_device_inflight_launches     dispatched, verdicts not yet fetched
    handel_device_breaker_state         0 closed / 0.5 half-open / 1 open
    handel_device_mesh_lanes            latency-plane mesh lanes (+_available)
    handel_device_mesh_launches         launches that rode the whole mesh

jax is imported lazily and every sample degrades to 0.0 on a missing API —
a fake-scheme node (which must never import jax) can still register this
collector as long as no scrape arrives, and a CPU-only run scrapes zeros
for the TPU-only stats instead of erroring.

`profile(seconds)` is the `POST /debug/profile?seconds=N` handler: captures
a `jax.profiler` trace into the run's trace dir (reusing the `--trace-dir`
plumbing from the span flight recorder) and returns the capture directory.
"""

from __future__ import annotations

import os
import threading
import time

#: process-wide compile counters, fed by the jax.monitoring listeners
#: (registered at most once per process; listeners cannot be unregistered
#: individually, so the counters live at module scope, not per collector)
_compile_events = 0
_compile_secs = 0.0
_listener_registered = False
_listener_lock = threading.Lock()

#: one entry per backend (XLA) compilation — the mid-run-compile detector;
#: jax 0.4.x records it as a duration event
_COMPILE_EVENT = "backend_compile"


def _on_event(event: str, **kwargs) -> None:
    global _compile_events
    if _COMPILE_EVENT in event:
        _compile_events += 1


def _on_duration(event: str, duration_secs: float, **kwargs) -> None:
    global _compile_events, _compile_secs
    if _COMPILE_EVENT in event:
        _compile_events += 1
        _compile_secs += float(duration_secs)


def _ensure_listener() -> bool:
    """Register the compile listeners once (both forms: plain events and
    duration events — jax 0.4.x reports backend compiles as the latter);
    False if the monitoring API is unavailable in this jax build."""
    global _listener_registered
    with _listener_lock:
        if _listener_registered:
            return True
        try:
            from jax import monitoring

            monitoring.register_event_listener(_on_event)
            monitoring.register_event_duration_secs_listener(_on_duration)
            _listener_registered = True
            return True
        except Exception:
            return False


class DeviceTelemetry:
    """Reporter-shaped (`values()` / `gauge_keys()`) device-state sampler.

    service: the process's BatchVerifierService, or None (chip-less node).
    trace_dir: where `profile()` drops its capture ("" = a tmp-adjacent
    default under the current directory).
    """

    def __init__(self, service=None, trace_dir: str = ""):
        self.service = service
        self.trace_dir = trace_dir
        self.profile_captures = 0
        self._profiling = threading.Lock()
        _ensure_listener()

    # -- sampling ------------------------------------------------------------

    def _jax(self):
        """The already-imported jax module, or None. NEVER imports: a scrape
        must not be the thing that initializes a backend (and so takes the
        chip from the process that owns it)."""
        import sys

        return sys.modules.get("jax")

    def values(self) -> dict[str, float]:
        out = {
            "xlaCompileCt": float(_compile_events),
            "xlaCompileTimeMs": _compile_secs * 1000.0,
            "liveArrays": 0.0,
            "liveArrayBytes": 0.0,
            "memBytesInUse": 0.0,
            "dispatchQueueDepth": 0.0,
            "inflightLaunches": 0.0,
            "breakerState": 0.0,
            "deviceLanes": 0.0,
            "deviceLanesAvailable": 0.0,
            "meshLanes": 0.0,
            "meshLanesAvailable": 0.0,
            "meshLaunches": 0.0,
            "profileCaptures": float(self.profile_captures),
        }
        jax = self._jax()
        if jax is not None:
            try:
                live = jax.live_arrays()
                out["liveArrays"] = float(len(live))
                out["liveArrayBytes"] = float(
                    sum(getattr(a, "nbytes", 0) for a in live)
                )
            except Exception:
                pass
            try:
                stats = jax.local_devices()[0].memory_stats()
                if stats:
                    out["memBytesInUse"] = float(
                        stats.get("bytes_in_use", 0.0)
                    )
            except Exception:
                pass  # CPU backends have no memory_stats
        svc = self.service
        if svc is not None:
            out["dispatchQueueDepth"] = float(svc.queue_depth())
            # fleet-aware in-flight count (parallel/plane.py): sums every
            # lane's window, not just device 0's. Stub services without the
            # method fall back to the single _fetch_q.
            infl = getattr(svc, "inflight_launches", None)
            if callable(infl):
                out["inflightLaunches"] = float(infl())
            else:
                q = getattr(svc, "_fetch_q", None)
                out["inflightLaunches"] = (
                    float(q.qsize()) if q is not None else 0.0
                )
            out["breakerState"] = {
                "closed": 0.0, "half-open": 0.5, "open": 1.0
            }[svc.breaker.state]
            plane = getattr(svc, "plane", None)
            if plane is not None:
                out["deviceLanes"] = float(len(plane.lanes))
                out["deviceLanesAvailable"] = float(len(plane.allowed()))
                # latency plane (parallel/mesh_plane.py): mesh lane census
                # and whole-mesh launch count; getattr keeps pre-mesh stub
                # planes scrapeable
                mesh_lanes = getattr(plane, "mesh_lanes", None)
                if callable(mesh_lanes):
                    mesh = mesh_lanes()
                    out["meshLanes"] = float(len(mesh))
                    out["meshLanesAvailable"] = float(sum(
                        1 for l in mesh
                        if not l.draining and l.breaker.allow()
                    ))
                    out["meshLaunches"] = float(
                        sum(l.launches for l in mesh)
                    )
            else:
                out["deviceLanes"] = out["deviceLanesAvailable"] = 1.0
        return out

    def gauge_keys(self) -> set[str]:
        # everything here is point-in-time except the event/launch counters
        return {
            "liveArrays", "liveArrayBytes", "memBytesInUse",
            "dispatchQueueDepth", "inflightLaunches", "breakerState",
            "deviceLanes", "deviceLanesAvailable",
            "meshLanes", "meshLanesAvailable",
        }

    # -- profiler capture (POST /debug/profile) ------------------------------

    def profile(self, seconds: float) -> str:
        """Capture a jax.profiler trace for `seconds`; returns the capture
        dir. Raises on an unavailable profiler (the HTTP layer turns that
        into a 500/501, never a crash) and refuses concurrent captures."""
        jax = self._jax()
        if jax is None:
            raise RuntimeError("jax not initialized in this process")
        if not self._profiling.acquire(blocking=False):
            raise RuntimeError("a profile capture is already running")
        try:
            out = os.path.join(
                self.trace_dir or os.getcwd(),
                f"profile_{int(time.time())}",
            )
            os.makedirs(out, exist_ok=True)
            jax.profiler.start_trace(out)
            try:
                time.sleep(seconds)
            finally:
                jax.profiler.stop_trace()
            self.profile_captures += 1
            return out
        finally:
            self._profiling.release()

    def profiler(self):
        """The MetricsServer `profiler=` hook: seconds -> capture dir."""
        return self.profile
