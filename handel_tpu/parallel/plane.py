"""Fleet-of-chips device plane: K devices behind one verifier service.

ROADMAP item 2 ("standing ceiling"): every service launch used to land on
one chip, so the 8-device mesh kernels compiled by the MULTICHIP gate were
never fed by a real dispatch path. A `DevicePlane` owns K device engines —
real mesh chips, or host devices forced via
`XLA_FLAGS=--xla_force_host_platform_device_count=8` so the whole plane is
testable on a CPU-only CI box — and gives each one a `DeviceLane`: its own
dispatch hand-off cell, in-flight fetch window, circuit breaker, and
occupancy counters. `BatchVerifierService` schedules launch groups onto
lanes least-loaded-first, so fetch latency on one chip never idles the
others; a lane whose breaker opens simply stops receiving work until its
cooldown probe succeeds (degrade to K-1 chips, not to zero).

The plane is also the fleet's reporter surface: `values()` sums the
per-engine host pack/dispatch costs (the service used to read them off
device 0 only), and `labeled_values()` exposes one row per device for the
`device`-labeled metrics dimension beside `session`
(`handel_device_verifier_launches{device="3"}`).

This module must import neither jax nor the service driver at module level
— fake-crypto simulations construct planes of host stubs in processes that
never touch jax. The jax-backed builder (`scheme_plane`) imports lazily.
"""

from __future__ import annotations

import asyncio

from handel_tpu.core.trace import LAUNCH_CLASSES, LAUNCH_STAGES
from handel_tpu.utils.breaker import CircuitBreaker

__all__ = [
    "DeviceLane", "DevicePlane", "bn254_plane", "host_plane", "scheme_plane",
]

#: breaker state -> exposition value (shared with BatchVerifierService)
BREAKER_CODE = {"closed": 0.0, "half-open": 0.5, "open": 1.0}


class DeviceLane:
    """One chip of the plane: an engine plus everything the scheduler needs
    to route around it — hand-off cell, in-flight window, breaker, and
    per-device counters. The asyncio queues are created by the service at
    start() (they must bind to its event loop) and torn down at stop().

    `dispatching` holds the launch group from the moment the scheduler
    hands it to this lane until its handle reaches `fetch_q` (or it fails
    over): while set, the lane's dispatch slot is occupied AND stop() can
    fail the group's futures. `fetching` mirrors it for the fetch stage.
    """

    __slots__ = (
        "index", "engine", "breaker", "q", "fetch_q", "dispatching",
        "fetching", "launches", "candidates", "fill_sum", "last_fill",
        "retries", "fetched", "queued_ts", "draining", "tasks", "mesh",
    )

    def __init__(self, index: int, engine, breaker: CircuitBreaker | None = None,
                 mesh: bool = False):
        self.index = index
        self.engine = engine
        self.breaker = breaker or CircuitBreaker()
        # latency plane (parallel/mesh_plane.py): a mesh lane's engine
        # spans the WHOLE device mesh for one launch. pick() skips it —
        # only latency-mode groups routed via pick_mesh() land here.
        self.mesh = mesh
        self.q: asyncio.Queue | None = None
        self.fetch_q: asyncio.Queue | None = None
        self.dispatching: list | None = None
        self.fetching: list | None = None
        self.launches = 0
        self.candidates = 0
        self.fill_sum = 0.0
        self.last_fill = 0.0
        self.retries = 0
        self.fetched = 0
        # trace stamp: when the launch group currently in `q` was handed to
        # this lane (the launch_queued span's start, batch_verifier.py)
        self.queued_ts = 0.0
        # elasticity (lifecycle/autoscaler.py): a draining lane finishes
        # its in-flight launches but the scheduler stops routing to it —
        # the graceful half of drain_lane/remove_lane
        self.draining = False
        # the lane's dispatcher/fetcher task pair while the service runs
        # (BatchVerifierService start()/attach_lane(); drain cancels them)
        self.tasks: tuple = ()

    @property
    def trace_tid(self) -> int:
        """Chrome-trace thread id for this lane's launch-lifecycle spans:
        negative ids keep lanes clear of node tids, below SERVICE_TID."""
        return -(2 + self.index)

    def free(self) -> bool:
        """Can accept a launch group right now (dispatch slot empty)."""
        return self.dispatching is None

    def inflight(self) -> int:
        """Launches dispatched to the device whose verdicts haven't landed."""
        n = 1 if self.fetching is not None else 0
        if self.fetch_q is not None:
            n += self.fetch_q.qsize()
        return n

    def load(self) -> int:
        """Launches this lane is responsible for right now — the scheduling
        key: queued/dispatching + awaiting fetch."""
        return (1 if self.dispatching is not None else 0) + self.inflight()

    def values(self) -> dict[str, float]:
        """One `device`-labeled metrics row."""
        st = getattr(self.engine, "rlc_stats", None)
        return {
            # scheduling mode of this row: 1 = whole-mesh latency lane,
            # 0 = per-chip throughput lane (`sim watch` mode column)
            "mode": 1.0 if self.mesh else 0.0,
            # batch-check mode of the engine (models/rlc.py): 1 = rlc
            # combined check, 0 = per-candidate (`sim watch` check column)
            "checkMode": (
                1.0 if getattr(self.engine, "batch_check", "per_candidate")
                == "rlc" else 0.0
            ),
            # RLC plane: top-level combined checks, post-failure bisection
            # rechecks, deepest recheck level this engine ever reached
            "rlcLaunches": float(st.rlc_launches) if st else 0.0,
            "bisectionCt": float(st.bisection_ct) if st else 0.0,
            "bisectionDepthMax": float(st.bisection_depth_max) if st else 0.0,
            "launches": float(self.launches),
            "candidates": float(self.candidates),
            "fillRatio": (
                self.fill_sum / self.launches if self.launches else 0.0
            ),
            "lastFill": self.last_fill,
            "inflight": float(self.inflight()),
            "load": float(self.load()),
            "retries": float(self.retries),
            "breakerState": BREAKER_CODE[self.breaker.state],
            "breakerOpenCt": float(self.breaker.open_count),
        }


class DevicePlane:
    """K `DeviceLane`s and the least-loaded-first pick over them.

    `pick()` returns the least-loaded FREE lane among those whose breaker
    admits work, or None when every admissible lane is occupied (the
    caller waits) — so an idle chip is always preferred over queueing
    behind a busy one. `sched_picks`/`idle_violations` audit exactly the
    acceptance property "no device idles while another has ≥ 2 queued
    launches": a violation is counted iff an idle admissible lane existed,
    some lane carried ≥ 2 launches, and the pick was NOT idle — impossible
    under min-load, so tests/test_plane.py asserts the counter stays 0.
    """

    def __init__(self, engines, breakers=None):
        engines = list(engines)
        if not engines:
            raise ValueError("DevicePlane needs at least one device engine")
        if breakers is not None and len(breakers) != len(engines):
            raise ValueError("breakers must match engines 1:1")
        self.lanes = [
            DeviceLane(i, eng, breakers[i] if breakers else None)
            for i, eng in enumerate(engines)
        ]
        self.sched_picks = 0
        self.idle_violations = 0
        # elasticity counters (lifecycle/autoscaler.py) + a monotonically
        # increasing index source so a replacement lane never reuses a
        # retired lane's metrics row / trace thread
        self._next_index = len(self.lanes)
        self.lanes_added = 0
        self.lanes_removed = 0
        # dual-mode scheduling audit (parallel/mesh_plane.py): latency-mode
        # picks taken off the mesh lane(s)
        self.mesh_picks = 0

    def __len__(self) -> int:
        return len(self.lanes)

    def _throughput_engine(self):
        """The engine that speaks for the THROUGHPUT lanes (they serve one
        registry at one shape): the first that is no mesh lane's."""
        for l in self.lanes:
            if not l.mesh:
                return l.engine
        return self.lanes[0].engine

    @property
    def batch_size(self) -> int:
        # the THROUGHPUT batch width: a mesh lane's engine is typically a
        # small-batch shape and must not set the collector's drain size
        return self._throughput_engine().batch_size

    def add_lane(self, engine, breaker: CircuitBreaker | None = None,
                 mesh: bool = False) -> DeviceLane:
        """Grow the plane by one lane (verify-plane elasticity, or a
        latency-plane mesh lane when `mesh=True`). The caller
        (BatchVerifierService.attach_lane) wires the asyncio plumbing; a
        bare plane user just gets a new schedulable lane."""
        lane = DeviceLane(self._next_index, engine, breaker, mesh=mesh)
        self._next_index += 1
        self.lanes.append(lane)
        self.lanes_added += 1
        return lane

    def remove_lane(self, lane: DeviceLane) -> None:
        """Retire one lane. The last lane is irremovable — a plane with no
        engine cannot serve, and `batch_size`/`device` aliases would
        dangle. Likewise the last THROUGHPUT lane while mesh lanes remain:
        bulk groups don't fit a small-batch mesh engine, so a mesh-only
        plane (unless built that way outright) cannot serve them."""
        others = [l for l in self.lanes if l is not lane]
        if not others:
            raise ValueError("cannot remove the last lane of a DevicePlane")
        if not lane.mesh and all(l.mesh for l in others):
            raise ValueError(
                "cannot remove the last throughput lane of a DevicePlane"
            )
        self.lanes.remove(lane)
        self.lanes_removed += 1

    def allowed(self) -> list[DeviceLane]:
        """Lanes whose breaker currently admits launches (a draining lane
        admits nothing — it only finishes what it already carries)."""
        return [l for l in self.lanes if not l.draining and l.breaker.allow()]

    def throughput_pool(self) -> list[DeviceLane]:
        """Admissible lanes a THROUGHPUT pick may return: the non-mesh
        lanes. A plane built purely of mesh lanes (degenerate, but must not
        deadlock the collector) falls back to the whole admissible set —
        there a "bulk" group is whatever fits the mesh engine."""
        allowed = self.allowed()
        if any(not l.mesh for l in self.lanes):
            return [l for l in allowed if not l.mesh]
        return allowed

    def mesh_lanes(self) -> list[DeviceLane]:
        return [l for l in self.lanes if l.mesh]

    def pick(self) -> DeviceLane | None:
        """Least-loaded free admissible THROUGHPUT lane; None when none is
        free. Mesh lanes are never returned here — only latency-mode
        groups, routed via `pick_mesh`, may occupy the whole mesh."""
        pool = self.throughput_pool()
        free = [l for l in pool if l.free()]
        if not free:
            return None
        lane = min(free, key=lambda l: (l.load(), l.index))
        self.sched_picks += 1
        if (
            lane.load() > 0
            and any(l.load() == 0 for l in pool)
            and any(l.load() >= 2 for l in self.lanes if not l.mesh)
        ):
            self.idle_violations += 1
        return lane

    def pick_mesh(self) -> DeviceLane | None:
        """Free admissible mesh lane for a latency-mode group (least-loaded
        when several), or None — the caller falls back to the throughput
        path and counts a mesh fallback. A mesh lane whose breaker is open
        simply makes latency mode unavailable; it never fails the group."""
        free = [
            l for l in self.mesh_lanes()
            if not l.draining and l.breaker.allow() and l.free()
        ]
        if not free:
            return None
        lane = min(free, key=lambda l: (l.load(), l.index))
        self.mesh_picks += 1
        return lane

    def inflight_launches(self) -> int:
        return sum(l.inflight() for l in self.lanes)

    def launch_class(self, bitsets) -> list[int]:
        """The launch class each candidate needs, as the throughput lanes'
        engine names it (models/bn254_jax.py `launch_class`). An engine
        without classes (the host stubs) has no such method: one class for
        everything, and the service's planning by class is the identity."""
        classify = getattr(self._throughput_engine(), "launch_class", None)
        return classify(bitsets) if classify else [0] * len(bitsets)

    def host_cost(self) -> dict:
        """Per-launch host accounting SUMMED over the fleet's engines (the
        service used to read the counters off device 0 only): the pack and
        dispatch totals any engine may carry, and `stage_ms`, the same path
        by stage, from engines with a stage clock (core/trace.py); and how
        often each launch class engaged (`class_launches`) with the wide
        class's patch slots and holes and the Miller loop's steps, executed
        additions and accumulator multiplications and the `agg` stage's
        multiplications, from engines that count them."""
        counts = ("patch_slots", "patch_holes", "miller_steps",
                  "miller_add_steps", "miller_acc_fp_muls", "agg_fp_muls")
        out = {"pack_ms": 0.0, "pack_launches": 0.0,
               "dispatch_ms": 0.0, "dispatch_launches": 0.0,
               "fetch_launches": 0.0, "pack_cpu_ms": 0.0,
               **dict.fromkeys(counts, 0.0)}
        stage_ms = dict.fromkeys(LAUNCH_STAGES, 0.0)
        classes = dict.fromkeys(LAUNCH_CLASSES, 0.0)
        for lane in self.lanes:
            eng = lane.engine
            for name, ct in getattr(eng, "class_launches", {}).items():
                classes[name] += ct
            for key in counts:
                out[key] += float(getattr(eng, key, 0.0))
            for key in ("pack_ms", "pack_launches", "dispatch_ms",
                        "dispatch_launches", "fetch_launches"):
                out[key] += float(getattr(eng, f"host_{key}", 0.0))
            clock = getattr(eng, "stage_clock", None)
            if clock is not None:
                for name, ms in clock.ms.items():
                    stage_ms[name] += ms
                out["pack_cpu_ms"] += clock.cpu_ms["pack"]
        out["stage_ms"] = stage_ms
        out["class_launches"] = classes
        return out

    def values(self) -> dict[str, float]:
        """Fleet aggregates (folded into the service's values())."""
        mesh = self.mesh_lanes()
        stats = [
            st for l in self.lanes
            if (st := getattr(l.engine, "rlc_stats", None)) is not None
        ]
        return {
            # RLC batch-check plane (models/rlc.py): counters SUM over the
            # fleet, the depth high-water mark is a MAX (a per-engine
            # maximum summed across lanes would mean nothing)
            "rlcLaunches": float(sum(s.rlc_launches for s in stats)),
            "bisectionCt": float(sum(s.bisection_ct for s in stats)),
            "bisectionDepthMax": float(max(
                (s.bisection_depth_max for s in stats), default=0
            )),
            "checkMode": (
                1.0 if any(
                    getattr(l.engine, "batch_check", "per_candidate") == "rlc"
                    for l in self.lanes
                ) else 0.0
            ),
            "devicesTotal": float(len(self.lanes)),
            "devicesAvailable": float(len(self.allowed())),
            # which base field the engines compute in: 16-bit limbs of its
            # modulus (16 = BN254, 24 = BLS12-381; 0 for host stubs), so a
            # reader of the counters need not parse kernel names
            "fieldLimbs": float(max(
                (getattr(l.engine, "field_limbs", 0) for l in self.lanes),
                default=0,
            )),
            # ... how many lanes a pass of its multiplication kernel's body
            # computes and how many sublanes of a register a limb row fills
            # there (ops/fp.py `mul_tile`; 0 for host stubs)
            "fpMulStepLanes": float(max(
                (getattr(l.engine, "fp_mul_step_lanes", 0) for l in self.lanes),
                default=0,
            )),
            "fpMulRowSublanes": float(max(
                (getattr(l.engine, "fp_mul_row_sublanes", 0) for l in self.lanes),
                default=0,
            )),
            # ... and which group their registry keys live in (1 or 2:
            # models/bn254_jax.py `key_group`; 0 for host stubs)
            "keyGroup": float(max(
                (getattr(l.engine, "key_group", 0) for l in self.lanes),
                default=0,
            )),
            # executables the lanes' shared launch programs hold, by how
            # a chip came by them (models/bn254_jax.py `PlanePrograms`):
            # compiled there (or read from the compile cache), or loaded
            # from another chip's; 0 for host stubs and unpinned engines
            **self._program_counts(),
            "schedPicks": float(self.sched_picks),
            "schedIdleViolations": float(self.idle_violations),
            "lanesAdded": float(self.lanes_added),
            "lanesRemoved": float(self.lanes_removed),
            # latency plane (parallel/mesh_plane.py): mesh lane census +
            # the launches that actually rode the whole mesh
            "meshLanes": float(len(mesh)),
            "meshLanesAvailable": float(sum(
                1 for l in mesh if not l.draining and l.breaker.allow()
            )),
            "meshPicks": float(self.mesh_picks),
            "meshLaunches": float(sum(l.launches for l in mesh)),
        }

    def _program_counts(self) -> dict[str, float]:
        sets = {
            id(p): p for l in self.lanes
            if (p := getattr(l.engine, "programs", None)) is not None
        }.values()
        return {
            "programCompiles": float(sum(p.compiles.total() for p in sets)),
            "programLoads": float(sum(p.loads.total() for p in sets)),
        }

    def labeled_values(self) -> dict[str, dict[str, float]]:
        """Per-device rows for the `device` label dimension
        (core/metrics.py register_labeled_values(label="device"))."""
        return {str(l.index): l.values() for l in self.lanes}

    def labeled_gauge_keys(self) -> set[str]:
        return {
            "fillRatio", "lastFill", "inflight", "load", "breakerState",
            "mode",
        }


def host_plane(constructor, devices: int, batch_size: int = 64,
               launch_ms: float = 0.0,
               batch_check: str = "per_candidate") -> DevicePlane:
    """A plane of K host-math engines (service/driver.py HostDevice) — the
    CI shape: real scheduling + breakers, no kernels compiled."""
    from handel_tpu.service.driver import HostDevice

    return DevicePlane([
        HostDevice(constructor, batch_size=batch_size, launch_ms=launch_ms,
                   batch_check=batch_check)
        for _ in range(max(1, devices))
    ])


def scheme_plane(registry_pubkeys, devices: int, batch_size: int = 16, *,
                 scheme: str, **device_options) -> DevicePlane:
    """A plane of K engines of the device scheme `scheme` (a name of
    models/registry.py: `bn254-jax`, `bls12-381-jax`, `bls12-381-minpk-jax`),
    one pinned to each of the first K visible jax devices, with the
    scheme's own options (`device_options`: what its constructor takes). A
    configuration names this function as its `program.plane` and the scheme
    among its `device_options` (benchmark/README.md).

    The lanes serve one registry at one shape, so set-up does once what is
    the same on every chip: the keys are converted on the host once and
    the bank copied chip to chip, the prefix table is computed on the first
    chip and copied, and each launch class is traced once and, where the
    runtime lets a compiled program be loaded on another chip, lowered and
    compiled once (models/bn254_jax.py `plane_of`, `PlanePrograms`).
    Nothing is warmed here: a pairing class is minutes of compiling, and
    which classes a deployment reaches is its traffic's to say."""
    import jax

    from handel_tpu.models.registry import is_device_scheme, new_scheme

    if not is_device_scheme(scheme):
        raise ValueError(f"{scheme!r} is no device scheme: a plane pins "
                         "device engines to chips")
    devs = jax.devices()
    if devices > len(devs):
        raise ValueError(
            f"requested {devices} devices but only {len(devs)} visible "
            "(set XLA_FLAGS=--xla_force_host_platform_device_count=N)"
        )
    cons = new_scheme(
        scheme, batch_size=batch_size, warmup=False, **device_options
    ).constructor
    first = cons.new_device(registry_pubkeys, jax_device=devs[0])
    return DevicePlane([first] + [
        cons.new_device(registry_pubkeys, jax_device=dev, plane_of=first)
        for dev in devs[1:max(1, devices)]
    ])


def bn254_plane(registry_pubkeys, devices: int, batch_size: int = 16,
                **device_options) -> DevicePlane:
    """`scheme_plane` for `bn254-jax` (the smokes' plane, and the name the
    one-chip BN254 configurations carry)."""
    return scheme_plane(registry_pubkeys, devices, batch_size,
                        scheme="bn254-jax", **device_options)
