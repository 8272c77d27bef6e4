"""Mesh latency plane: one verify launch model-parallel over the whole mesh.

ROADMAP item 2: PR 9 sharded *across* launches — `bn254_plane` pins one
engine per chip and the DevicePlane schedules launch groups least-loaded —
but one Miller loop + final exponentiation still ran on a single chip, so a
small/urgent batch (the ACE "sub-second cryptographic finality" regime,
PAPERS.md arxiv 2603.10242) could never use more than 1/K of the mesh. This
module adds the second shape: a MESH LANE whose engine spans ALL K devices
for a single launch (`BN254Device(mesh_devices=K)` — registry axis of the
masked G2 sum and candidate axis of the Miller loop/final exp partitioned
with shard_map, parallel/sharding.py), plus the policy that decides, per
launch group, which shape it rides:

  * **latency** mode — the group is small enough to fit one mesh launch,
    the backlog is shallow, and its best SLO tier is entitled to the mesh
    (gold by default): route to the mesh lane, cutting the single-launch
    wall (not measured on chips).
  * **throughput** mode — bulk batches and backlogged queues: today's
    per-lane path, where the mesh is worth more as K independent lanes.

The scheduler integration lives in `DevicePlane.pick_mesh` (parallel/
plane.py) and `BatchVerifierService._route_mesh` (batch_verifier.py); this
module owns the policy, the engine builders, and the CI host engine.
Like plane.py, nothing here imports jax at module level — the jax-backed
builder (`bn254_mesh_engine`) imports lazily.
"""

from __future__ import annotations

import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass

from handel_tpu.service.fairness import SloTier

__all__ = [
    "MODE_LATENCY",
    "MODE_THROUGHPUT",
    "ModePolicy",
    "HostMeshDevice",
    "bn254_mesh_engine",
    "host_mesh_engine",
    "enable_latency_plane",
]

MODE_LATENCY = "latency"
MODE_THROUGHPUT = "throughput"


@dataclass(frozen=True)
class ModePolicy:
    """When does a launch group ride the whole-mesh latency lane?

    `small_batch_max` caps latency-mode group size (the mesh engine's own
    batch_size also caps it — a group must fit ONE mesh launch).
    `max_queue_depth` is the backlog bound: a queue deeper than this keeps
    groups on the per-lane throughput path, where K independent lanes beat
    one fast lane. `latency_tiers` names the SLO tiers (service/fairness.py
    TIERS) entitled to the mesh — the routing table HACKING.md documents:
    gold-tier small batches go latency, bronze bulk stays per-lane.
    """

    small_batch_max: int = 64
    max_queue_depth: int = 128
    latency_tiers: tuple = ("gold",)

    def pick_mode(
        self,
        n_items: int,
        queue_depth: int,
        tier,
        mesh_batch: int,
    ) -> str:
        if n_items > min(self.small_batch_max, mesh_batch):
            return MODE_THROUGHPUT
        if queue_depth > self.max_queue_depth:
            return MODE_THROUGHPUT
        name = tier.name if isinstance(tier, SloTier) else str(tier)
        if name not in self.latency_tiers:
            return MODE_THROUGHPUT
        return MODE_LATENCY


class HostMeshDevice:
    """Host-math engine modeling ONE whole-mesh launch (the CI shape).

    The real latency engine is `BN254Device(mesh_devices=K)`; its pairing
    walls can't be measured on a CI box where K forced host devices share
    one core, so — exactly like service/driver.py HostDevice —
    this engine keeps the real verdict math (the scheme
    constructor's batch_verify) and SIMULATES the wall. Unlike HostDevice's
    fixed `launch_ms`, the wall here models INTRA-launch parallelism: each
    candidate costs `per_candidate_ms`, the candidates shard over
    `devices` concurrent workers (real threads — the measured wall is the
    max over workers, contention included), and `collective_ms` is the
    serial all_gather + combine-tree share that Amdahl-caps the win. So a
    batch-n launch walls ~ per_candidate_ms * ceil(n/K) + collective_ms,
    and `devices=1` is the single-lane baseline with identical code.
    """

    def __init__(
        self,
        constructor,
        batch_size: int = 64,
        devices: int = 8,
        per_candidate_ms: float = 1.0,
        collective_ms: float = 0.5,
    ):
        self.constructor = constructor
        self.batch_size = batch_size
        self.mesh_devices = max(1, devices)
        self.per_candidate_ms = per_candidate_ms
        self.collective_ms = collective_ms
        self.dispatched = 0
        self.mesh_launches = 0
        self.mesh_candidates = 0
        self._pool = (
            ThreadPoolExecutor(max_workers=self.mesh_devices)
            if self.mesh_devices > 1
            else None
        )
        # epoch-rotation protocol parity (lifecycle/epoch.py, same stubs as
        # HostDevice): no resident bank to flip, but the stage -> quiesce ->
        # activate choreography must reach mesh lanes too
        self.epoch = 0
        self._staged = None
        self.registry_stagings = 0
        self.registry_staged_ms = 0.0

    def stage_registry(self, registry_pubkeys, build_prefix: bool = True) -> int:
        self._staged = registry_pubkeys
        self.registry_stagings += 1
        return len(registry_pubkeys)

    def activate_staged(self) -> int:
        if self._staged is None:
            raise RuntimeError("no staged registry: call stage_registry first")
        self._staged = None
        self.epoch += 1
        return self.epoch

    def _verify_shard(self, items, idxs):
        verdicts = {}
        for i in idxs:
            msg, pubkeys, bitset, sig = items[i]
            ok = self.constructor.batch_verify(msg, pubkeys, [(bitset, sig)])
            verdicts[i] = bool(ok[0])
        if self.per_candidate_ms > 0:
            time.sleep(self.per_candidate_ms * len(idxs) / 1000.0)
        return verdicts

    def dispatch_multi(self, items):
        k = self.mesh_devices
        shards = [list(range(i, len(items), k)) for i in range(k)]
        shards = [s for s in shards if s]
        if self._pool is None or len(shards) <= 1:
            merged = self._verify_shard(items, list(range(len(items))))
        else:
            futs = [
                self._pool.submit(self._verify_shard, items, s)
                for s in shards
            ]
            merged = {}
            for f in futs:
                merged.update(f.result())
        if self.collective_ms > 0:
            time.sleep(self.collective_ms / 1000.0)
        self.dispatched += 1
        self.mesh_launches += 1
        self.mesh_candidates += len(items)
        return [merged[i] for i in range(len(items))]

    def fetch(self, handle):
        return handle


def host_mesh_engine(
    constructor,
    devices: int = 8,
    batch_size: int = 64,
    per_candidate_ms: float = 1.0,
    collective_ms: float = 0.5,
) -> HostMeshDevice:
    """The CI mesh engine (see HostMeshDevice)."""
    return HostMeshDevice(
        constructor,
        batch_size=batch_size,
        devices=devices,
        per_candidate_ms=per_candidate_ms,
        collective_ms=collective_ms,
    )


def bn254_mesh_engine(
    registry_pubkeys,
    devices: int,
    batch_size: int = 8,
    curves=None,
    warmup: bool = False,
):
    """The real whole-mesh latency engine: ONE BN254Device spanning all K
    devices (`mesh_devices=K` — the staged sharded pipeline of models/
    bn254_jax.py), vs `bn254_plane`'s one-engine-per-chip throughput shape.
    Warmup is off by default for the same reason as bn254_plane: the
    pairing tail compiles in minutes — smokes drive the aggregation stage
    standalone."""
    import jax

    from handel_tpu.models.bn254_jax import BN254Device
    from handel_tpu.ops.curve import BN254Curves

    if devices > len(jax.devices()):
        raise ValueError(
            f"mesh of {devices} devices requested but only "
            f"{len(jax.devices())} visible "
            "(set XLA_FLAGS=--xla_force_host_platform_device_count=N)"
        )
    eng = BN254Device(
        registry_pubkeys,
        batch_size=batch_size,
        curves=curves or BN254Curves(),
        mesh_devices=devices,
    )
    if warmup:
        eng.warmup()
    return eng


def enable_latency_plane(service, engine, policy: ModePolicy | None = None,
                         breaker=None):
    """Attach `engine` as the service's mesh lane and arm dual-mode
    scheduling (BatchVerifierService._route_mesh consults the policy the
    moment a mesh lane exists). On a running service the lane's
    dispatcher/fetcher pair spawns immediately; before start() it simply
    joins the plane and wires with the rest. Returns the new lane."""
    if policy is not None:
        service.mode_policy = policy
    return service.attach_lane(engine, breaker, mesh=True)
