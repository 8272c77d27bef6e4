"""Driver benchmark: batched BLS verification on one chip.

Measures the headline target from BASELINE.md: verify a batch of aggregate
BN254 signatures over a 4096-key registry (the reference's 4000-node AWS
scenario, README.md:32-33: ~900 ms avg completion) with the device path —
masked G2 aggregation + batched product-of-pairings check in one launch per
128 candidates.

Prints ONE JSON line:
  {"metric": "4096sig_batch_verify_p50_ms", "value": ..., "unit": "ms",
   "vs_baseline": <reference 900 ms / our p50>}

The measurement runs in this process, which owns the chip: a run that
finds no TPU fails (non-zero exit, no line) — a CPU number is never printed
under a device metric's name. Every successful measurement is also
persisted with backend/device provenance (HANDEL_TPU_BENCH_ARTIFACT).
HANDEL_TPU_BENCH_FORCE_ACCEL_SHAPE drives the same plumbing on the CPU at
a tiny forced size for tests; its line labels itself `forced_shape`.
"""

from __future__ import annotations

import json
import os
import random
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
# chip captures land in chiprun_out/ (git-ignored; the directory the chip
# tool brings back). Paths overridable so tests never clobber a capture.
ARTIFACT = os.environ.get(
    "HANDEL_TPU_BENCH_ARTIFACT",
    os.path.join(REPO, "chiprun_out", "bench_device.json"),
)
FP_ARTIFACT = os.environ.get(
    "HANDEL_TPU_BENCH_FP_ARTIFACT",
    os.path.join(REPO, "chiprun_out", "fp_microbench.json"),
)
PAIRING_ARTIFACT = os.environ.get(
    "HANDEL_TPU_BENCH_PAIRING_ARTIFACT",
    os.path.join(REPO, "results", "pairing_bench.json"),
)
REFERENCE_HEADLINE_MS = 900.0  # README.md:32-33, 4000-sig AWS scenario


def _emit(line: dict) -> None:
    print(json.dumps(line))


def write_json_atomic(path: str, obj: dict) -> None:
    """All evidence-artifact writers go through here: unique temp +
    os.replace so a watchdog kill mid-write can never truncate an
    already-captured artifact, and two concurrent writers (bench.py and the
    lab scripts share the fp microbench artifact) can't interleave on one
    scratch file (the corrupt-read guards downstream are a second line of
    defense, not a license to write non-atomically). Newline-terminated so
    the committed file's final byte doesn't flap between writers."""
    import tempfile

    fd, tmp = tempfile.mkstemp(
        dir=os.path.dirname(path) or ".", suffix=".tmp"
    )
    try:
        with os.fdopen(fd, "w") as f:
            json.dump(obj, f, indent=1)
            f.write("\n")
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


PIPELINE_DEPTH = 8


def host_pipeline_bench(
    n_registry: int = 1024,
    lanes: int = 256,
    trials: int = 20,
    seed: int = 77,
) -> dict:
    """Host half of the verify pipeline, measured on ANY backend (no verify
    kernel launches): per-launch cost of the zero-copy packer vs the old
    per-candidate loop at `lanes` candidates — for BOTH the range path
    (Handel's contiguous partitioner hulls) and the dense fallback
    (scattered signer sets) — the staging-handoff half of dispatch
    (`host_dispatch_ms`), a steady-state probe that pins the handoff to
    explicit transfers only (`jax.transfer_guard`), and the dedup hit rate
    of the service cache on a Handel-shaped duplicate-delivery trace.
    Returns the metric dict merged into the bench line: host_pack_ms,
    host_pack_loop_ms, host_pack_speedup, host_pack_dense_ms,
    host_dispatch_ms, no_transfer_steady_state, dedup_hit_rate.
    """
    import asyncio
    import threading  # noqa: F401  (parity with the service's test stubs)

    import jax
    import numpy as np

    from handel_tpu import native as nat
    from handel_tpu.core.bitset import BitSet
    from handel_tpu.models.bn254 import BN254PublicKey, BN254Signature
    from handel_tpu.models.bn254_jax import BN254Device
    from handel_tpu.ops import bn254_ref as bn

    rng = random.Random(seed)
    sks = [rng.randrange(1, 1 << 20) for _ in range(n_registry)]
    pks = [
        BN254PublicKey(p) for p in nat.g2_mul_batch([bn.G2_GEN] * n_registry, sks)
    ]
    device = BN254Device(pks, batch_size=lanes)

    # Handel-realistic requests: contiguous partitioner ranges, <=8 holes
    sig = BN254Signature(bn.G1_GEN)
    requests = []
    for _ in range(lanes):
        size = rng.choice([n_registry // 8, n_registry // 4, n_registry // 2])
        lo = rng.randrange(0, n_registry - size)
        max_holes = min(9, max(1, size - 2))
        holes = set(
            rng.sample(range(lo + 1, lo + size - 1), rng.randrange(0, max_holes))
        )
        bs = BitSet(n_registry)
        for i in range(lo, lo + size):
            if i not in holes:
                bs.set(i, True)
        requests.append((bs, sig))
    # dense-fallback phase: scattered signer sets (hull holes past the
    # widest range patch, n // 4)
    dense_requests = []
    for _ in range(lanes):
        bs = BitSet(n_registry)
        for i in rng.sample(range(n_registry), n_registry // 4):
            bs.set(i, True)
        dense_requests.append((bs, sig))

    def p50(pack, reqs):
        ts = []
        for _ in range(trials):
            t0 = time.perf_counter()
            pack(reqs)
            ts.append((time.perf_counter() - t0) * 1000.0)
        return float(np.percentile(ts, 50))

    # phase boundaries reset the device's cumulative host counters so no
    # phase inherits the previous phase's accumulation
    device.reset_host_counters()
    pack_vec_ms = p50(device._pack_requests, requests)
    pack_loop_ms = p50(device._pack_requests_loop, requests)
    device.reset_host_counters()
    pack_dense_ms = p50(device._pack_requests, dense_requests)
    device.reset_host_counters()

    def stage_p50(reqs):
        ts = []
        for _ in range(trials):
            plan = device._pack_requests(reqs)
            t0 = time.perf_counter()
            device._stage_plan(plan)
            ts.append((time.perf_counter() - t0) * 1000.0)
        return float(np.percentile(ts, 50))

    dispatch_ms = stage_p50(requests)

    # steady-state no-transfer probe: with implicit host->device transfers
    # disallowed, a warm pack+stage cycle must run clean (registry/prefix
    # are device-resident; staging moves via explicit jax.device_put only)
    try:
        with jax.transfer_guard_host_to_device("disallow"):
            device._stage_plan(device._pack_requests(requests))
            device._stage_plan(device._pack_requests(dense_requests))
        no_implicit = 1.0
    except Exception as e:
        print(f"bench: steady-state transfer probe tripped: {e}",
              file=sys.stderr)
        no_implicit = 0.0
    device.reset_host_counters()

    # dedup hit rate over a multi-peer delivery trace: 32 distinct winning
    # aggregates, each re-delivered by 8 peers, shuffled — the shape
    # processing.go re-verifies in full and the cache short-circuits
    class _StubDevice:
        batch_size = lanes

        def dispatch(self, msg, reqs):
            return len(reqs)

        def fetch(self, handle):
            return [True] * handle

    distinct, fanout = min(32, lanes), 8
    deliveries = list(range(distinct)) * fanout
    rng.shuffle(deliveries)

    async def dedup_trace():
        from handel_tpu.parallel.batch_verifier import BatchVerifierService

        svc = BatchVerifierService(_StubDevice(), max_delay_ms=0.1)
        for i in deliveries:
            await svc.verify(b"bench", [], [requests[i]])
        vals = svc.values()
        svc.stop()
        return vals

    vals = asyncio.run(dedup_trace())
    return {
        "host_pack_ms": round(pack_vec_ms, 3),
        "host_pack_loop_ms": round(pack_loop_ms, 3),
        "host_pack_speedup": round(pack_loop_ms / pack_vec_ms, 2)
        if pack_vec_ms > 0
        else None,
        "host_pack_dense_ms": round(pack_dense_ms, 3),
        "host_dispatch_ms": round(dispatch_ms, 3),
        "no_transfer_steady_state": no_implicit,
        "dedup_hit_rate": round(vals["dedupHitRate"], 4),
    }


def service_bench(
    sessions: int = 8,
    nodes: int = 16,
    batch_size: int = 64,
    timeout_s: float = 120.0,
) -> dict:
    """Multi-tenant service sustained rate (ROADMAP item 3): K concurrent
    fake-crypto sessions share one BatchVerifierService and its coalesced
    launches; reports sustained completed aggregations per second, the p99
    session-completion latency under that concurrency, and the per-launch
    lane fill ratio the cross-session coalescing achieves. Protocol-layer
    and backend-independent (no kernels) — the 64x128 capture form runs
    through `sim serve` (results/handel_service_64.json); this in-bench
    shape keeps the metric fresh every round without minutes of wall.
    Returns {aggregates_per_s, session_p99_s, launch_fill_ratio}.
    """
    import asyncio

    from handel_tpu.service.driver import MultiSessionCluster

    async def go():
        cluster = MultiSessionCluster(
            sessions, nodes, batch_size=batch_size
        )
        try:
            return await cluster.run(timeout_s)
        finally:
            cluster.stop()

    summary = asyncio.run(go())
    if summary["completed"] != sessions:
        # a partial run must not publish a flattering rate
        print(
            f"bench: service bench completed {summary['completed']}/"
            f"{sessions} sessions",
            file=sys.stderr,
        )
        return {}
    return {
        "aggregates_per_s": summary["aggregates_per_s"],
        "session_p99_s": summary["session_p99_s"],
        "launch_fill_ratio": summary["launch_fill_ratio"],
    }


def _service_metrics() -> dict:
    """service_bench behind the degrade-don't-die contract (+ a shape
    override for tests: HANDEL_TPU_BENCH_SERVICE_SHAPE =
    'sessions,nodes,batch')."""
    shape = os.environ.get("HANDEL_TPU_BENCH_SERVICE_SHAPE")
    try:
        if shape:
            sessions, nodes, batch = (int(x) for x in shape.split(","))
            return service_bench(sessions, nodes, batch)
        return service_bench()
    except Exception as e:
        print(f"bench: service bench failed: {e}", file=sys.stderr)
        return {}


def fleet_bench(
    devices: int = 8,
    requests_n: int = 160,
    batch_size: int = 4,
    launch_ms: float = 8.0,
    timeout_s: float = 60.0,
) -> dict:
    """Fleet-of-chips verify plane: K-lane DevicePlane throughput vs an
    identical 1-lane baseline under a flood of distinct aggregates. The
    launch wall is simulated by HostDevice.launch_ms so what's measured is
    the plane scheduler (least-loaded pick, per-lane queues overlapping
    dispatch), not crypto — the per-chip crypto figure is the headline
    above. Reports launches/s for the fleet, the speedup over the 1-lane
    run (the no-idle-while-queued claim: with launch wall dominating, K
    lanes must approach Kx), the fleet's per-launch fill, and the
    scheduler's idle-violation audit counter (a pick that left a queued
    batch while an idle lane existed — must stay 0).
    """
    import asyncio
    import concurrent.futures

    from handel_tpu.core.bitset import BitSet
    from handel_tpu.core.test_harness import FakeScheme
    from handel_tpu.models.fake import FakePublic, FakeSignature
    from handel_tpu.parallel.batch_verifier import BatchVerifierService
    from handel_tpu.parallel.plane import host_plane

    pks = [FakePublic(True) for _ in range(16)]

    def reqs():
        out = []
        for i in range(requests_n):
            bs = BitSet(16)
            bs.set(i % 16, True)
            # distinct message per request: no dedup/coalescing — every
            # request is a real candidate the plane must launch
            out.append((i.to_bytes(4, "big"), (bs, FakeSignature(True))))
        return out

    async def run(k: int) -> tuple[float, dict]:
        # a 1-core default executor (5 threads) would cap lane overlap
        # below the plane width — give the loop enough threads that every
        # lane's dispatch and fetch can be in flight at once
        loop = asyncio.get_running_loop()
        loop.set_default_executor(
            concurrent.futures.ThreadPoolExecutor(max_workers=2 * k + 4)
        )
        plane = host_plane(
            FakeScheme().constructor,
            k,
            batch_size=batch_size,
            launch_ms=launch_ms,
        )
        svc = BatchVerifierService(plane, max_delay_ms=0.2)
        try:
            t0 = time.perf_counter()
            verdicts = await asyncio.wait_for(
                asyncio.gather(
                    *(
                        svc.verify(msg, pks, [r], session=f"s{i % 8}")
                        for i, (msg, r) in enumerate(reqs())
                    )
                ),
                timeout_s,
            )
            wall = time.perf_counter() - t0
            if not all(v == [True] for v in verdicts):
                raise RuntimeError("fleet bench verdict mismatch")
            vals = svc.values()
            vals["_wall_s"] = wall
            return wall, vals
        finally:
            svc.stop()

    base_wall, base_vals = asyncio.run(run(1))
    fleet_wall, fleet_vals = asyncio.run(run(devices))
    base_rate = base_vals["verifierLaunches"] / base_wall
    fleet_rate = fleet_vals["verifierLaunches"] / fleet_wall
    return {
        "launches_per_s": round(fleet_rate, 2),
        "fleet_speedup_x": round(fleet_rate / base_rate, 2)
        if base_rate > 0
        else None,
        "fleet_fill_ratio": round(fleet_vals["launchFillRatio"], 4),
        "fleet_idle_violations": int(fleet_vals["schedIdleViolations"]),
        "fleet_devices": int(fleet_vals["devicesTotal"]),
    }


def small_batch_bench(
    devices: int = 8,
    rounds: int = 20,
    batch: int = 64,
    per_candidate_ms: float = 1.0,
    timeout_s: float = 60.0,
) -> dict:
    """Mesh latency plane: p50 verify latency of SMALL gold-tier launches
    riding the whole-mesh lane (parallel/mesh_plane.py) vs an identical-code
    single-device mesh lane. Where fleet_bench floods the throughput path
    with distinct aggregates, this bench issues one small launch group at a
    time — the regime where K per-chip lanes can't help (one launch lands
    on one chip) but one K-device mesh launch cuts the wall ~K/2x. The
    engine is HostMeshDevice: real verdict math + real threads, simulated
    per-candidate wall (per_candidate_ms each, sharded over `devices`
    workers, plus a serial collective share) — the measured quantity is the
    dual-mode routing plus genuine intra-launch parallelism, thread
    contention and Amdahl included. Both runs go through the full service
    latency path (gold tier -> ModePolicy -> pick_mesh), so the speedup is
    the contract the MULTICHIP smoke gates: > 1x, approaching K/2 at
    batch <= 64.
    """
    import asyncio
    import concurrent.futures

    import numpy as np

    from handel_tpu.core.bitset import BitSet
    from handel_tpu.core.test_harness import FakeScheme
    from handel_tpu.models.fake import FakePublic, FakeSignature
    from handel_tpu.parallel.batch_verifier import BatchVerifierService
    from handel_tpu.parallel.mesh_plane import (
        ModePolicy,
        enable_latency_plane,
        host_mesh_engine,
    )
    from handel_tpu.parallel.plane import host_plane

    # registry as wide as the batch so every candidate in a round is a
    # DISTINCT bitset — the dedup layer must not shrink the launch group
    # under the bench's feet
    n_keys = max(16, batch)
    pks = [FakePublic(True) for _ in range(n_keys)]

    async def run(k: int) -> tuple[float, dict]:
        loop = asyncio.get_running_loop()
        loop.set_default_executor(
            concurrent.futures.ThreadPoolExecutor(max_workers=2 * k + 4)
        )
        # one throughput lane (never picked here — every group is small +
        # gold) plus the mesh lane under test; k=1 is the baseline with
        # the exact same code path
        plane = host_plane(FakeScheme().constructor, 1, batch_size=64)
        svc = BatchVerifierService(plane, max_delay_ms=0.2)
        enable_latency_plane(
            svc,
            host_mesh_engine(
                FakeScheme().constructor,
                devices=k,
                batch_size=64,
                per_candidate_ms=per_candidate_ms,
            ),
            policy=ModePolicy(small_batch_max=64, latency_tiers=("gold",)),
        )
        svc.queue.set_tier("gold0", "gold")
        walls = []
        try:
            for r in range(rounds):
                msg = r.to_bytes(4, "big")
                reqs = []
                for i in range(batch):
                    bs = BitSet(n_keys)
                    bs.set(i % n_keys, True)
                    reqs.append((bs, FakeSignature(True)))
                t0 = time.perf_counter()
                verdicts = await asyncio.wait_for(
                    asyncio.gather(
                        *(
                            svc.verify(msg, pks, [q], session="gold0")
                            for q in reqs
                        )
                    ),
                    timeout_s,
                )
                walls.append((time.perf_counter() - t0) * 1000.0)
                if not all(v == [True] for v in verdicts):
                    raise RuntimeError("small-batch bench verdict mismatch")
            return float(np.percentile(walls, 50)), svc.values()
        finally:
            svc.stop()

    mesh_p50, mesh_vals = asyncio.run(run(devices))
    base_p50, base_vals = asyncio.run(run(1))
    if mesh_vals["modeLatencyLaunches"] < rounds:
        raise RuntimeError(
            "small-batch bench groups leaked off the latency path: "
            f"{mesh_vals['modeLatencyLaunches']:.0f}/{rounds} rode the mesh"
        )
    return {
        "small_batch_verify_p50_ms": round(mesh_p50, 3),
        "small_batch_baseline_p50_ms": round(base_p50, 3),
        "small_batch_speedup_x": round(base_p50 / mesh_p50, 2)
        if mesh_p50 > 0
        else None,
        "small_batch_mesh_devices": devices,
        "small_batch_n": batch,
        "small_batch_latency_launches": int(
            mesh_vals["modeLatencyLaunches"]
        ),
        "small_batch_mesh_fallbacks": int(mesh_vals["meshFallbacks"]),
    }


def _small_batch_metrics() -> dict:
    """small_batch_bench behind the degrade-don't-die contract (+ a shape
    override for tests: HANDEL_TPU_BENCH_SMALL_BATCH_SHAPE =
    'devices,rounds,batch')."""
    shape = os.environ.get("HANDEL_TPU_BENCH_SMALL_BATCH_SHAPE")
    try:
        if shape:
            devices, rounds, batch = (int(x) for x in shape.split(","))
            return small_batch_bench(devices, rounds, batch)
        return small_batch_bench()
    except Exception as e:
        print(f"bench: small-batch bench failed: {e}", file=sys.stderr)
        return {}


def swarm_bench(
    identities: int = 512,
    batch_size: int = 64,
    timeout_s: float = 120.0,
) -> dict:
    """Virtual-node swarm runtime (ROADMAP swarm item): one SwarmHost
    multiplexing `identities` Handel instances as vnodes on a single event
    loop — the in-process form of the `sim swarm` capture
    (results/swarm_65536_summary.json). Reports the committee size carried,
    summed-RSS bytes per identity (the 1M-identity extrapolation basis),
    and the wall until the LAST member held a threshold signature. Returns
    {} unless every vnode finished — a partial swarm must not publish a
    flattering memory figure.
    """
    import asyncio

    from handel_tpu.swarm.driver import SwarmHost, merge_summaries

    async def go():
        host = SwarmHost(identities, 0, identities, batch_size=batch_size)
        return await host.run(timeout_s)

    m = merge_summaries([asyncio.run(go())])
    if not m["ok"]:
        print(
            f"bench: swarm bench completed {m['completed']}/{identities} "
            "vnodes",
            file=sys.stderr,
        )
        return {}
    return {
        "swarm_identities": m["swarm_identities"],
        "mem_bytes_per_identity": m["mem_bytes_per_identity"],
        "swarm_time_to_threshold_s": m["swarm_time_to_threshold_s"],
    }


def _swarm_metrics() -> dict:
    """swarm_bench behind the degrade-don't-die contract (+ a shape
    override for tests: HANDEL_TPU_BENCH_SWARM_SHAPE =
    'identities,batch')."""
    shape = os.environ.get("HANDEL_TPU_BENCH_SWARM_SHAPE")
    try:
        if shape:
            identities, batch = (int(x) for x in shape.split(","))
            return swarm_bench(identities, batch)
        return swarm_bench()
    except Exception as e:
        print(f"bench: swarm bench failed: {e}", file=sys.stderr)
        return {}


def federation_bench(
    rate_sps: float = 5.0,
    duration_s: float = 8.0,
    nodes: int = 6,
) -> dict:
    """Geo-federated open-loop robustness (service/federation.py driven by
    sim/load.py): a seeded Poisson arrival clock against a 3-region
    federation with a mid-run region kill + epoch-path recovery. Reports
    the gold-tier open-loop arrival->verdict p99, the kill-to-first-
    post-recovery-completion wall, and the fraction of arrivals that
    spilled to a non-nearest region. This in-bench shape keeps the three
    SIDE_METRICS fresh every round; the 10-minute capture form runs
    through `sim load` (results/federation_report.json). Returns {} unless
    every report check held — a run that dropped work or never recovered
    must not publish a flattering p99.
    """
    import asyncio

    from handel_tpu.sim.config import FederationParams, LoadParams
    from handel_tpu.sim.load import LoadRun

    lp = LoadParams(
        rate_sps=rate_sps, duration_s=duration_s, nodes=nodes, seed=7
    )
    fp = FederationParams(
        kill_region="us-east", session_ttl_s=15.0,
        trace_capacity=1 << 14,
    )
    report = asyncio.run(LoadRun(lp, fp).run())
    if not report["ok"]:
        failed = [k for k, v in report["checks"].items() if not v]
        print(
            f"bench: federation bench checks failed: {failed}",
            file=sys.stderr,
        )
        return {}
    return {
        "open_loop_p99_s": report["open_loop_p99_s"],
        "region_recovery_s": report["region_recovery_s"],
        "spillover_rate": report["spillover_rate"],
    }


def _federation_metrics() -> dict:
    """federation_bench behind the degrade-don't-die contract (+ a shape
    override for tests: HANDEL_TPU_BENCH_FEDERATION_SHAPE =
    'rate_sps,duration_s,nodes')."""
    shape = os.environ.get("HANDEL_TPU_BENCH_FEDERATION_SHAPE")
    try:
        if shape:
            rate, duration, nodes = shape.split(",")
            return federation_bench(
                float(rate), float(duration), int(nodes)
            )
        return federation_bench()
    except Exception as e:
        print(f"bench: federation bench failed: {e}", file=sys.stderr)
        return {}


def _fleet_metrics() -> dict:
    """fleet_bench behind the degrade-don't-die contract (+ a shape
    override for tests: HANDEL_TPU_BENCH_FLEET_SHAPE =
    'devices,requests,batch')."""
    shape = os.environ.get("HANDEL_TPU_BENCH_FLEET_SHAPE")
    try:
        if shape:
            devices, requests_n, batch = (int(x) for x in shape.split(","))
            return fleet_bench(devices, requests_n, batch)
        return fleet_bench()
    except Exception as e:
        print(f"bench: fleet bench failed: {e}", file=sys.stderr)
        return {}


def rollup_bench(
    hosts: int = 4,
    vnodes: int = 1024,
    rounds: int = 20,
) -> dict:
    """Hierarchical roll-up plane (obs/rollup.py): `hosts` HostRollups
    each folding `vnodes` reporter surfaces emit changed-keys deltas into
    one master FleetRollup for `rounds` emission intervals. Reports the
    master's merged series count (the O(hosts) contract: flat across
    vnode sweeps), the wire bytes per host per emission interval (the
    1 Hz default cadence makes that bytes/host/s), and the master-side
    merge wall — all three must stay flat as identities scale.
    """
    import random as _random

    from handel_tpu.core.trace import LogHistogram
    from handel_tpu.obs.rollup import FleetRollup, HostRollup

    rng = _random.Random(13)
    fleet = FleetRollup(top_k=8, clock=lambda: 0.0)
    states = []
    hrs = []
    for h in range(hosts):
        state = [
            {"msgSentCt": 0.0, "verifiedCt": 0.0, "levelRate": 0.0}
            for _ in range(vnodes)
        ]
        states.append(state)

        hist = LogHistogram()

        class _Rep:
            def __init__(self, state, hist):
                self.state = state
                self.hist = hist

            def values(self):
                return {"launchesCt": float(sum(
                    v["verifiedCt"] for v in self.state))}

            def gauge_keys(self):
                return set()

            def histograms(self):
                return {"verifyLatencyS": self.hist}

        hr = HostRollup(f"bench{h}", clock=lambda: 0.0)
        hr.attach_fold(
            "swarm",
            lambda state=state: ((v, {"levelRate"}) for v in state),
        )
        hr.attach_reporter("device", _Rep(state, hist))
        hrs.append((hr, hist))
    for _ in range(rounds):
        for h in range(hosts):
            for v in states[h]:
                v["msgSentCt"] += rng.randrange(1, 8)
                v["verifiedCt"] += rng.randrange(0, 4)
                v["levelRate"] = rng.randrange(0, 64) / 8.0
            hrs[h][1].add(rng.randrange(1, 1 << 16) / 1e6)
            hrs[h][0].emit(fleet.ingest)
    series = fleet.series_count()  # refreshes last_merge_ms too
    return {
        "fleet_series_count": series,
        "rollup_bytes_per_host_s": round(
            fleet.ingest_bytes / hosts / rounds, 1
        ),
        "fleet_eval_ms": round(fleet.last_merge_ms, 3),
    }


def _rollup_metrics() -> dict:
    """rollup_bench behind the degrade-don't-die contract (+ a shape
    override for tests: HANDEL_TPU_BENCH_ROLLUP_SHAPE =
    'hosts,vnodes,rounds')."""
    shape = os.environ.get("HANDEL_TPU_BENCH_ROLLUP_SHAPE")
    try:
        if shape:
            hosts, vnodes, rounds = (int(x) for x in shape.split(","))
            return rollup_bench(hosts, vnodes, rounds)
        return rollup_bench()
    except Exception as e:
        print(f"bench: rollup bench failed: {e}", file=sys.stderr)
        return {}


def rlc_bench(batch: int = 64, messages: int = 4, trials: int = 5) -> dict:
    """Random-linear-combination batch verification (models/rlc.py) vs the
    per-candidate pairing loop, host math path: one launch of `batch`
    candidates over `messages` distinct messages, checked both ways.

    Both modes ride every bench line: `rlc_per_candidate_p50_ms` is the C
    independent 2-pairing checks (the per_candidate device contract),
    `rlc_verify_p50_ms` is the single combined check — two MSMs plus one
    M+1-Miller-loop product pairing — and `rlc_speedup_x` is their ratio
    (acceptance: >= 3x at batch 64). Aggregation cost is excluded from
    both sides: the apk is built once up front, exactly what a device
    launch stages, so the ratio isolates the pairing-tail change."""
    rng = random.Random(4096)

    from statistics import median

    from handel_tpu.core.bitset import BitSet
    from handel_tpu.models import rlc
    from handel_tpu.models.bn254 import BN254Scheme

    scheme = BN254Scheme()
    n = 16
    keys = [scheme.keygen(i) for i in range(n)]
    pubs = [pk for _, pk in keys]
    msgs = [f"rlc-bench-{m}".encode() for m in range(messages)]
    cands = []
    for j in range(batch):
        msg = msgs[j % messages]
        bs = BitSet(n)
        sig = None
        for i in rng.sample(range(n), rng.randrange(2, 6)):
            bs.set(i)
            s = keys[i][0].sign(msg)
            sig = s if sig is None else sig.combine(s)
        apk = scheme.constructor.aggregate_public_keys(pubs, bs)
        cands.append((msg, apk.point, sig.point))

    ops = rlc.host_ops_for(scheme.constructor)
    pc_times, rlc_times = [], []
    for _ in range(trials):
        t0 = time.perf_counter()
        for msg, x, s in cands:
            assert ops.pairing_check(
                [(ops.hash_to_g1(msg), x), (ops.g1_neg(s), ops.g2_gen)]
            )
        pc_times.append((time.perf_counter() - t0) * 1e3)
        t0 = time.perf_counter()
        assert rlc.host_rlc_check(ops, cands)
        rlc_times.append((time.perf_counter() - t0) * 1e3)
    pc_p50, rlc_p50 = median(pc_times), median(rlc_times)
    return {
        "rlc_verify_p50_ms": round(rlc_p50, 3),
        "rlc_per_candidate_p50_ms": round(pc_p50, 3),
        "rlc_speedup_x": round(pc_p50 / rlc_p50, 2),
        "rlc_batch": batch,
        "rlc_messages": messages,
    }


def _rlc_metrics() -> dict:
    """rlc_bench behind the degrade-don't-die contract (+ a shape override
    for tests: HANDEL_TPU_BENCH_RLC_SHAPE = 'batch,messages,trials')."""
    shape = os.environ.get("HANDEL_TPU_BENCH_RLC_SHAPE")
    try:
        if shape:
            batch, messages, trials = (int(x) for x in shape.split(","))
            return rlc_bench(batch, messages, trials)
        return rlc_bench()
    except Exception as e:
        print(f"bench: rlc bench failed: {e}", file=sys.stderr)
        return {}


def _host_metrics() -> dict:
    """host_pipeline_bench behind the bench's degrade-don't-die contract
    (+ a shape override for tests: HANDEL_TPU_BENCH_HOST_SHAPE =
    'registry,lanes,trials')."""
    shape = os.environ.get("HANDEL_TPU_BENCH_HOST_SHAPE")
    try:
        if shape:
            n_registry, lanes, trials = (int(x) for x in shape.split(","))
            return host_pipeline_bench(n_registry, lanes, trials)
        return host_pipeline_bench()
    except Exception as e:
        print(f"bench: host pipeline bench failed: {e}", file=sys.stderr)
        return {}


def measure_pipelined(launch, block, trials: int, depth: int = PIPELINE_DEPTH):
    """Sustained per-launch latency, ms: dispatch `depth` launches
    back-to-back and block only on the last (the chip executes in order, so
    the last completing implies all did) — the per-dispatch round trip
    then overlaps on-chip compute of the queued launches, which is how
    production traffic flows through the two-stage BatchVerifierService
    (parallel/batch_verifier.py).
    """
    rs = [launch() for _ in range(depth)]
    block(rs[-1])  # warm
    out = []
    for _ in range(trials):
        t0 = time.perf_counter()
        rs = [launch() for _ in range(depth)]
        block(rs[-1])
        out.append((time.perf_counter() - t0) * 1000.0 / depth)
    return out


def build_problem(
    curves,
    n_registry: int,
    lanes: int,
    n_candidates: int,
    ref=None,
    g1_mul_batch=None,
    g2_mul_batch=None,
    miss_k: int = 8,
    seed: int = 2024,
):
    """Handel-realistic candidate batch: contiguous partitioner ranges with a
    few offline holes, exactly the traffic `batch_verify` sees. Returns the
    range-kernel argument tuple (lo, hi, miss_idx, miss_ok, sig, h, valid)
    plus the keypair material.

    Curve-parametric: `ref` is the scalar-oracle module (G1_GEN/G2_GEN/R)
    and the *_mul_batch hooks do host keygen — defaults are BN254 through
    the native C++ path.
    """
    import jax.numpy as jnp
    import numpy as np

    if ref is None:
        from handel_tpu import native as nat
        from handel_tpu.ops import bn254_ref as ref

        g1_mul_batch = nat.g1_mul_batch
        g2_mul_batch = nat.g2_mul_batch
    bn = ref

    rng = random.Random(seed)
    # small scalars keep host-side keygen fast; verification cost on device
    # is independent of scalar magnitude
    sks = [rng.randrange(1, 1 << 30) for _ in range(n_registry)]
    pks = g2_mul_batch([bn.G2_GEN] * n_registry, sks)
    h = g1_mul_batch([bn.G1_GEN], [rng.randrange(1, bn.R)])[0]

    lo = np.zeros((lanes,), np.int32)
    hi = np.zeros((lanes,), np.int32)
    miss_idx = np.zeros((miss_k, lanes), np.int64)
    miss_ok = np.zeros((miss_k, lanes), dtype=bool)
    agg_sks = []
    for j in range(n_candidates):
        size = rng.choice([n_registry // 8, n_registry // 4, n_registry // 2])
        lo[j] = rng.randrange(0, n_registry - size)
        hi[j] = lo[j] + size
        max_holes = min(miss_k, size - 1)  # leave at least one signer
        holes = sorted(
            rng.sample(
                range(int(lo[j]), int(hi[j])),
                rng.randrange(0, max_holes) if max_holes > 0 else 0,
            )
        )
        miss_idx[: len(holes), j] = holes
        miss_ok[: len(holes), j] = True
        signers = set(range(int(lo[j]), int(hi[j]))) - set(holes)
        agg_sks.append(sum(sks[i] for i in signers) % bn.R)
    sig_pts = g1_mul_batch([h] * n_candidates, agg_sks)
    sig_pts += [bn.G1_GEN] * (lanes - n_candidates)

    F = curves.F
    valid = np.zeros((lanes,), dtype=bool)
    valid[:n_candidates] = True
    return (
        pks,
        miss_k,
        (
            jnp.asarray(lo),
            jnp.asarray(hi),
            jnp.asarray(miss_idx.reshape(-1)),
            jnp.asarray(miss_ok.reshape(-1)),
            F.pack([p[0] for p in sig_pts]),
            F.pack([p[1] for p in sig_pts]),
            F.pack([h[0]]),
            F.pack([h[1]]),
            jnp.asarray(valid),
        ),
    )


def _fp_microbench() -> None:
    """Capture the ops/fp.py throughput figure as a persisted artifact
    (round-2 verdict, "What's weak" #5: the ~150M mults/s docstring claim
    had no in-repo capture). Measures BOTH Field backends (CIOS and RNS)
    under the same chained-dispatch methodology: the legacy headline keys
    stay CIOS (history continuity), and a per-fp_backend "records" list
    carries one `mont_muls_per_s` row each for scripts/bench_check.py's
    like-for-like gate (a CIOS row never judges an RNS row)."""
    import contextlib

    import jax

    from handel_tpu.ops.fp import _throughput_bench

    batch = int(os.environ.get("HANDEL_TPU_BENCH_FP_BATCH", str(1 << 18)))
    measured = {}
    with contextlib.redirect_stdout(sys.stderr):
        # the microbench prints a human line; stdout is reserved for the
        # single headline JSON line
        for fp_backend in ("cios", "rns"):
            measured[fp_backend] = _throughput_bench(
                batch=batch, trials=3, backend=fp_backend
            )
    rate, floor = measured["cios"]
    if all(r <= 0 for r, _ in measured.values()) and os.path.exists(
        FP_ARTIFACT
    ):
        # a failed slope measurement must not erase previously captured
        # valid evidence (same resilience contract as the main artifact)
        print(
            "bench: fp microbench slope unmeasurable; keeping the existing "
            f"artifact {FP_ARTIFACT}",
            file=sys.stderr,
        )
        return
    os.makedirs(os.path.dirname(FP_ARTIFACT), exist_ok=True)
    # carry forward side-channel captures (scripts/mxu_limb_lab.py merges
    # an "mxu_lab" entry into this artifact) and the batch-scaling
    # reconciliation note: overwriting with only our own keys would
    # destroy captured evidence
    extra = {}
    if os.path.exists(FP_ARTIFACT):
        try:
            with open(FP_ARTIFACT) as f:
                prev = json.load(f)
            extra = {k: prev[k] for k in ("mxu_lab", "note") if k in prev}
        except (json.JSONDecodeError, OSError):
            pass
    now = time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())
    records = [
        {
            "metric": "mont_muls_per_s",
            "value": round(r / 1e6, 1),
            "invalid_measurement": r <= 0,
            "unit": "M muls/s",
            "dispatch_floor_ms": round(f * 1e3, 1),
            "backend": jax.default_backend(),
            "fp_backend": fp_backend,
            "batch": batch,
            "captured_at": now,
            # the reconciliation note travels with every new record so a
            # reader of one row still sees the one-number story
            **({"note": extra["note"]} if "note" in extra else {}),
        }
        for fp_backend, (r, f) in measured.items()
    ]
    write_json_atomic(
        FP_ARTIFACT,
        {
            "metric": "fp254_mont_mul_throughput_marginal",
            "value": round(rate / 1e6, 1),
            # rate 0.0 = the marginal slope was not measurable (timing
            # noise at this batch); an explicit marker, never a made-up
            # number (_throughput_bench retries once, then gives up)
            "invalid_measurement": rate <= 0,
            "unit": "M muls/s",
            "dispatch_floor_ms": round(floor * 1e3, 1),
            "backend": jax.default_backend(),
            "batch": batch,
            "captured_at": now,
            "device": str(jax.devices()[0]),
            "records": records,
            **extra,
        },
    )


def _pairing_bench() -> None:
    """Capture the full-pairing wall per Field backend plus the residue
    conversion count per pairing (residue-resident pairing, ops/rns.py /
    ops/pairing.py). Two record families in results/pairing_bench.json:

    - `pairing_p50_ms`, one row per fp_backend ("cios", "rns"): p50 wall
      of a jitted batch-4 `BN254Pairing.pairing` launch. Registered in
      scripts/bench_check.py SIDE_METRICS and PER_FP_BACKEND, so a CIOS
      row gates only against CIOS history (cross-backend judgment
      refused, same rule as mont_muls_per_s).
    - `rns_conversions_per_pairing` (rns only): CRT boundary crossings
      counted at TRACE time (`RnsField.conversion_counts`). The resident
      form converts O(line boundaries) per pairing — points in, f12 out —
      where the legacy form round-trips once per tower mul; the legacy
      trace count rides the same row as `legacy_per_mul` so the drop is
      one visible number.
    """
    import contextlib

    import jax
    import jax.numpy as jnp
    import numpy as np

    from handel_tpu.ops import bn254_ref as bn
    from handel_tpu.ops.curve import BN254Curves
    from handel_tpu.ops.pairing import BN254Pairing

    B = 4
    trials = int(os.environ.get("HANDEL_TPU_BENCH_PAIRING_TRIALS", "5"))
    rng = random.Random(1307)
    g1s = [bn.g1_mul(bn.G1_GEN, rng.randrange(1, bn.R)) for _ in range(B)]
    g2s = [bn.g2_mul(bn.G2_GEN, rng.randrange(1, bn.R)) for _ in range(B)]
    now = time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())
    records = []
    with contextlib.redirect_stdout(sys.stderr):
        for fp_backend in ("cios", "rns"):
            curves = BN254Curves(backend=fp_backend)
            pr = BN254Pairing(curves)
            xp = curves.F.pack([p[0] for p in g1s])
            yp = curves.F.pack([p[1] for p in g1s])
            xq = curves.T.f2_pack([q[0] for q in g2s])
            yq = curves.T.f2_pack([q[1] for q in g2s])
            args = ((xp, yp), (xq, yq))
            fn = jax.jit(lambda p, q: pr.pairing(p, q))
            jax.block_until_ready(fn(*args))  # compile + warm
            times = []
            for _ in range(trials):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(*args))
                times.append((time.perf_counter() - t0) * 1e3)
            records.append(
                {
                    "metric": "pairing_p50_ms",
                    "value": round(float(np.percentile(times, 50)), 3),
                    "unit": "ms",
                    "backend": jax.default_backend(),
                    "fp_backend": fp_backend,
                    "batch": B,
                    "trials": trials,
                    "captured_at": now,
                }
            )
            if fp_backend != "rns":
                continue
            # conversion counters increment at trace time — eval_shape is
            # enough, no compile. Construct the legacy (non-resident)
            # pairing BEFORE resetting so its gamma re-packs don't pollute
            # the count.
            legacy = BN254Pairing(curves, resident=False)
            F = curves.F
            F.reset_conversion_counts()
            jax.eval_shape(lambda p, q: pr.pairing(p, q), args[0], args[1])
            resident_n = F.conversion_counts()["total"]
            F.reset_conversion_counts()
            jax.eval_shape(
                lambda p, q: legacy.pairing(p, q), args[0], args[1]
            )
            legacy_n = F.conversion_counts()["total"]
            records.append(
                {
                    "metric": "rns_conversions_per_pairing",
                    "value": resident_n,
                    "unit": "CRT boundary crossings per pairing trace",
                    "backend": jax.default_backend(),
                    "fp_backend": fp_backend,
                    "legacy_per_mul": legacy_n,
                    "batch": B,
                    "captured_at": now,
                }
            )
    os.makedirs(os.path.dirname(PAIRING_ARTIFACT), exist_ok=True)
    write_json_atomic(
        PAIRING_ARTIFACT,
        {
            "metric": "pairing_bench",
            "backend": jax.default_backend(),
            "device": str(jax.devices()[0]),
            "batch": B,
            "captured_at": now,
            "records": records,
        },
    )


def main() -> None:
    """Measure in this process (one process owns the chip)."""
    from handel_tpu.utils.jaxenv import apply_platform_env, enable_compile_cache

    apply_platform_env()  # no-op when HANDEL_TPU_PLATFORM is unset
    enable_compile_cache()
    import jax
    import numpy as np

    from handel_tpu.models.bn254 import BN254PublicKey
    from handel_tpu.models.bn254_jax import BN254Device
    from handel_tpu.ops.curve import BN254Curves

    backend = jax.default_backend()
    # test hook: exercise the FULL measurement path (persist, provenance,
    # vs_baseline ratio) on the CPU backend with tiny sizes
    # (tests/test_bench.py; round-3 verdict "What's weak" #1)
    force_shape = os.environ.get("HANDEL_TPU_BENCH_FORCE_ACCEL_SHAPE")
    if backend != "tpu" and not force_shape:
        print(
            f"bench: no TPU (jax backend {backend!r}): nothing to measure — "
            "a CPU run reports no device metric",
            file=sys.stderr,
        )
        raise SystemExit(1)
    if force_shape:
        if not os.environ.get("HANDEL_TPU_BENCH_ARTIFACT"):
            # a forced run writing the DEFAULT artifact path would clobber
            # the real captured TPU evidence with a cpu-backend record
            print(
                "bench: HANDEL_TPU_BENCH_FORCE_ACCEL_SHAPE requires "
                "HANDEL_TPU_BENCH_ARTIFACT to protect the default chip capture",
                file=sys.stderr,
            )
            raise SystemExit(2)
        try:
            n_registry, lanes, n_candidates, trials = (
                int(x) for x in force_shape.split(",")
            )
            if min(n_registry, lanes, n_candidates, trials) < 1:
                raise ValueError("all fields must be >= 1")
        except ValueError as e:
            print(
                f"bench: bad HANDEL_TPU_BENCH_FORCE_ACCEL_SHAPE "
                f"{force_shape!r} (want 'registry,lanes,candidates,trials'):"
                f" {e}",
                file=sys.stderr,
            )
            raise SystemExit(2) from e
    else:
        # the 4000-node scenario
        n_registry, lanes, n_candidates, trials = 4096, 128, 64, 10

    curves = BN254Curves()
    pks, miss_k, args = build_problem(curves, n_registry, lanes, n_candidates)
    device = BN254Device(
        [BN254PublicKey(p) for p in pks], batch_size=lanes, curves=curves
    )
    kernel = device._range_kernel(miss_k)

    # warmup (compile)
    verdicts = kernel(*args)
    verdicts.block_until_ready()
    ok = np.asarray(verdicts)[:n_candidates]
    assert ok.all(), f"bench batch failed verification: {ok}"

    times = []
    for _ in range(trials):
        t0 = time.perf_counter()
        kernel(*args).block_until_ready()
        times.append((time.perf_counter() - t0) * 1000.0)
    p50 = float(np.percentile(times, 50))

    # reference headline: 4000-sig aggregation ~900 ms (README.md:32-33)
    line = {
        "metric": f"{n_registry}sig_batch_verify_p50_ms",
        "value": round(p50, 3),
        "unit": "ms",
        "vs_baseline": round(REFERENCE_HEADLINE_MS / p50, 3),
        "backend": backend,
    }
    if force_shape:
        # a forced tiny-shape run must never read as a real accelerator
        # measurement on the one-line contract
        line["forced_shape"] = True
        line["vs_baseline"] = None
    # host half of the pipeline: packing + dedup metrics (host-side,
    # backend-independent — measured in-process, no extra launches)
    line.update(_host_metrics())
    # multi-tenant service plane: sustained aggregates/s + p99 session
    # completion + coalesced launch fill (protocol-layer, no kernels)
    line.update(_service_metrics())
    # fleet plane: K-lane DevicePlane scheduler throughput vs 1 lane
    line.update(_fleet_metrics())
    # latency plane: small gold-tier launches over the whole-mesh lane
    line.update(_small_batch_metrics())
    # vnode swarm: identities carried + bytes/identity + completion wall
    line.update(_swarm_metrics())
    # geo-federation robustness: open-loop p99 under a region kill,
    # recovery wall, spillover fraction (protocol-layer, no kernels)
    line.update(_federation_metrics())
    # hierarchical roll-up plane: O(hosts) fleet series count, wire
    # bytes/host/s, and master merge wall (obs/rollup.py)
    line.update(_rollup_metrics())
    # RLC batch-check plane: both check modes on every line, keyed per
    # fp_backend in bench_check (PER_FP_BACKEND) via the line's tag
    line["fp_backend"] = curves.F.backend
    line.update(_rlc_metrics())

    def persist(extra_line: dict) -> None:
        # provenance rides every persisted capture
        os.makedirs(os.path.dirname(ARTIFACT), exist_ok=True)
        write_json_atomic(
            ARTIFACT,
            {
                **extra_line,
                "backend": backend,
                "device": str(jax.devices()[0]),
                "device_count": jax.device_count(),
                "registry": n_registry,
                "lanes": lanes,
                "candidates": n_candidates,
                "trials_ms": [round(t, 3) for t in times],
                "captured_at": time.strftime(
                    "%Y-%m-%dT%H:%M:%SZ", time.gmtime()
                ),
            },
        )

    # pipelined sustained rate (measure_pipelined above)
    pipe_times = measure_pipelined(
        lambda: kernel(*args), lambda r: r.block_until_ready(), trials
    )
    pipe_p50 = float(np.percentile(pipe_times, 50))
    line["pipelined_p50_ms"] = round(pipe_p50, 3)
    line["pipelined_vs_baseline"] = (
        None if force_shape else round(REFERENCE_HEADLINE_MS / pipe_p50, 3)
    )
    persist(line)

    # headline line FIRST: the side benches below must not cost an
    # already-captured measurement
    _emit(line)
    sys.stdout.flush()
    try:
        _fp_microbench()
    except Exception as e:
        print(f"bench: fp microbench failed: {e}", file=sys.stderr)
    try:
        _pairing_bench()
    except Exception as e:
        print(f"bench: pairing bench failed: {e}", file=sys.stderr)


if __name__ == "__main__":
    main()
