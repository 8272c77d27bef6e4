"""Fleet-of-chips verify-plane smoke: 8 forced host devices, end to end.

Promotes the MULTICHIP dryrun to a CI gate over the wired fleet path
(parallel/plane.py DevicePlane + the per-lane dispatch queues in
parallel/batch_verifier.py). Four phases:

1. Kernel fleet: 8 `BN254Device` engines pinned to distinct jax devices
   (`bn254_plane`), each committing the registry to its own chip, driving
   the AGGREGATION stage only (the same scope note as launch_smoke.py —
   pairing tails are the slow tier's job) with the launch-smoke shape
   (N=12, C=4) so the XLA persistent cache is shared with that gate.
   Every aggregate key is checked against the host oracle and every
   device must execute >= 1 launch.
2. Service fleet: a DevicePlane of 8 host-math engines behind ONE
   BatchVerifierService — every lane must dispatch >= 1 launch and every
   verdict must match the scheme's own serial batch_verify.
3. Degraded fleet: lane 0's breaker forced open before start — the run
   must complete on the 7 healthy lanes and lane 0 must launch nothing.
4. Latency plane (parallel/mesh_plane.py), two sub-gates:
   a. Mesh kernel: ONE `BN254Device(mesh_devices=8)` spanning all 8
      forced host devices drives a batch-8 launch through BOTH whole-mesh
      aggregation entries — the range class (`_range_agg_kernel`) and the
      dense masked-sum class (`_sharded_sum`, via the rule-placed padded
      mask exactly as `_run_plan` stages it; the registry size is chosen
      indivisible by 8 so the edge-padded shard boundary is live) — and
      every aggregate must match the host oracle bit-exactly.
   b. Mode pick: a dual-mode service (throughput HostDevice lanes + a
      HostMeshDevice mesh lane) must route a small gold-tier group to the
      mesh lane and a bulk standard-tier flood to the per-lane path, with
      verdicts matching the scheme and zero mesh fallbacks.
"""

import os
import random
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# 8 virtual host devices — must land before jax initializes its backends
from handel_tpu.utils.jaxenv import (  # noqa: E402
    apply_platform_env,
    enable_compile_cache,
)

os.environ.setdefault("HANDEL_TPU_PLATFORM", "cpu")
apply_platform_env(force_host_device_count=8)

import jax  # noqa: E402
import numpy as np  # noqa: E402

from handel_tpu import native as nat  # noqa: E402
from handel_tpu.core.bitset import BitSet  # noqa: E402
from handel_tpu.models.bn254 import BN254PublicKey, BN254Signature  # noqa: E402
from handel_tpu.ops import bn254_ref as bn  # noqa: E402

N, C, DEVICES = 12, 4, 8


def host_agg(pks, bs):
    acc = None
    for i in bs.indices():
        acc = pks[i].point if acc is None else bn.g2_add(acc, pks[i].point)
    return acc


def kernel_fleet_smoke() -> None:
    """Phase 1: one aggregation launch per pinned BN254 engine, aggregate
    keys vs the host oracle, every device dispatched."""
    from handel_tpu.parallel.plane import bn254_plane

    enable_compile_cache()
    rng = random.Random(99)
    sks = [rng.randrange(1, 1 << 20) for _ in range(N)]
    pks = [BN254PublicKey(p) for p in nat.g2_mul_batch([bn.G2_GEN] * N, sks)]
    sig = BN254Signature(bn.G1_GEN)
    assert len(jax.devices()) >= DEVICES, (
        f"forced host device count not applied: {len(jax.devices())}"
    )
    plane = bn254_plane(pks, DEVICES, batch_size=C)

    t0 = time.perf_counter()
    checked = 0
    for lane in plane.lanes:
        device = lane.engine
        reqs = []
        for _ in range(C):
            size = rng.randrange(2, N)
            lo = rng.randrange(0, N - size + 1)
            bs = BitSet(N)
            for i in range(lo, lo + size):
                bs.set(i, True)
            reqs.append((bs, sig))
        plan = device._pack_requests(reqs)
        args = device._stage_plan(plan)
        agg = device._range_agg_kernel(plan.miss_k)(*args[:4])
        # the launch must have executed on THIS lane's pinned chip
        devs = {b.device for b in jax.tree_util.tree_leaves(agg)}
        assert devs == {device.jax_device}, (
            f"lane {lane.index}: launch ran on {devs}, "
            f"pinned to {device.jax_device}"
        )
        lane.launches += 1
        x, y, inf = device.curves.g2.to_affine(agg)
        xs = device.curves.T.f2_unpack(x)
        ys = device.curves.T.f2_unpack(y)
        infs = np.asarray(inf)
        for j, (bs, _) in enumerate(reqs):
            want = host_agg(pks, bs)
            got = None if infs[j] else (xs[j], ys[j])
            assert got == want, (
                f"lane {lane.index} candidate {j}: aggregate mismatch"
            )
            checked += 1
    assert all(lane.launches >= 1 for lane in plane.lanes)
    print(
        f"multichip_smoke: {DEVICES} pinned engines, {checked} aggregates "
        f"verified against the host oracle in "
        f"{time.perf_counter() - t0:.1f}s"
    )


def _service_run(trip_lane: int | None = None) -> dict:
    """One fleet service run over 8 host-math lanes; returns per-lane
    launch counts + verdict check. trip_lane forces that lane's breaker
    open before the service starts."""
    import asyncio
    import concurrent.futures

    from handel_tpu.core.test_harness import FakeScheme
    from handel_tpu.models.fake import FakePublic, FakeSignature
    from handel_tpu.parallel.batch_verifier import BatchVerifierService
    from handel_tpu.parallel.plane import DevicePlane
    from handel_tpu.service.driver import HostDevice
    from handel_tpu.utils.breaker import CircuitBreaker

    scheme = FakeScheme()
    pks = [FakePublic(True) for _ in range(16)]
    engines = [
        HostDevice(scheme.constructor, batch_size=4, launch_ms=2.0)
        for _ in range(DEVICES)
    ]
    breakers = [
        CircuitBreaker(cooldown_s=600.0) for _ in range(DEVICES)
    ]
    plane = DevicePlane(engines, breakers=breakers)
    if trip_lane is not None:
        br = plane.lanes[trip_lane].breaker
        for _ in range(br.threshold):
            br.record_failure()
        assert not br.allow()

    reqs = []
    for i in range(96):
        b = BitSet(16)
        b.set(i % 16, True)
        # an invalid signature every 8th request: the verdict check below
        # must see the scheme's own False, not a blanket True
        reqs.append(
            (i.to_bytes(4, "big"), (b, FakeSignature(i % 8 != 7)))
        )
    want = [
        scheme.constructor.batch_verify(msg, pks, [r])[0]
        for msg, r in reqs
    ]

    async def go():
        loop = asyncio.get_running_loop()
        loop.set_default_executor(
            concurrent.futures.ThreadPoolExecutor(
                max_workers=2 * DEVICES + 4
            )
        )
        svc = BatchVerifierService(plane, max_delay_ms=0.2)
        try:
            got = await asyncio.gather(
                *(
                    svc.verify(msg, pks, [r], session=f"s{i % 4}")
                    for i, (msg, r) in enumerate(reqs)
                )
            )
            return [v[0] for v in got], svc.values()
        finally:
            svc.stop()

    got, vals = asyncio.run(go())
    assert got == want, "fleet verdicts diverge from the host scheme"
    return {
        "per_lane": [lane.engine.dispatched for lane in plane.lanes],
        "values": vals,
    }


def service_fleet_smoke() -> None:
    """Phase 2: all 8 lanes dispatch, verdicts match the host scheme."""
    out = _service_run()
    per_lane = out["per_lane"]
    assert all(n >= 1 for n in per_lane), (
        f"idle lane in a flooded fleet: {per_lane}"
    )
    print(
        f"multichip_smoke: service fleet per-lane launches {per_lane}, "
        f"fill {out['values']['launchFillRatio']:.2f}"
    )


def degraded_fleet_smoke() -> None:
    """Phase 3: breaker-open on lane 0 degrades to the 7 healthy lanes."""
    out = _service_run(trip_lane=0)
    per_lane = out["per_lane"]
    assert per_lane[0] == 0, (
        f"breaker-open lane 0 still dispatched: {per_lane}"
    )
    assert all(n >= 1 for n in per_lane[1:]), (
        f"healthy lane idle in degraded fleet: {per_lane}"
    )
    assert out["values"]["devicesAvailable"] == DEVICES - 1
    assert out["values"]["failoverBatches"] == 0.0
    print(
        f"multichip_smoke: degraded fleet completed on {DEVICES - 1} "
        f"lanes, per-lane launches {per_lane}"
    )


def mesh_kernel_smoke() -> None:
    """Phase 4a: one whole-mesh engine, batch-8 launch, both aggregation
    classes bit-exact vs the host oracle across the edge-padded registry
    shard boundary."""
    from handel_tpu.parallel.mesh_plane import bn254_mesh_engine

    # registry indivisible by the mesh width: 70 % 8 = 6, so the last
    # registry shard carries 2 padded identity rows — the boundary the
    # sharding tests call out
    n_mesh, c = 70, 8
    rng = random.Random(7)
    sks = [rng.randrange(1, 1 << 20) for _ in range(n_mesh)]
    pks = [
        BN254PublicKey(p)
        for p in nat.g2_mul_batch([bn.G2_GEN] * n_mesh, sks)
    ]
    sig = BN254Signature(bn.G1_GEN)
    eng = bn254_mesh_engine(pks, DEVICES, batch_size=c)
    assert eng.mesh is not None and eng._mesh_pad == 2, (
        f"mesh pad not live: pad={eng._mesh_pad}"
    )
    t0 = time.perf_counter()

    def check(plan, agg, reqs, label):
        x, y, inf = eng.curves.g2.to_affine(agg)
        xs = eng.curves.T.f2_unpack(x)
        ys = eng.curves.T.f2_unpack(y)
        infs = np.asarray(inf)
        for j, (bs, _) in enumerate(reqs):
            want = host_agg(pks, bs)
            got = None if infs[j] else (xs[j], ys[j])
            assert got == want, (
                f"mesh {label} candidate {j}: aggregate mismatch"
            )

    # range class: contiguous signer windows -> _range_agg_kernel over the
    # mesh-resident prefix table
    reqs = []
    for _ in range(c):
        size = rng.randrange(2, 16)
        lo = rng.randrange(0, n_mesh - size + 1)
        bs = BitSet(n_mesh)
        for i in range(lo, lo + size):
            bs.set(i, True)
        reqs.append((bs, sig))
    plan = eng._pack_requests(reqs)
    assert plan.kind == "range", plan.kind
    staged = eng._stage_plan(plan)
    agg = eng._range_agg_kernel(plan.miss_k)(*staged[:4])
    check(plan, agg, reqs, "range")

    # dense class: sparse signers across the full hull (> MISS_CAP holes)
    # -> the rule-placed padded mask into _sharded_sum, exactly the
    # staging _run_plan performs
    reqs = []
    for _ in range(c):
        bs = BitSet(n_mesh)
        bs.set(0, True)
        bs.set(n_mesh - 1, True)
        for i in rng.sample(range(1, n_mesh - 1), 3):
            bs.set(i, True)
        reqs.append((bs, sig))
    plan = eng._pack_requests(reqs)
    assert plan.kind == "dense", plan.kind
    mask = (
        np.unpackbits(
            plan.words.view(np.uint8), axis=1, count=n_mesh,
            bitorder="little",
        )
        .view(np.bool_)
        .T.copy()
    )
    mask = np.pad(mask, ((0, eng._mesh_pad), (0, 0)))
    mask = eng._mesh_put["mask"](mask)
    (rx0, rx1), (ry0, ry1) = eng._reg_sharded
    agg = eng._sharded_sum(rx0, rx1, ry0, ry1, mask)
    check(plan, agg, reqs, "dense")
    print(
        f"multichip_smoke: whole-mesh engine over {DEVICES} devices, "
        f"2x{c} aggregates (range + edge-padded dense) bit-exact vs the "
        f"host oracle in {time.perf_counter() - t0:.1f}s"
    )


def mode_pick_smoke() -> None:
    """Phase 4b: gold/small -> mesh lane, bulk -> per-lane, verdicts exact,
    zero fallbacks."""
    import asyncio
    import concurrent.futures

    from handel_tpu.core.test_harness import FakeScheme
    from handel_tpu.models.fake import FakePublic, FakeSignature
    from handel_tpu.parallel.batch_verifier import BatchVerifierService
    from handel_tpu.parallel.mesh_plane import (
        ModePolicy,
        enable_latency_plane,
        host_mesh_engine,
    )
    from handel_tpu.parallel.plane import host_plane

    scheme = FakeScheme()
    pks = [FakePublic(True) for _ in range(16)]
    # lane batch == mesh batch: the collector plans launch groups at the
    # throughput batch size, so a smaller lane batch would split the
    # 8-candidate gold group and the second half would find the mesh busy
    plane = host_plane(scheme.constructor, 2, batch_size=8, launch_ms=1.0)
    mesh_eng = host_mesh_engine(
        scheme.constructor, devices=DEVICES, batch_size=8,
        per_candidate_ms=0.2,
    )

    # bulk flood: distinct messages, default (standard) tier, every 8th
    # signature invalid so the verdict check is live
    bulk = []
    for i in range(48):
        b = BitSet(16)
        b.set(i % 16, True)
        bulk.append(
            (i.to_bytes(4, "big"), (b, FakeSignature(i % 8 != 7)))
        )
    want_bulk = [
        scheme.constructor.batch_verify(msg, pks, [r])[0]
        for msg, r in bulk
    ]
    # small gold group: one message, 8 distinct candidates
    gold = []
    for i in range(8):
        b = BitSet(16)
        b.set(i, True)
        gold.append((b, FakeSignature(True)))

    async def go():
        loop = asyncio.get_running_loop()
        loop.set_default_executor(
            concurrent.futures.ThreadPoolExecutor(max_workers=24)
        )
        svc = BatchVerifierService(plane, max_delay_ms=0.2)
        enable_latency_plane(
            svc, mesh_eng, policy=ModePolicy(small_batch_max=8)
        )
        svc.queue.set_tier("gold0", "gold")
        try:
            got_gold = await asyncio.gather(
                *(
                    svc.verify(b"gold-round", pks, [q], session="gold0")
                    for q in gold
                )
            )
            got_bulk = await asyncio.gather(
                *(
                    svc.verify(msg, pks, [r], session=f"s{i % 4}")
                    for i, (msg, r) in enumerate(bulk)
                )
            )
            return [v[0] for v in got_gold], [v[0] for v in got_bulk], (
                svc.values()
            )
        finally:
            svc.stop()

    got_gold, got_bulk, vals = asyncio.run(go())
    assert all(got_gold), "gold-tier mesh verdicts diverge"
    assert got_bulk == want_bulk, "bulk verdicts diverge from the scheme"
    assert vals["meshLanes"] == 1.0 and vals["meshLanesAvailable"] == 1.0
    assert mesh_eng.mesh_launches >= 1, (
        "small gold-tier group never rode the mesh lane"
    )
    assert vals["modeLatencyLaunches"] >= 1.0, vals
    assert vals["modeThroughputLaunches"] >= 1.0, (
        f"bulk flood never took the per-lane path: {vals}"
    )
    assert vals["meshFallbacks"] == 0.0, vals
    per_lane = [l.engine.dispatched for l in plane.lanes if not l.mesh]
    assert all(n >= 1 for n in per_lane), (
        f"idle throughput lane under the bulk flood: {per_lane}"
    )
    print(
        f"multichip_smoke: mode pick — "
        f"{vals['modeLatencyLaunches']:.0f} latency launches "
        f"({mesh_eng.mesh_candidates} candidates on the mesh), "
        f"{vals['modeThroughputLaunches']:.0f} throughput launches "
        f"across lanes {per_lane}, 0 fallbacks"
    )


def main() -> int:
    kernel_fleet_smoke()
    service_fleet_smoke()
    degraded_fleet_smoke()
    mesh_kernel_smoke()
    mode_pick_smoke()
    return 0


if __name__ == "__main__":
    sys.exit(main())
