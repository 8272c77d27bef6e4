"""Launch-path micro-smoke: 8 packed launches + batched combine, CPU tier.

The fast-tier guard for the zero-copy dispatch path (models/bn254_jax.py):
runs 8 packed launches through pack → rotated-staging handoff → on-device
registry aggregation (prefix gather + hole patch), checks every aggregate
key against the host oracle, runs the batched `combine_batch` entry against
host pairing-library folds.

Scope note: on one CPU core the pairing-tail kernels take minutes of XLA
each, so this smoke drives the AGGREGATION stage of the verify path — the
stage that consumes the registry/prefix residents and the staged launch
inputs; the identical staged arrays feed the pairing tail, which the slow
tier compiles and checks end to end (tests/test_bn254_device.py). Expected
wall: ~2 min of XLA compile on a cold cache, then milliseconds per launch.
"""

import os
import random
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# 8 virtual host devices — must land before jax initializes, so the
# devices-in-{1,8} parametrization below runs on a real multi-device
# topology (the same one conftest/multichip_smoke force)
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
from handel_tpu.models.bn254_jax import BN254Device  # noqa: E402
from handel_tpu.ops import bn254_ref as bn  # noqa: E402

N, C, LAUNCHES = 12, 4, 8
# plane sizes the fleet phase covers; override for a quick local run with
# HANDEL_TPU_SMOKE_DEVICES=1 (each pinned engine pays one XLA compile —
# persistent-cache-warm in CI after the first push)
DEVICE_COUNTS = tuple(
    int(x)
    for x in os.environ.get("HANDEL_TPU_SMOKE_DEVICES", "1,8").split(",")
)


def host_agg(pks, bs):
    acc = None
    for i in bs.indices():
        acc = pks[i].point if acc is None else bn.g2_add(acc, pks[i].point)
    return acc


def main() -> int:
    # share the persistent compile cache CI restores across runs (same dir
    # as the slow tier): warm pushes skip the XLA compiles
    enable_compile_cache()
    rng = random.Random(99)
    sks = [rng.randrange(1, 1 << 20) for _ in range(N)]
    pks = [BN254PublicKey(p) for p in nat.g2_mul_batch([bn.G2_GEN] * N, sks)]
    device = BN254Device(pks, batch_size=C)
    sig = BN254Signature(bn.G1_GEN)

    # warm the miss_k=8 aggregation class once so the 8 timed launches
    # measure steady state, not the cold XLA compile
    warm_bs = BitSet(N)
    for i in range(4):
        warm_bs.set(i, True)
    plan = device._pack_requests([(warm_bs, sig)])
    jax.block_until_ready(
        device._range_agg_kernel(plan.miss_k)(*device._stage_plan(plan)[:4])
    )
    device.reset_host_counters()

    # -- 8 packed launches through the staged aggregation path -------------
    t0 = time.perf_counter()
    checked = 0
    pack_ms = dispatch_ms = 0.0  # this loop's own hand-built launch path
    for launch in range(LAUNCHES):
        reqs = []
        for _ in range(C):
            size = rng.randrange(2, N)
            lo = rng.randrange(0, N - size + 1)
            holes = set(
                rng.sample(range(lo + 1, lo + size - 1), min(2, size - 2))
            )
            bs = BitSet(N)
            for i in range(lo, lo + size):
                if i not in holes:
                    bs.set(i, True)
            reqs.append((bs, sig))
        tp = time.perf_counter()
        plan = device._pack_requests(reqs)
        td = time.perf_counter()
        pack_ms += (td - tp) * 1000.0
        args = device._stage_plan(plan)
        agg = device._range_agg_kernel(plan.miss_k)(*args[:4])
        dispatch_ms += (time.perf_counter() - td) * 1000.0
        x, y, inf = device.curves.g2.to_affine(agg)
        xs = device.curves.T.f2_unpack(x)
        ys = device.curves.T.f2_unpack(y)
        infs = np.asarray(inf)
        for j, (bs, _) in enumerate(reqs):
            want = host_agg(pks, bs)
            got = None if infs[j] else (xs[j], ys[j])
            assert got == want, f"launch {launch} lane {j}: aggregate mismatch"
            checked += 1
    assert dispatch_ms > 0.0
    print(
        f"launch_smoke: {LAUNCHES} launches, {checked} aggregates verified "
        f"against the host oracle in {time.perf_counter() - t0:.1f}s "
        f"(pack {pack_ms / LAUNCHES:.3f} ms/launch, dispatch "
        f"{dispatch_ms / LAUNCHES:.3f} ms/launch)"
    )

    # -- fleet parametrization: the same staged aggregation on a plane of
    # k pinned engines, one launch per device, every aggregate against the
    # host oracle (devices in {1, 8}; 1 is the measured loop above) -------
    from handel_tpu.parallel.plane import bn254_plane

    for k in DEVICE_COUNTS:
        if k <= 1:
            continue  # the single-device loop above IS the k=1 phase
        plane = bn254_plane(pks, k, batch_size=C)
        t1 = time.perf_counter()
        fleet_checked = 0
        for lane in plane.lanes:
            eng = lane.engine
            reqs = []
            for _ in range(C):
                size = rng.randrange(2, N)
                lo = rng.randrange(0, N - size + 1)
                bs = BitSet(N)
                for i in range(lo, lo + size):
                    bs.set(i, True)
                reqs.append((bs, sig))
            plan = eng._pack_requests(reqs)
            agg = eng._range_agg_kernel(plan.miss_k)(
                *eng._stage_plan(plan)[:4]
            )
            placed = {b.device for b in jax.tree_util.tree_leaves(agg)}
            assert placed == {eng.jax_device}, (
                f"lane {lane.index}: launch ran on {placed}, "
                f"pinned to {eng.jax_device}"
            )
            lane.launches += 1
            x, y, inf = eng.curves.g2.to_affine(agg)
            xs = eng.curves.T.f2_unpack(x)
            ys = eng.curves.T.f2_unpack(y)
            infs = np.asarray(inf)
            for j, (bs, _) in enumerate(reqs):
                want = host_agg(pks, bs)
                got = None if infs[j] else (xs[j], ys[j])
                assert got == want, (
                    f"lane {lane.index} candidate {j}: aggregate mismatch"
                )
                fleet_checked += 1
        assert all(lane.launches >= 1 for lane in plane.lanes)
        print(
            f"launch_smoke: {k}-device plane, one pinned launch per "
            f"engine, {fleet_checked} aggregates verified in "
            f"{time.perf_counter() - t1:.1f}s"
        )

    # -- batched combine vs host pairing-library folds ---------------------
    pts = [bn.g1_mul(bn.G1_GEN, rng.randrange(1, bn.R)) for _ in range(8)]
    groups = [
        [rng.choice(pts) for _ in range(rng.randrange(2, 7))]
        for _ in range(2 * C)
    ]
    got = device.combine_batch(groups)
    for g, out in zip(groups, got):
        acc = g[0]
        for p in g[1:]:
            acc = bn.g1_add(acc, p)
        assert out == acc, "combine_batch mismatch vs host fold"
    print(f"launch_smoke: combine_batch verified on {len(groups)} groups")

    return 0


if __name__ == "__main__":
    sys.exit(main())
