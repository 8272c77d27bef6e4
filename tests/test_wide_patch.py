"""The wide range class: prefix hull minus an n // 4 wide hole patch.

A registry over 4 x MISS_CAP keys has a third range class (models/
bn254_jax.py `patch_widths`): a launch whose largest hole count is between
65 and n // 4 — a level range of a committee with its failing members
absent — is aggregated as `prefix[hi] - prefix[lo] - sum(holes)` instead of
the dense launch's masked tree sum over every registry key. Same group
element by another order of additions: pinned here against the dense stage
and the host oracle, lane for lane.

Fast-tier by design, like tests/test_device_residency.py: aggregation-stage
executables only (G2 point additions, no pairing graph), 4 lanes, and the
prefix table summed on the host so that its scan is not compiled here.
"""

import random

import jax
import numpy as np
import pytest

from handel_tpu import native as nat
from handel_tpu.core.bitset import BitSet
from handel_tpu.models.bn254 import BN254PublicKey, BN254Signature
from handel_tpu.models.bn254_jax import BN254Device
from handel_tpu.ops import bn254_ref as bn

N = 520  # n // 4 = 130 > MISS_CAP: the smallest ladder with room for a
C = 4    # top-level range (260 ids) to lose a quarter of its ids and 8 more


@pytest.fixture(scope="module")
def points():
    rng = random.Random(27)
    sks = [rng.randrange(1, 1 << 20) for _ in range(N)]
    return nat.g2_mul_batch([bn.G2_GEN] * N, sks)


@pytest.fixture(scope="module")
def device(points):
    """An engine whose prefix table was summed on the host (the scan's
    compile is tests/test_device_residency.py's, at 12 keys)."""
    dev = BN254Device([BN254PublicKey(p) for p in points], batch_size=C)
    T = dev.curves.T
    sums, acc = [], None
    for p in points:
        acc = p if acc is None else bn.g2_add(acc, p)
        sums.append(acc)
    zero = (0, 0)
    dev._prefix_cache = (
        T.f2_pack([zero] + [s[0] for s in sums]),
        T.f2_pack([zero] + [s[1] for s in sums]),
        jax.numpy.asarray([True] + [False] * N),
    )
    return dev


def level_candidates(rng, failing):
    """Aligned level ranges of n/2, n/4 and n/8 ids, each minus the failing
    ids inside it and 0-8 more; the last lane's bitset is empty."""
    sig = BN254Signature(bn.G1_GEN)
    reqs = []
    for size in (N // 2, N // 4, N // 8):
        lo = rng.randrange(N // size) * size
        alive = [i for i in range(lo, lo + size) if i not in failing]
        gone = set(rng.sample(alive, rng.randrange(0, 9)))
        bs = BitSet(N)
        for i in alive:
            if i not in gone:
                bs.set(i, True)
        reqs.append((bs, sig))
    reqs.append((BitSet(N), sig))
    return reqs


def host_affine(dev, agg):
    """Projective device points -> per-lane affine (x, y), None = infinity,
    with the host's field arithmetic."""
    X, Y, Z = (dev.curves.T.f2_unpack(c) for c in agg)
    out = []
    for x, y, z in zip(X, Y, Z):
        if z == (0, 0):
            out.append(None)
            continue
        zi = bn.f2_inv(z)
        out.append((bn.f2_mul(x, zi), bn.f2_mul(y, zi)))
    return out


@pytest.mark.parametrize("seed", [3, 4])
def test_wide_patch_equals_dense_sum(device, points, seed):
    rng = random.Random(seed)
    failing = set(rng.sample(range(N), N // 4))
    reqs = level_candidates(rng, failing)
    plan = device._pack_requests(reqs)
    holes = np.asarray(plan.miss_ok).sum(axis=0)
    assert (plan.kind, plan.miss_k) == ("range", N // 4), holes
    assert device.MISS_CAP < holes.max() <= N // 4  # what the class is for

    lo, hi, miss_idx, miss_ok = device._stage_plan(plan)[:4]
    wide = device._range_agg_kernel(plan.miss_k)(lo, hi, miss_idx, miss_ok)
    dense = jax.jit(device._dense_aggregate)(
        device._reg_x, device._reg_y,
        device._dput(plan.words.view(np.uint32)), device._dput(plan.valid),
    )
    got_wide, got_dense = host_affine(device, wide), host_affine(device, dense)
    for j, (bs, _) in enumerate(reqs):
        want = None
        for i in bs.indices():
            want = points[i] if want is None else bn.g2_add(want, points[i])
        assert got_wide[j] == got_dense[j] == want, j
    assert got_wide[-1] is None  # the empty lane reads as infinity
