"""Device-resident registry + zero-copy staging + batched-combine contracts.

The steady-state contract of the device-resident aggregation path
(models/bn254_jax.py): registry pubkeys and the prefix table are committed
to the device once, every per-launch input reaches the device through an
EXPLICIT `jax.device_put` of a rotated staging buffer, and therefore a
warm launch performs ZERO implicit host→device transfers — pinned here
under `jax.transfer_guard_host_to_device("disallow")` so device-residency
cannot silently regress (a stray `jnp.asarray(numpy)` in the hot path
fails these tests, not just a bench number).

Fast-tier by design: everything here drives the aggregation-stage kernels
(G1/G2 point adds, seconds-scale compiles) and the pack/stage layer. The
pairing-tail kernels — minutes of XLA on one core — stay slow-tier
(tests/test_bn254_device.py); they consume the same staged arrays, so the
transfer discipline proven here covers them.
"""

import random
from functools import partial

import jax
import numpy as np
import pytest

from handel_tpu import native as nat
from handel_tpu.core.bitset import BitSet
from handel_tpu.core.processing import CombineShim
from handel_tpu.models.bn254 import BN254PublicKey, BN254Signature
from handel_tpu.models.bn254_jax import BN254Device, BN254JaxConstructor
from handel_tpu.ops import bn254_ref as bn

N = 12  # small: the prefix scan / masked-sum compile cost scales with N
C = 4


@pytest.fixture(scope="module")
def device():
    rng = random.Random(5)
    sks = [rng.randrange(1, 1 << 20) for _ in range(N)]
    pks = [BN254PublicKey(p) for p in nat.g2_mul_batch([bn.G2_GEN] * N, sks)]
    return BN254Device(pks, batch_size=C)


def _range_requests(rng, k=C):
    sig = BN254Signature(bn.G1_GEN)
    reqs = []
    for _ in range(k):
        size = rng.randrange(2, N)
        lo = rng.randrange(0, N - size + 1)
        holes = set(rng.sample(range(lo + 1, lo + size - 1), min(2, size - 2)))
        bs = BitSet(N)
        for i in range(lo, lo + size):
            if i not in holes:
                bs.set(i, True)
        reqs.append((bs, sig))
    return reqs


def _host_agg(pks, bs):
    acc = None
    for i in bs.indices():
        acc = pks[i].point if acc is None else bn.g2_add(acc, pks[i].point)
    return acc


def test_steady_state_zero_implicit_transfers(device):
    """After warmup, a pack → stage → aggregate launch performs no implicit
    host→device transfer of registry/prefix (or any other) data; the
    explicit staging-buffer device_puts are the allowlist."""
    rng = random.Random(11)
    reqs = _range_requests(rng)
    # warm: build the prefix table and compile the aggregation kernel
    plan = device._pack_requests(reqs)
    args = device._stage_plan(plan)
    jax.block_until_ready(device._range_agg_kernel(plan.miss_k)(*args[:4]))

    for _ in range(3):  # several launches: rotation boundaries included
        reqs = _range_requests(rng)
        with jax.transfer_guard_host_to_device("disallow"):
            plan = device._pack_requests(reqs)
            args = device._stage_plan(plan)
            agg = device._range_agg_kernel(plan.miss_k)(*args[:4])
            jax.block_until_ready(agg)

    # the guard itself must bite on this backend, or the test proves nothing
    with pytest.raises(Exception, match="[Dd]isallowed"):
        with jax.transfer_guard_host_to_device("disallow"):
            device._range_agg_kernel(plan.miss_k)(
                np.asarray(args[0]).copy(), *args[1:4]
            )


def test_range_aggregate_matches_host(device):
    """The staged on-device aggregate (prefix gather + hole patch) equals
    the host oracle's G2 sum over each candidate's signers."""
    rng = random.Random(13)
    reqs = _range_requests(rng)
    plan = device._pack_requests(reqs)
    args = device._stage_plan(plan)
    agg = device._range_agg_kernel(plan.miss_k)(*args[:4])
    x, y, inf = device.curves.g2.to_affine(agg)
    xs = device.curves.T.f2_unpack(x)
    ys = device.curves.T.f2_unpack(y)
    infs = np.asarray(inf)
    for j, (bs, _) in enumerate(reqs):
        expect = _host_agg(device_pks(device), bs)
        if expect is None:
            assert infs[j]
        else:
            assert not infs[j] and (xs[j], ys[j]) == expect, j


def device_pks(device):
    """Registry points back from the device-resident arrays (round-trip
    through the committed copy, so the test reads what launches read)."""
    xs = device.curves.T.f2_unpack(device._reg_x)
    ys = device.curves.T.f2_unpack(device._reg_y)

    class _PK:
        __slots__ = ("point",)

        def __init__(self, p):
            self.point = p

    return [_PK((xs[i], ys[i])) for i in range(device.n)]


def test_unpack_words_matches_host_mask(device):
    """The dense kernel's on-device word unpack reproduces the host mask
    the old packer materialized, for random bitsets."""
    rng = random.Random(17)
    unpack = jax.jit(partial(device._unpack_words, n=N))
    for _ in range(5):
        words = np.zeros((C, (N + 63) // 64), np.uint64)
        valid = np.zeros((C,), bool)
        want = np.zeros((C, N), bool)
        for j in range(C):
            bs = BitSet(N)
            for i in rng.sample(range(N), rng.randrange(0, N)):
                bs.set(i, True)
            words[j] = bs.words()
            valid[j] = rng.random() < 0.8
            if valid[j]:
                for i in bs.indices():
                    want[j, i] = True
        got = np.asarray(
            unpack(
                jax.device_put(words.view(np.uint32)), jax.device_put(valid)
            )
        ).reshape(N, C)
        assert (got == want.T).all()


def test_epoch_flip_reaches_compiled_kernels():
    """Registry rotation vs the jitted-kernel cache: a kernel compiled
    under epoch 0 must answer for the NEW bank after `activate_staged`.
    The bank is a jit ARGUMENT (see _range_aggregate) — were it a closure
    read, the cached executable would bake the old prefix/registry in as
    compile-time constants and every post-flip launch would keep verifying
    against the retired validator set. Also pins the flip's residency: the
    staged bank was device_put at stage time, so the first post-flip
    launch performs no implicit host→device transfer."""
    rng = random.Random(31)

    def mk(seed):
        r = random.Random(seed)
        sks = [r.randrange(1, 1 << 20) for _ in range(N)]
        return [
            BN254PublicKey(p) for p in nat.g2_mul_batch([bn.G2_GEN] * N, sks)
        ]

    pks_a, pks_b = mk(37), mk(41)
    device = BN254Device(pks_a, batch_size=C)
    reqs = _range_requests(rng)

    def launch():
        plan = device._pack_requests(reqs)
        agg = device._range_agg_kernel(plan.miss_k)(
            *device._stage_plan(plan)[:4]
        )
        jax.block_until_ready(agg)
        return agg

    def aggs(agg=None):
        # the eager affine epilogue stays outside any transfer guard: it
        # uploads Python scalar constants, which is fine off the hot path
        agg = launch() if agg is None else agg
        x, y, inf = device.curves.g2.to_affine(agg)
        xs = device.curves.T.f2_unpack(x)
        ys = device.curves.T.f2_unpack(y)
        infs = np.asarray(inf)
        return [
            None if infs[j] else (xs[j], ys[j]) for j in range(len(reqs))
        ]

    assert all(
        g == _host_agg(pks_a, bs) for g, (bs, _) in zip(aggs(), reqs)
    )
    device.stage_registry(pks_b)
    # staged but not flipped: the compiled kernel still serves the old bank
    assert all(
        g == _host_agg(pks_a, bs) for g, (bs, _) in zip(aggs(), reqs)
    )
    assert device.activate_staged() == 1
    with jax.transfer_guard_host_to_device("disallow"):
        agg = launch()
    assert all(
        g == _host_agg(pks_b, bs) for g, (bs, _) in zip(aggs(agg), reqs)
    )


def test_combine_batch_matches_host(device):
    """combine_batch (one masked G1 tree-sum launch) equals the host
    pairing-library fold for random group shapes, including infinities,
    empty lanes, and widths across the power-of-two kernel classes."""
    rng = random.Random(19)
    pts = [bn.g1_mul(bn.G1_GEN, rng.randrange(1, bn.R)) for _ in range(12)]
    groups = [
        [rng.choice(pts + [None]) for _ in range(rng.randrange(1, 9))]
        for _ in range(2 * C + 1)  # > batch_size: exercises chunking
    ]
    got = device.combine_batch(groups)
    for g, out in zip(groups, got):
        acc = None
        for p in g:
            if p is not None:
                acc = p if acc is None else bn.g1_add(acc, p)
        assert out == acc, g


def test_staging_fence_blocks_before_reuse(device):
    """_pack_requests must wait on the fence of the staging set it reuses
    (the launch that last read those buffers), and clear it."""

    class Fence:
        waited = False

        def block_until_ready(self):
            self.waited = True

    fences = [Fence() for _ in device._stage]
    for st, f in zip(device._stage, fences):
        st.fence = f
    rng = random.Random(23)
    for i in range(len(fences)):
        nxt = (device._stage_idx + 1) % len(device._stage)
        device._pack_requests(_range_requests(rng))
        assert fences[nxt].waited
        assert device._stage[nxt].fence is None


def test_combine_shim_routing():
    """CombineShim: wide groups take one device launch, narrow ones fold on
    the host, a declining device degrades to host, and accumulate/flush
    resolves every queued group in a single combine_batch call."""
    calls = []

    def dev_combine(groups):
        calls.append([len(g) for g in groups])
        out = []
        for g in groups:
            acc = None
            for p in g:
                acc = p if acc is None else bn.g1_add(acc, p)
            out.append(acc)
        return out

    sigs = [
        BN254Signature(bn.g1_mul(bn.G1_GEN, k)) for k in (3, 5, 7, 11, 13)
    ]
    host = sigs[0]
    for s in sigs[1:]:
        host = host.combine(s)

    shim = CombineShim(dev_combine, min_device_points=4)
    assert shim.combine_many(sigs) == host  # wide: device
    assert calls == [[5]]
    assert shim.combine_many(sigs[:2]) == sigs[0].combine(sigs[1])  # narrow
    assert calls == [[5]]  # no new device call
    assert shim.combine_device_groups == 1 and shim.combine_host_groups == 1

    # accumulate-and-flush: both groups ride ONE device call
    shim.accumulate(sigs)
    shim.accumulate(sigs[1:])
    out = shim.flush()
    assert calls[-1] == [5, 4] and len(calls) == 2
    assert out[0] == host

    # device declines -> host fold, same result
    declining = CombineShim(lambda groups: None, min_device_points=2)
    assert declining.combine_many(sigs) == host
    assert declining.combine_host_groups == 1


def test_constructor_device_combine_lazy():
    """The constructor's device_combine hook declines (None) before the
    device exists — the shim must never force an eager registry upload —
    declines per-group while a width class is uncompiled (never a mid-round
    XLA compile), and serves real combines once the class is warm."""
    cons = BN254JaxConstructor(batch_size=2, warmup=False)
    assert cons.device_combine([[bn.G1_GEN, bn.G1_GEN]]) is None
    rng = random.Random(29)
    sks = [rng.randrange(1, 1 << 20) for _ in range(4)]
    pks = [BN254PublicKey(p) for p in nat.g2_mul_batch([bn.G2_GEN] * 4, sks)]
    cons.prepare(pks)
    # warmup=False: the k=2 class is not compiled -> per-group decline
    assert cons.device_combine([[bn.G1_GEN, bn.G1_GEN]]) == [None]
    cons._device.combine_batch([[bn.G1_GEN, bn.G1_GEN]])  # compiles k=2
    (got,) = cons.device_combine([[bn.G1_GEN, bn.G1_GEN]])
    assert got == bn.g1_add(bn.G1_GEN, bn.G1_GEN)
