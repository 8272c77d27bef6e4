"""Vectorized launch packing: equivalence with the per-candidate oracle.

The device packer (`BN254Device._pack_requests`) builds every launch input —
range bounds, missing-signer patch, dense mask, packed signature limbs —
with array-at-once numpy ops over the batch. It must be BIT-IDENTICAL to
the old per-candidate loop (`pack_requests_loop` below, the oracle) for
every signer-set shape: contiguous ranges, ranges with holes in every
quantization class (8, 64 and, where the registry has one, the wide class
of n // 4), scattered sets past the widest patch, empty bitsets, point-less
signatures, and partial batches.

Fast tier: packing is pure host numpy — nothing here compiles a kernel.
"""

import random
from functools import partial

import numpy as np
import pytest

from handel_tpu import native as nat
from handel_tpu.core.bitset import BitSet
from handel_tpu.models.bn254 import BN254PublicKey, BN254Signature
from handel_tpu.models.bn254_jax import BN254Device, LaunchPlan
from handel_tpu.ops import bn254_ref as bn
from handel_tpu.ops.fp import Field

N = 130  # > MISS_CAP + 3 so the dense fallback class is reachable
N_WIDE = 520  # n // 4 = 130 > MISS_CAP: a registry with a wide class
C = 8


def pack_requests_loop(device, requests) -> LaunchPlan:
    """The per-candidate packer `_pack_requests` replaced, kept as its
    oracle. Allocates fresh arrays (no staging); the launch class comes
    from the device's own `_patch_width`, so the ladder has one rule."""
    C = device.batch_size
    F = device.curves.F
    sig_pts = []
    valid = np.zeros((C,), dtype=bool)
    sets: list[np.ndarray] = []
    for j, (bs, sig) in enumerate(requests):
        if len(bs) != device.n:
            raise ValueError("bitset length != registry size")
        idx = np.fromiter(bs.indices(), dtype=np.int64)
        sig_pt = getattr(sig, "point", None)
        if idx.size and sig_pt is not None:
            valid[j] = True
            sig_pts.append(sig_pt)
        else:
            sig_pts.append(device.ref.G1_GEN)  # placeholder, lane masked out
        sets.append(idx)
    sig_pts += [device.ref.G1_GEN] * (C - len(sig_pts))  # pad lanes
    sig_x = F.pack([p[0] for p in sig_pts])
    sig_y = F.pack([p[1] for p in sig_pts])

    holes = [
        int(idx[-1] - idx[0] + 1 - idx.size) if v and idx.size else 0
        for idx, v in zip(sets, valid)
    ]
    miss_k = device._patch_width(max(holes, default=0))
    if not miss_k:
        mask = np.zeros((device.n, C), dtype=bool)
        for j, idx in enumerate(sets):
            if valid[j] and idx.size:
                mask[idx, j] = True
        return LaunchPlan(
            "dense", 0, None, None, None, None, None, mask,
            sig_x, sig_y, valid,
        )
    lo = np.zeros((C,), np.int32)
    hi = np.zeros((C,), np.int32)
    miss_idx = np.zeros((miss_k, C), np.int64)
    miss_ok = np.zeros((miss_k, C), dtype=bool)
    for j, idx in enumerate(sets):
        if not valid[j] or not idx.size:
            continue
        lo[j] = idx[0]
        hi[j] = idx[-1] + 1
        missing = np.setdiff1d(
            np.arange(idx[0], idx[-1] + 1), idx, assume_unique=True
        )
        miss_idx[: missing.size, j] = missing
        miss_ok[: missing.size, j] = True
    return LaunchPlan(
        "range", miss_k, lo, hi, miss_idx, miss_ok, None, None,
        sig_x, sig_y, valid,
    )


@pytest.fixture(scope="module", params=["per_candidate", "rlc", "wide"])
def device(request):
    """Both batch-check modes (models/rlc.py): launch packing is shared
    between the per-candidate and RLC launch classes, so every equivalence
    property below must hold identically under either device mode. "wide"
    is a per-candidate engine over a registry large enough for the third
    range class (patch width n // 4); the other two keep today's ladder."""
    n, mode = (N_WIDE, "per_candidate") if request.param == "wide" else (
        N, request.param)
    rng = random.Random(11)
    sks = [rng.randrange(1, 1 << 20) for _ in range(n)]
    pks = [BN254PublicKey(p) for p in nat.g2_mul_batch([bn.G2_GEN] * n, sks)]
    return BN254Device(pks, batch_size=C, batch_check=mode)


def _kinds(device):
    """The request kinds a random batch draws from: one per launch class
    the registry has, plus the two masked-lane shapes."""
    wide = ["wide"] if device.patch_widths[-1] > device.MISS_CAP else []
    return ["empty", "nosig", "range8", "range64", "dense"] + wide


def _rand_request(rng, kind, n=N):
    bs = BitSet(n)
    if kind == "empty":
        return (bs, BN254Signature(bn.G1_GEN))
    if kind == "nosig":
        for i in rng.sample(range(n), 5):
            bs.set(i, True)
        return (bs, object())  # no .point: lane must be masked out
    max_holes = {"range8": 9, "range64": 60, "wide": n // 4 + 1,
                 "dense": None}[kind]
    size = rng.randrange(1, n)
    lo = rng.randrange(0, n - size + 1)
    n_holes = rng.randrange(0, size if max_holes is None else min(size, max_holes))
    holes = set(rng.sample(range(lo, lo + size), n_holes))
    holes.discard(lo)  # keep the hull anchored so hole counts stay exact
    holes.discard(lo + size - 1)
    for i in range(lo, lo + size):
        if i not in holes:
            bs.set(i, True)
    return (bs, BN254Signature(bn.G1_GEN))


def _mask_of(plan, n):
    """Dense candidate mask of a plan in (n, C) layout, whichever source
    the plan carries: the loop oracle's host-built `mask`, or the
    vectorized plan's packed `words` (the device-transfer source — the
    kernel unpacks it on device with the same bit semantics)."""
    if plan.mask is not None:
        return np.asarray(plan.mask)
    bits = np.unpackbits(
        np.asarray(plan.words).view(np.uint8),
        axis=1,
        count=n,
        bitorder="little",
    ).view(np.bool_)
    return (bits & np.asarray(plan.valid)[:, None]).T


def _assert_plans_equal(a, b, ctx, n=N):
    assert a.kind == b.kind, ctx
    assert a.miss_k == b.miss_k, ctx
    for f in ("lo", "hi", "miss_idx", "miss_ok", "valid"):
        x, y = getattr(a, f), getattr(b, f)
        assert (x is None) == (y is None), (ctx, f)
        if x is not None:
            x, y = np.asarray(x), np.asarray(y)
            assert x.dtype == y.dtype, (ctx, f, x.dtype, y.dtype)
            assert x.shape == y.shape and (x == y).all(), (ctx, f)
    if a.kind == "dense":
        ma, mb = _mask_of(a, n), _mask_of(b, n)
        assert ma.shape == mb.shape and (ma == mb).all(), (ctx, "mask")
    for f in ("sig_x", "sig_y"):
        x, y = np.asarray(getattr(a, f)), np.asarray(getattr(b, f))
        assert x.dtype == y.dtype and (x == y).all(), (ctx, f)


_SNAP_FIELDS = ("lo", "hi", "miss_idx", "miss_ok", "words", "mask", "valid",
                "sig_x", "sig_y")


def _snap(plan):
    """Deep-copy a plan out of its staging views."""
    return plan._replace(
        **{
            f: np.asarray(getattr(plan, f)).copy()
            for f in _SNAP_FIELDS
            if getattr(plan, f) is not None
        }
    )


def test_pack_requests_matches_loop_property(device):
    """Random batches across all request shapes: the vectorized packer and
    the per-candidate loop must produce bit-identical device inputs."""
    rng = random.Random(23)
    kinds = _kinds(device)
    seen = set()
    for trial in range(120):
        reqs = [
            _rand_request(rng, rng.choice(kinds), device.n)
            for _ in range(rng.randrange(1, C + 1))
        ]
        vec = _snap(device._pack_requests(reqs))
        loop = pack_requests_loop(device, reqs)
        _assert_plans_equal(vec, loop, trial, device.n)
        seen.add((vec.kind, vec.miss_k))
    # every class of the registry's ladder was drawn
    assert seen == {("range", k) for k in device.patch_widths} | {("dense", 0)}


def test_pack_requests_rotation_boundary_property(device):
    """The double-buffered staging contract: across streams of consecutive
    launches, a plan's views must stay bit-identical to the loop oracle
    until the rotation wraps back onto its staging set — i.e. plan k is
    still valid while plan k+1 is packed, and is only invalidated by plan
    k + stage_sets. Verification is deliberately DEFERRED one launch: plan
    k is checked against the oracle after pack k+1 ran, unsnapshotted, so
    any buffer sharing between adjacent launches would corrupt it."""
    rng = random.Random(41)
    kinds = _kinds(device)
    n = device.n
    assert device.stage_sets >= 2  # the contract under test
    for trial in range(25):
        streams = [
            [
                _rand_request(rng, rng.choice(kinds), n)
                for _ in range(rng.randrange(1, C + 1))
            ]
            for _ in range(3 + trial % 3)  # >= 3 consecutive launches
        ]
        prev = None  # (reqs, live unsnapshotted plan)
        for reqs in streams:
            plan = device._pack_requests(reqs)
            if prev is not None:
                # the PREVIOUS plan's views survived this pack (other set)
                _assert_plans_equal(
                    _snap(prev[1]),
                    pack_requests_loop(device, prev[0]),
                    trial,
                    n,
                )
            prev = (reqs, plan)
        _assert_plans_equal(
            _snap(prev[1]), pack_requests_loop(device, prev[0]), trial, n
        )


def test_pack_requests_class_selection(device):
    """The class ladder, in the vectorised packer and in the loop alike:
    <=8 holes -> miss_k=8, <=64 -> miss_k=64, then — only for a registry
    whose n // 4 is over MISS_CAP — <= n // 4 -> the wide class, and dense
    past the widest patch. Registries of 256 keys or fewer keep the
    two-class ladder: 65 holes are dense there. `verify_dense` still serves
    every hull with more than n // 4 holes."""
    sig = BN254Signature(bn.G1_GEN)
    n = device.n

    def req_with_holes(n_holes):
        bs = BitSet(n)
        width = n_holes + 2
        for i in range(width):
            bs.set(i, True)
        for i in range(1, 1 + n_holes):
            bs.set(i, False)
        return (bs, sig)

    ladder = [(0, "range", 8), (8, "range", 8), (9, "range", 64),
              (64, "range", 64)]
    if n == N_WIDE:
        assert device.patch_widths == (8, 64, 130)
        ladder += [(65, "range", 130), (130, "range", 130), (131, "dense", 0),
                   (n - 2, "dense", 0)]
    else:
        assert device.patch_widths == (8, 64)
        ladder += [(65, "dense", 0), (n - 2, "dense", 0)]
    for n_holes, kind, miss_k in ladder:
        for pack in (device._pack_requests,
                     partial(pack_requests_loop, device)):
            plan = pack([req_with_holes(n_holes)])
            assert (plan.kind, plan.miss_k) == (kind, miss_k), n_holes
            if kind == "range":
                assert np.asarray(plan.miss_idx).shape == (miss_k, C)
                assert int(np.asarray(plan.miss_ok).sum()) == n_holes


@pytest.mark.parametrize("n,widths", [
    (8, (8, 64)), (256, (8, 64)), (259, (8, 64)), (260, (8, 64, 65)),
    (4096, (8, 64, 1024)),
])
def test_patch_widths_follow_the_registry(n, widths):
    """The wide width is read off the registry size (n // 4, only where
    that is over MISS_CAP) — not an option, not a per-cell width. Checked
    on the ladder rule itself: no engine, no keys."""
    eng = BN254Device.__new__(BN254Device)
    eng.n = n
    assert eng.patch_widths == widths
    assert [eng._patch_width(h) for h in (0, 8, 9, 64)] == [8, 8, 64, 64]
    wide = widths[-1]
    assert eng._patch_width(wide) == wide and eng._patch_width(wide + 1) == 0


def test_pack_requests_rejects_wrong_length(device):
    bs = BitSet(device.n + 1)
    bs.set(0, True)
    with pytest.raises(ValueError, match="bitset length"):
        device._pack_requests([(bs, BN254Signature(bn.G1_GEN))])
    with pytest.raises(ValueError, match="bitset length"):
        pack_requests_loop(device, [(bs, BN254Signature(bn.G1_GEN))])


def test_field_pack_batch_matches_pack():
    """The array-at-once limb packer is bit-identical to the per-element
    reference for random field elements, in and out of Montgomery form."""
    F = Field(bn.P)
    rng = random.Random(7)
    xs = [rng.randrange(0, bn.P) for _ in range(64)] + [0, 1, bn.P - 1]
    for mont in (True, False):
        a = np.asarray(F.pack(xs, mont=mont))
        b = np.asarray(F.pack_batch(xs, mont=mont))
        assert a.dtype == b.dtype and a.shape == b.shape
        assert (a == b).all()


def test_batch_verify_bounds_dispatch_window(device, monkeypatch):
    """batch_verify never runs more than MAX_DISPATCH_AHEAD chunks ahead of
    the fetch cursor (ADVICE r5 #3: an unbounded window kept every chunk's
    upload buffers resident on device simultaneously)."""
    in_flight = {"now": 0, "max": 0}
    serial = iter(range(1000))

    def fake_dispatch(msg, reqs):
        in_flight["now"] += 1
        in_flight["max"] = max(in_flight["max"], in_flight["now"])
        return ("h", next(serial), len(reqs))

    def fake_fetch(handle):
        in_flight["now"] -= 1
        return [True] * handle[2]

    monkeypatch.setattr(device, "dispatch", fake_dispatch)
    monkeypatch.setattr(device, "fetch", fake_fetch)
    bs = BitSet(device.n)
    bs.set(0, True)
    reqs = [(bs, BN254Signature(bn.G1_GEN))] * (C * 12)
    out = device.batch_verify(b"m", reqs)
    assert len(out) == C * 12
    assert in_flight["max"] <= device.MAX_DISPATCH_AHEAD
    assert in_flight["now"] == 0


def test_batch_check_mode_validated_and_routed(device):
    """The device carries its validated check mode; rlc-mode dispatch
    returns the rlc handle shape without compiling anything when the
    launch has at most one valid candidate (no combined pre-launch)."""
    assert device.batch_check in ("per_candidate", "rlc")
    with pytest.raises(ValueError, match="per_candidate.*rlc"):
        BN254Device(
            [BN254PublicKey(bn.G2_GEN)], batch_size=1, batch_check="bogus"
        )
    if device.batch_check != "rlc":
        return
    bs = BitSet(device.n)  # empty bitset: candidate invalid, nothing pre-launched
    handle = device.dispatch(b"m", [(bs, BN254Signature(bn.G1_GEN))])
    assert handle[0] == "rlc" and handle[3] is None
    assert device.fetch(handle) == [False]
