"""The G1-keyed device class against the plain reference, through the service.

`BLS12381MinPkDevice` (models/bls12_381_jax.py) is the launch engine of
models/bn254_jax.py in the other group binding: registry, prefix table, hull
gather, hole patch and dense mask in G1; staging, H(m) and `combine_batch`
in G2; the pairing handed (aggregate key, H(m)) and (-B1, signature). Keys
and signatures come from the benchmark's own reference
(benchmark/reference/bls12_381_minpk.py), which also gives the verdicts
every launch class has to return through `BatchVerifierService.verify`.

Fast tier: everything of a launch runs on the device path as compiled —
pack, staging, gathers, the patch's tree sum, the dense sum, `to_affine`,
the lanes' assembly and negation — except `pairing.pairing_check`, which a
host callback answers with the scalar oracle's pairing (ops/bls12_381_ref)
ON THE LANES THE TAIL HANDS IT: a point in the wrong group, a missing
negation or a wrong lane order fails here. The Miller loop and the final
exponentiation themselves are the same program the G2-keyed class runs
(tests/test_bls12_381_jax.py, slow tier); their 24-limb graph compiles in
4.5 minutes on this CPU, a class. The slow test at the end is ONE real
launch with nothing replaced.
"""

import asyncio
import os
import random
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from handel_tpu.core.bitset import BitSet
from handel_tpu.models.bls12_381 import MinPkPublicKey, MinPkSignature
from handel_tpu.models.bls12_381_jax import BLS12381MinPkDevice
from handel_tpu.models.registry import new_scheme
from handel_tpu.ops import bls12_381_ref as bls
from handel_tpu.ops.curve import BLS12Curves
from handel_tpu.parallel.batch_verifier import BatchVerifierService

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "benchmark")
N = 264   # n // 4 = 66 > MISS_CAP: the smallest registries with a wide class
C = 4
MSG = b"handel-tpu benchmark round"


@pytest.fixture(scope="module")
def ref():
    sys.path.insert(0, BENCH)
    try:
        from reference import bls12_381_minpk
    finally:
        sys.path.remove(BENCH)
    bls12_381_minpk.load()
    return bls12_381_minpk


def oracle_pairing_check(dev):
    """`pairing_check(p, q, mask, groups)` answered on the host by the
    scalar oracle, lane for lane as the device's would be."""
    F, T = dev.curves.F, dev.curves.T

    def check(p, q, mask, groups):
        def on_host(p, q, mask):
            P = list(zip(F.unpack(p[0]), F.unpack(p[1])))
            Q = list(zip(T.f2_unpack(q[0]), T.f2_unpack(q[1])))
            # every live lane's G1 input is a point of G1 (an infinity
            # aggregate reads (0, 0) and is masked out)
            assert all(bls.g1_is_valid(P[i]) for i in np.flatnonzero(mask))
            return np.asarray([
                bls.pairing_check(
                    [(P[i], Q[i]) for i in (j, j + groups) if mask[i]])
                for j in range(groups)
            ])

        return jax.pure_callback(
            on_host, jax.ShapeDtypeStruct((groups,), jnp.bool_), p, q, mask)

    return check


def engine(points):
    scheme = new_scheme("bls12-381-minpk-jax", batch_size=C, warmup=False)
    dev = scheme.constructor.prepare([MinPkPublicKey(p) for p in points])
    assert type(dev) is BLS12381MinPkDevice
    dev.pairing.pairing_check = oracle_pairing_check(dev)
    return dev


@pytest.fixture(scope="module")
def committee(ref):
    sks, points = ref.keygen(random.Random(3500000011), N)
    # ids 6 and 7 cancel: a candidate of exactly these two aggregates to
    # infinity (no honest registry holds such a pair; the lane must read
    # False, not a verdict of the pairing)
    points[7] = (points[6][0], -points[6][1] % ref.P)
    sks[7] = -sks[6] % ref.R
    return sks, points


@pytest.fixture(scope="module")
def device(committee):
    return engine(committee[1])


class Cand:
    def __init__(self, ref, sks, lo, size, holes, forged=False):
        self.signers = [i for i in range(lo, lo + size) if i not in holes]
        k = sum(sks[i] for i in self.signers) % ref.R
        self.secret = (k + 1) % ref.R if forged else k
        self.lo, self.size, self.holes = lo, size, holes

    def request(self, sig):
        bs = BitSet(N)
        bs.set_range(self.lo, self.lo + self.size)
        for i in self.holes:
            bs.set(i, False)
        return (bs, MinPkSignature(sig))


def serve(dev, pubkeys, requests, msg=MSG):
    async def go():
        svc = BatchVerifierService(dev, fallback=None)
        try:
            return await svc.verify(msg, pubkeys, requests, session="s"), \
                svc.values()
        finally:
            svc.stop()

    return asyncio.run(go())


def run_class(ref, committee, device, shapes):
    """C candidates through the service; the reference's verdicts back."""
    sks, points = committee
    cands = [Cand(ref, sks, *shape) for shape in shapes]
    sigs = ref.sign_batch(MSG, [c.secret for c in cands])
    want = [ref.verify(MSG, points, c.signers, s) for c, s in zip(cands, sigs)]
    device.reset_host_counters()
    got, v = serve(device, None, [c.request(s) for c, s in zip(cands, sigs)])
    assert got == want
    assert v["failoverBatches"] == v["deviceRetryCt"] == 0.0
    assert v["keyGroup"] == 1.0 and v["fieldLimbs"] == 24.0
    return want, v


def holes(rng, lo, size, k):
    return tuple(sorted(rng.sample(range(lo + 1, lo + size - 1), k)))


# a launch's class is its largest hole count: 8, 64, n // 4 = 66, else dense
CLASSES = {
    "range8": ("launchesRange8", [
        (0, 128, lambda r: holes(r, 0, 128, 8)), (128, 64, lambda r: ()),
        (192, 8, lambda r: (194,)), (64, 64, lambda r: holes(r, 64, 64, 3))]),
    "range64": ("launchesRange64", [
        (0, 128, lambda r: holes(r, 0, 128, 64)), (128, 64, lambda r: ()),
        (192, 32, lambda r: holes(r, 192, 32, 9)), (64, 2, lambda r: ())]),
    "wide": ("launchesRangeWide", [
        (0, 128, lambda r: holes(r, 0, 128, 66)), (128, 128, lambda r: ()),
        (128, 64, lambda r: holes(r, 128, 64, 30)),
        (0, 256, lambda r: holes(r, 0, 256, 65))]),
    "dense": ("launchesDense", [
        (0, 256, lambda r: holes(r, 0, 256, 67)), (128, 64, lambda r: ()),
        (0, 128, lambda r: holes(r, 0, 128, 100)), (256, 8, lambda r: (258,))]),
}


@pytest.mark.parametrize("name", list(CLASSES))
def test_launch_class_returns_the_reference_verdicts(ref, committee, device,
                                                     name):
    counter, ranges = CLASSES[name]
    rng = random.Random(35 + list(CLASSES).index(name))
    shapes = [(lo, size, make(rng), j == 2)  # candidate 2 is forged
              for j, (lo, size, make) in enumerate(ranges)]
    want, v = run_class(ref, committee, device, shapes)
    assert want == [True, True, False, True]
    assert v[counter] == v["hostDispatchLaunches"] == 1.0, v
    assert v["aggFpMuls"] == device.agg_fp_muls > 0
    if name == "wide":
        assert v["patchSlots"] == 66 * C
        assert v["patchHoles"] == 66 + 0 + 30 + 65


def test_wrong_holes_empty_and_infinity_lanes(ref, committee, device):
    """A signature over another signer set than the bitset states is
    rejected; a lane with no signer and a lane whose keys sum to infinity
    read False; the launch's other lanes are not disturbed."""
    sks, points = committee
    honest = Cand(ref, sks, 32, 16, (35, 40))
    claims_fewer_holes = Cand(ref, sks, 32, 16, (35,))
    cancel = Cand(ref, sks, 6, 2, ())
    assert cancel.secret == 0
    s_honest, s_other = ref.sign_batch(MSG, [honest.secret, honest.secret + 5])
    requests = [
        honest.request(s_honest),
        claims_fewer_holes.request(s_honest),  # signed with 40 absent
        (BitSet(N), MinPkSignature(s_honest)),  # no signer at all
        cancel.request(s_other),                # aggregate key = infinity
    ]
    assert [ref.verify(MSG, points, c.signers, s) for c, s in (
        (honest, s_honest), (claims_fewer_holes, s_honest),
        (cancel, s_other))] == [True, False, False]
    got, _ = serve(device, None, requests)
    assert got == [True, False, False, False]
    # a signature that is infinity is an invalid lane as well, and the
    # single-message dispatch / fetch path answers as the service does
    requests[3] = honest.request(None)
    assert device.batch_verify(MSG, requests) == [True, False, False, False]


def test_mixed_message_launch_stages_h_per_lane(device):
    """`dispatch_multi` with several messages stages H(m) as (L, C) G2
    columns, lane j holding ITS message's point and pad lanes the last real
    one (the launch programs broadcast (L, 1) and (L, C) alike; another
    shape of H is another compile of the class, not another path)."""
    msgs = [MSG, b"another round", MSG]
    T = device.curves.T
    hx, hy = device._h_lanes(msgs)
    want = [
        device._hash_to_sig_group(m) for m in msgs + msgs[-1:]]
    assert len(want) == C
    assert list(zip(T.f2_unpack(hx), T.f2_unpack(hy))) == want
    # the single-message cache holds the same point as (L, 1) columns
    px, py = device._h_point(MSG)
    assert (T.f2_unpack(px), T.f2_unpack(py)) == ([want[0][0]], [want[0][1]])


def test_combine_batch_sums_signatures_in_g2(device):
    rng = random.Random(35)
    pts = [bls.g2_mul(bls.G2_GEN, rng.randrange(1, 1 << 24)) for _ in range(5)]
    groups = [pts[:3], [pts[3], None, pts[4]], [None], [pts[0], bls.g2_neg(pts[0])]]
    want = []
    for g in groups:
        acc = None
        for p in g:
            acc = bls.g2_add(acc, p)
        want.append(acc)
    assert want[2] is None and want[3] is None
    assert device.combine_batch(groups) == want
    # the CombineShim path declines a width nothing compiled
    assert device.combine_batch([pts * 2], compiled_only=True) == [None]


@pytest.mark.parametrize("option, kwargs", [
    ('batch_check="rlc"', {"batch_check": "rlc"}),
    ("mesh_devices > 1", {"mesh_devices": 2}),
    ('fp_backend="rns"', {"curves": BLS12Curves(backend="rns")}),
])
def test_unsupported_options_are_refused_at_construction(option, kwargs):
    """No silent run of G2-keyed code and no fallback: the engine says what
    it does not do in this binding, before anything is built."""
    keys = [MinPkPublicKey(bls.G1_GEN)] * 2
    with pytest.raises(ValueError, match="keys in G1") as e:
        BLS12381MinPkDevice(keys, batch_size=C, **kwargs)
    assert option in str(e.value)


def test_scheme_refuses_through_the_registry():
    keys = [MinPkPublicKey(bls.G1_GEN)] * 2
    for kw in ({"batch_check": "rlc"}, {"fp_backend": "rns"},
               {"mesh_devices": 2}):
        cons = new_scheme(
            "bls12-381-minpk-jax", batch_size=C, warmup=False, **kw
        ).constructor
        with pytest.raises(ValueError, match="does not support"):
            cons.prepare(keys)
    with pytest.raises(ValueError, match="G1"):
        BLS12381MinPkDevice([MinPkPublicKey(None)], batch_size=C)


def test_registry_rotation_serves_the_staged_keys(ref, committee, device):
    """LAST of the tests that share `device`: it leaves the engine on the
    staged keys (staging the committee's again would be another scan).

    `stage_registry` / `activate_staged` in the G1 binding: the staged
    bank and its prefix table answer after the flip, the old keys' signatures
    no longer verify, and an equal-size bank reuses the compiled launch."""
    sks, points = committee
    cand = Cand(ref, sks, 32, 16, (34, 41))
    (sig,) = ref.sign_batch(MSG, [cand.secret])
    req = [cand.request(sig)]
    assert device.batch_verify(MSG, req) == [True]
    # the prefix table of the bank, against the host's running sum
    acc, sums = None, [None]
    for p in points:
        acc = bls.g1_add(acc, p)
        sums.append(acc)
    assert device.kg.unpack_affine(*device._prefix) == sums

    sks2, points2 = ref.keygen(random.Random(3500000012), N)
    (sig2,) = ref.sign_batch(MSG, [Cand(ref, sks2, 32, 16, (34, 41)).secret])
    epoch = device.epoch
    assert device.stage_registry([MinPkPublicKey(p) for p in points2]) == N
    assert device.batch_verify(MSG, req) == [True]  # still the old bank
    assert device.activate_staged() == epoch + 1
    assert device.batch_verify(MSG, req + [cand.request(sig2)]) == [
        False, True]
    with pytest.raises(ValueError, match="G1"):
        device.stage_registry([MinPkPublicKey(None)])


# -- ONE real launch, nothing replaced (slow tier: a cold compile of the
# 24-limb pairing graph takes 4.5 minutes on this CPU) ------------------------


@pytest.mark.slow
def test_one_served_launch_with_the_device_pairing(ref):
    n = 16
    sks, points = ref.keygen(random.Random(3500000013), n)
    cands = [(0, 8, (2, 5)), (8, 8, ()), (4, 4, (6,)), (0, 16, (1, 9, 14))]
    made = []
    for j, (lo, size, hs) in enumerate(cands):
        signers = [i for i in range(lo, lo + size) if i not in hs]
        made.append((signers,
                     (sum(sks[i] for i in signers) + (j == 2)) % ref.R))
    sigs = ref.sign_batch(MSG, [k for _, k in made])
    want = [ref.verify(MSG, points, s, sig)
            for (s, _), sig in zip(made, sigs)]
    assert want == [True, True, False, True]
    requests = []
    for (lo, size, hs), sig in zip(cands, sigs):
        bs = BitSet(n)
        bs.set_range(lo, lo + size)
        for i in hs:
            bs.set(i, False)
        requests.append((bs, MinPkSignature(sig)))
    pubkeys = [MinPkPublicKey(p) for p in points]
    scheme = new_scheme("bls12-381-minpk-jax", batch_size=C, warmup=False)
    dev = scheme.constructor.prepare(pubkeys)
    got, v = serve(dev, pubkeys, requests)
    assert got == want
    assert v["keyGroup"] == 1.0 and v["launchesRange8"] == 1.0
    assert v["verifierCandidates"] == 4.0 and v["failoverBatches"] == 0.0
