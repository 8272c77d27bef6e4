"""BLS12-381 device kernels vs the scalar oracle.

The second device curve (ops/pairing.py `BLS12Pairing`,
models/bls12_381_jax.py) validated bit-exactly against
ops/bls12_381_ref.py — same strategy as tests/test_pairing_jax.py: shared
B=4 shapes so every graph compiles once into the persistent cache.

Where the reference offers two interchangeable BN256 backends
(bn256/go/bn256.go, bn256/cf/bn256.go), this framework offers two device
curves behind one Constructor registry (simul/lib/config.go:211-225).
"""

import asyncio
import os
import random
import signal
import sys
import time

import jax
import numpy as np
import jax.numpy as jnp
import pytest

# slow tier: XLA-compile-bound (381-bit kernel graphs) — runs in
# test-slow/test-all (nightly/CI); the fast tier keeps the oracle +
# protocol + sharding guards
pytestmark = pytest.mark.slow

from handel_tpu.ops import bls12_381_ref as bls
from handel_tpu.ops.curve import BLS12Curves
from handel_tpu.ops.pairing import BLS12Pairing

B = 4  # lane count shared by every test


@pytest.fixture(scope="module", params=["cios", "rns"])
def stack(request):
    """Both Field backends; the rns param runs the residue-resident
    pairing (BLS12-381 bound walk: M-type twist lines, the z-power
    conjugate chain) against the same oracle assertions."""
    curves = BLS12Curves(backend=request.param)
    return curves, BLS12Pairing(curves)


def _rand_points(seed):
    rng = random.Random(seed)
    ks = [rng.randrange(1, bls.R) for _ in range(B)]
    ls = [rng.randrange(1, bls.R) for _ in range(B)]
    g1s = [bls.g1_mul(bls.G1_GEN, k) for k in ks]
    g2s = [bls.g2_mul(bls.G2_GEN, l) for l in ls]
    return ks, ls, g1s, g2s


def _pack_pairs(curves, g1s, g2s):
    xp = curves.F.pack([p[0] for p in g1s])
    yp = curves.F.pack([p[1] for p in g1s])
    xq = curves.T.f2_pack([q[0] for q in g2s])
    yq = curves.T.f2_pack([q[1] for q in g2s])
    return (xp, yp), (xq, yq)


def test_curve_ops_match_oracle(stack):
    curves, _ = stack
    _, _, g1s, g2s = _rand_points(2)
    P = curves.pack_g1(g1s)
    assert curves.unpack_g1(curves.g1.double(P)) == [
        bls.g1_add(p, p) for p in g1s
    ]
    Q = curves.pack_g2(g2s)
    assert curves.unpack_g2(curves.g2.add(Q, Q)) == [
        bls.g2_add(q, q) for q in g2s
    ]
    assert np.asarray(curves.g1.on_curve(P)).all()
    assert np.asarray(curves.g2.on_curve(Q)).all()


def test_pairing_matches_oracle(stack):
    curves, pr = stack
    _, _, g1s, g2s = _rand_points(3)
    p, q = _pack_pairs(curves, g1s, g2s)
    f = jax.jit(lambda p, q: pr.miller_loop(p, q))(p, q)
    got = curves.T.f12_unpack(f)
    exp = [bls.miller_loop(q_, p_) for p_, q_ in zip(g1s, g2s)]
    assert got == exp
    e = jax.jit(pr.final_exp)(f)
    assert curves.T.f12_unpack(e) == [bls.final_exponentiation(x) for x in exp]


def test_pairing_check_bls_verify(stack):
    """e(H, X_j) * e(-S_j, B2) == 1 for valid BLS signatures; corrupt lane
    rejected (bls12_381_ref.pairing_check device form)."""
    curves, pr = stack
    rng = random.Random(11)
    F, T = curves.F, curves.T
    h = bls.g1_mul(bls.G1_GEN, rng.randrange(1, bls.R))  # H(m)
    sks = [rng.randrange(1, bls.R) for _ in range(B)]
    pks = [bls.g2_mul(bls.G2_GEN, sk) for sk in sks]
    sigs = [bls.g1_mul(h, sk) for sk in sks]
    sigs[B - 1] = bls.g1_mul(bls.G1_GEN, 777)  # corrupt last lane

    px = F.pack([h[0]] * B + [bls.g1_neg(s)[0] for s in sigs])
    py = F.pack([h[1]] * B + [bls.g1_neg(s)[1] for s in sigs])
    qx = T.f2_pack([pk[0] for pk in pks] + [bls.G2_GEN[0]] * B)
    qy = T.f2_pack([pk[1] for pk in pks] + [bls.G2_GEN[1]] * B)
    mask = jnp.ones((2 * B,), bool)
    verdicts = np.asarray(
        jax.jit(lambda p, q, m: pr.pairing_check(p, q, m, B))(
            (px, py), (qx, qy), mask
        )
    )
    assert verdicts.tolist() == [True] * (B - 1) + [False]


def test_device_scheme_batch_verify():
    """models/bls12_381_jax.py end-to-end: host keygen/sign, device verify
    through the Constructor interface (batch of 4: 3 valid + 1 forged)."""
    from handel_tpu.core.bitset import BitSet
    from handel_tpu.models.bls12_381 import BLS12381Signature, new_keypair
    from handel_tpu.models.bls12_381_jax import BLS12381JaxConstructor

    rng = random.Random(13)
    N = 8
    keys = [new_keypair(seed=i) for i in range(N)]
    pks = [pk for _, pk in keys]
    msg = b"bls12-381 device e2e"
    reqs, expect = [], []
    for j in range(B):
        signers = sorted(rng.sample(range(N), rng.randrange(2, N)))
        bs = BitSet(N)
        sig = None
        for i in signers:
            bs.set(i, True)
            s = keys[i][0].sign(msg)
            sig = s if sig is None else sig.combine(s)
        if j == B - 1:
            sig = BLS12381Signature(bls.g1_mul(bls.G1_GEN, 12345))
            expect.append(False)
        else:
            expect.append(True)
        reqs.append((bs, sig))
    cons = BLS12381JaxConstructor(batch_size=B)
    assert cons.batch_verify(msg, pks, reqs) == expect


def test_scheme_registry_dispatch():
    from handel_tpu.models.registry import new_scheme

    scheme = new_scheme("bls12-381-jax", batch_size=4)
    sk, pk = scheme.keygen(0)
    assert scheme.unmarshal_public(pk.marshal()).point == pk.point


# -- ONE real launch through the served path -----------------------------------
# `BatchVerifierService` over a `BLS12381Device` (16 keys, 4 lanes): a range
# launch with holes and one forged candidate returns the plain reference's
# verdicts — the benchmark's own reference (benchmark/reference/bls12_381.py),
# which makes the keys and the signatures, as in the cell
# `bls12-381-4096.closed256`. Slow tier: the launch's cold compile took 273 s
# on the sandbox's CPU (PR 28), over the 180 s a tier-1 test may take alone.

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "benchmark")
SERVED_N, SERVED_LANES = 16, 4
SERVED_MSG = b"handel-tpu benchmark round"
LIMIT_S = 900  # the test's own time limit: one cold compile of the launch


@pytest.fixture
def time_limit():
    """Fail, do not hang: SIGALRM in the test's own process."""
    def over(signum, frame):
        raise TimeoutError(f"the launch did not end inside {LIMIT_S} s")

    before = signal.signal(signal.SIGALRM, over)
    signal.alarm(LIMIT_S)
    yield
    signal.alarm(0)
    signal.signal(signal.SIGALRM, before)


def test_one_served_launch_returns_the_reference_verdicts(time_limit):
    from handel_tpu.core.bitset import BitSet
    from handel_tpu.models.bls12_381 import BLS12381PublicKey, BLS12381Signature
    from handel_tpu.models.registry import new_scheme
    from handel_tpu.parallel.batch_verifier import BatchVerifierService

    sys.path.insert(0, BENCH)
    try:
        from reference import bls12_381 as ref
    finally:
        sys.path.remove(BENCH)
    sks, points = ref.keygen(random.Random(2800000003), SERVED_N)
    # level ranges of the 16-id tree, holes inside; candidate 2 is forged
    cands = [(0, 8, (2, 5)), (8, 8, ()), (4, 4, (6,)), (0, 16, (1, 9, 14))]
    signers = [[i for i in range(lo, lo + size) if i not in holes]
               for lo, size, holes in cands]
    secrets = [sum(sks[i] for i in s) % ref.R for s in signers]
    secrets[2] = (secrets[2] + 1) % ref.R
    sigs = ref.sign_batch(SERVED_MSG, secrets)
    want = [ref.verify(SERVED_MSG, points, s, sig) for s, sig in zip(signers, sigs)]
    assert want == [True, True, False, True]

    requests = []
    for (lo, size, holes), sig in zip(cands, sigs):
        bs = BitSet(SERVED_N)
        bs.set_range(lo, lo + size)
        for i in holes:
            bs.set(i, False)
        requests.append((bs, BLS12381Signature(sig)))
    pubkeys = [BLS12381PublicKey(p) for p in points]
    scheme = new_scheme("bls12-381-jax", batch_size=SERVED_LANES, warmup=False)
    device = scheme.constructor.prepare(pubkeys)
    assert device.field_limbs == 24

    async def go():
        svc = BatchVerifierService(device, fallback=None)
        try:
            got = await svc.verify(SERVED_MSG, pubkeys, requests, session="s")
            return got, svc.values()
        finally:
            svc.stop()

    t0 = time.perf_counter()
    got, v = asyncio.run(go())
    print(f"served launch: {time.perf_counter() - t0:.0f} s")
    assert got == want
    assert v["fieldLimbs"] == 24.0
    assert v["launchesRange8"] == v["verifierLaunches"] == 1.0
    assert v["verifierCandidates"] == 4.0
    assert v["failoverBatches"] == v["deviceRetryCt"] == 0.0
