"""The benchmark's plain minimal-pubkey-size reference against the program's
oracle.

`benchmark/reference/bls12_381_minpk.py` decides `correct` in the cell
`bls12-381-minpk-4096-failing.closed256`; it imports nothing from handel_tpu
and takes its arithmetic from the benchmark's own `bls12_381.py` (affine
Miller loop, Jacobian sums, fixed-base window tables). Here it is held to
`ops/bls12_381_ref.py` and the host scheme of this orientation in
`models/bls12_381.py` (`MinPk*`: keys in G1, signatures in G2): keys,
signatures, verdicts, and the two control flags `benchmark/control.py`
switches on. Host arithmetic only: seconds.
"""

import os
import random
import re
import sys

import pytest

from handel_tpu.models import bls12_381 as scheme
from handel_tpu.ops import bls12_381_ref as bls

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "benchmark")
N = 24
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


@pytest.fixture(scope="module")
def keys(ref):
    return ref.keygen(random.Random(3500000001), N)


def _agg(ref, sks, signers):
    return sum(sks[i] for i in signers) % ref.R


def test_imports_nothing_of_the_program():
    for name in ("bls12_381_minpk.py", "bls12_381.py"):  # and what it imports
        with open(os.path.join(BENCH, "reference", name)) as f:
            src = f.read()
        assert not re.search(r"^\s*(import|from)\s+handel_tpu", src, re.M), name


def test_parameters(ref):
    assert (ref.P, ref.R, ref.Z) == (bls.P, bls.R, bls.Z)
    assert ref.G1_GEN == bls.G1_GEN and ref.G2_GEN == bls.G2_GEN


def test_keygen_matches_oracle(ref, keys):
    sks, pks = keys
    assert len(set(sks)) == N and all(0 < sk < bls.R for sk in sks)
    assert pks == [bls.g1_mul(bls.G1_GEN, sk) for sk in sks]
    # the same stream gives the same keys; the program's key type takes them
    assert ref.keygen(random.Random(3500000001), N) == keys
    assert scheme.MinPkSecretKey(sks[0]).public_key().point == pks[0]
    assert scheme.unmarshal_g1(scheme.MinPkPublicKey(pks[0]).marshal()) == pks[0]


@pytest.mark.parametrize("msg", [MSG, b"", b"\x00" * 64])
def test_hash_to_g2_matches_scheme(ref, msg):
    h = ref.hash_to_g2(msg)
    assert h == scheme.hash_to_g2(msg) and bls.g2_is_valid(h)


def test_sign_batch_matches_oracle(ref, keys):
    sks, _ = keys
    scalars = sks[:3] + [0, bls.R, bls.R + 7, 1]
    h = scheme.hash_to_g2(MSG)
    sigs = ref.sign_batch(MSG, scalars)
    assert sigs == [bls.g2_mul(h, k) for k in scalars]
    assert sigs[3] is None and sigs[4] is None  # a zero secret signs infinity
    assert scheme.MinPkSecretKey(sks[0]).sign(MSG).point == sigs[0]
    # the wire layer takes what the reference signs (on the curve, order r)
    cons = scheme.MinPkConstructor()
    assert cons.unmarshal_signature(
        scheme.MinPkSignature(sigs[0]).marshal()).point == sigs[0]


CASES = {
    "full_range": (list(range(8, 16)), 0),
    "holed": ([i for i in range(0, 16) if i not in (3, 9, 10)], 0),
    "single": ([5], 0),
    "whole_registry": (list(range(N)), 0),
    "forged": (list(range(8, 16)), 1),
    "forged_holed": ([i for i in range(16, 24) if i != 20], 1),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_verdict_matches_oracle(ref, keys, case):
    sks, pks = keys
    signers, off = CASES[case]
    sig = ref.sign_batch(MSG, [_agg(ref, sks, signers) + off])[0]
    got = ref.verify(MSG, pks, signers, sig)
    assert got == (off == 0)
    agg = None
    for i in signers:
        agg = bls.g1_add(agg, pks[i])
    assert got == scheme.MinPkPublicKey(agg).verify(
        MSG, scheme.MinPkSignature(sig))


def test_verdict_of_a_wrong_signer_set(ref, keys):
    sks, pks = keys
    signers = [0, 1, 2, 5, 7]
    sig = ref.sign_batch(MSG, [_agg(ref, sks, signers)])[0]
    assert ref.verify(MSG, pks, signers, sig)
    assert not ref.verify(MSG, pks, signers + [6], sig)   # a hole filled
    assert not ref.verify(MSG, pks, signers[:-1], sig)    # a hole too many
    assert not ref.verify(b"another message", pks, signers, sig)


def test_empty_signers_and_no_signature_are_rejected(ref, keys):
    sks, pks = keys
    sig = ref.sign_batch(MSG, [sks[0]])[0]
    assert not ref.verify(MSG, pks, [], sig)
    assert not ref.verify(MSG, pks, [0], None)
    assert not ref.verify(MSG, pks, [], sig, accept_any=True)


def test_control_flags_break_one_guarantee_each(ref, keys):
    """`accept_any` lets a forged aggregate pass; `ignore_holes` aggregates
    the hull, so a holed candidate fails and a full range still passes."""
    sks, pks = keys
    full, holed = list(range(8, 16)), [8, 9, 11, 12, 15]
    s_full, s_holed, s_forged = ref.sign_batch(
        MSG, [_agg(ref, sks, full), _agg(ref, sks, holed),
              _agg(ref, sks, full) + 1])
    assert not ref.verify(MSG, pks, full, s_forged)
    assert ref.verify(MSG, pks, full, s_forged, accept_any=True)
    assert ref.verify(MSG, pks, holed, s_holed)
    assert not ref.verify(MSG, pks, holed, s_holed, ignore_holes=True)
    assert ref.verify(MSG, pks, full, s_full, ignore_holes=True)


# -- the host scheme's wire layer ---------------------------------------------


def test_unmarshal_g2_holds_a_signature_to_curve_and_subgroup():
    """`MinPkConstructor.unmarshal_signature` refuses what the launch must
    never see: a point off the curve, and a point of E'(Fp2) outside the
    order-r subgroup (G2 has a large cofactor)."""
    cons = scheme.MinPkConstructor()
    assert cons.signature_size() == 192
    good = bls.g2_mul(bls.G2_GEN, 5)
    assert cons.unmarshal_signature(scheme.marshal_g2(good)).point == good
    assert cons.unmarshal_signature(b"\x00" * 192).point is None
    (x0, x1), (y0, y1) = good
    with pytest.raises(ValueError):  # off the curve
        cons.unmarshal_signature(scheme.marshal_g2(((x0, x1), (y0 + 1, y1))))
    # on the curve, off the subgroup: solve y^2 = x^3 + 4(1 + i) for small x
    rng = random.Random(35)
    while True:
        x = (rng.randrange(bls.P), rng.randrange(bls.P))
        rhs = bls.f2_add(bls.f2_mul(bls.f2_sqr(x), x), bls.TWIST_B)
        y = _f2_sqrt(rhs)
        if y is not None and not bls.g2_is_valid((x, y)):
            break
    assert bls.f2_sqr(y) == rhs  # on E', so only the subgroup check fails
    with pytest.raises(ValueError):
        cons.unmarshal_signature(scheme.marshal_g2((x, y)))
    with pytest.raises(ValueError):  # wrong length
        scheme.unmarshal_g2(b"\x00" * 96)


def _f2_sqrt(a):
    """A square root in Fp2 = Fp[i]/(i^2 + 1), p = 3 mod 4, or None."""
    p = bls.P
    a0, a1 = a
    if a1 == 0:
        r = pow(a0, (p + 1) // 4, p)
        if r * r % p == a0:
            return (r, 0)
        r = pow(-a0 % p, (p + 1) // 4, p)
        return (0, r) if r * r % p == -a0 % p else None
    norm = (a0 * a0 + a1 * a1) % p
    n = pow(norm, (p + 1) // 4, p)
    if n * n % p != norm:
        return None
    for s in (n, -n % p):
        half = (a0 + s) * pow(2, -1, p) % p
        r0 = pow(half, (p + 1) // 4, p)
        if r0 * r0 % p == half and r0:
            r1 = a1 * pow(2 * r0, -1, p) % p
            if bls.f2_sqr((r0, r1)) == a:
                return (r0, r1)
    return None


def test_registry_names_the_orientation():
    from handel_tpu.models.registry import (
        SCHEMES, is_device_scheme, new_keygen_scheme, new_scheme)

    assert {"bls12-381-minpk", "bls12-381-minpk-jax"} <= set(SCHEMES)
    assert not is_device_scheme("bls12-381-minpk")
    assert is_device_scheme("bls12-381-minpk-jax")
    host = new_scheme("bls12-381-minpk")
    assert type(host) is scheme.MinPkScheme
    # the device scheme's keygen facade is the host scheme (no jax import)
    assert type(new_keygen_scheme("bls12-381-minpk-jax")) is scheme.MinPkScheme
    sk, pk = host.keygen(3)
    assert host.unmarshal_public(pk.marshal()) == pk
    assert host.unmarshal_secret(sk.marshal()).scalar == sk.scalar
    sig = sk.sign(MSG)
    assert pk.verify(MSG, sig) and not pk.verify(b"other", sig)
    sk2, pk2 = host.keygen(4)
    assert pk.combine(pk2).verify(MSG, sig.combine(sk2.sign(MSG)))
