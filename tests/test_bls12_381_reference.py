"""The benchmark's plain BLS12-381 reference against the program's oracle.

`benchmark/reference/bls12_381.py` decides `correct` in the cell
`bls12-381-4096.closed256`; it imports nothing from handel_tpu and computes
differently (affine Miller loop, Jacobian sums, fixed-base window tables).
Here it is held to `ops/bls12_381_ref.py` and the host scheme of
`models/bls12_381.py`: keys, signatures, verdicts, and the two control
flags `benchmark/control.py` switches on. Host arithmetic only: seconds.
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
        from reference import bls12_381
    finally:
        sys.path.remove(BENCH)
    bls12_381.load()
    return bls12_381


@pytest.fixture(scope="module")
def keys(ref):
    return ref.keygen(random.Random(2800000001), N)


def _agg(ref, sks, signers):
    return sum(sks[i] for i in signers) % ref.R


def test_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "reference", "bls12_381.py")) as f:
        src = f.read()
    assert not re.search(r"^\s*(import|from)\s+handel_tpu", src, re.M)


def test_parameters(ref):
    assert (ref.P, ref.R, ref.Z) == (bls.P, bls.R, bls.Z)
    assert ref.G1_GEN == bls.G1_GEN and ref.G2_GEN == bls.G2_GEN
    assert bls.g1_is_valid(ref.G1_GEN) and bls.g2_is_valid(ref.G2_GEN)


def test_keygen_matches_oracle(ref, keys):
    sks, pks = keys
    assert len(set(sks)) == N and all(0 < sk < bls.R for sk in sks)
    assert pks == [bls.g2_mul(bls.G2_GEN, sk) for sk in sks]
    # the same stream gives the same keys; the program's key type takes them
    assert ref.keygen(random.Random(2800000001), N) == keys
    assert scheme.unmarshal_g2(scheme.BLS12381PublicKey(pks[0]).marshal()) == pks[0]


@pytest.mark.parametrize("msg", [MSG, b"", b"\x00" * 64])
def test_hash_to_g1_matches_scheme(ref, msg):
    assert ref.hash_to_g1(msg) == scheme.hash_to_g1(msg)


def test_sign_batch_matches_oracle(ref, keys):
    sks, _ = keys
    scalars = sks[:5] + [0, bls.R, bls.R + 7, 1]
    h = scheme.hash_to_g1(MSG)
    assert ref.sign_batch(MSG, scalars) == [bls.g1_mul(h, k) for k in scalars]
    assert scheme.BLS12381SecretKey(sks[0]).sign(MSG).point == \
        ref.sign_batch(MSG, [sks[0]])[0]


def test_pairing_matches_oracle(ref, keys):
    sks, pks = keys
    sig = ref.sign_batch(MSG, [sks[1]])[0]
    assert ref.final_exponentiation(ref.miller_loop(pks[2], sig)) == \
        bls.pairing(pks[2], sig)
    # bilinear: e(a H, b B2) == e(H, ab B2), and not e(H, (ab + 1) B2)
    a, b = sks[3], sks[4]
    h = ref.hash_to_g1(MSG)
    neg = lambda p: (p[0], -p[1] % ref.P)
    ab, ab1 = ref.load().mul_batch([a * b, a * b + 1])
    assert ref.pairing_check([(ref.sign_batch(MSG, [a])[0], pks[4]), (neg(h), ab)])
    assert not ref.pairing_check(
        [(ref.sign_batch(MSG, [a])[0], pks[4]), (neg(h), ab1)])


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
        agg = bls.g2_add(agg, pks[i])
    assert got == scheme.BLS12381PublicKey(agg).verify(
        MSG, scheme.BLS12381Signature(sig))


def test_verdict_of_a_wrong_signer_set(ref, keys):
    sks, pks = keys
    signers = [0, 1, 2, 5, 7]
    sig = ref.sign_batch(MSG, [_agg(ref, sks, signers)])[0]
    assert ref.verify(MSG, pks, signers, sig)
    assert not ref.verify(MSG, pks, signers + [6], sig)
    assert not ref.verify(MSG, pks, signers[:-1], sig)
    assert not ref.verify(b"another message", pks, signers, sig)


def test_empty_signers_and_no_signature_are_rejected(ref, keys):
    sks, pks = keys
    sig = ref.sign_batch(MSG, [sks[0]])[0]
    assert not ref.verify(MSG, pks, [], sig)
    assert not ref.verify(MSG, pks, [0], None)
    assert not ref.verify(MSG, pks, [], sig, accept_any=True)
    # a zero aggregate secret signs with infinity
    assert ref.sign_batch(MSG, [0]) == [None]


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
