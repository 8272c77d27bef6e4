"""Plain reference for BLS12-381 in the minimal-pubkey-size orientation.

Independent of the program: imports nothing from handel_tpu and takes nothing
the program made. The field, tower, group and pairing arithmetic is the
benchmark's own plain-Python copy beside this file (`bls12_381.py`: affine
Miller loop, Jacobian sums, fixed-base window tables); this module only turns
the scheme round. Points are affine tuples of ints (G2 coordinates are
(c0, c1) pairs over Fp2), None is infinity — the representation the program's
key and signature wrappers take.

The scheme, in the orientation draft-irtf-cfrg-bls-signature 2.1 calls
minimal-pubkey-size (keys in G1, signatures in G2; its ciphersuites
BLS_SIG_BLS12381G2_*), as the program states it:
    X_i = x_i * B1,  S = x * H(m),  verify  e(sum X_i, H(m)) == e(B1, S),
    H(m) = k * B2 with k = SHA-256("bls12-381:" || m) mod r (0 -> 1):
the known-scalar construction of the sibling, on the other generator (RFC
9380's map to G2 is outside both program and reference).
"""

from __future__ import annotations

import functools
import hashlib

from . import bls12_381 as _c

P, R, Z = _c.P, _c.R, _c.Z
G1_GEN, G2_GEN = _c.G1_GEN, _c.G2_GEN
Fp, Fp2 = _c.Fp, _c.Fp2

_g1_table = None


def load():
    """Nothing to compile. What is built, once a process, is the fixed-base
    table over B1 that `keygen` multiplies through."""
    global _g1_table
    if _g1_table is None:
        _g1_table = _c._Comb(Fp, G1_GEN)
    return _g1_table


@functools.lru_cache(maxsize=8)
def hash_to_g2(msg: bytes):
    """H(m) = k * B2 (models/bls12_381.py `hash_to_g2`, restated)."""
    k = int.from_bytes(hashlib.sha256(b"bls12-381:" + msg).digest(), "big") % R
    return _c._mul(Fp2, G2_GEN, k or 1)


def keygen(rng, n: int):
    """n seeded secret scalars and their G1 public keys."""
    sks = [rng.randrange(1, R) for _ in range(n)]
    return sks, load().mul_batch(sks)


def sign_batch(msg: bytes, scalars):
    """S_j = k_j * H(m) in G2: aggregate signatures from aggregate secrets,
    through one fixed-base table over H(m)."""
    return _c._Comb(Fp2, hash_to_g2(msg)).mul_batch(scalars)


def g1_sum(points):
    acc = (Fp.one, Fp.one, Fp.zero)
    for p in points:
        if p is not None:
            acc = _c.jac_add_affine(Fp, acc, p)
    return _c.to_affine_batch(Fp, [acc])[0]


def verify(msg: bytes, pubkeys, signers, sig, ignore_holes: bool = False,
           accept_any: bool = False) -> bool:
    """One verdict: e(sum_{i in signers} X_i, H(m)) * e(-B1, S) == 1.

    The two flags are the CONTROLS of the comparison that decides `correct`
    (never set by a benchmark run): `ignore_holes` aggregates the whole hull
    [min, max] of the signer set, the fault of a range path that drops its
    hole patch; `accept_any` skips the pairing equation, the fault of a
    verifier that no longer rejects forged aggregates."""
    if sig is None or not signers:
        return False
    if accept_any:
        return True
    if ignore_holes:
        signers = range(min(signers), max(signers) + 1)
    agg = g1_sum([pubkeys[i] for i in signers])
    if agg is None:
        return False
    neg_b1 = (G1_GEN[0], -G1_GEN[1] % P)
    return _c.pairing_check([(agg, hash_to_g2(msg)), (neg_b1, sig)])
