"""Plain reference for the BN254 BLS deployments: keys, signatures, verdicts.

Independent of the program: imports nothing from handel_tpu and takes nothing
the program made. Group arithmetic and the pairing are the benchmark's own
copy of the host C++ library (bn254.cc beside this file), built with g++ into
`benchmark/_build/` on first use. Points are affine tuples of ints (G2
coordinates are (c0, c1) pairs), None is infinity — the representation the
program's key and signature wrappers take.

The scheme (Handel's bn256: keys in G2, signatures in G1):
    X_i = x_i * B2,  S = x * H(m),  verify  e(H(m), sum X_i) == e(S, B2).
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess

HERE = os.path.dirname(os.path.abspath(__file__))
BUILD_DIR = os.path.join(os.path.dirname(HERE), "_build")
SRC = os.path.join(HERE, "bn254.cc")
LIB = os.path.join(BUILD_DIR, "libbn254ref.so")

U = 4965661367192848881
P = 36 * U**4 + 36 * U**3 + 24 * U**2 + 6 * U + 1  # field modulus
R = 36 * U**4 + 36 * U**3 + 18 * U**2 + 6 * U + 1  # group order
G1_GEN = (1, 2)
G2_GEN = (
    (
        10857046999023057135944570762232829481370756359578518086990519993285655852781,
        11559732032986387107991004021392285783925812861821192530917403151452391805634,
    ),
    (
        8495653923123431417604973247489272438418190587263600148770280649306958101930,
        4082367875863433681332203403145435568316851327593401208105741076214120093531,
    ),
)

_lib = None


def load():
    """Build (once per checkout) and bind the library. A missing compiler
    is an error: the pure-Python pairing would take minutes per request."""
    global _lib
    if _lib is not None:
        return _lib
    if not (
        os.path.exists(LIB) and os.path.getmtime(LIB) >= os.path.getmtime(SRC)
    ):
        os.makedirs(BUILD_DIR, exist_ok=True)
        tmp = f"{LIB}.{os.getpid()}.tmp"
        subprocess.run(
            ["g++", "-O2", "-shared", "-fPIC", "-o", tmp, SRC],
            check=True, capture_output=True, timeout=300,
        )
        os.replace(tmp, LIB)
    lib = ctypes.CDLL(LIB)
    lib.bn254_native_version.restype = ctypes.c_int
    if lib.bn254_native_version() != 1:
        raise OSError("reference library ABI version mismatch")
    lib.bn254_pairing_check.restype = ctypes.c_int
    _lib = lib
    return lib


def _i2b(x: int) -> bytes:
    return int(x).to_bytes(32, "little")


def _b2i(b: bytes) -> int:
    return int.from_bytes(b, "little")


def _g1_buf(p) -> bytes:
    return b"\x00" * 64 if p is None else _i2b(p[0]) + _i2b(p[1])


def _g2_buf(p) -> bytes:
    if p is None:
        return b"\x00" * 128
    (x0, x1), (y0, y1) = p
    return _i2b(x0) + _i2b(x1) + _i2b(y0) + _i2b(y1)


def _infs(points):
    return (ctypes.c_int * len(points))(*[p is None for p in points])


def g1_mul_batch(points, scalars):
    """n independent [k_i]P_i in one native call."""
    lib, n = load(), len(points)
    out = ctypes.create_string_buffer(64 * n)
    oinf = (ctypes.c_int * n)()
    lib.bn254_g1_mul_batch(
        out, oinf, b"".join(map(_g1_buf, points)), _infs(points),
        b"".join(_i2b(k % R) for k in scalars), n,
    )
    raw = bytes(out)
    return [
        None if oinf[i]
        else (_b2i(raw[64 * i: 64 * i + 32]), _b2i(raw[64 * i + 32: 64 * i + 64]))
        for i in range(n)
    ]


def g2_mul_batch(points, scalars):
    lib, n = load(), len(points)
    out = ctypes.create_string_buffer(128 * n)
    oinf = (ctypes.c_int * n)()
    lib.bn254_g2_mul_batch(
        out, oinf, b"".join(map(_g2_buf, points)), _infs(points),
        b"".join(_i2b(k % R) for k in scalars), n,
    )
    raw = bytes(out)
    res = []
    for i in range(n):
        o = raw[128 * i: 128 * (i + 1)]
        res.append(
            None if oinf[i] else (
                (_b2i(o[:32]), _b2i(o[32:64])),
                (_b2i(o[64:96]), _b2i(o[96:128])),
            )
        )
    return res


def g2_sum(points):
    lib, n = load(), len(points)
    out = ctypes.create_string_buffer(128)
    oinf = ctypes.c_int()
    lib.bn254_g2_sum(
        out, ctypes.byref(oinf), b"".join(map(_g2_buf, points)),
        _infs(points), n,
    )
    if oinf.value:
        return None
    o = bytes(out)
    return (
        (_b2i(o[:32]), _b2i(o[32:64])), (_b2i(o[64:96]), _b2i(o[96:128]))
    )


def pairing_check(pairs) -> bool:
    """prod e(p_i, q_i) == 1 with one shared final exponentiation."""
    lib, n = load(), len(pairs)
    g1s = [p for p, _ in pairs]
    g2s = [q for _, q in pairs]
    return bool(lib.bn254_pairing_check(
        b"".join(map(_g1_buf, g1s)), _infs(g1s),
        b"".join(map(_g2_buf, g2s)), _infs(g2s), n,
    ))


def hash_to_g1(msg: bytes):
    """H(m) = k * G1 with k from SHA-256(m): top byte masked to the order's
    bit length, re-hashed while k is 0 or >= r (the scheme's definition,
    models/bn254.py `hash_to_g1`, restated)."""
    keep = R.bit_length() % 8
    mask = (1 << keep) - 1 if keep else 0xFF
    digest = hashlib.sha256(msg).digest()
    while True:
        k = int.from_bytes(bytes([digest[0] & mask]) + digest[1:], "big")
        if 0 < k < R:
            return g1_mul_batch([G1_GEN], [k])[0]
        digest = hashlib.sha256(digest).digest()


def keygen(rng, n: int):
    """n seeded secret scalars and their G2 public keys."""
    sks = [rng.randrange(1, R) for _ in range(n)]
    return sks, g2_mul_batch([G2_GEN] * n, sks)


def sign_batch(msg: bytes, scalars):
    """S_j = k_j * H(m): aggregate signatures from aggregate secrets."""
    return g1_mul_batch([hash_to_g1(msg)] * len(scalars), scalars)


def verify(msg: bytes, pubkeys, signers, sig, ignore_holes: bool = False,
           accept_any: bool = False) -> bool:
    """One verdict: e(H(m), sum_{i in signers} X_i) * e(-S, B2) == 1.

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
    agg = g2_sum([pubkeys[i] for i in signers])
    if agg is None:
        return False
    neg = (sig[0], (P - sig[1]) % P)
    return pairing_check([(hash_to_g1(msg), agg), (neg, G2_GEN)])
