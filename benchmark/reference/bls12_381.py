"""Plain reference for the BLS12-381 BLS deployments: keys, signatures, verdicts.

Independent of the program: imports nothing from handel_tpu and takes nothing
the program made. The arithmetic is the benchmark's own copy, in plain Python
integers — no kernels, no batching, no native library (`load` compiles
nothing). Points are affine tuples of ints (G2 coordinates are (c0, c1) pairs
over Fp2 = Fp[i]/(i^2 + 1)), None is infinity — the representation the
program's key and signature wrappers take.

The curve (draft-irtf-cfrg-pairing-friendly-curves, 4.2.1): z = -0xd201000000010000,
E: y^2 = x^3 + 4 over Fp, E': y^2 = x^3 + 4(1 + i) over Fp2 (M-type twist),
r = z^4 - z^2 + 1. The scheme, in the orientation the BLS draft calls
minimal-signature-size (keys in G2, signatures in G1), as the program states it:
    X_i = x_i * B2,  S = x * H(m),  verify  e(H(m), sum X_i) == e(S, B2),
    H(m) = k * G1 with k = SHA-256("bls12-381:" || m) mod r (0 -> 1).

How it differs from the program's own oracle (ops/bls12_381_ref.py), so that
a fault there is not repeated here: the Miller loop runs in AFFINE coordinates
(one Fp2 inversion a step, lines from the slope), the group sums run in
Jacobian coordinates, and scalar multiplications go through fixed-base window
tables (`_Comb`) — keygen of 4096 keys and a signature for each of the pool's
9 200 candidates would take 250 s by double-and-add, and is set-up of every run.
"""

from __future__ import annotations

import hashlib

Z = -0xD201000000010000
P = 0x1A0111EA397FE69A4B1BA7B6434BACD764774B84F38512BF6730D2A0F6B0F6241EABFFFEB153FFFFB9FEFFFFFFFFAAAB
R = 0x73EDA753299D7D483339D80809A1D80553BDA402FFFE5BFEFFFFFFFF00000001
assert R == Z**4 - Z**2 + 1 and P == (Z - 1) ** 2 * R // 3 + Z

G1_GEN = (
    0x17F1D3A73197D7942695638C4FA9AC0FC3688C4F9774B905A14E3A3F171BAC586C55E83FF97A1AEFFB3AF00ADB22C6BB,
    0x08B3F481E3AAA0F1A09E30ED741D8AE4FCF5E095D5D00AF600DB18CB2C04B3EDD03CC744A2888AE40CAA232946C5E7E1,
)
G2_GEN = (
    (
        0x024AA2B2F08F0A91260805272DC51051C6E47AD4FA403B02B4510B647AE3D1770BAC0326A805BBEFD48056C8C121BDB8,
        0x13E02B6052719F607DACD3A088274F65596BD0D09920B61AB5DA61BBDC7F5049334CF11213945D57E5AC7D055D042B7E,
    ),
    (
        0x0CE5D527727D6E118CC9CDC6DA2E351AADFD9BAA8CBDD3A76D429A695160D12C923AC9CC3BACA289E193548608B82801,
        0x0606C4A02EA734CC32ACD2B02BC28B99CB3E287E85A763AF267492AB572E99AB3F370D275CEC1DA1AAA9075FF05F79BE,
    ),
)


_g2_table = None


def load():
    """Nothing to compile: the reference is plain Python. What is built, once
    a process, is the fixed-base table over B2 that `keygen` multiplies
    through (8 160 points, 0.3 s)."""
    global _g2_table
    if _g2_table is None:
        _g2_table = _Comb(Fp2, G2_GEN)
    return _g2_table


# -- Fp and Fp2 ---------------------------------------------------------------
# One small class per field, with the same method names, so that the curve
# code below is written once for G1 (over Fp) and G2 (over Fp2).


class Fp:
    zero, one = 0, 1

    @staticmethod
    def add(a, b):
        return (a + b) % P

    @staticmethod
    def sub(a, b):
        return (a - b) % P

    @staticmethod
    def mul(a, b):
        return a * b % P

    @staticmethod
    def sqr(a):
        return a * a % P

    @staticmethod
    def inv(a):
        return pow(a, -1, P)


class Fp2:
    zero, one = (0, 0), (1, 0)

    @staticmethod
    def add(a, b):
        return ((a[0] + b[0]) % P, (a[1] + b[1]) % P)

    @staticmethod
    def sub(a, b):
        return ((a[0] - b[0]) % P, (a[1] - b[1]) % P)

    @staticmethod
    def mul(a, b):
        a0, a1 = a
        b0, b1 = b
        return ((a0 * b0 - a1 * b1) % P, (a0 * b1 + a1 * b0) % P)

    @staticmethod
    def sqr(a):
        a0, a1 = a
        return ((a0 + a1) * (a0 - a1) % P, 2 * a0 * a1 % P)

    @staticmethod
    def inv(a):
        a0, a1 = a
        d = pow(a0 * a0 + a1 * a1, -1, P)
        return (a0 * d % P, -a1 * d % P)


f2_add, f2_sub, f2_mul, f2_sqr, f2_inv = Fp2.add, Fp2.sub, Fp2.mul, Fp2.sqr, Fp2.inv


def f2_neg(a):
    return (-a[0] % P, -a[1] % P)


def f2_conj(a):
    return (a[0], -a[1] % P)


def f2_mul_xi(a):
    """(1 + i) * a."""
    return ((a[0] - a[1]) % P, (a[0] + a[1]) % P)


def f2_pow(a, e: int):
    out = Fp2.one
    while e:
        if e & 1:
            out = f2_mul(out, a)
        a = f2_sqr(a)
        e >>= 1
    return out


# -- group law (Jacobian, a = 0), once for both groups --------------------------
# A Jacobian point is (X, Y, Z) standing for (X / Z^2, Y / Z^3); Z = zero is
# infinity. Doubling and mixed addition are the textbook formulas.


def jac_double(F, pt):
    X, Y, Zc = pt
    if Zc == F.zero:
        return pt
    A = F.sqr(X)
    B = F.sqr(Y)
    C = F.sqr(B)
    D = F.sub(F.sqr(F.add(X, B)), F.add(A, C))
    D = F.add(D, D)                      # 4 X Y^2
    E = F.add(F.add(A, A), A)            # 3 X^2
    X3 = F.sub(F.sqr(E), F.add(D, D))
    C8 = F.add(C, C)
    C8 = F.add(C8, C8)
    C8 = F.add(C8, C8)
    Y3 = F.sub(F.mul(E, F.sub(D, X3)), C8)
    YZ = F.mul(Y, Zc)
    return (X3, Y3, F.add(YZ, YZ))


def jac_add_affine(F, pt, q):
    """pt + q, q affine and not infinity."""
    X, Y, Zc = pt
    if Zc == F.zero:
        return (q[0], q[1], F.one)
    ZZ = F.sqr(Zc)
    U2 = F.mul(q[0], ZZ)
    S2 = F.mul(q[1], F.mul(ZZ, Zc))
    H = F.sub(U2, X)
    r = F.sub(S2, Y)
    if H == F.zero:
        if r == F.zero:
            return jac_double(F, pt)
        return (F.one, F.one, F.zero)
    HH = F.sqr(H)
    HHH = F.mul(HH, H)
    V = F.mul(X, HH)
    X3 = F.sub(F.sub(F.sqr(r), HHH), F.add(V, V))
    Y3 = F.sub(F.mul(r, F.sub(V, X3)), F.mul(Y, HHH))
    return (X3, Y3, F.mul(Zc, H))


def to_affine_batch(F, pts):
    """Jacobian -> affine for a list, with ONE field inversion (Montgomery's
    trick over the Z coordinates); infinity -> None."""
    live = [i for i, pt in enumerate(pts) if pt[2] != F.zero]
    acc, running = [], F.one
    for i in live:
        running = F.mul(running, pts[i][2])
        acc.append(running)
    out = [None] * len(pts)
    inv = F.inv(running) if live else None
    for k in range(len(live) - 1, -1, -1):
        i = live[k]
        zi = F.mul(inv, acc[k - 1]) if k else inv
        inv = F.mul(inv, pts[i][2])
        zz = F.sqr(zi)
        out[i] = (F.mul(pts[i][0], zz), F.mul(pts[i][1], F.mul(zz, zi)))
    return out


class _Comb:
    """Fixed-base scalar multiplication: for every `WINDOW`-bit digit
    position j the multiples d * 2^(WINDOW j) * B, d = 1 .. 2^WINDOW - 1, in
    affine. A 255-bit scalar is then at most 32 mixed additions and no
    doubling."""

    WINDOW = 8

    def __init__(self, F, base):
        self.F = F
        w, rows = self.WINDOW, []
        col = (base[0], base[1], F.one)
        for _ in range((R.bit_length() + w - 1) // w):
            aff = to_affine_batch(F, [col])[0]
            row, acc = [col], col
            for _ in range((1 << w) - 2):
                acc = jac_add_affine(F, acc, aff)
                row.append(acc)
            rows.append(row)
            for _ in range(w):
                col = jac_double(F, col)
        flat = to_affine_batch(F, [pt for row in rows for pt in row])
        n = (1 << w) - 1
        self.rows = [flat[k * n:(k + 1) * n] for k in range(len(rows))]

    def mul_batch(self, scalars):
        F, mask, w = self.F, (1 << self.WINDOW) - 1, self.WINDOW
        out = []
        for k in scalars:
            k %= R
            acc = (F.one, F.one, F.zero)
            for row in self.rows:
                d = k & mask
                if d:
                    acc = jac_add_affine(F, acc, row[d - 1])
                k >>= w
            out.append(acc)
        return to_affine_batch(F, out)


def hash_to_g1(msg: bytes):
    """H(m) = k * G1 by the known-scalar construction (models/bls12_381.py
    `hash_to_g1`, restated)."""
    k = int.from_bytes(hashlib.sha256(b"bls12-381:" + msg).digest(), "big") % R
    return _mul(Fp, G1_GEN, k or 1)


def _mul(F, base, k: int):
    """Plain double-and-add, for the few one-off multiplications."""
    acc = (F.one, F.one, F.zero)
    for bit in bin(k % R)[2:]:
        acc = jac_double(F, acc)
        if bit == "1":
            acc = jac_add_affine(F, acc, base)
    return to_affine_batch(F, [acc])[0]


def keygen(rng, n: int):
    """n seeded secret scalars and their G2 public keys."""
    sks = [rng.randrange(1, R) for _ in range(n)]
    return sks, load().mul_batch(sks)


def sign_batch(msg: bytes, scalars):
    """S_j = k_j * H(m): aggregate signatures from aggregate secrets."""
    return _Comb(Fp, hash_to_g1(msg)).mul_batch(scalars)


def g2_sum(points):
    acc = (Fp2.one, Fp2.one, Fp2.zero)
    for q in points:
        if q is not None:
            acc = jac_add_affine(Fp2, acc, q)
    return to_affine_batch(Fp2, [acc])[0]


# -- Fp6 = Fp2[v]/(v^3 - xi), Fp12 = Fp6[w]/(w^2 - v), xi = 1 + i --------------

F6_ZERO = (Fp2.zero, Fp2.zero, Fp2.zero)
F6_ONE = (Fp2.one, Fp2.zero, Fp2.zero)
F12_ONE = (F6_ONE, F6_ZERO)


def f6_add(a, b):
    return (f2_add(a[0], b[0]), f2_add(a[1], b[1]), f2_add(a[2], b[2]))


def f6_sub(a, b):
    return (f2_sub(a[0], b[0]), f2_sub(a[1], b[1]), f2_sub(a[2], b[2]))


def f6_neg(a):
    return (f2_neg(a[0]), f2_neg(a[1]), f2_neg(a[2]))


def f6_mul(a, b):
    """Schoolbook: c_k = sum a_i b_j over i + j = k, v^3 = xi."""
    a0, a1, a2 = a
    b0, b1, b2 = b
    return (
        f2_add(f2_mul(a0, b0),
               f2_mul_xi(f2_add(f2_mul(a1, b2), f2_mul(a2, b1)))),
        f2_add(f2_add(f2_mul(a0, b1), f2_mul(a1, b0)),
               f2_mul_xi(f2_mul(a2, b2))),
        f2_add(f2_add(f2_mul(a0, b2), f2_mul(a1, b1)), f2_mul(a2, b0)),
    )


def f6_mul_v(a):
    return (f2_mul_xi(a[2]), a[0], a[1])


def f6_inv(a):
    a0, a1, a2 = a
    t0 = f2_sub(f2_sqr(a0), f2_mul_xi(f2_mul(a1, a2)))
    t1 = f2_sub(f2_mul_xi(f2_sqr(a2)), f2_mul(a0, a1))
    t2 = f2_sub(f2_sqr(a1), f2_mul(a0, a2))
    d = f2_inv(f2_add(
        f2_mul(a0, t0), f2_mul_xi(f2_add(f2_mul(a2, t1), f2_mul(a1, t2)))))
    return (f2_mul(t0, d), f2_mul(t1, d), f2_mul(t2, d))


def f12_mul(a, b):
    """(a0 + a1 w)(b0 + b1 w) = a0 b0 + a1 b1 v + (a0 b1 + a1 b0) w."""
    a0, a1 = a
    b0, b1 = b
    return (
        f6_add(f6_mul(a0, b0), f6_mul_v(f6_mul(a1, b1))),
        f6_add(f6_mul(a0, b1), f6_mul(a1, b0)),
    )


def f12_sqr(a):
    a0, a1 = a
    m = f6_mul(a0, a1)
    return (
        f6_add(f6_mul(a0, a0), f6_mul_v(f6_mul(a1, a1))),
        f6_add(m, m),
    )


def f12_conj(a):
    """a^(p^6): w -> -w."""
    return (a[0], f6_neg(a[1]))


def f12_inv(a):
    a0, a1 = a
    d = f6_inv(f6_sub(f6_mul(a0, a0), f6_mul_v(f6_mul(a1, a1))))
    return (f6_mul(a0, d), f6_neg(f6_mul(a1, d)))


def f12_pow(a, e: int):
    out = F12_ONE
    for bit in bin(e)[2:]:
        out = f12_sqr(out)
        if bit == "1":
            out = f12_mul(out, a)
    return out


# Frobenius: with W = w (W^6 = xi), (c W^k)^p = conj(c) * xi^(k (p - 1) / 6) W^k
_FROB = [f2_pow((1, 1), k * (P - 1) // 6) for k in range(6)]


def f12_frobenius(a):
    (c0, c2, c4), (c1, c3, c5) = a  # coefficient of W^k: v = W^2, v w = W^3
    c = [f2_mul(f2_conj(x), _FROB[k])
         for k, x in enumerate((c0, c1, c2, c3, c4, c5))]
    return ((c[0], c[2], c[4]), (c[1], c[3], c[5]))


# -- pairing ------------------------------------------------------------------
# Untwist psi(x', y') = (x' / W^2, y' / W^3) maps E' to E over Fp12. The line
# through psi(T) with slope lambda' / W (lambda' the slope on E'), evaluated
# at P = (xp, yp) and multiplied by W^3 (an element of Fp4, which the final
# exponentiation kills), is
#       (lambda' x_T - y_T)  -  lambda' xp W^2  +  yp W^3.


def _line(lam, T, p):
    xp, yp = p
    return (
        (f2_sub(f2_mul(lam, T[0]), T[1]), (-lam[0] * xp % P, -lam[1] * xp % P),
         Fp2.zero),
        (Fp2.zero, (yp, 0), Fp2.zero),
    )


def miller_loop(q, p):
    """f_{|z|, Q}(P), conjugated because z < 0; q affine on E', p affine on
    E, neither infinity. Affine steps: T never meets infinity or +-Q for a
    point of order r inside the |z|-bit loop."""
    T, f = q, F12_ONE
    for bit in bin(-Z)[3:]:
        lam = f2_mul(f2_mul((3, 0), f2_sqr(T[0])), f2_inv(f2_add(T[1], T[1])))
        f = f12_mul(f12_sqr(f), _line(lam, T, p))
        x3 = f2_sub(f2_sqr(lam), f2_add(T[0], T[0]))
        T = (x3, f2_sub(f2_mul(lam, f2_sub(T[0], x3)), T[1]))
        if bit == "1":
            lam = f2_mul(f2_sub(q[1], T[1]), f2_inv(f2_sub(q[0], T[0])))
            f = f12_mul(f, _line(lam, T, p))
            x3 = f2_sub(f2_sub(f2_sqr(lam), T[0]), q[0])
            T = (x3, f2_sub(f2_mul(lam, f2_sub(T[0], x3)), T[1]))
    return f12_conj(f)


def final_exponentiation(f):
    """f^(3 (p^12 - 1) / r): the easy part, then the hard part through
    3 (p^4 - p^2 + 1) / r = (z - 1)^2 (z + p) (z^2 + p^2 - 1) + 3 (asserted
    below). The cube changes no verdict: gcd(3, r) = 1."""
    f = f12_mul(f12_conj(f), f12_inv(f))             # ^(p^6 - 1)
    f = f12_mul(f12_frobenius(f12_frobenius(f)), f)  # ^(p^2 + 1)
    # now f^(p^6) = 1 / f: an inverse is a conjugation
    pow_z = lambda x: f12_conj(f12_pow(x, -Z))
    a = f12_mul(pow_z(f), f12_conj(f))               # ^(z - 1)
    a = f12_mul(pow_z(a), f12_conj(a))               # ^(z - 1)^2
    g = f12_mul(pow_z(a), f12_frobenius(a))          # ^(z + p)
    h = f12_mul(f12_mul(pow_z(pow_z(g)), f12_frobenius(f12_frobenius(g))),
                f12_conj(g))                         # ^(z^2 + p^2 - 1)
    return f12_mul(h, f12_mul(f12_sqr(f), f))


assert 3 * (P**4 - P**2 + 1) // R == (Z - 1) ** 2 * (Z + P) * (Z**2 + P**2 - 1) + 3


def pairing_check(pairs) -> bool:
    """prod e(p_i, q_i) == 1 with one shared final exponentiation."""
    f = F12_ONE
    for p, q in pairs:
        if p is not None and q is not None:
            f = f12_mul(f, miller_loop(q, p))
    return final_exponentiation(f) == F12_ONE


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
    neg = (sig[0], -sig[1] % P)
    return pairing_check([(hash_to_g1(msg), agg), (neg, G2_GEN)])
