"""JAX extension-field tower Fp2 -> Fp6 -> Fp12 for BN254.

Mirrors the scalar tower in ops/bn254_ref.py (the correctness oracle) on limb
vectors. TPU-first structure: every tower multiplication flattens its
independent base-field multiplications into the *batch* dimension and issues a
single `Field.mul` call —

    Fp12 mul = 3 Fp6 muls = 18 Fp2 muls = 54 Fp muls  ->  ONE mont_mul at 54xB
    Fp12 sqr = 2 Fp6 muls                = 36 Fp muls  ->  ONE mont_mul at 36xB
    Fp12 x line (3 of 6 slots zero) = 13 Fp2 muls = 39 ->  ONE mont_mul at 39xB

so the Pallas kernel's lanes stay full even for small pairing batches
(ops/fp.py "batch stacking beats vmap"). The last two are the Miller
accumulator's updates (`f12_sqr`, `f12_mul_line`): a doubling step costs it
75 multiplications a pair and an executed addition 39, where the general
product for both cost 108 and 54. Elements are pytrees of (nlimbs, B)
uint32 arrays: Fp2 = (c0, c1), Fp6 = (Fp2, Fp2, Fp2), Fp12 = (Fp6, Fp6).

All values Montgomery-form, canonical (< p) — EXCEPT under the resident
field adapter (`Tower.as_resident()`, ops/rns.py `ResidentRns`), where every
coordinate is a (k_all, B) int32 joint-residue array bounded by 2^lb * p for
a statically-tracked exponent lb. The tower formulas are representation-
agnostic; the only resident-specific obligation is the `blog` literal passed
at each subtraction/negation site — the static bound exponent of the
subtrahend at that site, derived once by the bound walk in HACKING.md
"Residue-resident pairing" and simply ignored by the positional backends.
"""

from __future__ import annotations

import jax.numpy as jnp

from handel_tpu.ops import bn254_ref as bn
from handel_tpu.ops.fp import Field


def _split3(x):
    b = x.shape[1] // 3
    return x[:, :b], x[:, b : 2 * b], x[:, 2 * b :]


class Tower:
    """Fp2/Fp6/Fp12 arithmetic over a base Field (tower shape shared by BN254
    and BLS12-381: i^2 = -1, v^3 = xi, w^2 = v).

    `params` is the scalar-oracle module defining the curve family's field
    constants — P, XI, _GAMMA, and (for BN) U. Defaults to BN254
    (ops/bn254_ref.py); pass ops/bls12_381_ref for the 381-bit tower with
    xi = 1 + i."""

    # base-field multiplications a lane of each stacked product: the width
    # of its one `Field.mul` call is this times the batch
    # (tests/test_miller_products.py holds each to the lanes really handed
    # over; ops/pairing.py `miller_acc_fp_muls` adds them up)
    F2_MUL_FP_MULS = 3
    F6_MUL_FP_MULS = 6 * F2_MUL_FP_MULS
    F12_MUL_FP_MULS = 3 * F6_MUL_FP_MULS
    F12_MUL_LINE_FP_MULS = 13 * F2_MUL_FP_MULS

    def __init__(self, field: Field | None = None, params=bn):
        self.params = params
        self.F = field or Field(params.P)
        self.xi = tuple(params.XI)
        if self.xi not in ((9, 1), (1, 1)):
            raise ValueError(f"unsupported Fp6 non-residue xi={self.xi}")
        # Frobenius constants gamma_j = xi^(j(p-1)/6) as Montgomery limb pairs
        self._gamma = [None] + [
            tuple(self.F.pack([g[0], g[1]])[:, i : i + 1] for i in range(2))
            for g in params._GAMMA[1:]
        ]

    # -- raw limb stacking (ONE carry-propagating Field call for many ops) --
    #
    # Every Field.add/sub pays a carry-lookahead + conditional-subtract; the
    # tower batches all independent adds/subs of a formula into one wide call
    # (same "batch stacking" discipline as the muls, ops/fp.py). This is what
    # keeps both XLA graph size (compile time) and Pallas launch count low.

    @staticmethod
    def _cat(xs):
        return jnp.concatenate(xs, axis=1)

    @staticmethod
    def _split(x, k):
        b = x.shape[1] // k
        return [x[:, i * b : (i + 1) * b] for i in range(k)]

    def _add_n(self, lhs, rhs):
        """[(a_i + b_i)] for equal-width limb arrays — one Field.add."""
        if len(lhs) == 1:
            return [self.F.add(lhs[0], rhs[0])]
        return self._split(self.F.add(self._cat(lhs), self._cat(rhs)), len(lhs))

    def _sub_n(self, lhs, rhs, blog=None):
        if len(lhs) == 1:
            return [self.F.sub(lhs[0], rhs[0], blog)]
        return self._split(
            self.F.sub(self._cat(lhs), self._cat(rhs), blog), len(lhs)
        )

    # -- Fp2 ---------------------------------------------------------------

    def f2_add(self, a, b):
        c = self.F.add(self._cat([a[0], a[1]]), self._cat([b[0], b[1]]))
        c0, c1 = self._split(c, 2)
        return (c0, c1)

    def f2_sub(self, a, b, blog=None):
        c = self.F.sub(self._cat([a[0], a[1]]), self._cat([b[0], b[1]]), blog)
        c0, c1 = self._split(c, 2)
        return (c0, c1)

    def f2_neg(self, a, blog=None):
        z = self._cat([a[0], a[1]])
        c0, c1 = self._split(self.F.sub(jnp.zeros_like(z), z, blog), 2)
        return (c0, c1)

    def f2_conj(self, a, blog=None):
        return (a[0], self.F.neg(a[1], blog))

    def f2_add_many(self, pairs):
        """[(a+b)] for a list of Fp2 pairs — one Field.add total."""
        out = self._add_n(
            [p[0][0] for p in pairs] + [p[0][1] for p in pairs],
            [p[1][0] for p in pairs] + [p[1][1] for p in pairs],
        )
        k = len(pairs)
        return [(out[i], out[k + i]) for i in range(k)]

    def f2_sub_many(self, pairs, blog=None):
        out = self._sub_n(
            [p[0][0] for p in pairs] + [p[0][1] for p in pairs],
            [p[1][0] for p in pairs] + [p[1][1] for p in pairs],
            blog,
        )
        k = len(pairs)
        return [(out[i], out[k + i]) for i in range(k)]

    def f2_mul(self, a, b):
        """Karatsuba: 3 base muls in one stacked call.
        (a0+a1 i)(b0+b1 i) = (a0b0 - a1b1) + ((a0+a1)(b0+b1) - a0b0 - a1b1) i

        Resident bounds: products out <= 2^6*p, so the subtrahends (v1, v0
        and then v1) sit at blog=6 and the outputs land at (c0 <= 2^7*p,
        c1 <= 2^8*p). Operand constraint: la + lb <= 54.
        """
        F = self.F
        s = F.add(self._cat([a[0], b[0]]), self._cat([a[1], b[1]]))
        sa, sb = self._split(s, 2)  # a0+a1, b0+b1
        lhs = self._cat([a[0], a[1], sa])
        rhs = self._cat([b[0], b[1], sb])
        v0, v1, v2 = _split3(F.mul(lhs, rhs))
        d = F.sub(self._cat([v0, v2]), self._cat([v1, v0]), 6)
        c0, t = self._split(d, 2)
        c1 = F.sub(t, v1, 6)
        return (c0, c1)

    def f2_sqr(self, a):
        """(a0+a1 i)^2 = (a0+a1)(a0-a1) + 2 a0 a1 i — 2 base muls.

        Resident bounds: the internal a0 - a1 uses the universal blog=24
        offset (every tower call site keeps coordinates <= 2^24*p; input
        constraint la <= 24 so (la+1) + 25 stays inside RES_MUL_LOG2). Out
        (c0 <= 2^6*p, c1 <= 2^7*p)."""
        F = self.F
        m = F.add(a[0], a[1])
        s = F.sub(a[0], a[1], 24)
        prod = F.mul(self._cat([m, a[0]]), self._cat([s, a[1]]))
        c0, t = self._split(prod, 2)
        return (c0, F.add(t, t))

    def f2_sqr_many(self, elems):
        """Square a list of Fp2 elements in ONE stacked f2_sqr call."""
        k = len(elems)
        e = (
            self._cat([x[0] for x in elems]),
            self._cat([x[1] for x in elems]),
        )
        s = self.f2_sqr(e)
        return list(zip(self._split(s[0], k), self._split(s[1], k)))

    def f2_mul_fp(self, a, s):
        """Fp2 element times a base-field element (2 base muls, stacked)."""
        F = self.F
        prod = F.mul(self._cat([a[0], a[1]]), self._cat([s, s]))
        c0, c1 = self._split(prod, 2)
        return (c0, c1)

    def _x9(self, z):
        """9*z by add chain on an arbitrary-width limb array (4 adds)."""
        F = self.F
        z2 = F.add(z, z)
        z4 = F.add(z2, z2)
        z8 = F.add(z4, z4)
        return F.add(z8, z)

    def f2_mul_xi(self, a, blog=None):
        """Multiply by the Fp6 non-residue via add chains (no base mul).
        xi = 9+i (BN254): (9a0 - a1, 9a1 + a0), one stacked x9 chain;
        xi = 1+i (BLS12-381): (a0 - a1, a0 + a1).

        Resident: `blog` is the INPUT bound exponent (the subtrahend is an
        input coordinate); output bound la + 5 for xi = 9+i (the x9 chain
        adds 4, the sub 1), la + 1 for xi = 1+i."""
        F = self.F
        if self.xi == (1, 1):
            return (F.sub(a[0], a[1], blog), F.add(a[0], a[1]))
        n9 = self._x9(self._cat([a[0], a[1]]))
        n90, n91 = self._split(n9, 2)
        return (F.sub(n90, a[1], blog), F.add(n91, a[0]))

    def f2_mul_xi_many(self, elems, blog=None):
        """xi * e for a list of Fp2 elements — one stacked chain. `blog`
        bounds the WIDEST input element (resident mode)."""
        k = len(elems)
        c0s = self._cat([e[0] for e in elems])
        c1s = self._cat([e[1] for e in elems])
        if self.xi == (1, 1):
            d = self.F.sub(c0s, c1s, blog)
            s = self.F.add(c0s, c1s)
            return list(zip(self._split(d, k), self._split(s, k)))
        n9 = self._x9(self._cat([c0s, c1s]))
        parts = self._split(n9, 2 * k)
        d = self.F.sub(self._cat(parts[:k]), c1s, blog)
        s = self.F.add(self._cat(parts[k:]), c0s)
        return list(zip(self._split(d, k), self._split(s, k)))

    def f2_inv(self, a):
        """1/(a0+a1 i) = (a0 - a1 i)/(a0^2+a1^2).

        Resident: den <= 2^7*p feeds the Fermat chain (bounds stay <= 2^7*p
        throughout — see ResidentRns.pow_const); products cap at 2^6*p, so
        the final negation's subtrahend sits at blog=6."""
        F = self.F
        den = F.add(F.mul(a[0], a[0]), F.mul(a[1], a[1]))
        inv = F.inv(den)
        return (F.mul(a[0], inv), F.neg(F.mul(a[1], inv), 6))

    def f2_select(self, mask, a, b):
        return (self.F.select(mask, a[0], b[0]), self.F.select(mask, a[1], b[1]))

    def f2_eq(self, a, b):
        return self.F.eq(a[0], b[0]) & self.F.eq(a[1], b[1])

    def f2_is_zero(self, a):
        return self.F.is_zero(a[0]) & self.F.is_zero(a[1])

    def f2_zero(self, batch: int):
        # F.limb_dtype keeps lax.scan carries dtype-consistent across
        # representations (uint32 positional limbs, int32 residue rows)
        z = jnp.zeros((self.F.nlimbs, batch), self.F.limb_dtype)
        return (z, z)

    def f2_one(self, batch: int):
        return (
            self.F.constant(1, batch),
            jnp.zeros((self.F.nlimbs, batch), self.F.limb_dtype),
        )

    def f2_constant(self, c, batch: int):
        """Embed a bn254_ref Fp2 value (int pair) as broadcast limbs."""
        return (
            jnp.broadcast_to(self.F.pack([c[0]]), (self.F.nlimbs, batch)),
            jnp.broadcast_to(self.F.pack([c[1]]), (self.F.nlimbs, batch)),
        )

    # -- Fp2 stacking helpers ----------------------------------------------

    def _f2_stack(self, elems):
        """Concatenate Fp2 elements along the batch axis."""
        return (
            jnp.concatenate([e[0] for e in elems], axis=1),
            jnp.concatenate([e[1] for e in elems], axis=1),
        )

    def _f2_unstack(self, e, k):
        b = e[0].shape[1] // k
        return [
            (e[0][:, i * b : (i + 1) * b], e[1][:, i * b : (i + 1) * b])
            for i in range(k)
        ]

    # -- Fp6 ---------------------------------------------------------------

    def f6_add(self, a, b):
        out = self.f2_add_many(list(zip(a, b)))
        return tuple(out)

    def f6_sub(self, a, b, blog=None):
        out = self.f2_sub_many(list(zip(a, b)), blog)
        return tuple(out)

    def f6_neg(self, a, blog=None):
        z = self._cat([a[i][j] for i in range(3) for j in range(2)])
        parts = self._split(self.F.sub(jnp.zeros_like(z), z, blog), 6)
        return ((parts[0], parts[1]), (parts[2], parts[3]), (parts[4], parts[5]))

    def f6_mul(self, a, b):
        """Toom/Karatsuba: 6 Fp2 muls in ONE stacked f2_mul call
        (bn254_ref.f6_mul structure); all interpolation adds/subs stacked."""
        a0, a1, a2 = a
        b0, b1, b2 = b
        # the six pre-mul sums in one add call
        s = self.f2_add_many(
            [(a1, a2), (a0, a1), (a0, a2), (b1, b2), (b0, b1), (b0, b2)]
        )
        lhs = self._f2_stack([a0, a1, a2, s[0], s[1], s[2]])
        rhs = self._f2_stack([b0, b1, b2, s[3], s[4], s[5]])
        t0, t1, t2, u0, u1, u2 = self._f2_unstack(self.f2_mul(lhs, rhs), 6)
        # pairwise t-sums, then u - sums, in one call each. Resident bounds
        # (operand constraint max(la, lb) <= 26): products t, u <= 2^8*p,
        # w <= 2^9*p, d <= 2^10*p, xi-folds <= 2^15*p, out <= 2^16*p.
        w = self.f2_add_many([(t1, t2), (t0, t1), (t0, t2)])
        d0, d1, d2 = self.f2_sub_many([(u0, w[0]), (u1, w[1]), (u2, w[2])], 9)
        x0, x2 = self.f2_mul_xi_many([d0, t2], 10)  # xi*(u0-t1-t2), xi*t2
        c0, c1, c2 = self.f2_add_many([(t0, x0), (d1, x2), (d2, t1)])
        return (c0, c1, c2)

    def f6_mul_v(self, a, blog=None):
        """(c0,c1,c2) * v = (xi*c2, c0, c1). `blog` bounds a[2] (resident)."""
        return (self.f2_mul_xi(a[2], blog), a[0], a[1])

    def f6_inv(self, a):
        """bn254_ref.f6_inv structure. Resident bound walk (input <= 2^22*p,
        the f12_inv feed): squares <= 2^7*p, products <= 2^8*p, xi-folds
        <= 2^13*p, so t0 <= 2^14*p, t1 <= 2^13*p, t2 <= 2^9*p, den <=
        2^15*p — every product constraint inside RES_MUL_LOG2."""
        a0, a1, a2 = a
        t0 = self.f2_sub(
            self.f2_sqr(a0), self.f2_mul_xi(self.f2_mul(a1, a2), 8), 13
        )
        t1 = self.f2_sub(
            self.f2_mul_xi(self.f2_sqr(a2), 7), self.f2_mul(a0, a1), 8
        )
        t2 = self.f2_sub(self.f2_sqr(a1), self.f2_mul(a0, a2), 8)
        den = self.f2_add(
            self.f2_mul(a0, t0),
            self.f2_mul_xi(
                self.f2_add(self.f2_mul(a2, t1), self.f2_mul(a1, t2)), 9
            ),
        )
        inv = self.f2_inv(den)
        return (self.f2_mul(t0, inv), self.f2_mul(t1, inv), self.f2_mul(t2, inv))

    def f6_zero(self, batch):
        return (self.f2_zero(batch),) * 3

    def f6_one(self, batch):
        return (self.f2_one(batch), self.f2_zero(batch), self.f2_zero(batch))

    def f6_select(self, mask, a, b):
        return tuple(self.f2_select(mask, x, y) for x, y in zip(a, b))

    # -- Fp12 --------------------------------------------------------------

    def f12_mul(self, a, b):
        """Karatsuba over Fp6: 3 Fp6 muls -> one stacked f6_mul (54x batch);
        the six karatsuba input sums in one add call.

        Resident bounds (operand constraint max coords <= 2^25*p): f6_mul
        outputs v <= 2^16*p, so c0 <= 2^22*p and c1 <= 2^18*p — i.e.
        f12_mul(f, g) with coords <= 2^22*p lands back at <= 2^22*p, the
        stable fixed point the Miller/final-exp accumulators live at."""
        a0, a1 = a
        b0, b1 = b
        s = self.f2_add_many(
            [(a0[i], a1[i]) for i in range(3)] + [(b0[i], b1[i]) for i in range(3)]
        )
        lhs = tuple(self._f2_stack([a0[i], a1[i], s[i]]) for i in range(3))
        rhs = tuple(self._f2_stack([b0[i], b1[i], s[3 + i]]) for i in range(3))
        prod = self.f6_mul(lhs, rhs)
        v0, v1, v2 = zip(*(self._f2_unstack(c, 3) for c in prod))
        v0, v1, v2 = tuple(v0), tuple(v1), tuple(v2)
        c0 = self.f6_add(v0, self.f6_mul_v(v1, 16))
        # c1 = v2 - v0 - v1: six components, two stacked sub calls
        d = self.f2_sub_many(list(zip(v2, v0)), 16)
        c1 = tuple(self.f2_sub_many(list(zip(d, v1)), 16))
        return (c0, tuple(c1))

    @property
    def f12_sqr_fp_muls(self) -> int:
        """Base-field multiplications a lane that `f12_sqr` hands to its one
        `Field.mul` call: two Fp6 products, or the general product's three
        under the resident field."""
        if getattr(self.F, "is_resident", False):
            return self.F12_MUL_FP_MULS
        return 2 * self.F6_MUL_FP_MULS

    def f12_sqr(self, a):
        """Complex squaring over Fp6: with v0 = a0 a1 and
        t = (a0 + a1)(a0 + v a1) = a0^2 + v a1^2 + v0 + v v0,

            c0 = t - v0 - v v0,   c1 = 2 v0

        — two Fp6 products in ONE stacked f6_mul at twice the width (36x
        batch, where `f12_mul(a, a)` stacks 54x), `v a1` by the xi add
        chain. The values are `f12_mul(a, a)`'s, limb for limb.

        Resident: the operand a0 + v a1 carries a1's bound plus the xi
        chain's 5 bits plus one, which from the <= 2^22*p product fixed
        point leaves f6_mul's <= 2^26*p operand budget — so the resident
        tower keeps the general product and its bound row (HACKING.md
        "Residue-resident pairing"); no blog literal below is ever read."""
        if getattr(self.F, "is_resident", False):
            return self.f12_mul(a, a)
        a0, a1 = a
        va1 = self.f6_mul_v(a1)
        s = self.f2_add_many(list(zip(a0, a1)) + list(zip(a0, va1)))
        lhs = tuple(self._f2_stack([a0[i], s[i]]) for i in range(3))
        rhs = tuple(self._f2_stack([a1[i], s[3 + i]]) for i in range(3))
        prod = self.f6_mul(lhs, rhs)
        v0, t = (tuple(x) for x in zip(*(self._f2_unstack(c, 2) for c in prod)))
        # u = v0 + v v0 and c1 = v0 + v0 in one add call, then c0 = t - u
        w = self.f2_add_many(list(zip(v0, self.f6_mul_v(v0))) + list(zip(v0, v0)))
        c0 = self.f2_sub_many(list(zip(t, w[:3])))
        return (tuple(c0), tuple(w[3:]))

    def f12_mul_line(self, f, line, slots):
        """f * l for a Miller line l, sparse in Fp12: `line` holds its three
        Fp2 coefficients and `slots` says where they sit — for each half of
        l = l0 + l1 w, for each power of v, an index into `line` or None.
        The two twists fill w-degrees 0, 1, 3 (D-type: ((0, None, None),
        (1, 2, None))) or 0, 2, 3 (M-type); any placement that leaves v^2
        empty and puts one coefficient in one half, two in the other, runs.

        Karatsuba over Fp6 with the zeros left out: t0 = f0 l0, t1 = f1 l1,
        t2 = (f0 + f1)(l0 + l1), c0 = t0 + v t1, c1 = t2 - t0 - t1. The
        half with one coefficient S (at v^k) costs 3 Fp2 products, a * S;
        a half with two, and the sum of the halves, 5 each:

            a (b0 + b1 v) = (m0 + xi m4, m2 - m0 - m1, m1 + m3),
            m = a0 b0, a1 b1, (a0 + a1)(b0 + b1), a2 b0, a2 b1

        — all 13 in ONE stacked f2_mul (39x batch, where f12_mul of the
        zero-padded line stacks 54x), every sum of a stage in one call.
        The values are that f12_mul's, limb for limb.

        Resident bounds (f <= 2^23*p, line coefficients <= 2^10*p): the
        widest operands s0 + s1 <= 2^25*p and E0 + E1 <= 2^12*p; products
        <= 2^8*p, their pair sums <= 2^9*p (the xi chain's and the first
        sub's blog), xi-folds <= 2^14*p, the 5-product halves <= 2^15*p,
        u = t0 + t1 <= 2^16*p (the last sub's blog): out c0 <= 2^16*p,
        c1 <= 2^17*p."""
        counts = [sum(i is not None for i in half) for half in slots]
        if any(half[2] is not None for half in slots) or sorted(counts) != [1, 2]:
            raise ValueError(f"unsupported line placement {slots!r}")
        h = counts.index(1)  # the half with one coefficient, S at v^k
        k = 0 if slots[h][0] is not None else 1
        S = line[slots[h][k]]
        D0, D1 = (line[i] for i in slots[1 - h][:2])
        fs, fd = f[h], f[1 - h]
        E = [D0, D1]
        # stage 1: f0 + f1, the halves' sum and the two-coefficient half's
        # Karatsuba sums; stage 2: the same sums of the sums
        s0, s1, s2, E[k], fd01, D01 = self.f2_add_many(
            list(zip(fs, fd)) + [(E[k], S), (fd[0], fd[1]), (D0, D1)]
        )
        s01, E01 = self.f2_add_many([(s0, s1), (E[0], E[1])])
        lhs = [fs[0], fs[1], fs[2], fd[0], fd[1], fd01, fd[2], fd[2],
               s0, s1, s01, s2, s2]
        rhs = [S, S, S, D0, D1, D01, D0, D1, E[0], E[1], E01, E[0], E[1]]
        r0, r1, r2, p0, p1, p2, p3, p4, q0, q1, q2, q3, q4 = self._f2_unstack(
            self.f2_mul(self._f2_stack(lhs), self._f2_stack(rhs)), 13
        )
        p01, p13, q01, q13 = self.f2_add_many(
            [(p0, p1), (p1, p3), (q0, q1), (q1, q3)]
        )
        # one xi chain: the two m4 folds, the single half's wrap when it
        # sits at v, and t1's v^2 coordinate for c0 = t0 + v t1
        fold = [p4, q4, p13 if h == 0 else (r1 if k else r2)] + [r2] * k
        xp4, xq4, xt12, *xr2 = self.f2_mul_xi_many(fold, 9)
        p1m, q1m = self.f2_sub_many([(p2, p01), (q2, q01)], 9)
        p0x, q0x = self.f2_add_many([(p0, xp4), (q0, xq4)])
        ts = (xr2[0], r0, r1) if k else (r0, r1, r2)
        td, t2 = (p0x, p1m, p13), (q0x, q1m, q13)
        t0, t1 = (ts, td) if h == 0 else (td, ts)
        w = self.f2_add_many(
            [(t0[0], xt12), (t0[1], t1[0]), (t0[2], t1[1])] + list(zip(ts, td))
        )
        c1 = self.f2_sub_many(list(zip(t2, w[3:])), 16)
        return (tuple(w[:3]), tuple(c1))

    def f12_cyclo_sqr(self, a):
        """Squaring for elements of the cyclotomic subgroup G_{Phi6}(Fp2)
        (Granger–Scott 2010) — valid ONLY after the easy part of the final
        exponentiation has mapped the Miller value into that subgroup.

        With f = (x0 + x1 v + x2 v^2) + (x3 + x4 v + x5 v^2) w, the three
        Fp4 = Fp2[w^3]-subalgebra pairs (x0,x4), (x3,x2), (x1,x5) square
        independently, and the Phi6 norm relation recovers f^2 from those
        squares alone:

          a_j = xi*hi_j^2 + lo_j^2,  b_j = 2*lo_j*hi_j   (per Fp4 pair)
          C0 coords: 3*a_j - 2*x_j ;  C1 coords: 3*b'_j + 2*x_j

        Cost: 9 Fp2 squarings — all fused into ONE width-9B f2_sqr launch
        (= 18 base muls) vs the generic f12_sqr's 54. The 2ab terms come from
        (lo+hi)^2 - lo^2 - hi^2 so no extra multiply is spent on them.
        """
        if getattr(self.F, "is_resident", False):
            # reset the accumulator's bound before squaring: the cyclo
            # formula subtracts INPUT coordinates from derived terms, so it
            # converges only from a small input bound. One stacked refresh
            # (12 coords wide) drops any bound <= RES_MUL_LOG2 to <= 2^6*p
            # without leaving the residue domain; bound walk proceeds from
            # there to an output <= 2^18*p.
            a = self._f12_refresh(a)
        x0, x1, x2 = a[0]
        x3, x4, x5 = a[1]
        s40, s23, s51 = self.f2_add_many([(x4, x0), (x2, x3), (x5, x1)])
        q4, q0, q40, q2, q3, q23, q5, q1, q51 = self.f2_sqr_many(
            [x4, x0, s40, x2, x3, s23, x5, x1, s51]
        )
        # cross terms 2*x4*x0, 2*x2*x3, 2*x5*x1
        d = self.f2_sub_many([(q40, q4), (q23, q2), (q51, q5)], 7)
        t6, t7, t8 = self.f2_sub_many([(d[0], q0), (d[1], q3), (d[2], q1)], 7)
        # xi-folded Fp4 squares (one xi add-chain for all four)
        xt8, xt4, xt2, xt5 = self.f2_mul_xi_many([t8, q4, q2, q5], 9)
        u0, u1, u2 = self.f2_add_many([(xt4, q0), (xt2, q3), (xt5, q1)])
        # z = 3u - 2x (C0) / 3t + 2x (C1), via (u -/+ x) doubled + u
        w = self.f2_sub_many([(u0, x0), (u1, x1), (u2, x2)], 6)
        w += self.f2_add_many([(xt8, x3), (t6, x4), (t7, x5)])
        w2 = self.f2_add_many([(t, t) for t in w])
        z = self.f2_add_many(
            list(zip(w2, (u0, u1, u2, xt8, t6, t7)))
        )
        return ((z[0], z[1], z[2]), (z[3], z[4], z[5]))

    def f12_add(self, a, b):
        return (self.f6_add(a[0], b[0]), self.f6_add(a[1], b[1]))

    def f12_conj(self, a, blog=None):
        return (a[0], self.f6_neg(a[1], blog))

    def f12_inv(self, a):
        """Resident bounds (input <= 2^22*p): f6 squares <= 2^16*p, the
        mul_v fold <= 2^21*p, f6_inv input <= 2^22*p, output products <=
        2^16*p."""
        den = self.f6_inv(
            self.f6_sub(
                self._f6_sqr_via_mul(a[0]),
                self.f6_mul_v(self._f6_sqr_via_mul(a[1]), 16),
                21,
            )
        )
        return (
            self.f6_mul(a[0], den),
            self.f6_neg(self.f6_mul(a[1], den), 16),
        )

    def _f6_sqr_via_mul(self, a):
        return self.f6_mul(a, a)

    def f12_zero(self, batch):
        return (self.f6_zero(batch), self.f6_zero(batch))

    def f12_one(self, batch):
        return (self.f6_one(batch), self.f6_zero(batch))

    def f12_select(self, mask, a, b):
        return (
            self.f6_select(mask, a[0], b[0]),
            self.f6_select(mask, a[1], b[1]),
        )

    def f12_eq(self, a, b):
        out = None
        for x, y in zip(self._flatten12(a), self._flatten12(b)):
            e = self.F.eq(x, y)
            out = e if out is None else (out & e)
        return out

    def _flatten12(self, a):
        return [a[i][j][k] for i in range(2) for j in range(3) for k in range(2)]

    def _f12_refresh(self, a):
        """Resident-only: reset all 12 coordinate bounds to <= 2^6*p in ONE
        stacked refresh (a single mul_resident by the Montgomery one at 12x
        batch width — same batch-stacking discipline as the muls)."""
        parts = self._split(self.F.refresh(self._cat(self._flatten12(a))), 12)
        return (
            ((parts[0], parts[1]), (parts[2], parts[3]), (parts[4], parts[5])),
            ((parts[6], parts[7]), (parts[8], parts[9]), (parts[10], parts[11])),
        )

    def as_resident(self) -> "Tower":
        """A Tower over the resident form of this tower's RNS field: same
        formulas, values stay joint-residue arrays end to end (CRT deferred
        to the caller's genuine boundaries). Gammas and embedded constants
        re-pack through the adapter at construction. Cached."""
        if not hasattr(self.F, "resident"):
            raise TypeError(
                f"as_resident() needs the 'rns' field backend; this tower's "
                f"field is {self.F.backend!r}"
            )
        cached = getattr(self, "_resident_tower", None)
        if cached is None:
            cached = Tower(self.F.resident(), params=self.params)
            self._resident_tower = cached
        return cached

    def f12_frobenius(self, a):
        """x -> x^p (bn254_ref.f12_frobenius structure: conjugate each Fp2
        coordinate, multiply w-degree-j slots by gamma_j). All six
        conjugations in one stacked neg; the 5 gamma muls in one f2_mul."""
        (c00, c01, c02), (c10, c11, c12) = a
        batch = c00[0].shape[1]
        coords = [c00, c01, c02, c10, c11, c12]
        z = self._cat([c[1] for c in coords])
        # resident: every Frobenius call site (final exp) holds coords at
        # the <= 2^22*p accumulator fixed point — blog=22 covers them all
        negs = self._split(self.F.sub(jnp.zeros_like(z), z, 22), 6)
        conj = [(coords[i][0], negs[i]) for i in range(6)]

        def g(j):
            g0, g1 = self._gamma[j]
            return (
                jnp.broadcast_to(g0, (self.F.nlimbs, batch)),
                jnp.broadcast_to(g1, (self.F.nlimbs, batch)),
            )

        if getattr(self.F, "is_resident", False):
            # multiply the w^0 slot by one as well (6-wide instead of
            # 5-wide — same single f2_mul launch) so EVERY output slot is a
            # product with its bound reset to <= 2^8*p; leaving the slot as
            # a raw conjugate would let bounds accumulate across chained
            # Frobenius applications (fp3 = frobenius^3 in the final exp)
            lhs = self._f2_stack(conj)
            rhs = self._f2_stack([self.f2_one(batch), g(2), g(4), g(1), g(3), g(5)])
            m00, m01, m02, m10, m11, m12 = self._f2_unstack(
                self.f2_mul(lhs, rhs), 6
            )
            return ((m00, m01, m02), (m10, m11, m12))
        lhs = self._f2_stack(conj[1:])
        rhs = self._f2_stack([g(2), g(4), g(1), g(3), g(5)])
        m01, m02, m10, m11, m12 = self._f2_unstack(self.f2_mul(lhs, rhs), 5)
        return ((conj[0], m01, m02), (m10, m11, m12))

    def f12_frobenius2(self, a):
        return self.f12_frobenius(self.f12_frobenius(a))

    def f12_pow_const(
        self,
        a,
        e: int,
        cyclo: bool = False,
        unroll: bool = False,
        window: int | None = None,
    ):
        """a^e for a fixed public exponent. cyclo=True uses the 3x-cheaper
        cyclotomic squaring — only valid when a lives in the cyclotomic
        subgroup (final exp).

        Two lowerings, same algebra:
          * scan (default): a loop over the runs of the public bits, the
            squarings of a run and then one multiply, or a selected table
            multiply per digit (`windowed_pow`) — keeps the traced graph
            ~60x smaller than unrolling, which matters for XLA compile
            times (task spec: compiler-friendly control flow).
          * unroll: python loop over the statically-known bits, emitting the
            multiply ONLY on 1-bits, at a graph that grows with bits(e). No
            production caller opts in — this environment's compilers cannot
            absorb pairing-sized unrolled graphs (BN254Pairing.__init__
            note) — but the lowering is kept, tested at small exponents, for
            co-located deployments whose compiler can.

        `window` pins the scan's digit width (1 = plain bit scan, 4 = the
        accelerator table+gather form) so tests can oracle-check both
        lowerings on any backend; None reads it off the exponent
        (`pow_window`)."""
        import jax

        from handel_tpu.ops.fp import pow_window, windowed_pow

        sqr = self.f12_cyclo_sqr if cyclo else self.f12_sqr
        if unroll:
            # static bit chain: only the 1-bit multiplies are emitted. The
            # graph grows with bits(e); fine for the small exponents the
            # flag is tested with, and an option for co-located deployments
            # whose compiler absorbs large graphs (this environment's remote
            # compile helper cannot — see BN254Pairing docstring note)
            acc = a
            for c in bin(e)[3:]:
                acc = sqr(acc)
                if c == "1":
                    acc = self.f12_mul(acc, a)
            return acc

        # the digit width follows the exponent (`pow_window`): the 63-bit
        # BN U (27 f12_muls) and BLS12-381's sparse |z| (5) take the bit
        # scan, whose runs multiply on the set bits only, where the 4-bit
        # window would execute 29; plain bit scan on CPU too
        return windowed_pow(
            a,
            e,
            pow_window(e) if window is None else window,
            mul=self.f12_mul,
            sqr=sqr,
            stack=lambda t: jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *t
            ),
            take=lambda s, i: jax.tree_util.tree_map(lambda x: x[i], s),
            select=lambda c, x, y: self.f12_select(
                jnp.broadcast_to(c, x[0][0][0].shape[1:]), x, y
            ),
        )

    def f12_pow_u(self, a, cyclo: bool = False, unroll: bool = False):
        """a^U for the BN parameter U (BN254 tower only).

        BLS parameter sets define no U (they expose X instead and override
        final_exp entirely), so fail loudly rather than with an opaque
        AttributeError mid-trace."""
        U = getattr(self.params, "U", None)
        if U is None:
            raise TypeError(
                f"f12_pow_u needs a BN parameter set with U; "
                f"{type(self.params).__name__} has none (BLS towers use "
                f"their own final-exp chain)"
            )
        return self.f12_pow_const(a, U, cyclo=cyclo, unroll=unroll)

    # -- host conversions ---------------------------------------------------

    def f2_pack(self, vals):
        """List of bn254_ref Fp2 values -> batched limb Fp2."""
        return (
            self.F.pack([v[0] for v in vals]),
            self.F.pack([v[1] for v in vals]),
        )

    def f2_unpack(self, a):
        c0 = self.F.unpack(a[0])
        c1 = self.F.unpack(a[1])
        return list(zip(c0, c1))

    def f12_pack(self, vals):
        """List of bn254_ref Fp12 values -> batched limb Fp12."""
        return tuple(
            tuple(
                self.f2_pack([v[i][j] for v in vals]) for j in range(3)
            )
            for i in range(2)
        )

    def f12_unpack(self, a):
        flat = [self.f2_unpack(a[i][j]) for i in range(2) for j in range(3)]
        batch = len(flat[0])
        return [
            (
                (flat[0][k], flat[1][k], flat[2][k]),
                (flat[3][k], flat[4][k], flat[5][k]),
            )
            for k in range(batch)
        ]
