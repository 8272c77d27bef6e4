"""Batched JAX elliptic-curve group ops for BN254 G1 (Fp) and G2 (Fp2').

Replaces the reference's point arithmetic (`Combine`'s G1/G2 adds at
bn256/cf/bn256.go:107,199 and scalar mults at :134,153) with TPU-shaped
kernels. Design choices, TPU-first:

  * **Complete projective formulas** (Renes–Costello–Batina 2015, Alg. 7 for
    a = 0): ONE branch-free formula covers generic add, doubling, the
    identity, and inverse points. No data-dependent control flow inside jit —
    the whole point-add graph is straight-line VPU code, so it vmaps/scans/
    reduces freely. (The scalar oracle bn254_ref.pt_add branches four ways;
    that shape would force `lax.cond` everywhere on device.)
  * Points are (X, Y, Z) homogeneous projective; infinity = (0, 1, 0).
  * **Mul stacking**: the 14 field multiplications of one complete add are
    grouped into 3 stacked `Field.mul` calls (widths 3, 4, 6 and one b3 mul),
    keeping the Pallas mont-mul lanes full even at small point batches
    (ops/fp.py "batch stacking beats vmap").
  * **Tree reduction** for aggregate keys/sigs: `sum_points` folds an
    n-block batch in ceil(log2 n) complete-add stages — the device-side
    replacement for the reference's sequential pubkey-aggregation loop
    (processing.go:355-361).

Correctness oracle: ops/bn254_ref.py (g1_add/g2_add/pt_mul); tests in
tests/test_curve_jax.py.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from handel_tpu.ops import bls12_381_ref as _bls
from handel_tpu.ops import bn254_ref as bn
from handel_tpu.ops.fp import Field
from handel_tpu.ops.tower import Tower


class _FpAdapter:
    """Base-field element algebra for G1: elements are (nlimbs, B) arrays.

    b3 = 3b for the curve constant (y^2 = x^3 + b): 9 for BN254's b = 3,
    12 for BLS12-381's b = 4 — both realized as add chains."""

    # host side of an element: the scalar oracle's value of 0 and 1, the
    # base-field columns an element is staged as, and what one element
    # multiplication / one `mul_b3` hands to `Field.mul` (lanes a lane)
    ZERO, ONE = 0, 1
    COLS = 1
    MUL_FP_MULS = 1
    B3_FP_MULS = 0  # an add chain

    def __init__(self, F: Field, b3: int = 9):
        self.F = F
        self.b3 = b3
        if b3 not in (9, 12):
            raise ValueError(f"unsupported curve constant b3={b3}")

    def pack(self, vals):
        """List of scalar-oracle elements -> device element of that batch."""
        return self.F.pack(vals)

    def pack_np(self, vals):
        """`pack` stopping at the host: the element's numpy limb columns."""
        return self.F.pack_batch_np(vals)

    def unpack(self, e):
        return self.F.unpack(e)

    def add(self, a, b):
        return self.F.add(a, b)

    def sub(self, a, b):
        return self.F.sub(a, b)

    def neg(self, a):
        return self.F.neg(a)

    def select(self, mask, a, b):
        return self.F.select(mask, a, b)

    def zero(self, batch):
        return jnp.zeros((self.F.nlimbs, batch), jnp.uint32)

    def one(self, batch):
        return self.F.constant(1, batch)

    def eq(self, a, b):
        return self.F.eq(a, b)

    def is_zero(self, a):
        return self.F.is_zero(a)

    def batch(self, a):
        return a.shape[1]

    def mul_many(self, lhs, rhs):
        """Stacked multiplication: one mont_mul call for k independent muls."""
        k = len(lhs)
        prod = self.F.mul(jnp.concatenate(lhs, axis=1), jnp.concatenate(rhs, axis=1))
        b = prod.shape[1] // k
        return [prod[:, i * b : (i + 1) * b] for i in range(k)]

    def mul_b3(self, a):
        """x * b3 by add chain, no mul (x9 for BN254, x12 for BLS12-381)."""
        a2 = self.F.add(a, a)
        a4 = self.F.add(a2, a2)
        a8 = self.F.add(a4, a4)
        return self.F.add(a8, a if self.b3 == 9 else a4)

    def inv(self, a):
        return self.F.inv(a)

    def mul(self, a, b):
        return self.F.mul(a, b)

    def concat(self, elems):
        return jnp.concatenate(elems, axis=1)

    def split(self, e, k):
        b = e.shape[1] // k
        return [e[:, i * b : (i + 1) * b] for i in range(k)]


class _Fp2Adapter:
    """Quadratic-extension algebra for G2': elements are Fp2 pairs."""

    ZERO, ONE = (0, 0), (1, 0)
    COLS = 2
    MUL_FP_MULS = Tower.F2_MUL_FP_MULS
    B3_FP_MULS = Tower.F2_MUL_FP_MULS  # the twist's b' is no small integer

    def __init__(self, T: Tower, params=bn):
        self.T = T
        # E' twist coefficient b' (3/xi for BN254's D-twist, 4*xi for
        # BLS12-381's M-twist); b3 = 3*b' as a host constant
        self._b3 = params.f2_scalar(params.TWIST_B, 3)
        self._b3_packed = None

    def pack(self, vals):
        return self.T.f2_pack(vals)

    def pack_np(self, vals):
        F = self.T.F
        return (
            F.pack_batch_np([v[0] for v in vals]),
            F.pack_batch_np([v[1] for v in vals]),
        )

    def unpack(self, e):
        return self.T.f2_unpack(e)

    def add(self, a, b):
        return self.T.f2_add(a, b)

    def sub(self, a, b):
        return self.T.f2_sub(a, b)

    def neg(self, a):
        return self.T.f2_neg(a)

    def select(self, mask, a, b):
        return self.T.f2_select(mask, a, b)

    def zero(self, batch):
        return self.T.f2_zero(batch)

    def one(self, batch):
        return self.T.f2_one(batch)

    def eq(self, a, b):
        return self.T.f2_eq(a, b)

    def is_zero(self, a):
        return self.T.f2_is_zero(a)

    def batch(self, a):
        return a[0].shape[1]

    def mul_many(self, lhs, rhs):
        out = self.T.f2_mul(self.T._f2_stack(lhs), self.T._f2_stack(rhs))
        return self.T._f2_unstack(out, len(lhs))

    def mul_b3(self, a):
        b3 = self.T.f2_constant(self._b3, a[0].shape[1])
        return self.T.f2_mul(a, b3)

    def inv(self, a):
        return self.T.f2_inv(a)

    def mul(self, a, b):
        return self.T.f2_mul(a, b)

    def concat(self, elems):
        return self.T._f2_stack(elems)

    def split(self, e, k):
        return self.T._f2_unstack(e, k)


class Curve:
    """Batched short-Weierstrass group (y^2 = x^3 + b, a = 0) over an element
    algebra. Points are (X, Y, Z) pytrees; identity is (0, 1, 0)."""

    def __init__(self, ops, gen=None):
        self.ops = ops
        # the group's generator as the scalar oracle writes it (affine)
        self.gen = gen

    # -- what the group's additions cost --------------------------------------

    @property
    def add_fp_muls(self) -> int:
        """Base-field multiplications a lane of one complete `add`: its 12
        element products and two `mul_b3`, in the lanes each hands to
        `Field.mul` (12 in G1, 42 in G2)."""
        o = self.ops
        return 12 * o.MUL_FP_MULS + 2 * o.B3_FP_MULS

    def sum_fp_muls(self, n: int, b: int) -> int:
        """Base-field multiplications of `sum_points` (and `masked_sum`)
        over n blocks of b lanes: each stage adds half the blocks left, an
        odd count padded by one first."""
        adds = 0
        while n > 1:
            n = (n + 1) // 2
            adds += n
        return adds * b * self.add_fp_muls

    # -- host boundary: scalar-oracle points <-> device batches --------------

    def pack(self, pts):
        """List of affine scalar-oracle points (None = infinity) ->
        projective batch."""
        o = self.ops
        return (
            o.pack([o.ZERO if p is None else p[0] for p in pts]),
            o.pack([o.ONE if p is None else p[1] for p in pts]),
            o.pack([o.ZERO if p is None else o.ONE for p in pts]),
        )

    def unpack_affine(self, x, y, inf):
        """Affine device batch (`to_affine`'s result) -> list of points."""
        xs, ys, infs = self.ops.unpack(x), self.ops.unpack(y), np.asarray(inf)
        return [None if infs[i] else (xs[i], ys[i]) for i in range(len(xs))]

    def unpack(self, P):
        return self.unpack_affine(*self.to_affine(P))

    # -- constructors -------------------------------------------------------

    def infinity(self, batch: int):
        o = self.ops
        return (o.zero(batch), o.one(batch), o.zero(batch))

    def from_affine(self, x, y):
        o = self.ops
        return (x, y, o.one(o.batch(x)))

    # -- predicates ---------------------------------------------------------

    def is_infinity(self, P):
        return self.ops.is_zero(P[2])

    def eq(self, P, Q):
        """Projective equality: X1 Z2 == X2 Z1 and Y1 Z2 == Y2 Z1, with both-
        infinite handled by the cross products all being zero."""
        o = self.ops
        a, b, c, d = o.mul_many([P[0], Q[0], P[1], Q[1]], [Q[2], P[2], Q[2], P[2]])
        both_inf = self.is_infinity(P) & self.is_infinity(Q)
        one_inf = self.is_infinity(P) ^ self.is_infinity(Q)
        return (o.eq(a, b) & o.eq(c, d) & ~one_inf) | both_inf

    # -- group law ----------------------------------------------------------

    def add(self, P, Q):
        """Complete projective addition (RCB15 Alg. 7, a = 0): 12 muls +
        2 b3-muls, stacked into 3 wide Field.mul calls. Handles P == Q,
        P == -Q, and either operand at infinity with the same code path."""
        o = self.ops
        X1, Y1, Z1 = P
        X2, Y2, Z2 = Q
        a, b, c = o.mul_many([X1, Y1, Z1], [X2, Y2, Z2])
        d, e, f = o.mul_many(
            [o.add(X1, Y1), o.add(Y1, Z1), o.add(X1, Z1)],
            [o.add(X2, Y2), o.add(Y2, Z2), o.add(X2, Z2)],
        )
        d = o.sub(d, o.add(a, b))  # X1Y2 + X2Y1
        e = o.sub(e, o.add(b, c))  # Y1Z2 + Y2Z1
        f = o.sub(f, o.add(a, c))  # X1Z2 + X2Z1
        g = o.add(o.add(a, a), a)  # 3 X1X2
        h = o.mul_b3(c)
        i = o.add(b, h)
        j = o.sub(b, h)
        k = o.mul_b3(f)
        m0, m1, m2, m3, m4, m5 = o.mul_many([d, e, j, k, g, i], [j, k, i, g, d, e])
        X3 = o.sub(m0, m1)  # d*j - e*k
        Y3 = o.add(m2, m3)  # j*i + k*g
        Z3 = o.add(m5, m4)  # i*e + g*d
        return (X3, Y3, Z3)

    def double(self, P):
        return self.add(P, P)

    def neg(self, P):
        return (P[0], self.ops.neg(P[1]), P[2])

    def select(self, mask, P, Q):
        o = self.ops
        return tuple(o.select(mask, p, q) for p, q in zip(P, Q))

    # -- scalar multiplication ----------------------------------------------

    def scalar_mul(self, P, bits):
        """[k]P with per-lane scalars. bits: (nbits, B) uint32 array, MSB
        first. Double-and-add under lax.scan with a per-lane select — fixed
        trip count, no data-dependent control flow."""

        def step(acc, bit):
            acc = self.double(acc)
            added = self.add(acc, P)
            acc = self.select(bit == 1, added, acc)
            return acc, None

        acc, _ = jax.lax.scan(step, self.infinity(self.ops.batch(P[0])), bits)
        return acc

    # -- reductions ----------------------------------------------------------

    def sum_points(self, P, n: int):
        """Tree-sum n point blocks laid out block-major along the batch axis:
        each coordinate has shape (..., n*b); returns points of batch b.

        This is the aggregation kernel: ceil(log2 n) complete-add stages,
        each a single stacked launch at half the remaining width — vs the
        reference's n sequential `Combine` calls (processing.go:355-361)."""
        o = self.ops
        b = o.batch(P[0]) // n
        while n > 1:
            if n % 2:  # pad with one infinity block
                inf = self.infinity(b)
                P = tuple(
                    o.concat([coord, icoord]) for coord, icoord in zip(P, inf)
                )
                n += 1
            half = n // 2 * b
            lo = tuple(o.split(coord, 2)[0] for coord in P)
            hi = tuple(o.split(coord, 2)[1] for coord in P)
            P = self.add(lo, hi)
            n //= 2
        return P

    def masked_sum(self, P, mask, n: int):
        """Sum of the blocks whose mask bit is set. mask: (n*b,) bool over the
        block-major batch axis. Unset blocks are replaced by infinity first,
        then tree-summed — the device form of bitset-selected aggregation."""
        P = self.select(mask, P, self.infinity(self.ops.batch(P[0])))
        return self.sum_points(P, n)

    def msm(self, P, bits, n: int, window: int = 4):
        """Batched multi-scalar multiplication: out lane j = sum_i k[i,j]·P[i,j]
        over n point blocks laid out block-major along the batch axis (block i
        lane j = batch index i*b + j, like `sum_points`). bits: (nbits, n*b)
        uint32 MSB-first per-lane scalar bits (`BN254Curves.scalar_bits` /
        `scalar_bits64` shape). Lanes whose scalar is 0 contribute the
        identity, so masking to the launch hull is just zeroing those
        columns before the call.

        Windowed/bucketed accumulation shaped for the existing reduction
        kernels rather than a per-point double-and-add: the scalar stream is
        cut into w-bit digits; each window step sorts blocks into the
        V = 2^w - 1 nonzero buckets with ONE `masked_sum` over a (n, V, b)
        tiling (the bucket histogram is a select mask, not a gather), turns
        bucket sums into Σ v·B_v with a Hillis-Steele *suffix* scan over the
        bucket axis (Σ_v v·B_v = Σ_v Σ_{u≥v} B_u — log2 V adds, no scalar
        mul), and Horner-folds windows under `lax.scan` (w doublings + one
        add per step), so compile cost is independent of nbits. window=1
        degenerates to a shared double-and-add over `masked_sum`.

        Cost per window step: w doubles + [log2 n + 2·log2 V + 1] complete
        adds, all stacked full-width — 64-bit scalars at w=4 are 16 steps."""
        o = self.ops
        tree = jax.tree_util.tree_map
        nb = o.batch(P[0])
        b = nb // n
        V = (1 << window) - 1
        nbits = bits.shape[0]
        pad = (-nbits) % window
        if pad:
            bits = jnp.concatenate([jnp.zeros((pad, nb), bits.dtype), bits])
        nwin = (nbits + pad) // window
        weights = (1 << jnp.arange(window - 1, -1, -1, dtype=jnp.uint32))
        digits = (bits.reshape(nwin, window, nb) * weights[None, :, None]).sum(
            axis=1, dtype=jnp.int32
        )  # (nwin, n*b), each in [0, 2^w)

        # Tile each block across the V buckets: (..., n*b) -> (..., n*V*b),
        # tiled index i*V*b + v*b + j <- block i lane j. Loop-invariant.
        tiled = tree(
            lambda a: jnp.broadcast_to(
                a.reshape(a.shape[:-1] + (n, 1, b)), a.shape[:-1] + (n, V, b)
            ).reshape(a.shape[:-1] + (n * V * b,)),
            P,
        )
        bucket_of = jnp.arange(V * b) // b  # suffix-scan block ids

        def step(acc, d):
            for _ in range(window):
                acc = self.double(acc)
            # bucket membership: tiled lane (i, v, j) set iff digit == v+1
            hit = d[None, :] == jnp.arange(1, V + 1, dtype=jnp.int32)[:, None]
            mask = hit.reshape(V, n, b).transpose(1, 0, 2).reshape(n * V * b)
            buckets = self.masked_sum(tiled, mask, n)  # (V, b) bucket-major
            d2 = 1
            while d2 < V:  # suffix sums R_v = sum_{u >= v} B_u
                keep = bucket_of + d2 < V
                shifted = self.select(
                    keep,
                    tree(lambda a: jnp.roll(a, -d2 * b, axis=-1), buckets),
                    self.infinity(V * b),
                )
                buckets = self.add(buckets, shifted)
                d2 *= 2
            return self.add(acc, self.sum_points(buckets, V)), None

        acc, _ = jax.lax.scan(step, self.infinity(b), digits)
        return acc

    def prefix_scan(self, P):
        """Inclusive prefix sums along the batch axis: out lane i = sum of
        lanes 0..i. Hillis-Steele doubling scan over the complete add: every
        stage is one full-width add + shift/select, so all ceil(log2 n)
        stages share a single op shape (Pallas-friendly, one executable)
        — unlike `associative_scan`, whose interior odd-width slices each
        compile separately.

        One-time registry precompute for O(1) range aggregation: a Handel
        candidate's signer set is an ID range of the binomial partitioner
        (partitioner.go rangeLevel), so its aggregate key is
        prefix[hi] - prefix[lo] — two gathers and one add instead of a
        masked tree-sum over the whole registry."""
        o = self.ops
        n = o.batch(P[0])
        tree = jax.tree_util.tree_map
        d = 1
        while d < n:
            keep = jnp.arange(n) >= d  # lanes with a neighbor d to the left
            shifted = tree(lambda a: jnp.roll(a, d, axis=-1), P)
            inf = self.infinity(n)
            shifted = self.select(keep, shifted, inf)
            P = self.add(P, shifted)
            d *= 2
        return P

    # -- affine conversion (host boundary) -----------------------------------

    def to_affine(self, P):
        """(x, y, inf_mask): one field inversion per lane. Infinity lanes
        return (0, 0) with the mask set."""
        o = self.ops
        inf = self.is_infinity(P)
        z = o.select(inf, o.one(o.batch(P[2])), P[2])
        zinv = o.inv(z)
        x, y = o.mul_many([P[0], P[1]], [zinv, zinv])
        zero = o.zero(o.batch(x))
        return (
            o.select(inf, zero, x),
            o.select(inf, zero, y),
            inf,
        )

    def on_curve(self, P):
        """Projective curve membership: Y^2 Z == X^3 + b Z^3 (b3/3 = b).
        Infinity (0,1,0) satisfies it."""
        o = self.ops
        yy, xx, zz = o.mul_many([P[1], P[0], P[2]], [P[1], P[0], P[2]])
        lhs, x3, z3 = o.mul_many([yy, xx, zz], [P[2], P[0], P[2]])
        # b*Z^3 = b3*Z^3 / 3: cheaper to compute b3*z3 then... 3 is not
        # invertible by shifts; instead compute b*Z^3 via b3 chain on a third.
        # Use: rhs = X^3 + b*Z^3 where b*Z^3 = mul_b3(z3) "minus" 2/3 — avoid
        # division: compare 3*Y^2 Z == 3*X^3 + b3*Z^3.
        three = lambda t: o.add(o.add(t, t), t)
        return o.eq(three(lhs), o.add(three(x3), o.mul_b3(z3)))


class BN254Curves:
    """The two pairing groups sharing one Field/Tower, plus host conversions.

    Parameterized by the scalar-oracle module (`params`): BN254 by default;
    `BLS12Curves` below binds the same machinery to BLS12-381 (b = 4,
    M-type twist, 381-bit field)."""

    params = bn
    g1_b3 = 9  # 3*b for E: y^2 = x^3 + 3

    def __init__(
        self,
        field: Field | None = None,
        tower: Tower | None = None,
        backend: str | None = None,
    ):
        # `backend` picks the Field modmul kernel ("cios"/"rns", ops/fp.py
        # seam); everything above the Field — tower, curve adapters, pairing
        # — routes through whichever kernel the constructed Field carries.
        self.F = field or Field(self.params.P, backend=backend)
        self.T = tower or Tower(self.F, params=self.params)
        self.g1 = Curve(
            _FpAdapter(self.F, b3=self.g1_b3), gen=self.params.G1_GEN
        )
        self.g2 = Curve(
            _Fp2Adapter(self.T, params=self.params), gen=self.params.G2_GEN
        )

    # -- host packing: scalar oracle points <-> device batches ---------------

    def pack_g1(self, pts):
        """List of scalar-oracle affine G1 points (or None) -> projective batch."""
        return self.g1.pack(pts)

    def unpack_g1(self, P):
        return self.g1.unpack(P)

    def pack_g2(self, pts):
        return self.g2.pack(pts)

    def unpack_g2(self, P):
        return self.g2.unpack(P)

    @staticmethod
    def scalar_bits(ks, nbits: int = 256):
        """Host: list of ints -> (nbits, len(ks)) uint32 MSB-first bit array.
        Vectorized over 32-bit words so packing C scalars per launch is numpy
        work, not a python bit loop."""
        import numpy as np

        nwords = (nbits + 31) // 32
        words = np.empty((nwords, len(ks)), np.uint32)
        for w in range(nwords):
            words[w] = [(k >> (32 * w)) & 0xFFFFFFFF for k in ks]
        shifts = np.arange(31, -1, -1, dtype=np.uint32)
        bits = (words[:, None, :] >> shifts[None, :, None]) & np.uint32(1)
        # word w covers bit rows [nbits-32(w+1), nbits-32w): stack words
        # high-to-low (bit order within each word is already MSB-first),
        # then trim any rows above nbits
        bits = bits[::-1].reshape(nwords * 32, len(ks))
        bits = bits[nwords * 32 - nbits :]
        return jnp.asarray(np.ascontiguousarray(bits))

    @staticmethod
    def scalar_bits64(ks):
        """Host: 64-bit scalars -> (64, len(ks)) uint32 MSB-first — the RLC
        launch's per-candidate random-coefficient operand."""
        import numpy as np

        a = np.asarray(ks, dtype=np.uint64)
        shifts = np.arange(63, -1, -1, dtype=np.uint64)
        return jnp.asarray(((a[None, :] >> shifts[:, None]) & np.uint64(1)).astype(np.uint32))


class BLS12Curves(BN254Curves):
    """BLS12-381 binding: E: y^2 = x^3 + 4 (b3 = 12) over the 381-bit field,
    E'(Fp2) with the M-type twist coefficient 4(1+i)
    (ops/bls12_381_ref.py TWIST_B)."""

    params = _bls
    g1_b3 = 12
