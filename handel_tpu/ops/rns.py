"""Residue-number-system Montgomery multiplication — the MXU-shaped modmul.

The production CIOS kernel (ops/fp.py) is a VPU workload: a 254-bit limb
product is an outer product (contraction depth 1), so the 128x128 systolic
array contributes nothing and the MXU sits idle through every pairing.
RNS restructures the same arithmetic so the heavy steps ARE deep matmul
contractions against constant matrices — the shape the AI-ASIC ZKP
literature targets (PAPERS.md, arxiv 2604.17808; ROADMAP item 1):

  * **Residue mapping.** An operand's 8-bit limb vector (2n, B) maps to
    residues mod k small coprime primes via one constant-matrix contraction
    ``W @ limbs`` with ``W[i, j] = 2^(8j) mod m_i`` — contraction depth 2n
    (32 for BN254), batch B in the other MXU dimension.
  * **Residue-wise product.** Elementwise (k, B) int32 lane multiplies —
    k ≈ 42 small products replace the CIOS kernel's n^2 = 256 limb products
    plus its interleaved reduction columns.
  * **Montgomery step in RNS** (Bajard/Kawamura shape). With base A
    (product M, the RNS Montgomery constant) and base B (product MB):
    q = T·(-p^{-1}) mod M is elementwise in base A; extending q's residues
    to base B is another constant-matrix contraction ``E @ xi`` with
    ``E[j, i] = (M/m_i) mod m_j``; then r = (T + q·p)/M is elementwise in
    base B. The extension is offset-tolerant (q may come out as q + c·M,
    c < k_A): it only shifts r by c·p, absorbed by the final reduction.
  * **Exact CRT reconstruction** (Shenoy–Kumaresan). A redundant channel
    m_r rides the whole pipeline, so the CRT offset alpha in
    r = sum(xi'_j · MB/m_j) - alpha·MB is recovered EXACTLY (alpha < k_B
    <= m_r) — no floating-point base-extension approximation anywhere in
    the value path. Positional limbs come back via a third constant
    contraction against the 8-bit limb decomposition of the MB/m_j.

`RnsField` keeps the public Field contract intact: canonical (< p)
(nlimbs, B) uint32 limbs at every op boundary, so `ops/tower.py`'s
batch-stacking entry points, the curve adapters, and `BN254Device`
dispatch route through unchanged — CRT reconstruction is paid inside
`mul`, i.e. at every call boundary. add/sub/neg/inv/pow/select/eq are
inherited verbatim.

**Resident value form.** That per-mul CRT round trip is the standing
ceiling for the pairing (ROADMAP item 2): the Miller loop never needs
positional limbs between line evaluations, so `mul` repacking at every
tower multiplication is pure overhead. The resident form keeps a value as
its JOINT residue vector — a plain (k_all, B) int32 array, base A rows ++
base B rows ++ the m_r channel — and closes multiplication inside that
representation:

  * `mul_resident` runs the Montgomery steps on the joint residues and
    base-extends the result B -> A (a second Shenoy-exact extension with
    constants `E2[i, j] = (MB/m_j) mod m_i`), so the output is again a
    full joint-residue vector. No positional limbs anywhere.
  * Chained products stay exact because base A is built with
    M >= 2^RES_MUL_LOG2 * p: any product of operands bounded by
    2^la * p and 2^lb * p with la + lb <= RES_MUL_LOG2 keeps T < M*p, so
    r = (T + q_hat*p)/M < (kA+1)*p <= 2^6*p — the loop-invariant output
    bound. `ops/tower.py` threads static per-site bound literals (`blog`)
    through its subtraction sites; HACKING.md "Residue-resident pairing"
    carries the full bound walk.
  * `add_resident`/`sub_resident` are residue-wise; subtraction adds the
    precomputed residues of (p << blog) so the represented value stays
    nonnegative (blog >= the subtrahend's static bound).
  * `to_resident`/`from_resident` convert at genuine boundaries only;
    `from_resident` first refreshes (one `mul_resident` by the Montgomery
    one, resetting any bound <= RES_MUL_LOG2 to < (kA+1)p < MB) and then
    runs the same exact CRT + conditional-subtract ladder as `mul`, so
    canonical boundary limbs remain bit-identical to the CIOS backend.
  * `ResidentRns` (via `RnsField.resident()`) wraps all of this in the
    Field method surface so `Tower.as_resident()` reuses every tower
    formula unchanged; `eq`/`is_zero` raise — comparisons are positional
    boundaries by definition.

The residue<->positional conversion counters (`conversion_counts`)
increment at TRACE time — one count per traced call site, so a
`lax.scan` body counts once however many steps it runs. That is exactly
the right unit for the claim they substantiate (scripts/rns_smoke.py's
conversion count): per-mul before, per-line-boundary after.

**Montgomery convention.** The backend's Montgomery constant is M (the
base-A product), not the CIOS kernel's R = 2^(16n): division by M is what
the RNS reduction gets for free. `mont_r`/`mont_r2` are overridden
accordingly, so pack/unpack/to_mont/from_mont stay self-consistent and
every *non-Montgomery* boundary value (unpacked results, verify verdicts,
affine coordinates) is bit-identical to the CIOS backend — that is the
bit-exactness contract tests/test_fp_jax.py and scripts/rns_smoke.py pin.

**Exactness.** Every modular reduction is the float-assisted
`v - floor(v/m)·m` with integer correction (`_mod_rows`): the float
estimate may be off by ±1, the integer fix-up makes the result exact, so
the whole pipeline is integer-exact end to end. All intermediate
magnitudes are proven < 2^30 (comments at each site), inside int32.

On CPU the contractions run as single int32 `dot_general`s (exact, XLA);
`int8_dots=True` (default on accelerators) splits each constant matrix
and operand into <=7-bit planes so every contraction is an int8 x int8 ->
int32 MXU matmul — bit-identical output, property-tested against the
int32 lowering.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from handel_tpu.ops.fp import (
    LIMB_BITS,
    LIMB_MASK,
    Field,
    _has_pallas_tpu,
    _int_to_limbs,
)

_PRIME_BOUND = 1 << 13  # residue moduli < 2^13: products and dot terms fit int32


def _small_primes_desc(bound: int) -> list[int]:
    sieve = np.ones(bound, dtype=bool)
    sieve[:2] = False
    for i in range(2, int(bound**0.5) + 1):
        if sieve[i]:
            sieve[i * i :: i] = False
    return [int(x) for x in np.nonzero(sieve)[0][::-1]]


def _limbs8(x: int, n: int) -> list[int]:
    return [(x >> (8 * t)) & 0xFF for t in range(n)]


class RnsField(Field):
    """Field with `mul` replaced by the RNS Montgomery pipeline.

    Representation-compatible with the CIOS backend (canonical positional
    limbs at boundaries); Montgomery constant is M = prod(base A) instead
    of 2^(16n). Works for any odd prime p with enough sub-2^13 primes —
    BN254 (k_A=20, k_B=21) and BLS12-381 (k_A=30, k_B=31) both fit.
    """

    backend = "rns"

    # Closure exponent for resident chaining: base A is grown until
    # M >= 2^RES_MUL_LOG2 * p, so mul_resident stays exact for any operand
    # pair whose static bound exponents sum to <= RES_MUL_LOG2. The tower's
    # deepest product (conj(f) * f^-1 in the final-exp easy part) multiplies
    # bounds 2^24*p x 2^16*p inside f6_mul pre-sums — max exponent sum 54;
    # 56 leaves margin without growing base A by another prime.
    RES_MUL_LOG2 = 56
    # sub_resident offset table covers blog in [0, RES_MAX_BLOG]
    RES_MAX_BLOG = 32

    def __init__(self, p: int, use_pallas: bool | None = None,
                 backend: str | None = None):
        # the CIOS Pallas kernel computes a*b*R^-1 — wrong constant for this
        # backend; mul() below never consults use_pallas
        super().__init__(p, use_pallas=False)
        if backend not in (None, "rns"):
            raise ValueError(
                f"RnsField is the 'rns' backend, got {backend!r}: construct "
                f"Field(p, backend='cios') for the CIOS kernel or "
                f"Field(p, backend='rns') for this one"
            )
        self._build_bases(p)
        # Montgomery constant: M, not R (see module docstring)
        self.mont_r = self.M % p
        self.mont_r2 = self.mont_r * self.mont_r % p
        # int8-plane lowering maps the contractions onto the MXU; the int32
        # single-dot lowering is bit-identical and cheaper to compile on CPU
        self.int8_dots = _has_pallas_tpu()
        # Pallas-fused resident kernel (elementwise Montgomery steps + both
        # base extensions in one VMEM-resident body) where available; the
        # XLA lowering is the same `_mul_resident_core` body, bit-identical
        self.fused_resident = _has_pallas_tpu()
        self._fused_fns: dict = {}
        # residue<->positional conversion counters (trace-time semantics —
        # module docstring): per traced call site, the provable-win metric
        self._n_to_resident = 0
        self._n_from_resident = 0
        self._adapter = None

    # -- construction -------------------------------------------------------

    def _build_bases(self, p: int) -> None:
        primes = iter(_small_primes_desc(_PRIME_BOUND))
        mA: list[int] = []
        M = 1
        # M >= 2^RES_MUL_LOG2 * p closes RESIDENT chaining: for operands
        # bounded by 2^la*p, 2^lb*p with la+lb <= RES_MUL_LOG2 the product
        # T < M*p, so r = (T + q_hat*p)/M < p + kA*p = (kA+1)*p — the same
        # output bound the canonical path (T < p^2 << M*p) always had. The
        # per-mul path's old M >= 4p condition is strictly implied.
        while M < (p << self.RES_MUL_LOG2):
            mA.append(next(primes))
            M *= mA[-1]
        kA = len(mA)
        # mul_resident's advertised output bound is 2^6 * p (HACKING.md
        # bound walk); (kA+1) <= 64 makes (kA+1)p <= 2^6*p. Holds with huge
        # margin for 13-bit moduli (kA ~ 24 for BN254, ~34 for BLS12-381).
        assert kA + 1 <= 64, "resident output bound 2^6*p needs kA+1 <= 64"
        mB: list[int] = []
        MB = 1
        while MB <= 2 * (kA + 1) * p:  # r < (k_A+1)p must be < MB (CRT range)
            mB.append(next(primes))
            MB *= mB[-1]
        kB = len(mB)
        mr = next(primes)
        assert mr > kB + 1, "redundant modulus must bound the CRT offset"
        self.mA, self.mB, self.mr = mA, mB, mr
        self.M, self.MB = M, MB
        self.kA, self.kB = kA, kB
        self.k_all = kA + kB + 1  # joint base: A ++ B ++ [m_r]

        n8 = 2 * self.nlimbs  # 8-bit limb count of the positional form
        m_all = mA + mB + [mr]
        # positional->residue conversion: W[i, j] = 2^(8j) mod m_i
        W = np.array(
            [[pow(2, 8 * j, m) for j in range(n8)] for m in m_all], np.int32
        )
        # folded q/xi constant: xi_i = T_i * (-p^{-1} * (M/m_i)^{-1}) mod m_i
        c1 = np.array(
            [(-pow(p, -1, m) * pow(M // m, -1, m)) % m for m in mA], np.int32
        )
        # base extension A -> B ++ [m_r]: E[j, i] = (M/m_i) mod m_j
        mB_r = mB + [mr]
        E = np.array([[(M // mi) % mj for mi in mA] for mj in mB_r], np.int32)
        p_modB = np.array([p % m for m in mB_r], np.int32)
        MinvB = np.array([pow(M % m, -1, m) for m in mB_r], np.int32)
        # exact CRT over base B: xi'_j = r_j * (MB/m_j)^{-1} mod m_j, then
        # r = sum(xi'_j * MB/m_j) - alpha*MB with alpha recovered through m_r
        c2 = np.array([pow(MB // m, -1, m) % m for m in mB], np.int32)
        L_mr = np.array([(MB // m) % mr for m in mB], np.int32)
        self._MBinv_r = int(pow(MB % mr, -1, mr))
        n8out = (MB.bit_length() + 7) // 8
        n8out += n8out % 2  # even, so 8->16 repack is a clean reshape
        L8 = np.array(
            [_limbs8(MB // m, n8out) for m in mB], np.int32
        ).T  # (n8out, kB)
        MB8 = np.array(_limbs8(MB, n8out), np.int32)
        self.n8out = n8out
        self.n16out = n8out // 2
        # binary canonicalization ladder: r < (kA+1)p <= 2^smax * p
        smax = (kA + 1 - 1).bit_length()
        self._sub_consts = [
            np.array(
                [((p << s) >> (16 * t)) & 0xFFFF for t in range(self.n16out)],
                np.int32,
            )
            for s in range(smax - 1, -1, -1)
        ]
        self._W, self._E, self._L8 = W, E, L8
        self._c1, self._c2 = c1, c2
        self._p_modB, self._MinvB = p_modB, MinvB
        self._L_mr, self._MB8 = L_mr, MB8
        self._m_all = np.array(m_all, np.int32)
        self._minv_all = (1.0 / self._m_all.astype(np.float64)).astype(
            np.float32
        )
        # -- resident-form constants ---------------------------------------
        # exact base extension B -> A (mul_resident's closing step): the
        # same Shenoy digits xi'_j = r_j * (MB/m_j)^{-1} the CRT uses, but
        # recombined mod base A instead of positionally
        self._E2 = np.array(
            [[(MB // mj) % mi for mj in mB] for mi in mA], np.int32
        )  # (kA, kB)
        self._MB_modA = np.array([MB % mi for mi in mA], np.int32)
        # Montgomery one (M mod p) as joint residues: the refresh multiplier
        # (x * one_hat * M^{-1} = x mod p with the bound reset to < (kA+1)p)
        self._one_res = np.array([(M % p) % m for m in m_all], np.int32)
        # sub_resident offsets: residues of (p << s) — adding the offset
        # keeps the represented difference nonnegative for any subtrahend
        # bounded by 2^s * p
        self._off_res = np.array(
            [[((p << s) % m) for m in m_all]
             for s in range(self.RES_MAX_BLOG + 1)],
            np.int32,
        )

    # -- exact modular primitives ------------------------------------------

    @staticmethod
    def _mod_rows(v, m, minv):
        """v mod m, exact, for int32 v in [0, 2^30) and m in (2, 2^13).

        Float estimate first: q = floor(f32(v)/m) is within ±1 of the true
        quotient (relative error <= ~3*2^-24 on a ratio < 2^19, absolute
        error < 0.1), then integer correction makes the residue exact —
        q*m <= v + m stays inside int32.
        """
        import jax.numpy as jnp

        q = jnp.floor(v.astype(jnp.float32) * minv).astype(jnp.int32)
        r = v - q * m
        r = jnp.where(r < 0, r + m, r)
        r = jnp.where(r >= m, r - m, r)
        return r

    def _dot(self, Wnp: np.ndarray, x, exact: bool = False, mvec=None,
             minvvec=None):
        """Constant-matrix contraction ``W @ x`` for int32 x (d, B).

        int32 mode: W split into <=7-bit planes so every partial dot stays
        < 2^26 (depth <= 48, terms < 2^7 * 2^13); the high plane is reduced
        mod m before the <<7 recombination unless `exact` (W < 2^8 there,
        so the raw recombination already fits).
        int8 mode (`self.int8_dots`): x additionally splits at bit 7 and
        all four partial contractions run as int8 x int8 -> int32
        `dot_general` — the MXU-native form; bit-identical results.
        """
        import jax
        import jax.numpy as jnp

        d = Wnp.shape[1]
        Wlo = jnp.asarray(Wnp & 0x7F)
        Whi = jnp.asarray(Wnp >> 7)  # < 2^6 (W < 2^13) or <= 1 (exact, W < 2^8)
        dn = (((1,), (0,)), ((), ()))

        def dot(a, b):
            return jax.lax.dot_general(a, b, dn,
                                       preferred_element_type=jnp.int32)

        if not self.int8_dots:
            # terms < 2^7 * 2^13 = 2^20, depth <= 48 -> partials < 2^26
            lo = dot(Wlo, x)
            hi = dot(Whi, x)
        else:
            xl = (x & 0x7F).astype(jnp.int8)
            xh = (x >> 7).astype(jnp.int8)  # < 2^6: residues and limbs < 2^13
            i8 = lambda w: w.astype(jnp.int8)
            # every partial: terms <= 127*127, depth <= 48 -> < 2^20.9
            lo = dot(i8(Wlo), xl) + (dot(i8(Wlo), xh) << 7)
            hi = dot(i8(Whi), xl) + (dot(i8(Whi), xh) << 7)
        if exact:
            # W < 2^8: hi <= depth * xmax -> lo + (hi << 7) < 2^27, exact
            return lo + (hi << 7)
        # congruence-preserving recombination: reduce hi first so the shift
        # cannot overflow (hi < 2^26 -> mod -> < 2^13 -> <<7 -> < 2^20)
        hi = self._mod_rows(hi, mvec, minvvec)
        return self._mod_rows(lo + (hi << 7), mvec, minvvec)

    # -- residue conversion -------------------------------------------------

    def _split8(self, a):
        """(nlimbs, B) uint32 16-bit limbs -> (2*nlimbs, B) int32 8-bit."""
        import jax.numpy as jnp

        a = a.astype(jnp.int32)
        return jnp.stack([a & 0xFF, a >> 8], axis=1).reshape(
            2 * self.nlimbs, a.shape[1]
        )

    def to_rns(self, a):
        """Positional (nlimbs, B) uint32 -> joint-base residues (k_all, B)
        int32 (base A rows, then base B rows, then the m_r channel)."""
        import jax.numpy as jnp

        m = jnp.asarray(self._m_all)[:, None]
        minv = jnp.asarray(self._minv_all)[:, None]
        return self._dot(self._W, self._split8(a), mvec=m, minvvec=minv)

    def from_rns_base_b(self, rB, rr):
        """Exact CRT: base-B residues (kB, B) + m_r channel (B,) of a value
        v < MB -> canonical positional 16-bit limbs (n16out, B) int32.

        Shenoy–Kumaresan: alpha = (sum(xi'_j * (MB/m_j)) - v) / MB is
        recovered exactly through the redundant channel (alpha < kB < m_r),
        then v = L8 @ xi' - alpha*MB8 in 8-bit columns, carry-propagated.
        """
        import jax.numpy as jnp

        mB = jnp.asarray(np.array(self.mB, np.int32))[:, None]
        mBinv = jnp.asarray(self._minv_all[self.kA : self.kA + self.kB])[:, None]
        mr = jnp.int32(self.mr)
        mrinv = jnp.float32(1.0 / self.mr)
        xi = self._mod_rows(rB * jnp.asarray(self._c2)[:, None], mB, mBinv)
        # alpha channel: per-term mod keeps the sum < kB * 2^13 < 2^19
        terms = self._mod_rows(xi * jnp.asarray(self._L_mr)[:, None], mr, mrinv)
        s = self._mod_rows(jnp.sum(terms, axis=0), mr, mrinv)
        # (s - v) * MB^{-1} mod m_r; + m_r keeps the difference nonnegative
        alpha = self._mod_rows(
            (s - rr + mr) * jnp.int32(self._MBinv_r), mr, mrinv
        )  # < kB exactly — the true CRT offset
        # positional columns: exact int32 (terms < 2^21, depth kB -> < 2^26)
        cols = self._dot(self._L8, xi, exact=True)
        cols = cols - alpha[None, :] * jnp.asarray(self._MB8)[:, None]
        # signed sequential carry: v - (v & 0xFF) is a multiple of 256, so
        # the arithmetic shift is exact floor division for negatives too
        carry = jnp.zeros_like(cols[0])
        out8 = []
        for t in range(self.n8out):
            v = cols[t] + carry
            low = v & 0xFF
            out8.append(low)
            carry = (v - low) >> 8
        # top carry is 0: the reconstructed integer is < MB by CRT range
        o8 = jnp.stack(out8)
        return o8[0::2] + (o8[1::2] << 8)  # (n16out, B) 16-bit rows

    def _cond_sub_const(self, v, cnp: np.ndarray):
        """v - C if v >= C else v, over (n16out, B) int32 16-bit rows."""
        import jax.numpy as jnp

        borrow = jnp.zeros_like(v[0])
        diff = []
        for i in range(self.n16out):
            d = v[i] - jnp.int32(int(cnp[i])) - borrow
            borrow = (d < 0).astype(jnp.int32)
            diff.append(d + (borrow << 16))
        keep = borrow > 0  # borrowed past the top -> v < C
        return jnp.stack(
            [jnp.where(keep, v[i], diff[i]) for i in range(self.n16out)]
        )

    # -- the kernel ---------------------------------------------------------

    def _mont_reduce(self, d):
        """Montgomery reduction steps 3-5 on a joint-residue product
        d = (ra*rb mod m) of shape (k_all, B): folded quotient digits in
        base A, offset-tolerant extension A -> B ++ [m_r], then
        r = (T + q_hat*p)/M elementwise. Returns (kB+1, B) residues of r in
        base B ++ [m_r]; r < (kA+1)p whenever T < M*p (always true for
        canonical operands, and guaranteed for resident chains by the
        RES_MUL_LOG2 basis condition). Shared by `mul` and `mul_resident`.
        """
        import jax.numpy as jnp

        kA = self.kA
        m_all = jnp.asarray(self._m_all)[:, None]
        minv_all = jnp.asarray(self._minv_all)[:, None]
        mB_r = m_all[kA:]
        mBinv_r = minv_all[kA:]
        # folded Montgomery quotient digits in base A (products < 2^26)
        xi = self._mod_rows(d[:kA] * jnp.asarray(self._c1)[:, None],
                            m_all[:kA], minv_all[:kA])
        # base extension A -> B ++ [m_r]: q_hat = q + c*M, c < kA — the
        # offset only shifts r by c*p, absorbed downstream (ladder or the
        # resident bound budget)
        Q = self._dot(self._E, xi, mvec=mB_r, minvvec=mBinv_r)
        # r = (T + q_hat*p)/M elementwise in B ++ [m_r]:
        # (d + Q*p) < 2^14 after reduction; * Minv < 2^27
        u = self._mod_rows(Q * jnp.asarray(self._p_modB)[:, None], mB_r,
                           mBinv_r)
        return self._mod_rows(
            (d[kA:] + u) * jnp.asarray(self._MinvB)[:, None], mB_r, mBinv_r
        )

    def _extend_b_to_a(self, r):
        """Exact base extension B ++ [m_r] -> A for a value v < MB given as
        (kB+1, B) residues: the Shenoy digits xi'_j plus the redundant
        channel recover the CRT offset alpha EXACTLY (alpha < kB < m_r), so
        v mod mA_i = (sum_j xi'_j * E2[i, j] - alpha * MB) mod mA_i with no
        approximation. Returns (kA, B) base-A residues."""
        import jax.numpy as jnp

        kA, kB = self.kA, self.kB
        mA = jnp.asarray(self._m_all[:kA])[:, None]
        mAinv = jnp.asarray(self._minv_all[:kA])[:, None]
        mB = jnp.asarray(self._m_all[kA : kA + kB])[:, None]
        mBinv = jnp.asarray(self._minv_all[kA : kA + kB])[:, None]
        mr = jnp.int32(self.mr)
        mrinv = jnp.float32(1.0 / self.mr)
        xi = self._mod_rows(r[:kB] * jnp.asarray(self._c2)[:, None], mB, mBinv)
        # alpha through the redundant channel (same algebra as
        # from_rns_base_b; per-term mod keeps the sum < kB * 2^13 < 2^19)
        terms = self._mod_rows(xi * jnp.asarray(self._L_mr)[:, None], mr, mrinv)
        s = self._mod_rows(jnp.sum(terms, axis=0), mr, mrinv)
        alpha = self._mod_rows(
            (s - r[kB] + mr) * jnp.int32(self._MBinv_r), mr, mrinv
        )
        rA = self._dot(self._E2, xi, mvec=mA, minvvec=mAinv)
        corr = self._mod_rows(
            alpha[None, :] * jnp.asarray(self._MB_modA)[:, None], mA, mAinv
        )
        # rA < mA, corr < mA: + mA keeps the difference nonnegative (< 2^14)
        return self._mod_rows(rA + mA - corr, mA, mAinv)

    def mul(self, a, b):
        """RNS Montgomery product: canonical a, b (< p, positional Montgomery
        form with constant M) -> canonical a*b*M^{-1} mod p. See module
        docstring for the step-by-step bound/exactness argument. Pays one
        residue conversion in and one CRT reconstruction out — the per-mul
        cost the resident form (`mul_resident`) eliminates."""
        import jax.numpy as jnp

        bsz = a.shape[1]
        if bsz == 0:  # empty slices appear inside library combinators
            return jnp.zeros_like(a)
        self._n_to_resident += 1
        self._n_from_resident += 1
        kB = self.kB
        m_all = jnp.asarray(self._m_all)[:, None]
        minv_all = jnp.asarray(self._minv_all)[:, None]

        # 1) residues of both operands in one contraction (batch-stacked)
        res = self._dot(
            self._W,
            jnp.concatenate([self._split8(a), self._split8(b)], axis=1),
            mvec=m_all,
            minvvec=minv_all,
        )
        ra, rb = res[:, :bsz], res[:, bsz:]
        # 2) residue-wise product T mod m_i (products < 2^26)
        d = self._mod_rows(ra * rb, m_all, minv_all)
        # 3-5) Montgomery reduction into base B ++ [m_r]
        r = self._mont_reduce(d)
        # 6) exact CRT back to positional form; r < (kA+1)p < MB
        v16 = self.from_rns_base_b(r[:kB], r[kB])
        # 7) canonicalize r < 2^smax * p down to < p (binary ladder)
        for cnp in self._sub_consts:
            v16 = self._cond_sub_const(v16, cnp)
        # value < p fits the field's limb count; higher rows are zero
        return v16[: self.nlimbs].astype(jnp.uint32)

    # -- resident form ------------------------------------------------------
    #
    # A resident value is a plain (k_all, B) int32 array of joint-base
    # residues (base A rows ++ base B rows ++ the m_r channel) representing
    # some integer v < 2^lb * p, where the bound exponent lb is a STATIC
    # property tracked by construction (ops/tower.py's per-site `blog`
    # literals), never materialized in arrays — so `jnp.concatenate`,
    # `lax.scan` carries, and `tree_map` stacking all work unchanged.

    def _mul_resident_core(self, ra, rb):
        """mul_resident body (shared verbatim by the XLA and Pallas-fused
        lowerings): joint residues x joint residues -> joint residues of
        ra*rb*M^{-1}, bound < (kA+1)p <= 2^6*p. Exact whenever the operand
        bound exponents sum to <= RES_MUL_LOG2 (T < M*p)."""
        import jax.numpy as jnp

        m_all = jnp.asarray(self._m_all)[:, None]
        minv_all = jnp.asarray(self._minv_all)[:, None]
        d = self._mod_rows(ra * rb, m_all, minv_all)
        r = self._mont_reduce(d)  # base B ++ [m_r] residues, r < (kA+1)p
        rA = self._extend_b_to_a(r)  # exact: (kA+1)p < MB
        return jnp.concatenate([rA, r], axis=0)

    def mul_resident(self, ra, rb):
        """Resident Montgomery product — no positional limbs anywhere.
        Inputs/outputs are (k_all, B) joint residues; the caller owns the
        static bound bookkeeping (sum of operand bound exponents must be
        <= RES_MUL_LOG2; output bound 2^6 * p)."""
        import jax.numpy as jnp

        ra = ra.astype(jnp.int32)
        rb = rb.astype(jnp.int32)
        if ra.shape[1] == 0:
            return jnp.zeros_like(ra)
        if self.fused_resident:
            return self._mul_resident_pallas(ra, rb)
        return self._mul_resident_core(ra, rb)

    def _mul_resident_pallas(self, ra, rb):
        """Pallas-fused lowering of `_mul_resident_core`: one kernel holds
        the residue product, both base extensions, and every float-assisted
        reduction in VMEM, so XLA cannot split the elementwise chain between
        the `dot_general`s into separate HBM round trips (it measurably
        won't fuse across the int8-plane contractions). Bit-identical by
        construction — the body IS `_mul_resident_core`."""
        import jax
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu
        import jax.numpy as jnp

        k = self.k_all
        bsz = ra.shape[1]
        tile = min(512, bsz)
        while bsz % tile != 0:
            tile //= 2
        key = (bsz, tile)
        cached = self._fused_fns.get(key)
        if cached is None:
            # a Pallas kernel may not capture array constants: trace the
            # body once, hoist its constants (moduli, reciprocals, the
            # base-extension matrices) and hand them in as whole-array
            # VMEM inputs
            blk = jax.ShapeDtypeStruct((k, tile), jnp.int32)
            closed = jax.make_jaxpr(self._mul_resident_core)(blk, blk)
            consts = closed.consts

            def kernel(a_ref, b_ref, *refs):
                *c_refs, o_ref = refs
                (out,) = jax.core.eval_jaxpr(
                    closed.jaxpr, [c[...] for c in c_refs],
                    a_ref[...], b_ref[...],
                )
                o_ref[...] = out

            def tile_spec():
                return pl.BlockSpec((k, tile), lambda i: (0, i),
                                    memory_space=pltpu.VMEM)

            fn = pl.pallas_call(
                kernel,
                name=f"rns_mul_{k}x{bsz}",
                out_shape=jax.ShapeDtypeStruct((k, bsz), jnp.int32),
                grid=(bsz // tile,),
                in_specs=[tile_spec(), tile_spec()] + [
                    pl.BlockSpec(c.shape, lambda i, nd=c.ndim: (0,) * nd,
                                 memory_space=pltpu.VMEM)
                    for c in consts
                ],
                out_specs=tile_spec(),
            )
            cached = self._fused_fns[key] = (fn, consts)
        fn, consts = cached
        return fn(ra, rb, *consts)

    def add_resident(self, ra, rb):
        """Residue-wise modular add; represented-value bound grows to
        max(la, lb) + 1 (caller-tracked)."""
        import jax.numpy as jnp

        m = jnp.asarray(self._m_all)[:, None]
        minv = jnp.asarray(self._minv_all)[:, None]
        return self._mod_rows(
            ra.astype(jnp.int32) + rb.astype(jnp.int32), m, minv
        )

    def sub_resident(self, ra, rb, blog: int):
        """Residue-wise subtract with a nonnegativity offset: computes
        ra + (p << blog) - rb in the residue domain, which represents
        a - b + 2^blog * p — congruent to a - b mod p and nonnegative
        whenever the subtrahend's static bound exponent is <= blog. Output
        bound max(la, blog) + 1 (caller-tracked)."""
        import jax.numpy as jnp

        if blog is None:
            raise ValueError(
                "resident subtraction needs a static `blog` bound literal "
                "for the subtrahend (see HACKING.md 'Residue-resident "
                "pairing'); positional backends ignore it"
            )
        if not 0 <= blog <= self.RES_MAX_BLOG:
            raise ValueError(
                f"blog={blog} outside the offset table [0, "
                f"{self.RES_MAX_BLOG}] — the tower bound walk never "
                f"exceeds 24; widen RES_MAX_BLOG if a new site does"
            )
        m = jnp.asarray(self._m_all)[:, None]
        minv = jnp.asarray(self._minv_all)[:, None]
        off = jnp.asarray(self._off_res[blog])[:, None]
        # ra + off + m - rb in [1, 3*2^13): inside _mod_rows' exact domain
        return self._mod_rows(
            ra.astype(jnp.int32) + off + m - rb.astype(jnp.int32), m, minv
        )

    def to_resident(self, a):
        """Canonical positional limbs -> resident joint residues (bound
        exponent 0). Counts one residue conversion (trace-time)."""
        self._n_to_resident += 1
        return self.to_rns(a)

    def refresh_resident(self, r):
        """Bound reset without leaving the residue domain: multiply by the
        Montgomery one (M mod p), so the value is unchanged mod p (and stays
        in Montgomery form) while the bound drops to < (kA+1)p <= 2^6*p.
        Valid for any input bound <= RES_MUL_LOG2."""
        import jax.numpy as jnp

        one = jnp.broadcast_to(
            jnp.asarray(self._one_res, jnp.int32)[:, None],
            (self.k_all, r.shape[1]),
        )
        return self.mul_resident(r, one)

    def from_resident(self, r):
        """Resident joint residues (any bound <= RES_MUL_LOG2) -> canonical
        positional limbs, bit-identical to the CIOS backend's boundary
        values. Refreshes first so the CRT range condition (value < MB)
        holds, then runs the same exact CRT + conditional-subtract ladder
        as `mul`. Counts one CRT reconstruction (trace-time)."""
        import jax.numpy as jnp

        self._n_from_resident += 1
        rr = self.refresh_resident(r)  # value < (kA+1)p < MB
        v16 = self.from_rns_base_b(
            rr[self.kA : self.kA + self.kB], rr[self.kA + self.kB]
        )
        for cnp in self._sub_consts:
            v16 = self._cond_sub_const(v16, cnp)
        return v16[: self.nlimbs].astype(jnp.uint32)

    # -- conversion accounting (trace-time; module docstring) ---------------

    def conversion_counts(self) -> dict:
        return {
            "to_resident": self._n_to_resident,
            "from_resident": self._n_from_resident,
            "total": self._n_to_resident + self._n_from_resident,
        }

    def reset_conversion_counts(self) -> None:
        self._n_to_resident = 0
        self._n_from_resident = 0

    def resident(self) -> "ResidentRns":
        """The Field-shaped adapter over resident values (cached)."""
        if self._adapter is None:
            self._adapter = ResidentRns(self)
        return self._adapter


class ResidentRns:
    """Field-shaped adapter over the resident value form.

    Duck-types the `Field` surface `ops/tower.py` consumes, with values as
    (k_all, B) int32 joint-residue arrays instead of (nlimbs, B) uint32
    positional limbs — so `Tower.as_resident()` reuses every tower formula
    (Karatsuba stacking, cyclotomic squaring, windowed pow) unchanged while
    no op pays a CRT round trip. The represented-value bound discipline is
    STATIC: `sub`/`neg` demand the per-site `blog` literal (subtrahend bound
    exponent, see HACKING.md "Residue-resident pairing"); the positional
    backends accept and ignore the same literal, keeping tower code
    backend-agnostic.

    `eq`/`is_zero` raise: two residue vectors of non-canonical values are
    not comparable without reconstruction — comparisons are positional
    boundaries by definition (`from_resident` first).
    """

    backend = "rns"
    is_resident = True
    limb_dtype = jnp.int32

    def __init__(self, F: RnsField):
        self.base = F
        self.p = F.p
        self.mont_r = F.mont_r
        self.mont_r2 = F.mont_r2
        # one batch row per joint residue channel: concatenation-stacking
        # and `_split` in ops/tower.py only need a consistent row count
        self.nlimbs = F.k_all

    # -- host-side conversions ---------------------------------------------

    def pack(self, xs, mont: bool = True):
        return self.base.to_resident(self.base.pack(xs, mont=mont))

    def unpack(self, limbs, mont: bool = True) -> list[int]:
        return self.base.unpack(self.base.from_resident(limbs), mont=mont)

    def constant(self, x: int, batch: int):
        """Montgomery-form constant broadcast to (k_all, batch) residues —
        computed directly on the host (bound exponent 0, no conversion
        counted: nothing crosses the residue/positional seam at runtime)."""
        v = x % self.p * self.mont_r % self.p
        res = np.array([v % int(m) for m in self.base._m_all], np.int32)
        return jnp.broadcast_to(res[:, None], (self.base.k_all, batch))

    # -- arithmetic ---------------------------------------------------------

    def add(self, a, b):
        return self.base.add_resident(a, b)

    def sub(self, a, b, blog: int | None = None):
        return self.base.sub_resident(a, b, blog)

    def neg(self, a, blog: int | None = None):
        return self.base.sub_resident(jnp.zeros_like(a), a, blog)

    def mul(self, a, b):
        return self.base.mul_resident(a, b)

    def sqr(self, a):
        return self.base.mul_resident(a, a)

    def refresh(self, a):
        return self.base.refresh_resident(a)

    def pow_const(self, a, e: int, window: int | None = None):
        """Windowed square-and-multiply on resident values. Bound-safe for
        inputs <= 2^28 * p: every internal product multiplies two values
        bounded by max(input, 2^6*p), well under the RES_MUL_LOG2 budget."""
        from handel_tpu.ops.fp import pow_window, windowed_pow

        return windowed_pow(
            a,
            e,
            pow_window(e) if window is None else window,
            mul=self.mul,
            sqr=lambda x: self.mul(x, x),
            stack=lambda t: jnp.stack(t),
            take=lambda s, i: s[i],
            select=lambda c, x, y: jnp.where(c, x, y),
        )

    def inv(self, a):
        """Fermat inverse a^(p-2); zero maps to zero. Output bound 2^6*p."""
        return self.pow_const(a, self.p - 2)

    def select(self, mask, a, b):
        return jnp.where(
            mask[None, :], a.astype(jnp.int32), b.astype(jnp.int32)
        )

    # -- positional-boundary ops: not available in residence ---------------

    def eq(self, a, b):
        raise RuntimeError(
            "ResidentRns.eq: residue vectors of non-canonical values are "
            "not directly comparable — reconstruct with from_resident() "
            "first (comparisons are positional boundaries)"
        )

    def is_zero(self, a):
        raise RuntimeError(
            "ResidentRns.is_zero: reconstruct with from_resident() first "
            "(comparisons are positional boundaries)"
        )
