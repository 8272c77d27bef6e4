"""Montgomery-mul kernel lab: candidate Pallas/XLA formulations, cross-checked
and raced against the production `Field.mul`.

Motivation: the production CIOS kernel's marginal mul rate and the dispatch
floor are not measured on this machine (`Field._throughput_bench` measures
both on the chip). The lab's goal is chip-side compute, where mul throughput
is the bound. The production kernel body (`Field._mul_cols`) accumulates columns with per-limb
(B,)-shaped 1-D ops; on TPU a 1-D vector occupies one sublane of the (8, 128)
VPU tile, so up to 7/8 of the unit idles. The variants here restructure the
arithmetic into full-width (nlimbs, B) ops:

  * `mul_cios_fullwidth` — same interleaved CIOS algebra, but the schoolbook
    products and the m*p rows accumulate via static slice-adds on (2n+1, B)
    arrays (only the per-i m scalar row stays 1-D).
  * `mul_separated` — separated Montgomery: T = a*b, m = (T mod R)*p' mod R,
    t = (T + m*p)>>256, with the two constant-operand products (p', p)
    unrolled as full-width multiply-accumulates against scalar limb constants
    split 8-bit to keep every column < 2^24 in uint32.

The lab also races the RNS backend (`Field(backend="rns")`, ops/rns.py) —
the MXU-shaped dot_general formulation — as a first-class candidate.

Every candidate is validated against its own Montgomery-constant oracle
(the production path is itself oracle-validated in tests/test_fp_jax.py),
then timed with the SHARED chained-dispatch marginal helper
(`handel_tpu.ops.fp.chained_marginal` — the same methodology behind
`_throughput_bench` and scripts/mxu_limb_lab.py, so every figure in
the fp microbench artifact is like-for-like). Run on the target backend:

    python scripts/fp_kernel_lab.py [batch] [--variants v1,v2,...]
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from handel_tpu.utils.jaxenv import apply_platform_env

apply_platform_env()  # honor $HANDEL_TPU_PLATFORM before jax is imported

import jax
import jax.numpy as jnp
import numpy as np

from handel_tpu.ops import bn254_ref as bn
from handel_tpu.ops.fp import (
    LIMB_BITS,
    LIMB_MASK,
    Field,
    _int_to_limbs,
    chained_marginal,
)

_LANE = 128


def _split8(x: int) -> tuple[int, int]:
    return x & 0xFF, (x >> 8) & 0xFF


def _slice_add(acc, v, i: int, pad: bool):
    """acc[i:i+len(v)] += v with a static offset. `.at[].add` traces to
    scatter-add, which Pallas TPU cannot lower — the pad form traces to
    pad+add, which it can."""
    if not pad:
        return acc.at[i : i + v.shape[0]].add(v)
    return acc + jnp.pad(v, ((i, acc.shape[0] - i - v.shape[0]), (0, 0)))


class LabField:
    """Variant mul formulations sharing the production Field's constants."""

    def __init__(self, F: Field):
        self.F = F
        self.n = F.nlimbs
        self.p = F.p
        self.n0 = F.n0
        # full n-limb Montgomery multiplier p' = -p^{-1} mod R
        R = 1 << (LIMB_BITS * self.n)
        self.pprime = (-pow(F.p, -1, R)) % R
        self.pprime_limbs = [int(v) for v in _int_to_limbs(self.pprime, self.n)]
        self.p_limbs = [int(v) for v in F.p_limbs_np]

    # -- V1: CIOS with full-width column accumulation -----------------------

    def cios_fullwidth_body(self, a, b, pad=False):
        """Interleaved CIOS identical in algebra to Field._mul_cols, but the
        n^2 product terms land via n static slice-adds on a (2n+1, B) array
        (full-width VPU ops) instead of n^2 per-limb 1-D adds."""
        F, n = self.F, self.n
        bsz = a.shape[1]
        cols = jnp.zeros((2 * n + 1, bsz), jnp.uint32)
        for i in range(n):
            prod = a[i][None, :] * b  # (n, B) exact
            lo = prod & LIMB_MASK
            hi = prod >> LIMB_BITS
            cols = _slice_add(cols, lo, i, pad)
            cols = _slice_add(cols, hi, i + 1, pad)
        n0 = jnp.uint32(self.n0)
        # built from python-int scalars: Pallas kernels may not capture
        # device-array constants from the closure
        p_col = jnp.concatenate(
            [jnp.full((1, 1), int(v), jnp.uint32) for v in F.p_limbs_np], axis=0
        )
        carry = jnp.zeros((bsz,), jnp.uint32)
        for i in range(n):
            t0 = cols[i] + carry
            m = (t0 * n0) & LIMB_MASK
            mp = m[None, :] * p_col  # (n, B)
            mlo = mp & LIMB_MASK
            mhi = mp >> LIMB_BITS
            carry = (t0 + mlo[0]) >> LIMB_BITS
            cols = _slice_add(cols, mlo[1:], i + 1, pad)
            cols = _slice_add(cols, mhi, i + 1, pad)
        cols = _slice_add(cols, carry[None, :], n, pad)
        hi = cols[n : 2 * n]
        spill = jnp.pad(hi >> LIMB_BITS, ((1, 0), (0, 0)))[:n]
        rows = [(hi[k] & LIMB_MASK) + spill[k] for k in range(n)]
        carry2 = jnp.zeros_like(rows[0])
        out = []
        for k in range(n):
            t = rows[k] + carry2
            out.append(t & LIMB_MASK)
            carry2 = t >> LIMB_BITS
        return F._cond_sub_p_rows(out)

    # -- V2: separated Montgomery, constant-operand products ----------------

    def _mac_const(self, acc, x, limb_consts, lo_col0: int, keep: int, pad=False):
        """acc[lo_col0+j : ...] += x * limb_consts[j] for each 16-bit constant
        limb, with the constant split 8-bit so products of x < 2^17 stay in
        uint32, truncated to columns < keep. x: (n, B) rows of value < 2^17.
        Full-width ops only."""
        n = x.shape[0]
        for j, c in enumerate(limb_consts):
            base = lo_col0 + j
            if base >= keep:
                break
            w = min(n, keep - base)
            clo, chi = _split8(c)
            if clo:
                v = x[:w] * jnp.uint32(clo)  # < 2^25
                acc = _slice_add(acc, v & LIMB_MASK, base, pad)
                acc = _slice_add(acc, v >> LIMB_BITS, base + 1, pad)
            if chi:
                v = x[:w] * jnp.uint32(chi)  # < 2^25
                # times 2^8 straddles the 16-bit column boundary; mask before
                # shifting so the uint32 lane cannot overflow
                acc = _slice_add(acc, (v & 0xFF) << 8, base, pad)
                acc = _slice_add(acc, v >> 8, base + 1, pad)
        return acc

    def _norm_pass1(self, cols, pad=False):
        """One lazy-carry pass: (k, B) columns < 2^c -> rows < 2^16 + 2^(c-16),
        returning (rows, carry_rows_shifted_in) as a single array."""
        r = cols & LIMB_MASK
        c = cols >> LIMB_BITS
        return _slice_add(r, c[:-1], 1, pad), c[-1]

    def _ks_rows(self, s, nl):
        """0/1 carry closure over nl<=16 limb rows with values < 2^17 via the
        packed-word adder identity (Field._carry_word)."""
        r = s & LIMB_MASK
        g = s >> LIMB_BITS
        pr = (r == LIMB_MASK).astype(jnp.uint32)
        # scalar-unrolled bit packing (no closure-captured arrays: Pallas)
        gb = jnp.zeros_like(r[0])
        pb = jnp.zeros_like(r[0])
        for i in range(nl):
            gb = gb | (g[i] << i)
            pb = pb | (pr[i] << i)
        bor = gb | pb
        cw = (gb + bor) ^ gb ^ bor
        rows = [(r[i] + ((cw >> i) & 1)) & LIMB_MASK for i in range(nl)]
        return jnp.stack(rows), ((cw >> nl) & 1).astype(jnp.uint32)

    def separated_body(self, a, b, pad=False):
        F, n = self.F, self.n
        bsz = a.shape[1]
        # T = a*b in column basis: (2n, B), columns < 2^21
        T = jnp.zeros((2 * n, bsz), jnp.uint32)
        for i in range(n):
            prod = a[i][None, :] * b
            T = _slice_add(T, prod & LIMB_MASK, i, pad)
            T = _slice_add(T, prod >> LIMB_BITS, i + 1, pad)
        # semi-normalize low half for the constant product (values < 2^17)
        tlo, _tlo_carry = self._norm_pass1(T[:n], pad)
        # note: dropping _tlo_carry is sound MOD R (it carries 2^256 weight),
        # and m is only needed mod R
        # m = tlo * p' mod R, columns < 2^25 accumulated 8-bit-split
        m_acc = jnp.zeros((n + 1, bsz), jnp.uint32)
        m_acc = self._mac_const(m_acc, tlo, self.pprime_limbs, 0, n, pad)
        m1, _ = self._norm_pass1(m_acc[:n], pad)
        m, _ = self._ks_rows(m1, n)  # canonical m < R (mod-R truncation sound)
        # Acc = T + m*p exactly (m canonical 16-bit rows < 2^16)
        acc = _slice_add(jnp.zeros((2 * n + 1, bsz), jnp.uint32), T, 0, pad)
        acc = self._mac_const(acc, m, self.p_limbs, 0, 2 * n + 1, pad)
        # low half is ≡ 0 mod R; propagate its real carry into column n
        low1, lowc = self._norm_pass1(acc[:n], pad)
        _, ks_out = self._ks_rows(low1, n)
        hi1, _hic = self._norm_pass1(acc[n : 2 * n], pad)
        hi1 = _slice_add(hi1, (lowc + ks_out)[None, :], 0, pad)
        hi2, _c2 = self._ks_rows(hi1, n)
        # _hic/_c2/acc[2n] are identically 0: every column sum is nonnegative
        # and the result t = (T + m*p)/R < 2p < 2^255, so any weight >= 2^256
        # contribution would contradict T + m*p < p^2 + R*p. validate() checks.
        return F._cond_sub_p_rows([hi2[k] for k in range(n)])

    # -- wrappers -----------------------------------------------------------

    def jit_xla(self, body):
        return jax.jit(body)

    def jit_pallas(self, body, bsz: int, tile: int = 512):
        import functools

        body = functools.partial(body, pad=True)
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        n = self.n
        while bsz % tile != 0:
            tile //= 2

        def kernel(a_ref, b_ref, o_ref):
            o_ref[:] = body(a_ref[:], b_ref[:])

        return pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((n, bsz), jnp.uint32),
            grid=(bsz // tile,),
            in_specs=[
                pl.BlockSpec((n, tile), lambda i: (0, i), memory_space=pltpu.VMEM),
                pl.BlockSpec((n, tile), lambda i: (0, i), memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec(
                (n, tile), lambda i: (0, i), memory_space=pltpu.VMEM
            ),
        )


def validate(F: Field, fn, bsz: int = 256, seed: int = 7) -> None:
    """Exactness vs the bigint oracle, under the candidate field's OWN
    Montgomery constant (mont_r is R mod p for CIOS-family candidates, the
    base-A product M mod p for the RNS backend — pow(mont_r, -1, p) is the
    right quotient either way)."""
    rng = np.random.default_rng(seed)
    xs = [int(rng.integers(0, 1 << 62)) * int(rng.integers(0, 1 << 62)) % F.p
          for _ in range(bsz)]
    ys = [int(rng.integers(0, 1 << 62)) * int(rng.integers(0, 1 << 62)) % F.p
          for _ in range(bsz)]
    a = F.pack(xs, mont=False)
    b = F.pack(ys, mont=False)
    got = F.unpack(np.asarray(jax.device_get(fn(a, b))), mont=False)
    m_inv = pow(F.mont_r, -1, F.p)
    want = [x * y * m_inv % F.p for x, y in zip(xs, ys)]
    bad = [k for k in range(bsz) if got[k] != want[k]]
    assert not bad, f"mismatch at lanes {bad[:5]} (of {len(bad)})"


def bench(name: str, fn, a, b, trials: int = 5) -> float:
    """Chained-dispatch marginal rate (shared methodology — see
    chained_marginal): a naive time-one-call loop measures the dispatch
    round trip instead of the kernel."""
    rate, _floor = chained_marginal(fn, a, b, k1=4, k2=20, trials=trials)
    if rate is None:
        print(f"  {name:28s} marginal slope unmeasurable (timing noise)")
        return 0.0
    print(f"  {name:28s} {rate/1e6:8.2f}M muls/s marginal")
    return rate


def main() -> int:
    batch = int(sys.argv[1]) if len(sys.argv) > 1 and sys.argv[1].isdigit() else 1 << 18
    F = Field(bn.P)
    lab = LabField(F)
    rng = np.random.default_rng(3)
    a = jnp.asarray(rng.integers(0, 1 << LIMB_BITS, (F.nlimbs, batch), np.uint32))
    b = jnp.asarray(rng.integers(0, 1 << LIMB_BITS, (F.nlimbs, batch), np.uint32))
    on_tpu = jax.default_backend() != "cpu"
    print(f"backend={jax.default_backend()} batch={batch}")

    # (name, bench_fn, validate_fn, field): pallas builds are
    # shape-specialized to the bench batch with a fixed grid, so they are
    # validated through a SEPARATE small-batch build of the same body —
    # validating the bench build with 256-wide inputs would shape-mismatch
    # every pallas variant out of the race (advisor finding, r04). One
    # shared small-batch build per body: the tile variants share algebra,
    # so revalidating per tile would only re-pay compiles. Non-pallas
    # entries validate the bench fn itself (shape-polymorphic). `field`
    # carries each candidate's Montgomery constant into validate().
    prod = jax.jit(F.mul)
    F_rns = Field(bn.P, backend="rns")
    rns = jax.jit(F_rns.mul)
    candidates: list[tuple[str, object, object, Field]] = [
        ("prod(Field.mul)", prod, prod, F),
        ("rns(Field backend)", rns, rns, F_rns),
    ]
    for nm, body in (
        ("cios_fullwidth", lab.cios_fullwidth_body),
        ("separated", lab.separated_body),
    ):
        xla_fn = lab.jit_xla(body)
        candidates.append((f"xla:{nm}", xla_fn, xla_fn, F))
        if on_tpu:
            vfn = lab.jit_pallas(body, 256, 256)
            for tile in (256, 512, 1024, 2048):
                candidates.append(
                    (f"pallas:{nm}:t{tile}", lab.jit_pallas(body, batch, tile),
                     vfn, F)
                )

    for nm, _fn, vfn, cf in candidates:
        try:
            validate(cf, vfn)
            print(f"  {nm:28s} validate: OK")
        except Exception as e:  # noqa: BLE001
            print(f"  {nm:28s} validate: FAIL ({type(e).__name__}: {e})")
            candidates = [c for c in candidates if c[0] != nm]
    print("-- timing --")
    for nm, fn, _vfn, _cf in candidates:
        try:
            bench(nm, fn, a, b)
        except Exception as e:  # noqa: BLE001
            print(f"  {nm:28s} bench FAIL ({type(e).__name__}: {e})")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
