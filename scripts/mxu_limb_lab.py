"""MXU experiment: can the systolic array beat the VPU CIOS kernel at
254-bit Montgomery multiplication? (SURVEY.md §2.2 "Montgomery/CRT form
suited to MXU"; VERDICT r04 next-round item 6.)

The structural question: the MXU wants deep contractions (K≥128 on a
128×128 array); a batched limb product is an OUTER product per element
(contraction depth 1), so the only MXU-shaped pieces are (a) the
schoolbook product against a CONSTANT matrix, which doesn't exist (both
operands vary), and (b) the reduction-by-constant REDMAT. This lab
measures the candidates and the raw ceiling so the question is closed
with numbers either way:

  * prod            — the production Pallas CIOS kernel (ops/fp.py), the
                      bar to beat (not measured on this machine).
  * outer8_f32      — 8-bit limb split (32 limbs), full (B,32,32) outer
                      product via einsum→dot_general, anti-diagonal fold,
                      then uint32 Montgomery reduction. All f32 products
                      ≤ 255²·63 < 2^24, so the fold is EXACT; the einsum
                      is the piece XLA may or may not map to the MXU.
  * mxu_int8_ceiling — a dense 4096³ s8×s8→s32 matmul: the chip's raw
                      int8 MXU rate, for computing what ANY
                      MXU-formulated mul could at best achieve.

  * rns             — the shipped answer to this lab's question:
                      `Field(backend="rns")` (ops/rns.py), residues +
                      base-extension as constant-matrix dot_general
                      contractions — deep-K MXU shape, no outer product.

Marginal methodology IS Field._throughput_bench's, via the shared
`handel_tpu.ops.fp.chained_marginal` helper (one copy, imported here and
by scripts/fp_kernel_lab.py): k-deep dependent chains inside one
executable so the dispatch floor cancels. Results land in
chiprun_out/fp_microbench.json under "mxu_lab" when run with --persist.

    python scripts/mxu_limb_lab.py [batch] [--persist]
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from handel_tpu.utils.jaxenv import apply_platform_env

apply_platform_env()

import jax
import jax.numpy as jnp
import numpy as np

from bench import write_json_atomic
from handel_tpu.ops import bn254_ref as bn
from handel_tpu.ops.fp import LIMB_BITS, Field, chained_marginal

N8 = 32  # 8-bit limbs for 256 bits


def split8(a16):
    """(16, B) uint32 16-bit limbs -> (32, B) uint32 8-bit limbs."""
    lo = a16 & 0xFF
    hi = (a16 >> 8) & 0xFF
    return jnp.concatenate(
        [jnp.stack([lo[i], hi[i]]) for i in range(a16.shape[0])], axis=0
    )


def outer8_product(a8, b8):
    """Exact schoolbook product of 8-bit-limb vectors via one einsum.

    P[b,i,j] = a8[i,b]·b8[j,b] in f32 (products ≤ 65025, exact), then the
    anti-diagonal fold c[k,b] = Σ_{i+j=k} P[b,i,j] with column sums ≤
    63·65025 < 2^24 — still exactly representable. Returns (63, B) f32.
    The einsum lowers to dot_general with batch dim b and NO contraction
    (outer product): the MXU-mapping question in one op.
    """
    af = a8.astype(jnp.float32)
    bf = b8.astype(jnp.float32)
    P = jnp.einsum("ib,jb->bij", af, bf)  # (B, 32, 32)
    # anti-diagonal fold: row i contributes to columns k = i..i+31
    B = P.shape[0]
    rows = [
        jnp.pad(P[:, i, :], ((0, 0), (i, N8 - 1 - i)))  # (B, 63)
        for i in range(N8)
    ]
    c = jnp.sum(jnp.stack(rows), axis=0)  # (B, 63)
    return c.T  # (63, B)


def make_outer8_mont(F: Field):
    """Full Montgomery mul in the outer-product formulation, oracle-exact.

    Reduction: carry-normalize the f32 columns to uint32 8-bit limbs, then
    Montgomery-reduce 8 bits at a time (32 iterations, m = c0·(-p^-1) mod
    2^8, c = (c + m·p) >> 8) with lazy uint32 carries — the standard CIOS
    tail at radix 2^8 on the VPU. The MXU (or not) part is the product.
    """
    p8 = np.zeros(N8, np.uint32)
    pv = F.p
    for i in range(N8):
        p8[i] = (pv >> (8 * i)) & 0xFF
    # the reduction accumulator keeps 64 8-bit columns; p only ever adds
    # into the low 32 at the current offset, so pad it with high zeros
    p8j = jnp.asarray(np.concatenate([p8, np.zeros(N8, np.uint32)]), jnp.uint32)
    ninv8 = (-pow(F.p, -1, 1 << 8)) % (1 << 8)

    def mont(a16, b16):
        a8 = split8(a16)
        b8 = split8(b16)
        c = outer8_product(a8, b8).astype(jnp.uint32)  # (63, B), ≤2^24
        c = jnp.concatenate([c, jnp.zeros((1, c.shape[1]), jnp.uint32)])

        def red_step(c, _):
            m = ((c[0] & 0xFF) * ninv8) & 0xFF  # (B,)
            c = c + m[None, :] * p8j[:, None]  # lazy, ≤ 2^24 + 2^16·2^8
            # shift one 8-bit limb: propagate c[0]'s carry into c[1] first
            c = c.at[1].add(c[0] >> 8)
            return jnp.concatenate([c[1:], jnp.zeros((1, c.shape[1]), jnp.uint32)]), None

        c, _ = jax.lax.scan(red_step, c, None, length=N8)
        # final carry propagation to canonical 8-bit limbs
        def carry_step(carry, limb):
            v = limb + carry
            return v >> 8, v & 0xFF

        _, c = jax.lax.scan(carry_step, jnp.zeros((c.shape[1],), jnp.uint32), c)
        # repack 8-bit (64,B) -> 16-bit (16,B); rows ≥32 are zero
        c16 = c[0::2] + (c[1::2] << 8)
        c16 = c16[: F.nlimbs]
        # canonicalize: Montgomery leaves results < 2p; match the
        # production kernel's < p convention with one borrow-propagated
        # conditional subtract
        p16 = jnp.asarray(
            [(F.p >> (LIMB_BITS * i)) & 0xFFFF for i in range(F.nlimbs)],
            jnp.uint32,
        )[:, None]

        def sub_step(borrow, xy):
            x, y = xy
            d = x - y - borrow
            return (d >> 31) & 1, d & 0xFFFF

        borrow_out, diff = jax.lax.scan(
            sub_step,
            jnp.zeros((c16.shape[1],), jnp.uint32),
            (c16, jnp.broadcast_to(p16, c16.shape)),
        )
        ge_p = borrow_out == 0
        return jnp.where(ge_p[None, :], diff, c16)

    return mont


def marginal(fn, a, b, k1=4, k2=20, trials=5):
    """Lab-depth wrapper over the shared `chained_marginal` (one copy of
    the chained-dispatch methodology for every fp_microbench figure).
    Returns muls/s, or None (JSON null, never NaN) when the slope is lost
    to timing noise — best-of-trials per chain depth happens inside the
    shared helper, so one contended trial only inflates that trial's time
    instead of poisoning the slope."""
    rate, _floor = chained_marginal(fn, a, b, k1=k1, k2=k2, trials=trials)
    return rate


def main() -> int:
    batch = 1 << 15
    persist = "--persist" in sys.argv
    for arg in sys.argv[1:]:
        if arg.isdigit():
            batch = int(arg)
    F = Field(bn.P)
    print(f"backend={jax.default_backend()} batch={batch}")

    rng = np.random.default_rng(11)
    # full-range residues (256 random bits mod p): every 8-bit limb row,
    # every anti-diagonal pad, and the high-limb carry paths must carry
    # nonzero data through the agreement check below — small operands
    # (earlier draft: < 2^75) would leave rows i >= 10 multiplied by zero
    # and the "oracle-exact" claim unverified there
    raw = rng.integers(0, 256, (batch, 32), np.uint8)
    vals_a = [int.from_bytes(bytes(r), "little") % F.p for r in raw]
    raw_b = rng.integers(0, 256, (batch, 32), np.uint8)
    vals_b = [int.from_bytes(bytes(r), "little") % F.p for r in raw_b]
    a = F.pack(vals_a, mont=False)
    b = F.pack(vals_b, mont=False)

    # correctness first: outer8 Montgomery vs the production kernel
    mont8 = make_outer8_mont(F)
    got = np.asarray(jax.device_get(jax.jit(mont8)(a[:, :256], b[:, :256])))
    want = np.asarray(jax.device_get(jax.jit(F.mul)(a[:, :256], b[:, :256])))
    ok = np.array_equal(got, want)
    print(f"outer8_f32 vs prod agreement: {ok}")
    if not ok:
        bad = np.nonzero((got != want).any(axis=0))[0][:4]
        print(f"  first mismatching lanes: {bad}")
        return 1
    # rns gate: its Montgomery constant is M (not R), so compare against
    # the bigint oracle under its own constant rather than F.mul's output
    F_rns = Field(bn.P, backend="rns")
    got_r = F_rns.unpack(
        jax.device_get(jax.jit(F_rns.mul)(a[:, :256], b[:, :256])), mont=False
    )
    m_inv = pow(F_rns.mont_r, -1, F.p)
    want_r = [x * y * m_inv % F.p
              for x, y in zip(vals_a[:256], vals_b[:256])]
    ok_r = got_r == want_r
    print(f"rns vs oracle agreement: {ok_r}")
    if not ok_r:
        bad = [k for k in range(256) if got_r[k] != want_r[k]][:4]
        print(f"  first mismatching lanes: {bad}")
        return 1

    out = {"batch": batch, "backend": jax.default_backend()}
    for key, label, fn in (
        ("prod_muls_per_s", "prod (Pallas CIOS)", F.mul),
        ("outer8_muls_per_s", "outer8_f32 (einsum)", mont8),
        ("rns_muls_per_s", "rns (dot_general)", F_rns.mul),
    ):
        r = marginal(fn, a, b)
        out[key] = r
        if r is None:
            # provenance for the null, carried into the artifact so a
            # re-run keeps the committed entry reproducible
            out[key.split("_")[0] + "_note"] = (
                "slope lost to host timing noise; the top-level artifact "
                "carries the production figure"
            )
        shown = f"{r/1e6:9.1f}M muls/s marginal" if r else "unmeasurable (noise)"
        print(f"{label:22s} {shown}")

    # raw int8 MXU ceiling: one dense matmul, amortized over repeats
    n = 4096
    x8 = jnp.asarray(rng.integers(-127, 127, (n, n), np.int32), jnp.int8)

    @jax.jit
    def mm(x):
        y = x
        for _ in range(8):
            y = jax.lax.dot_general(
                y, x, (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32
            ).astype(jnp.int8)
        return y

    jax.block_until_ready(mm(x8))
    t0 = time.perf_counter()
    jax.block_until_ready(mm(x8))
    dt = time.perf_counter() - t0
    out["mxu_int8_ops_per_s"] = 8 * 2 * n**3 / dt
    print(f"mxu int8 ceiling:     {out['mxu_int8_ops_per_s']/1e12:9.2f} T int8-ops/s")
    # context: one 254-bit mont mul at radix 2^8 needs ~2·32² limb
    # mul-adds ≈ 4096 int8-ops, so the ceiling implies
    ceiling = out["mxu_int8_ops_per_s"] / 4096
    print(
        f"  => if the mul were perfectly MXU-shaped: ~{ceiling/1e9:.1f}B muls/s; "
        f"the blocker is that outer products contract over K=1, wasting "
        f"127/128 of the array"
    )

    # clobber protections mirroring bench.py's artifact contract: honor the
    # same env override tests use to redirect writes, never overwrite a
    # chip capture from a CPU run, and never replace it with a tiny-batch
    # run's noise-depressed figures
    path = os.environ.get("HANDEL_TPU_BENCH_FP_ARTIFACT") or os.path.normpath(
        os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "..",
            "chiprun_out",
            "fp_microbench.json",
        )
    )
    if (
        persist
        and jax.default_backend() == "cpu"
        and not os.environ.get("HANDEL_TPU_BENCH_FP_ARTIFACT")
    ):
        # a redirected artifact (the env override) can't clobber a chip
        # capture, so CPU-only tests may drive the persist path through it
        print("refusing --persist on the cpu backend (would overwrite the "
              "chip-captured mxu_lab entry)")
        persist = False
    if (
        persist
        and batch < (1 << 15)
        and not os.environ.get("HANDEL_TPU_BENCH_FP_ARTIFACT")
    ):
        print(
            f"refusing --persist at batch {batch} < 32768 to the default "
            "artifact (set HANDEL_TPU_BENCH_FP_ARTIFACT to redirect a "
            "small-batch run)"
        )
        persist = False
    if persist:
        art = {}
        if os.path.exists(path):
            # same corrupt-artifact guard as bench.py's merge: a truncated
            # file (non-atomic writer killed mid-write) must not crash the
            # persist after minutes of TPU measurement
            try:
                with open(path) as fh:
                    art = json.load(fh)
            except (json.JSONDecodeError, OSError):
                pass
        entry = {
            **{k: (round(v, 1) if isinstance(v, float) else v) for k, v in out.items()},
            "captured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        }
        prev = art.get("mxu_lab", {})
        if isinstance(prev, dict):
            # a lost slope (None) must not erase a previously captured valid
            # figure for the same key (bench.py keeps its artifact on
            # rate<=0 for the same reason)
            for k in ("prod_muls_per_s", "outer8_muls_per_s",
                      "rns_muls_per_s"):
                if entry.get(k) is None and prev.get(k) is not None:
                    entry[k] = prev[k]
                    # provenance: the carried figure was measured under the
                    # PRIOR entry's conditions, not this run's batch/time
                    entry[k.split("_")[0] + "_note"] = (
                        "carried from the prior capture (batch "
                        f"{prev.get('batch')}, {prev.get('captured_at')}); "
                        "this run's slope was lost to host timing noise"
                    )
        art["mxu_lab"] = entry
        write_json_atomic(path, art)
        print(f"persisted mxu_lab -> {path}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
