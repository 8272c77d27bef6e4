"""RNS-backend CI gate (fast tier, CPU XLA path — ISSUE 14 + 16 acceptance).

Five checks, each a hard exit-nonzero failure:

1. Bit-exactness: a seeded batch of products (random + edge operands,
   including both operands at p-1) through `Field(backend="rns")` must
   match the CIOS kernel BIT-FOR-BIT at the canonical boundary — the
   representation the two backends contract to agree on (their Montgomery
   constants differ: R = 2^16n vs the base-A product M).
2. CRT round-trip: to_rns -> from_rns_base_b is exact over the full
   16n-bit positional range (top value 2^256-1 exercises the Shenoy
   alpha-recovery channel at its limit).
3. Backend plumbing: fp_backend survives TOML load/dump round-trip,
   rejects junk values, and reaches the constructed Field through
   new_scheme (TOML -> SimConfig -> scheme kwargs -> Curves -> Field).
4. Residue-resident conversion count (ISSUE 16): tracing the resident
   pairing crosses the CRT boundary O(line boundaries) times (points in,
   f12 out — <= 8), while the legacy form round-trips once per tower mul
   (thousands). Counted at trace time via `jax.eval_shape`, no compile.
5. Resident tower bit-exactness (compile-cheap): a seeded batch through
   the RESIDENT `f12_mul` — residue planes in, lazy CRT reconstruction
   out — matches the scalar oracle and the CIOS tower bit-for-bit at the
   canonical boundary.

`--full` additionally runs the full resident BN254 pairing NUMERICALLY
against the CIOS oracle — valid + forged candidates through both launch
classes (`pairing` and the batched `pairing_check` product) — minutes of
XLA compile on CPU, so it is opt-in (nightly), not every-push.

This gate is the CPU-only check of the kernel's arithmetic and the option's
plumbing on every commit; it measures no speed.

Usage: python scripts/rns_smoke.py [--full]
"""

from __future__ import annotations

import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def check_bit_exact() -> None:
    import numpy as np

    from handel_tpu.ops import bn254_ref as bn
    from handel_tpu.ops.fp import Field

    Fr = Field(bn.P, backend="rns")
    Fc = Field(bn.P, use_pallas=False)
    rng = np.random.default_rng(2024)
    xs = [int.from_bytes(rng.bytes(32), "little") % bn.P for _ in range(12)]
    xs += [0, 1, bn.P - 1, bn.P - 1]
    ys = list(reversed(xs))

    # correctness vs the bigint oracle
    got = Fr.unpack(Fr.mul(Fr.pack(xs), Fr.pack(ys)))
    want = [x * y % bn.P for x, y in zip(xs, ys)]
    assert got == want, "rns mul disagrees with the bigint oracle"

    # canonical-boundary limbs bitwise equal to CIOS
    plain = Fr.pack(xs, mont=False)
    assert np.array_equal(
        np.asarray(plain), np.asarray(Fc.pack(xs, mont=False))
    ), "canonical pack differs between backends"
    out_r = Fr.from_mont(Fr.mul(Fr.to_mont(plain), Fr.to_mont(plain)))
    out_c = Fc.from_mont(Fc.mul(Fc.to_mont(plain), Fc.to_mont(plain)))
    assert np.array_equal(np.asarray(out_r), np.asarray(out_c)), (
        "boundary limbs not bit-identical between rns and cios"
    )
    print(f"rns_smoke: bit-exact vs cios over {len(xs)} seeded products")


def check_crt_roundtrip() -> None:
    import jax.numpy as jnp
    import numpy as np

    from handel_tpu.ops import bn254_ref as bn
    from handel_tpu.ops.fp import Field

    F = Field(bn.P, backend="rns")
    n = F.nlimbs
    tops = [(1 << (16 * n)) - 1, bn.P, bn.P + 1, 12345, 0]
    arr = np.zeros((n, len(tops)), np.uint32)
    for j, v in enumerate(tops):
        for i in range(n):
            arr[i, j] = (v >> (16 * i)) & 0xFFFF
    r = F.to_rns(jnp.asarray(arr))
    v16 = np.asarray(
        F.from_rns_base_b(r[F.kA : F.kA + F.kB], r[F.kA + F.kB])
    )
    for j, v in enumerate(tops):
        rec = sum(int(v16[i, j]) << (16 * i) for i in range(F.n16out))
        assert rec == v, f"CRT round-trip broke at {v:#x}"
    print(f"rns_smoke: CRT round-trip exact over {len(tops)} values "
          f"(top {tops[0].bit_length()} bits)")


def check_toml_plumbing() -> None:
    from handel_tpu.models.registry import new_scheme
    from handel_tpu.ops.rns import RnsField
    from handel_tpu.sim.config import dump_config, load_config

    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "cfg.toml")
        with open(path, "w") as f:
            f.write('scheme = "bn254-jax"\nfp_backend = "rns"\n'
                    '[service]\nfp_backend = "cios"\n')
        cfg = load_config(path)
        assert cfg.fp_backend == "rns"
        assert cfg.service.fp_backend == "cios"
        dumped = dump_config(cfg)
        assert 'fp_backend = "rns"' in dumped
        bad = os.path.join(d, "bad.toml")
        with open(bad, "w") as f:
            f.write('fp_backend = "vpu"\n')
        try:
            load_config(bad)
        except ValueError:
            pass
        else:
            raise AssertionError("junk fp_backend accepted")
    sch = new_scheme(
        "bn254-jax", batch_size=4, mesh_devices=1, fp_backend="rns",
        warmup=False,
    )
    F = sch.constructor.curves.F
    assert type(F) is RnsField and F.backend == "rns"
    print("rns_smoke: fp_backend plumbed TOML -> SimConfig -> Field")


def _pairing_stack():
    """One RNS curve/pairing stack shared by the resident checks (the
    Field carries the conversion counters; the gamma re-packs at Tower
    construction must happen before any counter reset)."""
    from handel_tpu.ops.curve import BN254Curves
    from handel_tpu.ops.pairing import BN254Pairing

    curves = BN254Curves(backend="rns")
    return curves, BN254Pairing(curves), BN254Pairing(curves, resident=False)


def check_resident_conversions(stack) -> None:
    import jax

    from handel_tpu.ops import bn254_ref as bn

    curves, pr, legacy = stack
    F = curves.F
    B = 4
    xp = F.pack([bn.G1_GEN[0]] * B)
    yp = F.pack([bn.G1_GEN[1]] * B)
    xq = curves.T.f2_pack([bn.G2_GEN[0]] * B)
    yq = curves.T.f2_pack([bn.G2_GEN[1]] * B)
    p, q = (xp, yp), (xq, yq)

    F.reset_conversion_counts()
    jax.eval_shape(lambda p, q: pr.pairing(p, q), p, q)
    res = F.conversion_counts()["total"]
    F.reset_conversion_counts()
    jax.eval_shape(lambda p, q: legacy.pairing(p, q), p, q)
    leg = F.conversion_counts()["total"]
    F.reset_conversion_counts()

    assert res <= 8, (
        f"resident pairing crossed the CRT boundary {res} times — "
        "expected O(line boundaries) (points in + gamma embeds + f12 out)"
    )
    # the Miller scan body traces ONCE, so the legacy count here is
    # per-TRACED-mul (each executed iteration multiplies it again at
    # runtime); an order of magnitude at trace time is already the
    # O(tower muls) -> O(line boundaries) collapse
    assert leg >= 10 * res, (
        f"legacy trace converted only {leg} times vs resident {res} — "
        "the per-mul round trip should dominate by an order of magnitude"
    )
    print(f"rns_smoke: resident pairing converts {res}x per trace "
          f"(legacy per-mul form: {leg}x)")


def check_resident_tower_bit_exact(stack) -> None:
    import random as _random

    import jax

    from handel_tpu.ops import bn254_ref as bn

    curves, _, _ = stack
    rng = _random.Random(1606)

    def rand_f12():
        return tuple(
            tuple(
                (rng.randrange(bn.P), rng.randrange(bn.P)) for _ in range(3)
            )
            for _ in range(2)
        )

    a_vals = [rand_f12() for _ in range(4)]
    b_vals = [rand_f12() for _ in range(4)]
    # near-p operands stress the bound walk right at the modulus
    a_vals[0] = tuple(
        tuple((bn.P - 1, bn.P - 1) for _ in range(3)) for _ in range(2)
    )
    b_vals[0] = a_vals[0]

    Tr = curves.T.as_resident()
    ar, br = Tr.f12_pack(a_vals), Tr.f12_pack(b_vals)
    got = Tr.f12_unpack(jax.jit(Tr.f12_mul)(ar, br))
    exp = [bn.f12_mul(x, y) for x, y in zip(a_vals, b_vals)]
    assert got == exp, "resident f12_mul disagrees with the scalar oracle"

    Tc = curves.T
    got_c = Tc.f12_unpack(
        jax.jit(Tc.f12_mul)(Tc.f12_pack(a_vals), Tc.f12_pack(b_vals))
    )
    assert got == got_c, (
        "resident and per-mul towers disagree at the canonical boundary"
    )
    print("rns_smoke: resident f12_mul bit-exact vs oracle + legacy tower "
          f"over {len(a_vals)} lanes (incl. all-(p-1) operands)")


def check_resident_pairing_full(stack) -> None:
    """--full only: the resident pairing NUMERICALLY vs the CIOS oracle —
    valid + forged candidates through both launch classes. Minutes of XLA
    compile on CPU."""
    import random as _random

    import jax
    import jax.numpy as jnp

    from handel_tpu.ops import bn254_ref as bn

    curves, pr, _ = stack
    rng = _random.Random(16)
    B = 4

    # launch class 1: plain per-lane pairing vs the scalar oracle
    g1s = [bn.g1_mul(bn.G1_GEN, rng.randrange(1, bn.R)) for _ in range(B)]
    g2s = [bn.g2_mul(bn.G2_GEN, rng.randrange(1, bn.R)) for _ in range(B)]
    p = (curves.F.pack([pt[0] for pt in g1s]),
         curves.F.pack([pt[1] for pt in g1s]))
    q = (curves.T.f2_pack([pt[0] for pt in g2s]),
         curves.T.f2_pack([pt[1] for pt in g2s]))
    got = curves.T.f12_unpack(jax.jit(lambda p, q: pr.pairing(p, q))(p, q))
    exp = [bn.pairing(q_, p_) for p_, q_ in zip(g1s, g2s)]
    assert got == exp, "resident pairing disagrees with the oracle"
    print("rns_smoke[full]: resident pairing == oracle over "
          f"{B} seeded lanes")

    # launch class 2: the batched product check — one valid BLS candidate,
    # one forged (corrupted signature scalar)
    h = bn.g1_mul(bn.G1_GEN, rng.randrange(1, bn.R))
    sks = [rng.randrange(1, bn.R) for _ in range(2)]
    pks = [bn.g2_mul(bn.G2_GEN, sk) for sk in sks]
    sigs = [bn.g1_mul(h, sks[0]), bn.g1_mul(h, sks[1] + 1)]  # lane 1 forged
    g1s = [h, h, bn.g1_neg(sigs[0]), bn.g1_neg(sigs[1])]
    g2s = [pks[0], pks[1], bn.G2_GEN, bn.G2_GEN]
    p = (curves.F.pack([pt[0] for pt in g1s]),
         curves.F.pack([pt[1] for pt in g1s]))
    q = (curves.T.f2_pack([pt[0] for pt in g2s]),
         curves.T.f2_pack([pt[1] for pt in g2s]))
    mask = jnp.ones((4,), bool)
    ok = jax.jit(lambda p, q, m: pr.pairing_check(p, q, m, 2))(p, q, mask)
    assert list(map(bool, ok)) == [True, False], (
        "resident pairing_check verdicts wrong on valid+forged candidates"
    )
    print("rns_smoke[full]: resident pairing_check accepts valid / "
          "rejects forged")


def main() -> int:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    full = "--full" in sys.argv[1:]
    check_bit_exact()
    check_crt_roundtrip()
    check_toml_plumbing()
    stack = _pairing_stack()
    check_resident_conversions(stack)
    check_resident_tower_bit_exact(stack)
    if full:
        check_resident_pairing_full(stack)
    print("rns_smoke: OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
