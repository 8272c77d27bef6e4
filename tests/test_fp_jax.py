"""JAX limb field arithmetic vs the Python bigint oracle.

SURVEY.md §7 step 1: property tests of the Montgomery limb kernels against
ops/bn254_ref.py. Runs on CPU (pure-XLA path); the Pallas TPU path shares the
same `_mul_cols` body; tests/test_chip_compile.py sends it through the
chip's compiler and the benchmark's cells run it on the chip.

The `F` fixture is parametrized over the Field backend seam (ops/fp.py):
every property runs against BOTH the CIOS kernel and the RNS Montgomery
pipeline (ops/rns.py). The two backends use different Montgomery constants
(R vs the base-A product M), so properties are stated on unpacked integers
/ canonical boundary limbs — the representation the backends contract to
agree on bit-exactly. RNS-specific edge cases (operands near p, residue
overflow bounds, CRT exactness at the pairing-line boundary) follow at the
bottom; compile-cheap RNS unit checks live in the fast tier
(tests/test_rns.py, scripts/rns_smoke.py).
"""

import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# slow tier: XLA-compile-bound (every property test jits fresh field
# kernels) — runs in test-slow/test-all (nightly/CI); the fast tier keeps
# the oracle + protocol + sharding guards
pytestmark = pytest.mark.slow

from handel_tpu.ops import bn254_ref as bn
from handel_tpu.ops.fp import Field, LIMB_MASK

rng = random.Random(99)


@pytest.fixture(scope="module", params=["cios", "rns"])
def F(request):
    return Field(bn.P, use_pallas=False, backend=request.param)


def rand_elems(k):
    return [rng.randrange(bn.P) for _ in range(k)]


B = 8


def test_pack_unpack_roundtrip(F):
    xs = rand_elems(B) + [0, 1, bn.P - 1]
    assert F.unpack(F.pack(xs)) == xs
    assert F.unpack(F.pack(xs, mont=False), mont=False) == xs


def test_mul(F):
    xs, ys = rand_elems(B), rand_elems(B)
    out = jax.jit(F.mul)(F.pack(xs), F.pack(ys))
    assert F.unpack(out) == [x * y % bn.P for x, y in zip(xs, ys)]


def test_mul_edge_cases(F):
    xs = [0, 1, bn.P - 1, bn.P - 1, 2, (bn.P - 1) // 2]
    ys = [0, bn.P - 1, bn.P - 1, 1, (bn.P + 1) // 2, 2]
    out = jax.jit(F.mul)(F.pack(xs), F.pack(ys))
    assert F.unpack(out) == [x * y % bn.P for x, y in zip(xs, ys)]


def test_add_sub_neg(F):
    xs, ys = rand_elems(B) + [0, bn.P - 1], rand_elems(B) + [0, 1]
    ax, ay = F.pack(xs), F.pack(ys)
    assert F.unpack(jax.jit(F.add)(ax, ay)) == [
        (x + y) % bn.P for x, y in zip(xs, ys)
    ]
    assert F.unpack(jax.jit(F.sub)(ax, ay)) == [
        (x - y) % bn.P for x, y in zip(xs, ys)
    ]
    assert F.unpack(jax.jit(F.neg)(ax)) == [(-x) % bn.P for x in xs]


def test_mont_conversions(F):
    xs = rand_elems(B)
    plain = F.pack(xs, mont=False)
    m = jax.jit(F.to_mont)(plain)
    assert F.unpack(m) == xs
    back = jax.jit(F.from_mont)(m)
    assert F.unpack(back, mont=False) == xs


def test_pow_const_and_inv(F):
    xs = rand_elems(4)
    ax = F.pack(xs)
    out = jax.jit(lambda a: F.pow_const(a, 65537))(ax)
    assert F.unpack(out) == [pow(x, 65537, bn.P) for x in xs]
    inv = jax.jit(F.inv)(ax)
    assert F.unpack(inv) == [pow(x, -1, bn.P) for x in xs]


@pytest.mark.parametrize("window", [1, 4])
def test_pow_const_windowed_edges(F, window):
    """Both pow lowerings across their edge shapes: exponents at/below the
    window width (direct-chain branch), widths that pad, digits of 0 (skip
    lanes), and agreement with python pow on irregular bit patterns.

    The window is pinned EXPLICITLY (ADVICE r5 #2): default_pow_window
    returns 1 on the CPU CI backend, so leaving it to the default would
    silently drop coverage of the window=4 table+gather lowering — the
    production path on accelerators."""
    xs = rand_elems(3)
    ax = F.pack(xs)
    for e in (2, 3, 15, 16, 17, 0x8001, 0x10010, 0xF0F0F0F, bn.P - 2):
        got = F.unpack(
            jax.jit(lambda a, e=e: F.pow_const(a, e, window=window))(ax)
        )
        assert got == [pow(x, e, bn.P) for x in xs], f"e={e:#x} w={window}"


def test_windowed_pow_digits():
    from handel_tpu.ops.fp import windowed_pow_digits

    assert windowed_pow_digits(9, 4) is None  # <= window bits: direct chain
    assert windowed_pow_digits(0x1F, 4) == [1, 15]  # left-pad keeps MSB != 0
    assert windowed_pow_digits(0x100, 4) == [1, 0, 0]  # zero digits preserved
    digits = windowed_pow_digits(bn.P - 2, 4)
    acc = 0
    for d in digits:
        acc = (acc << 4) | d
    assert acc == bn.P - 2  # decomposition is exact


def test_eq_is_zero_select(F):
    xs = [0, 5, 7, 0]
    ys = [0, 5, 8, 1]
    ax, ay = F.pack(xs), F.pack(ys)
    assert jax.jit(F.eq)(ax, ay).tolist() == [True, True, False, False]
    assert jax.jit(F.is_zero)(F.pack(xs, mont=False)).tolist() == [
        True,
        False,
        False,
        True,
    ]
    mask = jnp.asarray([True, False, True, False])
    sel = F.select(mask, ax, ay)
    assert F.unpack(sel) == [0, 5, 7, 1]


def test_random_fuzz_mul(F):
    # wider fuzz: 64 random products in one batch
    xs, ys = rand_elems(64), rand_elems(64)
    out = jax.jit(F.mul)(F.pack(xs), F.pack(ys))
    assert F.unpack(out) == [x * y % bn.P for x, y in zip(xs, ys)]


@pytest.mark.parametrize("backend", ["cios", "rns"])
def test_bls12_381_field_params(backend):
    # the same engine must serve BLS12-381's 381-bit prime (24 limbs)
    p381 = 0x1A0111EA397FE69A4B1BA7B6434BACD764774B84F38512BF6730D2A0F6B0F6241EABFFFEB153FFFFB9FEFFFFFFFFAAAB
    F381 = Field(p381, use_pallas=False, backend=backend)
    assert F381.nlimbs == 24
    xs, ys = [rng.randrange(p381) for _ in range(4)], [
        rng.randrange(p381) for _ in range(4)
    ]
    out = jax.jit(F381.mul)(F381.pack(xs), F381.pack(ys))
    assert F381.unpack(out) == [x * y % p381 for x, y in zip(xs, ys)]


# -- RNS-specific edges (ops/rns.py) ------------------------------------------


@pytest.fixture(scope="module")
def Frns():
    return Field(bn.P, backend="rns")


@pytest.fixture(scope="module")
def Fcios():
    return Field(bn.P, use_pallas=False)


def test_rns_operands_near_p(Frns, Fcios):
    """The canonicalization ladder's worst inputs: both operands at the top
    of the field, where r = (T + q_hat*p)/M approaches the (kA+1)p bound
    and every binary conditional-subtract step fires. Boundary limbs must
    stay bit-identical to the CIOS backend."""
    near = [bn.P - 1 - k for k in range(6)] + [1, 2]
    a_r, b_r = Frns.pack(near), Frns.pack(list(reversed(near)))
    got = Frns.unpack(jax.jit(Frns.mul)(a_r, b_r))
    want = [x * y % bn.P for x, y in zip(near, reversed(near))]
    assert got == want
    # canonical-boundary bit-exactness vs the CIOS oracle
    plain = Frns.pack(near, mont=False)
    r_out = jax.jit(lambda a: Frns.from_mont(Frns.mul(Frns.to_mont(a),
                                                      Frns.to_mont(a))))(plain)
    c_out = jax.jit(lambda a: Fcios.from_mont(Fcios.mul(Fcios.to_mont(a),
                                                        Fcios.to_mont(a))))(plain)
    assert np.array_equal(np.asarray(r_out), np.asarray(c_out))


def test_rns_residue_overflow_bounds(Frns):
    """Construction-time range invariants the int32 exactness proofs rest
    on, plus a mul where every residue row sits at its maximum (operands
    whose residues are m_i - 1 for many i): no intermediate may exceed the
    float-assisted reduction's 2^30 domain."""
    F = Frns
    assert F.M >= 4 * F.p  # r < (kA+1)p bound
    assert F.MB > 2 * (F.kA + 1) * F.p  # second-extension CRT range
    assert F.mr > F.kB + 1  # exact alpha recovery channel
    assert all(m < (1 << 13) for m in F.mA + F.mB + [F.mr])
    assert (1 << 16 * F.nlimbs) <= F.MB  # any 16n-bit value CRT-round-trips
    # operands ≡ -1 mod every base-A prime: maximal residues through the
    # product, xi, and base-extension paths
    import math

    prodA = F.M
    x = prodA - 1  # < M but > p — reduce into the field first
    vals = [x % F.p, (prodA // 2) % F.p, (F.MB - 1) % F.p, F.p - 1]
    a = F.pack(vals)
    b = F.pack([F.p - 1] * len(vals))
    got = F.unpack(jax.jit(F.mul)(a, b))
    assert got == [v * (F.p - 1) % F.p for v in vals]
    assert math.gcd(F.M, F.MB * F.mr) == 1  # bases coprime (CRT validity)


def test_rns_crt_roundtrip_full_range(Frns):
    """to_rns -> from_rns_base_b is EXACT over the full 16n-bit positional
    range (not just < p): the Shenoy alpha recovery must hold at the very
    top, 2^256 - 1."""
    F = Frns
    n = F.nlimbs
    tops = [(1 << (16 * n)) - 1, F.p, F.p + 1, (1 << (16 * n)) - F.p, 12345]
    arr = np.zeros((n, len(tops)), np.uint32)
    for j, v in enumerate(tops):
        for i in range(n):
            arr[i, j] = (v >> (16 * i)) & 0xFFFF
    a = jnp.asarray(arr)
    r = jax.jit(F.to_rns)(a)
    v16 = jax.jit(
        lambda rB, rr: F.from_rns_base_b(rB, rr)
    )(r[F.kA : F.kA + F.kB], r[F.kA + F.kB])
    got = np.asarray(v16)
    for j, v in enumerate(tops):
        rec = sum(int(got[i, j]) << (16 * i) for i in range(F.n16out))
        assert rec == v, f"CRT round-trip broke at {v:#x}"


def test_resident_chain_bit_exact_near_p(Frns, Fcios):
    """Residue-RESIDENT chains (residue-resident pairing) over seeded and
    near-p operands: mul -> add -> sub(blog) -> mul stays in the residue
    domain throughout and reconstructs ONCE; the boundary limbs must be
    bit-identical to the CIOS backend computing the same chain
    positionally."""
    A = Frns.resident()
    xs = rand_elems(6) + [bn.P - 1, bn.P - 1]
    ys = [bn.P - 1 - k for k in range(6)] + [1, bn.P - 1]

    def chain_resident():
        a, b = A.pack(xs), A.pack(ys)
        c = A.mul(a, b)
        d = A.add(c, a)
        e = A.sub(d, b, 7)
        # the two backends carry different Montgomery constants (M vs R):
        # bit-identity is contracted at the CANONICAL boundary, after
        # from_mont strips the backend's own constant
        return Frns.from_mont(Frns.from_resident(A.mul(e, c)))

    def chain_cios():
        a, b = Fcios.pack(xs), Fcios.pack(ys)
        c = Fcios.mul(a, b)
        d = Fcios.add(c, a)
        e = Fcios.sub(d, b)
        return Fcios.from_mont(Fcios.mul(e, c))

    r_out = jax.jit(chain_resident)()
    c_out = jax.jit(chain_cios)()
    assert np.array_equal(np.asarray(r_out), np.asarray(c_out))
    want = [
        (x * y % bn.P + x - y) * (x * y) % bn.P for x, y in zip(xs, ys)
    ]
    assert Frns.unpack(jnp.asarray(r_out), mont=False) == want


def test_resident_pairing_line_boundary(Frns, Fcios):
    """The pairing's genuine boundary shape, computed RESIDENT: the
    sparse-line expression l = a*b + c*d + e accumulates in residues and
    crosses the CRT exactly once at the end — bit-identical to the CIOS
    backend paying positional form at every hop. Near-p operands push the
    Montgomery-quotient overshoot to its worst case."""
    A = Frns.resident()
    vals = rand_elems(4) + [bn.P - 1, bn.P - 2, 1, bn.P - 1]
    rev = list(reversed(vals))

    def line_resident():
        a, b = A.pack(vals), A.pack(rev)
        t1 = A.mul(a, b)
        t2 = A.mul(A.add(t1, a), A.sub(t1, b, 7))
        out = A.add(A.mul(t2, A.refresh(t1)), a)
        return Frns.from_mont(Frns.from_resident(out))

    def line_cios():
        a, b = Fcios.pack(vals), Fcios.pack(rev)
        t1 = Fcios.mul(a, b)
        t2 = Fcios.mul(Fcios.add(t1, a), Fcios.sub(t1, b))
        return Fcios.from_mont(Fcios.add(Fcios.mul(t2, t1), a))

    assert np.array_equal(
        np.asarray(jax.jit(line_resident)()),
        np.asarray(jax.jit(line_cios)()),
    )


def test_resident_inv_and_pow(Frns):
    """The adapter's Fermat inverse and windowed pow on resident values,
    against python pow — the exponent path the final-exp tower leans on."""
    A = Frns.resident()
    xs = rand_elems(3) + [bn.P - 1]
    a = A.pack(xs)
    got = A.unpack(jax.jit(A.inv)(a))
    assert got == [pow(x, -1, bn.P) for x in xs]
    got = A.unpack(jax.jit(lambda v: A.pow_const(v, 0x113, window=4))(a))
    assert got == [pow(x, 0x113, bn.P) for x in xs]


def test_rns_exact_at_pairing_line_boundary(Frns, Fcios):
    """The pairing consumes positional form at line evaluations: chains of
    mul -> add -> mul (each mul paying a full CRT reconstruction). A
    sparse-line-shaped expression l = a*b + c*d + e must agree bit-exactly
    with the CIOS backend at the canonical boundary after EVERY hop, not
    just at the end."""
    vals = rand_elems(8)
    packs = {}
    for name, Fx in (("rns", Frns), ("cios", Fcios)):
        a, b = Fx.pack(vals), Fx.pack(list(reversed(vals)))
        t1 = Fx.mul(a, b)
        t2 = Fx.mul(Fx.add(t1, a), Fx.sub(t1, b))
        line = Fx.add(Fx.mul(t2, t1), a)
        packs[name] = [Fx.unpack(t) for t in (t1, t2, line)]
    assert packs["rns"] == packs["cios"]
