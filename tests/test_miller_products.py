"""The Miller accumulator's own products (ISSUE 32): a squaring for
`f = f^2` and a sparse product for `f = f * line`, each ONE stacked
`Field.mul` call — 36 and 39 base-field multiplications a lane where the
general Fp12 product (`Tower.f12_mul`, which both used to be) runs 54.

Fast tier: the products run eagerly on a 4-lane batch (seconds; nothing
pairing-sized compiles) and the structure cases only TRACE the Miller loop.
The bit-exact oracles of the whole pairing are the slow files beside this
one (tests/test_tower_jax.py, tests/test_pairing_jax.py,
tests/test_bls12_381_jax.py), whose modules are marked slow as a whole.
"""

import random

import jax
import jax.numpy as jnp
import pytest

from handel_tpu.ops import bls12_381_ref as bls
from handel_tpu.ops import bn254_ref as bn
from handel_tpu.ops import fp
from handel_tpu.ops.pairing import BLS12Pairing, BN254Pairing
from handel_tpu.ops.tower import Tower

B = 4
# D-type (BN254) and M-type (BLS12-381) placements, and the two others the
# routine's rule admits (one coefficient in one half, two in the other)
D_TYPE = ((0, None, None), (1, 2, None))
M_TYPE = ((2, 1, None), (None, 0, None))
PLACEMENTS = [D_TYPE, M_TYPE, ((None, 0, None), (1, 2, None)),
              ((1, 2, None), (0, None, None))]


class _BN254:
    ref, Pairing, slots = bn, BN254Pairing, D_TYPE
    # 64 loop bits, 36 set, two tail additions
    bits, adds, acc_fp_muls, general = 64, 38, 6282, 8964


class _BLS12381:
    ref, Pairing, slots = bls, BLS12Pairing, M_TYPE
    # |z|: 63 loop bits, 5 set, no tail addition
    bits, adds, acc_fp_muls, general = 63, 5, 4920, 7074


@pytest.fixture(scope="module", params=[_BN254, _BLS12381],
                ids=["bn254", "bls12_381"])
def curve(request):
    return request.param


@pytest.fixture(scope="module")
def T(curve):
    return Tower(fp.Field(curve.ref.P, use_pallas=False), params=curve.ref)


def _f12s(ref, seed):
    """Seeded Fp12 elements, then edge ones: every coordinate 0, the one,
    every coordinate 1, every coordinate p - 1, and two mixed draws from
    {0, 1, p - 1} — the carries and borrows a random element never meets."""
    rng = random.Random(seed)
    edge = (0, 1, ref.P - 1)

    def f12(draw):
        return tuple(tuple((draw(), draw()) for _ in range(3))
                     for _ in range(2))

    out = [f12(lambda: rng.randrange(ref.P)) for _ in range(B)]
    out += [f12(lambda: 0), ref.F12_ONE, f12(lambda: 1),
            f12(lambda: ref.P - 1)]
    out += [f12(lambda: rng.choice(edge)) for _ in range(2)]
    return out


def _lines(ref, seed, n):
    """(yp-term, xp-term, constant) triples: seeded, then edge ones."""
    rng = random.Random(seed)
    edge = (0, 1, ref.P - 1)
    rand = lambda: [(rng.randrange(ref.P), rng.randrange(ref.P))
                    for _ in range(3)]
    out = [rand() for _ in range(B)]
    out += [[(0, 0)] * 3, [(1, 0)] * 3, [(ref.P - 1, ref.P - 1)] * 3,
            [(1, 1)] * 3]
    while len(out) < n:
        out.append([(rng.choice(edge), rng.choice(edge)) for _ in range(3)])
    return out[:n]


def _pad(slots, line, zero):
    """The line as a full Fp12 element: `zero` in the empty slots."""
    return tuple(tuple(zero if i is None else line[i] for i in half)
                 for half in slots)


def _same_limbs(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) == 12 and all(
        x.dtype == y.dtype and bool(jnp.array_equal(x, y))
        for x, y in zip(la, lb))


def test_f12_sqr_is_the_general_product_of_equal_operands(curve, T):
    xs = _f12s(curve.ref, 32)
    ax = T.f12_pack(xs)
    got = T.f12_sqr(ax)
    assert T.f12_unpack(got) == [curve.ref.f12_mul(x, x) for x in xs]
    assert _same_limbs(got, T.f12_mul(ax, ax))


@pytest.mark.parametrize("slots", PLACEMENTS,
                         ids=["d_type", "m_type", "l0_at_v", "halves_swapped"])
def test_line_product_is_the_general_product_of_the_padded_line(
        curve, T, slots):
    xs = _f12s(curve.ref, 33)
    ls = _lines(curve.ref, 34, len(xs))
    ax = T.f12_pack(xs)
    al = tuple(T.f2_pack([l[i] for l in ls]) for i in range(3))
    got = T.f12_mul_line(ax, al, slots)
    assert T.f12_unpack(got) == [
        curve.ref.f12_mul(x, _pad(slots, l, (0, 0))) for x, l in zip(xs, ls)]
    padded = _pad(slots, al, T.f2_zero(len(xs)))
    assert _same_limbs(got, T.f12_mul(ax, padded))


def test_pairing_classes_place_their_lines(curve):
    """The placement comes from the pairing class (the twist's untwist):
    w-degrees 0, 1, 3 for the D-type, 0, 2, 3 for the M-type, the
    coefficient order (yp-term, xp-term, constant) of the step formulas."""
    assert curve.Pairing._LINE_SLOTS == curve.slots
    degrees = sorted(2 * j + i for i, half in enumerate(curve.slots)
                     for j, c in enumerate(half) if c is not None)
    assert degrees == ([0, 1, 3] if curve is _BN254 else [0, 2, 3])


@pytest.mark.parametrize("slots", [
    ((0, 1, 2), (None, None, None)),  # a full half: not a Miller line
    ((0, None, None), (1, None, 2)),  # v^2 filled
    ((0, None, None), (1, None, None)),  # two coefficients only
])
def test_line_placement_outside_the_rule_is_refused(T, slots):
    x = T.f12_one(B)
    line = (T.f2_one(B),) * 3
    with pytest.raises(ValueError, match="line placement"):
        T.f12_mul_line(x, line, slots)


def _lanes_handed_to_field_mul(T, fn, *args):
    """Widths (lanes) of the `Field.mul` calls `fn` makes, in order."""
    calls = []
    mul = T.F.mul

    def counted(a, b):
        calls.append(a.shape[1])
        return mul(a, b)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(T.F, "mul", counted)
        fn(*args)
    return calls


def test_each_product_is_one_stacked_field_mul(curve, T):
    """ONE `Field.mul` call a product, at the lanes the tower's cost
    attributes say — and those are the numbers behind the pairing's
    `miller_acc_fp_muls` (the benchmark's `miller.acc_fp_muls`)."""
    x = T.f12_pack(_f12s(curve.ref, 35)[:B])
    line = (T.f2_one(B),) * 3
    seen = {
        "sqr": _lanes_handed_to_field_mul(T, T.f12_sqr, x),
        "line": _lanes_handed_to_field_mul(
            T, T.f12_mul_line, x, line, curve.slots),
        "mul": _lanes_handed_to_field_mul(T, T.f12_mul, x, x),
    }
    assert seen == {"sqr": [36 * B], "line": [39 * B], "mul": [54 * B]}
    assert (T.f12_sqr_fp_muls, T.F12_MUL_LINE_FP_MULS, T.F12_MUL_FP_MULS) \
        == (36, 39, 54)
    pr = curve.Pairing()
    assert (len(pr._LOOP_BITS), pr.miller_add_steps) == (curve.bits,
                                                         curve.adds)
    sqr, ln = seen["sqr"][0] // B, seen["line"][0] // B
    assert pr.miller_acc_fp_muls == curve.acc_fp_muls \
        == curve.bits * (sqr + ln) + curve.adds * ln
    # what a program of the general product alone would count
    assert 54 * (2 * curve.bits + curve.adds) == curve.general


def _mul_lanes(jaxpr):
    """Lanes of every Field.mul in a jaxpr, at any depth: off the chip a
    Montgomery product opens with the (n, n, lanes) limb-product tensor
    (ops/fp.py `_mul_cols_vec`), the only rank-3 `mul` of the program."""
    out = []
    for e in jaxpr.eqns:
        if e.primitive.name == "mul" and len(e.outvars[0].aval.shape) == 3:
            n, n2, lanes = e.outvars[0].aval.shape
            assert n == n2
            out.append(lanes)
        for v in e.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    out += _mul_lanes(inner)
    return out


def test_miller_loop_holds_no_general_product(curve):
    """The doubling body — the `fori_loop` after the last set bit is that
    body alone — squares at 36 x B and multiplies its line in at 39 x B;
    nothing in the loop, its additions and its tail multiplies at 54 x B."""
    pr = curve.Pairing()
    x = jax.ShapeDtypeStruct((pr.F.nlimbs, B), jnp.uint32)
    jaxpr = jax.make_jaxpr(pr._miller_loop_res)((x, x), ((x, x), (x, x))).jaxpr
    over_runs, after = [e for e in jaxpr.eqns if e.primitive.name == "scan"]
    doubling = _mul_lanes(after.params["jaxpr"].jaxpr)
    assert doubling.count(36 * B) == 1 and doubling.count(39 * B) == 1
    assert 54 * B not in doubling
    # a run: the doubling body (inner loop), then the addition's line
    run = _mul_lanes(over_runs.params["jaxpr"].jaxpr)
    assert run.count(36 * B) == 1 and run.count(39 * B) == 2
    everywhere = _mul_lanes(jaxpr)
    assert 54 * B not in everywhere
    assert everywhere.count(39 * B) == 3 + pr._TAIL_ADDS


def test_resident_tower_keeps_the_general_product_for_the_squaring(curve):
    """The residue-resident tower squares by `f12_mul(a, a)` (the squaring's
    a0 + v a1 operand can leave the resident operand budget) and counts
    it so; its line product is the sparse one, at its own bound literals."""
    ref = curve.ref
    T = Tower(fp.Field(ref.P, backend="rns"), params=ref).as_resident()
    assert T.F.is_resident
    assert (T.f12_sqr_fp_muls, T.F12_MUL_LINE_FP_MULS) == (54, 39)
    xs = _f12s(ref, 36)[:B]
    ls = _lines(ref, 37, B)
    ax = T.f12_pack(xs)
    al = tuple(T.f2_pack([l[i] for l in ls]) for i in range(3))
    lanes = _lanes_handed_to_field_mul(T, T.f12_sqr, ax)
    assert lanes == [54 * B]
    assert T.f12_unpack(T.f12_sqr(ax)) == [ref.f12_mul(x, x) for x in xs]
    lanes = _lanes_handed_to_field_mul(
        T, T.f12_mul_line, ax, al, curve.slots)
    assert lanes == [39 * B]
    # the squaring's output (the widest input the loop hands the line
    # product) times the line: two steps of the accumulator's walk
    got = T.f12_mul_line(T.f12_sqr(ax), al, curve.slots)
    assert T.f12_unpack(got) == [
        ref.f12_mul(ref.f12_mul(x, x), _pad(curve.slots, l, (0, 0)))
        for x, l in zip(xs, ls)]
