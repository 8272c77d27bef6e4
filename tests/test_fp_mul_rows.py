"""The multiplication kernel's rows as register tiles give the same limbs.

`Field._mul_cols` indexes limb rows with `a[i]` and never says how many axes
a row has: `ops/fp.py` `_mul_pallas` hands it `(limbs, S, 128)` blocks, so a
row fills S sublanes of its registers. Exact, on the CPU: the body eagerly on
3-D operands, and the Pallas call itself, interpreted, through the shipped
rule (`fp.mul_tile`) at every width the launch programs hold — both against
the plain XLA form `_mul_cols_vec` on the same values flattened.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from handel_tpu.ops import bls12_381_ref as bls
from handel_tpu.ops import bn254_ref as bn
from handel_tpu.ops import fp

FIELDS = {16: bn.P, 24: bls.P}
# widths of the launch programs (128 lanes x the products a tower operation
# stacks into one call), a stage of the wide patch in G1, and a call of two
# sublanes
WIDTHS = [128, 256, 1536, 2304, 3072, 3840, 4608, 6912, 9216, 9984, 12288]


def operands(F, shape, seed):
    """Canonical limbs: the first lanes hold 0, 1 and p - 1 against each
    other (Montgomery form or not, the kernel multiplies what it is given),
    the rest is random below p."""
    rng = np.random.default_rng(seed)
    lanes = int(np.prod(shape))
    edge = [0, 1, F.p - 1]
    pairs = [(x, y) for x in edge for y in edge]
    draw = lambda: int.from_bytes(rng.bytes(2 * F.nlimbs), "little") % F.p
    xs = [x for x, _ in pairs] + [draw() for _ in range(lanes - len(pairs))]
    ys = [y for _, y in pairs] + [draw() for _ in range(lanes - len(pairs))]
    pack = lambda vs: jnp.asarray(F.pack_batch_np(vs, mont=False)).reshape(
        (F.nlimbs,) + shape)
    return pack(xs), pack(ys)


@pytest.mark.parametrize("sublanes", [1, 2, 6, 8, 13, 18])
@pytest.mark.parametrize("nlimbs", sorted(FIELDS))
def test_body_on_tiles_equals_the_plain_form(nlimbs, sublanes):
    F = fp.Field(FIELDS[nlimbs], use_pallas=False)
    a, b = operands(F, (sublanes, 128), seed=sublanes)
    got = np.asarray(F._mul_cols(a, b))
    assert got.shape == (nlimbs, sublanes, 128)
    flat = lambda x: x.reshape(nlimbs, -1)
    want = np.asarray(F._mul_cols_vec(flat(a), flat(b)))
    assert np.array_equal(flat(got), want)
    # the values are products mod p (R^-1: Montgomery's), not only equal
    r_inv = pow(F.mont_r, -1, F.p)
    xs, ys = F.unpack(flat(a), mont=False), F.unpack(flat(b), mont=False)
    assert F.unpack(want, mont=False) == [
        x * y * r_inv % F.p for x, y in zip(xs, ys)]


@pytest.fixture
def interpreted(monkeypatch):
    """`_mul_pallas` builds its call from `pallas.pallas_call`: interpret it
    here, in the test, not through an option of the program."""
    from jax.experimental import pallas as pl

    monkeypatch.setattr(
        pl, "pallas_call", functools.partial(pl.pallas_call, interpret=True))


def compile_unfused(fn, *args):
    """XLA:CPU's fusion duplicates the unrolled body's column sums into their
    users (the 24-limb body then runs for tens of minutes): compile the
    interpreted kernel without it."""
    return jax.jit(fn).lower(*args).compile(
        compiler_options={"xla_disable_hlo_passes": "fusion"})


@pytest.mark.parametrize("width", WIDTHS)
@pytest.mark.parametrize("nlimbs", sorted(FIELDS))
def test_pallas_call_through_the_shipped_rule(interpreted, nlimbs, width):
    F = fp.Field(FIELDS[nlimbs], use_pallas=True)
    a, b = operands(F, (width,), seed=width)
    got = np.asarray(compile_unfused(F.mul, a, b)(a, b))
    want = np.asarray(jax.jit(F._mul_cols_vec)(a, b))
    assert got.shape == (nlimbs, width) and np.array_equal(got, want)


def test_odd_width_is_padded_and_cut_back(interpreted):
    """130 lanes (an interior slice of `associative_scan`) run as a 256-lane
    call of two sublanes and come back 130 wide."""
    F = fp.Field(bn.P, use_pallas=True)
    a, b = operands(F, (130,), seed=130)
    got = np.asarray(compile_unfused(F.mul, a, b)(a, b))
    assert np.array_equal(got, np.asarray(F._mul_cols_vec(a, b)))
