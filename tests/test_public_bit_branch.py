"""Loops over public bits (ISSUE 29): the Miller loop's addition step and a
pow chain's multiplication run only where the bit is set, instead of being
computed on every step and selected away. The loop is taken as the RUNS of
its bits (ops/fp.py `bit_runs`): an outer scan over the set bits, an inner
`fori_loop` of the doublings / squarings between them — no select on the
bit and no conditional (a taken `lax.cond` costs about as much again as what
it guards on the chip: PERF.md section 6, PR 29). A chain's digit width
follows its public exponent (`pow_window`).

Fast tier: the structure cases only TRACE the pairing (seconds, nothing
compiles) and the chain cases run a toy `mul`. The bit-exact oracles of the
same code are the slow files beside this one (tests/test_pairing_jax.py,
tests/test_tower_jax.py, tests/test_bls12_381_jax.py, tests/test_fp_jax.py),
whose modules are marked slow as a whole.
"""

import jax
import jax.extend
import jax.numpy as jnp
import pytest

from handel_tpu.ops import bls12_381_ref as bls
from handel_tpu.ops import bn254_ref as bn
from handel_tpu.ops import fp
from handel_tpu.ops.pairing import BLS12Pairing, BN254Pairing

B = 4

# exponent -> (window on the chip, multiplications the chain executes there
# and with the other width: `pow_chain_muls` at plan time, the instrumented
# `mul` at run time)
CHAINS = {
    "bls12_381_z": (-bls.Z, 1, (5, 29)),
    "bn254_u": (bn.U, 1, (27, 29)),
    "bn254_fermat": (bn.P - 2, 4, (77, 109)),
    "bls12_381_fermat": (bls.P - 2, 4, (109, 228)),
}


def _eqns(jaxpr, name):
    """Equations of primitive `name` at this level (not inside nested ones)."""
    return [e for e in jaxpr.eqns if e.primitive.name == name]


def _scan_body_uses_of_xs(scan_eqn):
    """Inside a scan's body, follow the scanned input (the step's public
    run length or digit) forward: the `while`s whose trip count derives
    from it, and the `cond`s and `select_n`s whose predicate does (at any
    depth under jit)."""
    body = scan_eqn.params["jaxpr"].jaxpr
    n_xs = len(body.invars) - scan_eqn.params["num_consts"] \
        - scan_eqn.params["num_carry"]
    assert n_xs == 1
    found = {"while": [], "cond": [], "select_n": []}

    def walk(jaxpr, tainted):
        for e in jaxpr.eqns:
            # (a literal operand is unhashable and never the scanned value)
            hit = [i for i, v in enumerate(e.invars)
                   if isinstance(v, jax.extend.core.Var) and v in tainted]
            if not hit:
                continue
            name = e.primitive.name
            if name == "while":
                # what it returns is the state, no longer the trip count
                found[name].append(e)
                continue
            if name in ("cond", "select_n") and 0 in hit:
                found[name].append(e)
            if name in ("jit", "pjit"):
                inner = e.params["jaxpr"].jaxpr
                walk(inner, {inner.invars[i] for i in hit})
            tainted.update(e.outvars)

    walk(body, {body.invars[-1]})
    return found


@pytest.mark.parametrize("cls,steps,adds", [(BN254Pairing, 66, 38),
                                            (BLS12Pairing, 63, 5)])
def test_miller_loop_adds_on_the_set_bits_only(cls, steps, adds):
    pr = cls()
    bits = pr._LOOP_BITS
    runs, tail = fp.bit_runs(bits)
    x = jax.ShapeDtypeStruct((pr.F.nlimbs, B), jnp.uint32)
    jaxpr = jax.make_jaxpr(pr._miller_loop_res)((x, x), ((x, x), (x, x))).jaxpr
    # the scan over the set bits, then the doublings after the last one
    over_runs, after = _eqns(jaxpr, "scan")
    assert over_runs.params["length"] == len(runs) == sum(bits)
    assert after.params["length"] == tail > 0
    found = _scan_body_uses_of_xs(over_runs)
    # the run's length drives one inner loop (the doublings) and nothing
    # else: no select on it, lane-wise or not, and no conditional
    assert len(found["while"]) == 1
    assert not found["cond"] and not found["select_n"]
    state = 6 + 12  # T (3 Fp2) and f (12 Fp): the whole of the step's state
    assert len(found["while"][0].outvars) >= state
    assert (pr.miller_steps, pr.miller_add_steps) == (steps, adds)


def test_bit_runs():
    assert fp.bit_runs([1, 0, 0, 1, 1, 0]) == ([1, 3, 1], 1)
    assert fp.bit_runs([0, 0]) == ([], 2) and fp.bit_runs([]) == ([], 0)
    for cls in (BN254Pairing, BLS12Pairing):
        bits = cls._LOOP_BITS
        runs, tail = fp.bit_runs(bits)
        assert len(runs) == sum(bits) and sum(runs) + tail == len(bits)
        again = [b for r in runs for b in [0] * (r - 1) + [1]] + [0] * tail
        assert again == list(bits)


@pytest.mark.parametrize("name", CHAINS)
def test_chain_planner_reads_the_exponent(name, monkeypatch):
    e, window, muls = CHAINS[name]
    monkeypatch.setattr(fp, "device_platform", lambda: "tpu")
    assert fp.pow_window(e) == window
    assert (fp.pow_chain_muls(e, window), fp.pow_chain_muls(e, 5 - window)) \
        == muls
    # off the chip the smallest graph wins, whatever the exponent
    monkeypatch.setattr(fp, "device_platform", lambda: "cpu")
    assert fp.pow_window(e) == 1


def _counted_pow(e, window, calls, m=8191):
    """x -> x^e mod m through `windowed_pow` under jit, every executed
    `mul` appending to `calls` (13-bit prime: products stay inside uint32)."""

    def mul(a, b):
        jax.debug.callback(lambda: calls.append(1))
        return (a * b) % m

    return jax.jit(lambda x: fp.windowed_pow(
        x, e, window, mul=mul, sqr=lambda a: (a * a) % m,
        stack=jnp.stack, take=lambda s, i: s[i], select=jnp.where,
    ))


@pytest.mark.parametrize("name", CHAINS)
def test_chain_executes_what_the_planner_counted(name):
    """The instrumented `mul` runs at run time as often as the planner
    counted: a bit scan only on the set bits (a zero bit multiplies
    nothing), a window on its table and every digit step."""
    e, window, muls = CHAINS[name]
    bases = [3, 5, 4242]
    for w, want in zip((window, 5 - window), muls):
        calls = []
        got = _counted_pow(e, w, calls)(jnp.asarray(bases, jnp.uint32))
        jax.effects_barrier()
        assert [int(v) for v in got] == [pow(b, e, 8191) for b in bases]
        assert len(calls) == want == fp.pow_chain_muls(e, w), (name, w)


@pytest.mark.parametrize("e", [0xF0F0F0F01, 0xF0F0F0F00, 0x1FF, 0x100, 5])
def test_bit_scan_of_any_exponent(e):
    """Runs of any shape: a set or a clear last bit, one long run, the
    direct chain of a tiny exponent."""
    got = _counted_pow(e, 1, [])(jnp.asarray([3, 4242], jnp.uint32))
    assert [int(v) for v in got] == [pow(b, e, 8191) for b in (3, 4242)]


@pytest.mark.parametrize("window", [1, 4])
def test_chain_scan_loops_over_runs_and_selects_on_digits(window):
    jaxpr = jax.make_jaxpr(lambda x: fp.windowed_pow(
        x, 0xF0F0F0F01, window, mul=lambda a, b: a * b, sqr=lambda a: a * a,
        stack=jnp.stack, take=lambda s, i: s[i], select=jnp.where,
    ))(jnp.ones((3,), jnp.uint32)).jaxpr
    (scan,) = _eqns(jaxpr, "scan")
    found = _scan_body_uses_of_xs(scan)
    assert not found["cond"]
    if window == 1:
        assert len(found["while"]) == 1 and not found["select_n"]
    else:
        assert not found["while"] and found["select_n"]
