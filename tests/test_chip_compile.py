"""The served path's kernels, compiled for a described TPU v5e — no chip.

The TPU's compiler is installed here and compiles for a chip that is
described, not attached (`topologies.get_topology_desc`). These cases hand
it the kernels of the verify path at the widths the full-width program
really contains (4096-key bank, 128 lanes) and check the Mosaic call is in
the result: what the chip's compiler refuses fails here, at no chip time.

Under JAX_PLATFORMS=cpu the code would take its CPU branches, so the
`chip_choices` fixture forces what the chip picks (Pallas on, pow window 4)
by steering `ops/fp.device_platform` — in the test, not through an option.

Rules of this file (the libtpu lock is per process): the topology is
described inside a module-scoped fixture that skips when it cannot be —
never at import, in a `skipif` or in `parametrize` — everything compiles in
the test's own process, and all such tests live in this one file.
"""

import hashlib
import re
import time
from functools import partial

import jax
import jax.numpy as jnp
import pytest

from handel_tpu.ops import bls12_381_ref as bls
from handel_tpu.ops import bn254_ref as bn
from handel_tpu.ops import fp

N_KEYS = 4096
LANES = 128
U32, I32, BOOL = jnp.uint32, jnp.int32, jnp.bool_


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def shape(topo):
    """ShapeDtypeStruct factory placed on the described chip 0."""
    from jax.sharding import SingleDeviceSharding

    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda dims, dtype: jax.ShapeDtypeStruct(
        dims, dtype, sharding=one_chip
    )


@pytest.fixture(scope="module")
def chip_choices():
    """What the code picks on the chip: Pallas kernels, pow window 4."""
    mp = pytest.MonkeyPatch()
    mp.setattr(fp, "device_platform", lambda: "tpu")
    yield
    mp.undo()


@pytest.fixture(scope="module", autouse=True)
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one (every rerun would warn and
    recompile): switch the cache off around this file."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


def mosaic_calls(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


def assert_grid_follows_the_width(F, x):
    """The kernel's grid and block are what `fp.mul_tile` reads off the
    width: full blocks of whole passes and, where they do not divide it, a
    partial one at the end."""
    (call,) = [e for e in jax.make_jaxpr(F.mul)(x, x).eqns
               if e.primitive.name == "pallas_call"]
    _, block = fp.mul_tile(x.shape[1])
    grid = call.params["grid_mapping"]
    assert grid.grid == (-(-x.shape[1] // block),)
    assert {tuple(d.block_size for d in b.block_shape)
            for b in grid.block_mappings} == {(F.nlimbs, block)}


# lane widths of stacked Field.mul calls read out of the lowered full-width
# launches: 128 = one per-lane mul, 6912 = the Fp12 mul stacked 54x (the
# most frequent width in both launches), 9216 and 9984 = the Miller
# accumulator's squaring (36x) and sparse line product (39x) over the 256
# pairs, 13824 = the general Fp12 product over them (the widest mul of the
# range launch), 4718592 = the dense launch's widest (4096 keys x 128 lanes
# x 9 stacked muls of a G2 add), 589824 = the wide hole patch's first
# stage in G2 (512 x 128 x 9: 576 passes of 1 024 lanes), 256 and 2304 =
# a call of two sublanes and one whose last pass is a quarter full
@pytest.mark.parametrize(
    "width", [128, 256, 2304, 6912, 9216, 9984, 13824, 589824, 4718592])
def test_cios_mul_bn254(shape, chip_choices, width):
    F = fp.Field(bn.P)
    assert F.use_pallas and F.nlimbs == 16
    x = shape((F.nlimbs, width), U32)
    compiled = jax.jit(F.mul).lower(x, x).compile()
    assert mosaic_calls(compiled) == 1
    # the kernel's name says operation and stacked width (profiler traces)
    assert f"%fp_mul_16x{width}" in compiled.as_text()
    assert_grid_follows_the_width(F, x)


# the same widths of the BLS12-381 range launch's pairing tail, and the G1
# patch's first stage (512 x 128 x 6 = 393216: 384 passes of 1 024 lanes)
@pytest.mark.parametrize("width", [128, 256, 2304, 6912, 9216, 9984, 13824, 393216])
def test_cios_mul_bls12_381(shape, chip_choices, width):
    F = fp.Field(bls.P)
    assert F.use_pallas and F.nlimbs == 24
    x = shape((F.nlimbs, width), U32)
    compiled = jax.jit(F.mul).lower(x, x).compile()
    assert mosaic_calls(compiled) == 1
    assert f"%fp_mul_24x{width}" in compiled.as_text()
    assert_grid_follows_the_width(F, x)


def test_rns_resident_mul(shape, chip_choices):
    """The fused resident kernel behind fp_backend="rns" (ops/rns.py): the
    residue product, both base extensions and the reductions in one body."""
    F = fp.Field(bn.P, backend="rns")
    assert F.fused_resident and F.int8_dots
    r = shape((F.k_all, 6912), I32)
    compiled = jax.jit(F.mul_resident).lower(r, r).compile()
    assert mosaic_calls(compiled) == 1
    assert f"%rns_mul_{F.k_all}x6912" in compiled.as_text()


def _device(n_keys: int, curve: str = "bn254"):
    if curve == "bls12_381_minpk":
        from handel_tpu.models.bls12_381 import MinPkPublicKey as Key
        from handel_tpu.models.bls12_381_jax import BLS12381MinPkDevice as Device
    elif curve == "bls12_381":
        from handel_tpu.models.bls12_381 import BLS12381PublicKey as Key
        from handel_tpu.models.bls12_381_jax import BLS12381Device as Device
    else:
        from handel_tpu.models.bn254 import BN254PublicKey as Key
        from handel_tpu.models.bn254_jax import BN254Device as Device

    gen = Device.ref.G2_GEN if Device.key_group == 2 else Device.ref.G1_GEN
    dev = Device([Key(gen)] * n_keys, batch_size=LANES)
    assert dev.curves.F.use_pallas and fp.default_pow_window() == 4
    return dev


def _coord(shape, nlimbs: int, n: int, cols: int):
    """Shape of one packed coordinate over n lanes: a limb array in G1
    (cols 1), an Fp2 pair of them in G2 (cols 2)."""
    col = shape((nlimbs, n), U32)
    return col if cols == 1 else (col,) * cols


def _bank(shape, n_keys: int, nlimbs: int = 16, cols: int = 2):
    """Shapes of a registry bank and its prefix table (jit arguments)."""
    key = lambda n: _coord(shape, nlimbs, n, cols)
    prefix = (key(n_keys + 1), key(n_keys + 1), shape((n_keys + 1,), BOOL))
    return prefix, key(n_keys), key(n_keys)


def _range_args(shape, miss_k: int):
    return (
        shape((LANES,), I32),
        shape((LANES,), I32),
        shape((miss_k * LANES,), I32),
        shape((miss_k * LANES,), BOOL),
    )


# 8 = the narrow class every near-full range takes; 1024 = the 4096-key
# registry's wide class (n // 4: a level range with its failing members
# absent), a 131072-column key gather and a 1024-block tree sum
@pytest.mark.parametrize("miss_k", [8, N_KEYS // 4])
def test_range_aggregate(shape, chip_choices, miss_k):
    """The aggregation stage of the range launch at full width: prefix-table
    gathers plus the miss_k-wide hole patch (point adds only, no pairing).
    The bank is a jit argument, so a 2-key engine lowers the 4096-key
    program."""
    from handel_tpu.models.bn254_jax import _named

    dev = _device(2)
    name = f"range_agg{miss_k}"
    fn = jax.jit(_named(partial(dev._range_aggregate, miss_k=miss_k), name))
    compiled = fn.lower(
        *_range_args(shape, miss_k), *_bank(shape, N_KEYS)
    ).compile()
    assert mosaic_calls(compiled) > 0
    # program and phase reach the compiled module: its name, and the scope
    # in the operations' metadata — the Mosaic calls' too
    text = compiled.as_text()
    assert text.startswith(f"HloModule jit_{name}")
    assert re.search(rf'op_name="jit\({name}\)/agg/[^"]*fp_mul_16x', text)
    # the first stage of the patch's tree sum is the widest multiplication
    # of the stage: half the patch x lanes x the stacked muls of a G2 add
    if miss_k > 8:
        widths = {int(w) for w in re.findall(r"%fp_mul_16x(\d+)", text)}
        assert max(widths) % (miss_k // 2 * LANES) == 0, sorted(widths)
    _report(name, compiled)


def _report(name, compiled):
    ma = compiled.memory_analysis()
    print(
        f"{name}: mosaic_calls={mosaic_calls(compiled)} "
        f"temp_bytes={ma.temp_size_in_bytes} "
        f"code_bytes={ma.generated_code_size_in_bytes}"
    )


def _assert_phases(compiled):
    """The four phases of a launch are scopes in the compiled program; the
    Miller loop and the final exponentiation's bit-scan chains are loops
    over the runs of their public bits — a loop nested in a loop — and hold
    no conditional (a taken one costs as much again as what it guards)."""
    text = compiled.as_text()
    for scope in ("agg", "to_affine", "miller_loop", "final_exp"):
        assert re.search(rf'op_name="jit\([^"]*/{scope}/', text), scope
    for scope in ("miller_loop", "final_exp"):
        inside = rf'op_name="jit\([^"]*/{scope}/[^"]*'
        assert re.search(inside + r'while/body/[^"]*while/body/', text), scope
        assert not re.search(r" conditional\([^\n]*" + inside, text), scope
    # the Miller accumulator's updates over the launch's 2 x LANES pairs: a
    # squaring (36x) and a sparse line product (39x), never the general
    # Fp12 product (54x) at that width
    widths = {int(w) for w in re.findall(r"%fp_mul_\d+x(\d+)", text)}
    assert {36 * 2 * LANES, 39 * 2 * LANES} <= widths, sorted(widths)
    assert 54 * 2 * LANES not in widths


# The full pairing launches are minutes each (2-4 min on this sandbox): run
# by hand before a chip call,
#   pytest tests/test_chip_compile.py -m slow -s
# 8 = cell 1's class, 1024 = the wide class the failing-committee cell runs
@pytest.mark.slow
@pytest.mark.parametrize("miss_k", [8, N_KEYS // 4])
def test_full_range_launch(shape, chip_choices, miss_k):
    dev = _device(2)
    sig = shape((16, LANES), U32)
    h = shape((16, 1), U32)
    fn = jax.jit(
        partial(dev._verify_batch_range, miss_k=miss_k),
        donate_argnums=(0, 1, 2, 3, 4, 5, 8),
    )
    compiled = fn.lower(
        *_range_args(shape, miss_k), sig, sig, h, h, shape((LANES,), BOOL),
        *_bank(shape, N_KEYS),
    ).compile()
    _report(f"range{miss_k} launch", compiled)
    assert mosaic_calls(compiled) > 100
    _assert_phases(compiled)


# The 24-limb launch the cell `bls12-381-4096.closed256` runs: the prefix
# table's scan and the range launch over (24, N) banks. ~3 min here.
@pytest.mark.slow
def test_full_range_launch_bls12_381(shape, chip_choices):
    dev = _device(2, "bls12_381")
    nl = dev.curves.F.nlimbs
    assert nl == 24
    _, reg_x, reg_y = _bank(shape, N_KEYS, nl)
    t0 = time.perf_counter()
    table = dev._prefix_table_kernel().lower(reg_x, reg_y).compile()
    _report(f"bls12-381 prefix table ({time.perf_counter() - t0:.0f} s)", table)
    assert "%fp_mul_24x" in table.as_text()
    sig = shape((nl, LANES), U32)
    h = shape((nl, 1), U32)
    fn = jax.jit(
        partial(dev._verify_batch_range, miss_k=8),
        donate_argnums=(0, 1, 2, 3, 4, 5, 8),
    )
    t0 = time.perf_counter()
    compiled = fn.lower(
        *_range_args(shape, 8), sig, sig, h, h, shape((LANES,), BOOL),
        *_bank(shape, N_KEYS, nl),
    ).compile()
    _report(f"bls12-381 range launch ({time.perf_counter() - t0:.0f} s)", compiled)
    assert mosaic_calls(compiled) > 100
    _assert_phases(compiled)
    text = compiled.as_text()
    # every Mosaic call of the launch is the 24-limb multiplication
    assert not re.search(r"%fp_mul_(?!24x)", text)
    assert "%fp_mul_24x9984" in text


@pytest.mark.slow
def test_full_dense_launch(shape, chip_choices):
    dev = _device(N_KEYS)  # the dense launch reads self.n
    _, reg_x, reg_y = _bank(shape, N_KEYS)
    sig = shape((16, LANES), U32)
    h = shape((16, 1), U32)
    fn = jax.jit(dev._verify_batch, donate_argnums=(2, 3, 4, 7))
    compiled = fn.lower(
        reg_x, reg_y, shape((LANES, 2 * (N_KEYS // 64)), U32), sig, sig, h, h,
        shape((LANES,), BOOL),
    ).compile()
    _report("dense launch", compiled)
    assert mosaic_calls(compiled) > 100
    _assert_phases(compiled)


# -- the other group binding: keys in G1, signatures in G2 ---------------------
# (models/bls12_381_jax.py `BLS12381MinPkDevice`, the cell
# `bls12-381-minpk-4096-failing.closed256`: every launch `jit_verify_range1024`)


@pytest.mark.parametrize("miss_k", [8, N_KEYS // 4])
def test_range_aggregate_keys_in_g1(shape, chip_choices, miss_k):
    """The G1 aggregation stage at full width: prefix gathers over (24, N)
    single-column banks and the miss_k-wide patch's tree sum, none of which
    had been lowered for the chip before this class."""
    from handel_tpu.models.bn254_jax import _named

    dev = _device(2, "bls12_381_minpk")
    assert dev.key_group == 1 and dev.curves.F.nlimbs == 24
    name = f"range_agg{miss_k}"
    fn = jax.jit(_named(partial(dev._range_aggregate, miss_k=miss_k), name))
    compiled = fn.lower(
        *_range_args(shape, miss_k), *_bank(shape, N_KEYS, 24, cols=1)
    ).compile()
    text = compiled.as_text()
    assert text.startswith(f"HloModule jit_{name}")
    assert re.search(rf'op_name="jit\({name}\)/agg/[^"]*fp_mul_24x', text)
    assert not re.search(r"%fp_mul_(?!24x)", text)
    # a G1 addition stacks its 12 multiplications as 3, 3 and 6: the widest
    # call is the tree's first stage, half the patch x lanes x 6
    widths = {int(w) for w in re.findall(r"%fp_mul_24x(\d+)", text)}
    assert max(widths) == miss_k // 2 * LANES * 6, sorted(widths)
    _report(f"G1 {name}", compiled)


# What the refactor to a group binding must not move: the launch programs of
# the two G2-keyed classes, lowered for the described chip at the cells' own
# shapes, are the programs the parent commit lowers. Pinned as the SHA-256 of
# the StableHLO text with each Mosaic call's serialized body cut out (the
# body's bytes hold source line numbers of its callers). A change that means
# to alter a launch program — or a new jax — refreshes the pins from the
# assertion's message; ISSUE 35's refactor compared them with the parent's
# own lowering in one run (CHANGES.md).
LOWERED = {
    ("bn254", "prefix_table"):
        "397eece2002baaa1e2232f09dfe1b11a698ed92d9fb4ed30190fefecf4ade75d",
    ("bn254", "verify_range8"):
        "70980f1ffbde228f7e9240597ef0c95639aeb2087c18d77e0f96aa12c557fa5c",
    ("bn254", "verify_range1024"):
        "d6ffaa20bbcc5f5d06d947d326b255adb1ab2e51af4190fb7614a9200f1bafc0",
    ("bls12_381", "prefix_table"):
        "c2bbdeafcb1ca615b5cd32b4cb7917420928e4cec2bb097a7beeaf846d975fc0",
    ("bls12_381", "verify_range8"):
        "33a73d96291f94cef14836681d09b45ebab6dbe71d4603f20ddb19a62583e821",
    ("bls12_381", "verify_range1024"):
        "84c7a1417e5ecdde8e91d4ba9ea84b80e962234924645419499f83669b4e8996",
}


def _lower_launch(shape, dev, program: str):
    """The Lowered of one launch program at 4096 keys and 128 lanes."""
    from handel_tpu.models.bn254_jax import _named

    nl = dev.curves.F.nlimbs
    cols_key, cols_sig = dev.kg.ops.COLS, dev.sg.ops.COLS
    prefix, reg_x, reg_y = _bank(shape, N_KEYS, nl, cols_key)
    if program == "prefix_table":
        return dev._prefix_table_kernel().lower(reg_x, reg_y)
    miss_k = int(program[len("verify_range"):])
    fn = jax.jit(
        _named(partial(dev._verify_batch_range, miss_k=miss_k), program),
        donate_argnums=(0, 1, 2, 3, 4, 5, 8),
    )
    sig = _coord(shape, nl, LANES, cols_sig)
    h = _coord(shape, nl, 1, cols_sig)
    return fn.lower(
        *_range_args(shape, miss_k), sig, sig, h, h, shape((LANES,), BOOL),
        prefix, reg_x, reg_y,
    )


@pytest.mark.parametrize("curve, program", list(LOWERED))
def test_launch_programs_lower_as_pinned(shape, chip_choices, curve, program):
    text = _lower_launch(shape, _device(2, curve), program).as_text()
    assert f"jit_{program}" in text and "tpu_custom_call" in text
    cut = re.sub(r'\\22body\\22: \\22[^\\]*\\22', "BODY", text)
    assert cut.count("BODY") == text.count("tpu_custom_call") > 0
    got = hashlib.sha256(cut.encode()).hexdigest()
    assert got == LOWERED[curve, program], (curve, program, got)


# The G1-keyed launch the new cell runs, and its prefix table, through the
# chip's compiler (minutes: by hand before a chip call, like its siblings).
@pytest.mark.slow
def test_full_range_launch_bls12_381_minpk(shape, chip_choices):
    dev = _device(2, "bls12_381_minpk")
    t0 = time.perf_counter()
    table = _lower_launch(shape, dev, "prefix_table").compile()
    _report(f"G1 prefix table ({time.perf_counter() - t0:.0f} s)", table)
    assert "%fp_mul_24x" in table.as_text()
    t0 = time.perf_counter()
    compiled = _lower_launch(
        shape, dev, f"verify_range{N_KEYS // 4}").compile()
    _report(f"G1 range1024 launch ({time.perf_counter() - t0:.0f} s)", compiled)
    assert mosaic_calls(compiled) > 100
    _assert_phases(compiled)
    text = compiled.as_text()
    assert text.startswith("HloModule jit_verify_range1024")
    assert not re.search(r"%fp_mul_(?!24x)", text)
    # the tail is the G2-keyed class's: the same accumulator products
    assert "%fp_mul_24x9984" in text and "%fp_mul_24x9216" in text
