"""How the multiplication kernel walks a call's lanes is read off the width
(`ops/fp.py` `mul_tile`): plain Python, no chip, no compile.

The widths are those the launch programs and the dense class hand to
`Field.mul` at 4096 keys and 128 lanes (kernel names `fp_mul_<limbs>x<lanes>`
in a trace): per-lane calls, the pairing tail's stacked products, the wide
hole patch's tree stages (9 x 2^k lanes in G2, 3 x 2^k and 6 x 2^k in G1) and
the dense sum's first stage.
"""

import pytest

from handel_tpu.ops import fp

TAIL = [128, 1536, 2304, 3072, 3840, 4608, 6144, 6912, 9216, 9984, 13824]
PATCH_G2 = [147456, 294912, 589824, 1179648]
PATCH_G1 = [12288, 24576, 49152, 98304, 196608, 393216]
DENSE = [4718592]
WIDTHS = TAIL + PATCH_G1 + PATCH_G2 + DENSE
# a row fills its register: 8 sublanes of 128 lanes a pass of the body ...
STEP = 1024
# ... and this many passes a grid step's block at most
BLOCK = fp._MUL_BLOCK_STEPS * STEP


@pytest.mark.parametrize("width", WIDTHS)
@pytest.mark.parametrize("nlimbs", [16, 24])
def test_rows_fill_the_register_at_every_width(nlimbs, width):
    """The rule reads the width alone, so both fields walk a width alike."""
    sublanes, block = fp.mul_tile(width)
    if width == 128:  # one sublane of work: the row stays (128,)
        assert (sublanes, block) == (1, 128)
        return
    assert sublanes == 8 and block % STEP == 0 and STEP <= block <= BLOCK
    # a call no wider than a full block is ONE block, rounded up to passes
    # (2 304 = 18 x 128 takes three passes, the last a quarter full); a
    # wider one a grid of full blocks, the last partial where they do not
    # divide it (9 984 = 2 x 4 096 + 1 792)
    if width <= BLOCK:
        assert block - STEP < width <= block
    else:
        assert block == BLOCK


@pytest.mark.parametrize("width,tile", [
    (256, (2, 256)), (384, (3, 384)), (896, (7, 896)), (1024, (8, 1024)),
    (1152, (8, 2048)),
])
def test_narrow_calls_take_the_sublanes_they_have(width, tile):
    """Under eight registers' worth of lanes a row is as tall as the call
    (interior slices of `associative_scan`, small batches)."""
    assert fp.mul_tile(width) == tile


def test_trace_op_counts_says_whose_rule_the_tile_is():
    """A trace does not record a kernel's grid: the script names the fields
    for what they are, the tile by the rule of the checkout it runs from."""
    from scripts.trace_op_counts import per_lane

    assert per_lane("fp_mul_24x9984", 80220.0) == {
        "ns_per_lane": 80220.0 / 9984,
        "sublanes_by_this_checkout": 8, "block_by_this_checkout": BLOCK}
    assert per_lane("fp_mul_24x128", 1070.0)["sublanes_by_this_checkout"] == 1
    assert set(per_lane("rns_mul_40x6912", 6912.0)) == {"ns_per_lane"}


def test_odd_widths_are_padded_to_the_lane_granularity_first():
    """`_mul_pallas` pads a width that 128 does not divide before it asks:
    the rule itself never sees less than one lane tile."""
    assert fp.Field.pad_batch(130) == 256 and fp.Field.pad_batch(1) == 128
    assert fp.mul_tile(fp.Field.pad_batch(130)) == (2, 256)
