"""The multiplication kernel's step is a property of the field (`ops/fp.py`
`mul_step`), not of the call's width: plain Python, no chip, no compile.

The widths are those the four launch programs and the dense class hand to
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
# the field's step: `fp.mul_step_cap`
FIELD_STEP = {16: 512, 24: 256}


@pytest.mark.parametrize("width", WIDTHS)
@pytest.mark.parametrize("nlimbs", sorted(FIELD_STEP))
def test_step_follows_the_field_not_the_width(nlimbs, width):
    cap = fp.mul_step_cap(nlimbs)
    assert cap == FIELD_STEP[nlimbs]
    step = fp.mul_step(nlimbs, width)
    assert width % step == 0 and step % 128 == 0 and 128 <= step <= cap
    # the width only says how many steps there are: every width the field's
    # step divides runs at it, whatever larger power of two divides it too
    # (the widest patch call and the Miller squaring share one step), and a
    # width it does not divide takes the widest power of two that does
    if width % cap == 0:
        assert step == cap
    else:
        assert width % (2 * step) != 0


def test_trace_op_counts_says_whose_rule_the_step_is():
    """A trace does not record a kernel's grid: the script names the field
    for what it is, the step by the rule of the checkout it runs from."""
    from scripts.trace_op_counts import per_lane

    assert per_lane("fp_mul_24x9984", 80220.0) == {
        "ns_per_lane": 80220.0 / 9984, "step_by_this_checkout": 256}
    assert set(per_lane("rns_mul_40x6912", 6912.0)) == {"ns_per_lane"}


def test_odd_widths_are_padded_to_the_lane_granularity_first():
    """`_mul_pallas` pads a width that 128 does not divide before it asks:
    the rule itself never returns less than one lane tile."""
    assert fp.Field.pad_batch(130) == 256 and fp.Field.pad_batch(1) == 128
    assert fp.mul_step(16, fp.Field.pad_batch(130)) == 256
