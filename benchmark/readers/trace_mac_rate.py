"""Limb multiply-adds a second of the Montgomery multiplication kernels, in
1e9/s: the numerator of the kernels' roofline share, in one unit for the
16-limb (BN254) and the 24-limb (BLS12-381) field.

Every Mosaic multiplication in the reduced trace is taken by its grouped name
(`trace_reduce.op_group`: `custom-call:tpu_custom_call u32[<limbs>,<lanes>]`),
which gives the shape of its result: `limbs` 16-bit limbs of `lanes`
independent field elements. The sum of `macs(limbs, lanes)` over their
executions is divided by their self time. A Mosaic call whose result is not a
(limbs, lanes) uint32 array is no field multiplication (the residue kernels
of `fp_backend="rns"` return int32) and is left out of both sums. No such
operation in the trace reads as nothing, never as 0.

The edges of the reduced interval clip at most one execution a plane, which
is counted whole: one in some ten thousand."""

import re

from trace_reduce import op_group

_MUL = re.compile(r"^custom-call:tpu_custom_call u32\[(\d+),(\d+)\]$")


def macs(limbs: int, lanes: int) -> int:
    """16 x 16-bit multiply-adds of one execution. One Montgomery product of
    two `limbs`-limb numbers (`ops/fp.py` `_mul_cols`) is the schoolbook
    product — every limb of a times every limb of b, limbs^2 products, each
    added into its column — and the interleaved reduction: for each of the
    `limbs` rounds one m = t * n0 and then m times every limb of p, limbs^2
    more, each added into its column. The `limbs` products t * n0, the carry
    pass and the conditional subtraction are linear in `limbs` and left out:
    what the algorithm needs is 2 * limbs^2 a lane."""
    return 2 * limbs * limbs * lanes


def read(ctx):
    tr = ctx.trace
    if tr is None:
        return None
    done, ns = 0, 0.0
    for plane in tr.planes:
        for name, self_ns in plane.self_ns.items():
            m = _MUL.match(op_group(name))
            if m:
                done += macs(int(m.group(1)), int(m.group(2))) * plane.count[name]
                ns += self_ns
    if not ns:
        return None
    return done / ns  # multiply-adds per nanosecond = 1e9 per second
