#!/usr/bin/env python3
"""`Field.mul`'s kernel alone, swept over the form of a limb row, the lanes
a pass of the body computes and the lanes a grid step moves.

    python scripts/fp_mul_sweep.py [--limbs 16 24] [--out chiprun_out/fp_mul_sweep.json]

The table behind `ops/fp.py` `mul_tile`: for each field (16 limbs BN254, 24
BLS12-381) and each stacked width the launch programs contain, the Montgomery
multiplication's body (`Field._mul_cols`) is built into a kernel of its own
(`kernel_call`) in these forms —

  * `flat`, the form `Field.mul` had up to PR 38: a limb row is `(step,)`,
    one sublane of each register it takes, at that rule's step (512 lanes at
    16 limbs, 256 at 24, or the widest power of two under it that divides);
  * `tiled`: a limb row is a `(step // 128, 128)` tile, reshaped inside the
    kernel. At 8 sublanes (a whole register a row) with blocks of 1, 2, 4, 8
    and 16 passes and the whole width as one block, the last block partial
    where the width is no multiple; and at the sublane counts that divide
    a width 8 does not (6, 13, 18), a pass a grid step;

and the row marked `shipped` is the form `Field.mul` itself takes
(`mul_tile`). Every kernel runs 16 times under the profiler (two calls of a
jitted chain of eight); a row is the median device time of its kernel's
events, in ns a lane, beside the host's clock over 20 more calls. Every form
and `Field.mul` itself must give the limbs of the plain XLA form
(`Field._mul_cols_vec`) exactly. The dense class's widest call
(`fp_mul_16x4718592`, no benchmark cell) is timed in three forms only.

Without a TPU it measures nothing and exits 1. `--tiny` is the CPU rehearsal
of the script itself: the first field of `--limbs` at one small width,
interpreted, equal limbs only — it reads no clock and writes no file.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import statistics
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import lax  # noqa: E402
from jax.experimental import pallas as pl  # noqa: E402
from jax.experimental.pallas import tpu as pltpu  # noqa: E402

from benchmark import trace_reduce  # noqa: E402
from handel_tpu.ops import bls12_381_ref as bls  # noqa: E402
from handel_tpu.ops import bn254_ref as bn  # noqa: E402
from handel_tpu.ops import fp  # noqa: E402

CHAIN = 8
# the flat form's step up to PR 38 (`mul_step_cap`), cut to a power of two
# that divides the width
FLAT_STEP = {16: 512, 24: 256}
# widths of the launch programs: the tail's stacked products from 1 536 lanes
# (12 registers a row) to the Miller squaring (9 216) and line product
# (9 984 = 78 x 128), and stages of the wide patch in G2 at 16 limbs / G1 at 24
DENSE = 4718592  # the dense sum's first stage: 4096 keys x 128 lanes x 9
WIDTHS = {
    16: (256, 1536, 2304, 3072, 4608, 6912, 9216, 9984, 147456, 589824, DENSE),
    24: (256, 1536, 2304, 3840, 6912, 9216, 9984, 49152, 196608),
}
PRIMES = {16: bn.P, 24: bls.P}


def kernel_call(F, width: int, block: int, step: int, name: str, interpret: bool,
                tiled: bool = False):
    """`Field._mul_cols` as a kernel of its own: the pipeline moves `block`
    lanes a grid step, the body computes them `step` lanes at a time. Flat
    (the form up to PR 38): a limb row is `(step,)`, one sublane of each
    register it takes. `tiled`: a limb row is a `(step // 128, 128)` tile,
    reshaped inside the kernel. A width the block does not divide ends in a
    partial block; the loop then stops at the last step that holds lanes."""
    n = F.nlimbs
    tile = (n, step // fp._LANE, fp._LANE)

    def body(a, b):
        if not tiled:
            return F._mul_cols(a, b)
        return F._mul_cols(a.reshape(tile), b.reshape(tile)).reshape(n, step)

    def kernel(a_ref, b_ref, o_ref):
        if block == step:
            o_ref[:] = body(a_ref[:], b_ref[:])
            return

        def one(k, carry):
            at = pl.ds(pl.multiple_of(k * step, step), step)
            o_ref[:, at] = body(a_ref[:, at], b_ref[:, at])
            return carry

        steps = block // step
        if width % block:
            left = pl.cdiv(width, step) - pl.program_id(0) * steps
            steps = jnp.minimum(steps, left)
        lax.fori_loop(0, steps, one, 0)

    spec = pl.BlockSpec((n, block), lambda i: (0, i), memory_space=pltpu.VMEM)
    return pl.pallas_call(
        kernel,
        name=name,
        out_shape=jax.ShapeDtypeStruct((n, width), jnp.uint32),
        grid=(pl.cdiv(width, block),),
        in_specs=[spec, spec],
        out_specs=spec,
        interpret=interpret,
    )


def forms(nlimbs: int, width: int):
    """(block, step, tiled) of every form timed at this width."""
    flat = math.gcd(width, FLAT_STEP[nlimbs])
    yield flat, flat, False
    sublanes, shipped = fp.mul_tile(width)
    step = sublanes * fp._LANE
    passes = -(-width // step)
    if width == DENSE:
        yield from ((k * step, step, True) for k in (4, 16))
        return
    # the whole width as one block where it is at most 16 passes (VMEM)
    blocks = {min(k, passes) * step for k in (1, 2, 4, 8, 16)}
    for block in sorted(blocks | {shipped}):
        yield block, step, True
    for rows in (6, 13, 18):
        if width % step and width % (rows * fp._LANE) == 0:
            yield rows * fp._LANE, rows * fp._LANE, True


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--limbs", type=int, nargs="+", default=[16, 24])
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out", "fp_mul_sweep.json"))
    ap.add_argument("--tiny", action="store_true", help="CPU rehearsal: one field, one small width")
    args = ap.parse_args()
    dev = jax.devices()[0]
    on_chip = dev.platform == "tpu"
    device = {"platform": dev.platform, "kind": dev.device_kind}
    if not on_chip and not args.tiny:
        print(json.dumps({"device": device, "ok": False,
                          "error": "no TPU: this sweep measures nothing on another backend"}))
        return 1
    rng = np.random.default_rng(36)
    # wrong: the calls at which `Field.mul` itself differs from the plain form
    rows, chains, wrong = [], [], []
    for n in args.limbs[:1] if args.tiny else args.limbs:
        F = fp.Field(PRIMES[n])
        assert F.nlimbs == n
        for width in ((2304,) if args.tiny else WIDTHS[n]):
            a, b = (
                jnp.asarray(rng.integers(0, 1 << 16, (n, width), dtype=np.uint32))
                for _ in range(2)
            )
            # the plain XLA form holds (limbs, limbs, lanes) words: by slices
            vec, cut = jax.jit(F._mul_cols_vec), min(width, 49152)
            want = np.concatenate(
                [np.asarray(vec(a[:, i : i + cut], b[:, i : i + cut]))
                 for i in range(0, width, cut)], axis=1)
            sublanes, shipped_block = fp.mul_tile(width)
            shipped = (shipped_block, sublanes * fp._LANE, True)
            if on_chip and not np.array_equal(np.asarray(F.mul(a, b)), want):
                wrong.append(f"fp_mul_{n}x{width}")
            for block, step, tiled in forms(n, width):
                name = f"sweep_{n}x{width}_{'t' if tiled else 'f'}_b{block}_s{step}"
                mul = kernel_call(F, width, block, step, name, not on_chip, tiled)

                same = bool(np.array_equal(np.asarray(mul(a, b)), want))
                row = {
                    "limbs": n, "width": width, "rows": "tiled" if tiled else "flat",
                    "sublanes": step // fp._LANE if tiled else 1,
                    "block": block, "step": step, "kernel": name,
                    "shipped": (block, step, tiled) == shipped,
                    "same_limbs": same,
                }
                rows.append(row)
                if on_chip:

                    def chain(x, y, mul=mul):
                        for _ in range(CHAIN):
                            x = mul(x, y)
                        return x

                    t0 = time.perf_counter()
                    fn = jax.jit(chain).lower(a, b).compile()
                    row["compile_s"] = round(time.perf_counter() - t0, 2)
                    fn(a, b).block_until_ready()
                    chains.append((fn, a, b))
                print(json.dumps(row), file=sys.stderr, flush=True)

    if not on_chip:  # the rehearsal: the forms agree, and nothing is timed
        ok = all(r["same_limbs"] for r in rows)
        print(json.dumps({"device": device, "rows": len(rows), "timed": False, "ok": ok}))
        return 0 if ok else 1

    # the host's clock over 20 calls of the chain (a cross-check: for narrow
    # widths it reads the dispatch, not the device)
    for row, (fn, a, b) in zip(rows, chains):
        t0 = time.perf_counter()
        for _ in range(20):
            out = fn(a, b)
        out.block_until_ready()
        row["host_ns_per_lane"] = (time.perf_counter() - t0) / (20 * CHAIN) / row["width"] * 1e9

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with tempfile.TemporaryDirectory(dir=os.path.dirname(args.out) or ".") as tdir:
        jax.profiler.start_trace(tdir)
        try:
            for row, (fn, a, b) in zip(rows, chains):
                for _ in range(2):
                    fn(a, b).block_until_ready()
        finally:
            jax.profiler.stop_trace()
        _, ops, _ = trace_reduce.load_trace(trace_reduce.find_xplane(tdir)).planes[0]
    took: dict[str, list] = {}
    for s, e, name in ops:
        kernel = name.split(" = ")[0].lstrip("%").rsplit(".", 1)[0]
        took.setdefault(kernel, []).append(e - s)
    for row in rows:
        ns = took.get(row["kernel"], [])
        if len(ns) == 2 * CHAIN:
            row["us_per_call"] = statistics.median(ns) / 1e3
            row["ns_per_lane"] = statistics.median(ns) / row["width"]
        else:  # say what the trace called its operations instead
            row["events"] = len(ns)
            row["seen"] = sorted(took)[:40]
    result = {"device": device, "chain": CHAIN, "field_mul_differs": wrong, "rows": rows}
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)
    for row in rows:
        print(json.dumps(row))
    ok = not wrong and all(r["same_limbs"] and "ns_per_lane" in r for r in rows)
    print(json.dumps({"device": device, "rows": len(rows), "timed": True,
                      "field_mul_differs": wrong, "ok": ok}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
