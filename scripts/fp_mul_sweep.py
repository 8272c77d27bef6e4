#!/usr/bin/env python3
"""`Field.mul`'s kernel alone, swept over how many lanes one step computes.

    python scripts/fp_mul_sweep.py [--limbs 16 24] [--out chiprun_out/fp_mul_sweep.json]

The table behind `ops/fp.py` `mul_step`: for each field (16 limbs BN254, 24
BLS12-381) and each stacked width the launch programs contain, the Montgomery
multiplication's body (`Field._mul_cols`) is built into a kernel of its own for
every (block, step) that divides the width —

  * form A, block == step: one grid step computes `step` lanes;
  * form B, block > step: the pipeline moves a block of up to 2 048 lanes
    and a `fori_loop` computes it in slices of `step` lanes;

and the row marked `shipped` is the form `Field.mul` itself takes, at the step
`mul_step` gives. Every kernel runs 16 times under the profiler (two calls of a
jitted chain of eight); a row is the median device time of its kernel's
events, in ns a lane, beside the host's clock over 20 more calls. Every form
must give `Field.mul`'s limbs exactly. The dense class's widest call
(`fp_mul_16x4718592`, no benchmark cell) is timed at the shipped step and at
a 2 048-lane block only.

Without a TPU it measures nothing and exits 1. `--tiny` is the CPU rehearsal
of the script itself: the first field of `--limbs` at one small width,
interpreted, equal limbs only — it reads no clock and writes no file.
"""

from __future__ import annotations

import argparse
import json
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
STEPS = (128, 256, 512, 1024, 2048)
# widths of the launch programs: the cyclotomic squarings (2 304), a patch
# stage of three blocks of 2 048 (6 144), the Miller squaring (9 216) and line
# product (9 984), and the patch's first stage in G1 at 24 limbs / G2 at 16
DENSE = 4718592  # the dense sum's first stage: 4096 keys x 128 lanes x 9
WIDTHS = {16: (2304, 6144, 9216, 9984, 589824, DENSE), 24: (2304, 6144, 9216, 9984, 196608)}
PRIMES = {16: bn.P, 24: bls.P}


def kernel_call(F, width: int, block: int, step: int, name: str, interpret: bool):
    n = F.nlimbs

    def kernel(a_ref, b_ref, o_ref):
        if block == step:
            o_ref[:] = F._mul_cols(a_ref[:], b_ref[:])
            return

        def one(k, carry):
            at = pl.ds(pl.multiple_of(k * step, step), step)
            o_ref[:, at] = F._mul_cols(a_ref[:, at], b_ref[:, at])
            return carry

        lax.fori_loop(0, block // step, one, 0)

    spec = pl.BlockSpec((n, block), lambda i: (0, i), memory_space=pltpu.VMEM)
    return pl.pallas_call(
        kernel,
        name=name,
        out_shape=jax.ShapeDtypeStruct((n, width), jnp.uint32),
        grid=(width // block,),
        in_specs=[spec, spec],
        out_specs=spec,
        interpret=interpret,
    )


def forms(width: int):
    """(block, step) of every form the width admits."""
    blocks = [s for s in STEPS if width % s == 0]
    if width == DENSE:
        yield from ((s, s) for s in (512, 2048))
        return
    for s in blocks:
        yield s, s
    if blocks[-1] >= 1024:
        for s in blocks[:-1]:
            yield blocks[-1], s


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
    rows, chains = [], []
    for n in args.limbs[:1] if args.tiny else args.limbs:
        F = fp.Field(PRIMES[n])
        assert F.nlimbs == n
        for width in ((1024,) if args.tiny else WIDTHS[n]):
            a, b = (
                jnp.asarray(rng.integers(0, 1 << 16, (n, width), dtype=np.uint32))
                for _ in range(2)
            )
            want = np.asarray(F.mul(a, b))
            for block, step in forms(width):
                name = f"sweep_{n}x{width}_b{block}_s{step}"
                mul = kernel_call(F, width, block, step, name, not on_chip)

                same = bool(np.array_equal(np.asarray(mul(a, b)), want))
                row = {
                    "limbs": n, "width": width, "block": block, "step": step,
                    "form": "A" if block == step else "B", "kernel": name,
                    "shipped": block == step == fp.mul_step(n, width),
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
    result = {"device": device, "chain": CHAIN, "rows": rows}
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)
    for row in rows:
        print(json.dumps(row))
    ok = all(r["same_limbs"] and "ns_per_lane" in r for r in rows)
    print(json.dumps({"device": device, "rows": len(rows), "timed": True, "ok": ok}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
