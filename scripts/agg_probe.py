#!/usr/bin/env python3
"""The aggregation stage of a launch, alone, timed on whatever device JAX has.

    python scripts/agg_probe.py [--keys 4096] [--lanes 128] [--reps 10]

One engine over a seeded registry, one launch of candidates shaped like the
failing-committee traffic (an aligned level range of n/8, n/4 or n/2 ids
minus a seeded quarter of the committee and 0-8 more), and for it:

  * `range_agg<wide>`  — the stage as the launch runs it (`_range_agg_kernel`:
    prefix-table hull minus the wide hole patch), point additions only;
  * `gather`           — the patch's key gather alone;
  * `patch_sum`        — the patch's masked tree sum alone, keys pre-gathered;
  * `dense_agg`        — the stage the dense launch runs for the SAME
    candidates (`_dense_aggregate`), which must give the same points;
  * `range_agg8`       — the narrow class on hole-free ranges, for scale.

Each is its own executable (seconds to a minute of compile, no pairing
graph). Prints one JSON line per timing, host clock around
`block_until_ready`, median of `--reps`. A number from a CPU is no speed.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from handel_tpu.utils.jaxenv import enable_compile_cache  # noqa: E402

enable_compile_cache()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from handel_tpu import native as nat  # noqa: E402
from handel_tpu.core.bitset import BitSet  # noqa: E402
from handel_tpu.models.bn254 import BN254PublicKey, BN254Signature  # noqa: E402
from handel_tpu.models.bn254_jax import BN254Device, _named  # noqa: E402
from handel_tpu.ops import bn254_ref as bn  # noqa: E402


def say(**kv) -> None:
    print(json.dumps(kv), flush=True)


def failing_requests(rng, n: int, lanes: int, failing: set):
    """`lanes` level aggregates of a committee whose `failing` ids never
    sign: range minus the failing ids in it minus 0-8 more."""
    sig = BN254Signature(bn.G1_GEN)
    reqs = []
    for _ in range(lanes):
        size = n >> rng.randrange(1, 4)
        lo = rng.randrange(n // size) * size
        alive = [i for i in range(lo, lo + size) if i not in failing]
        gone = set(rng.sample(alive, rng.randrange(0, 9)))
        bs = BitSet(n)
        for i in alive:
            if i not in gone:
                bs.set(i, True)
        reqs.append((bs, sig))
    return reqs


def timed(name: str, fn, make_args, reps: int, **note):
    """Median wall of `fn(*make_args())` to `block_until_ready`; fresh
    arguments every call (the stage kernels donate their inputs)."""
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*make_args()))
    first = time.perf_counter() - t0
    walls = []
    for _ in range(reps):
        args = jax.block_until_ready(make_args())
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        walls.append(1e3 * (time.perf_counter() - t0))
    say(probe=name, ms_median=statistics.median(walls), ms_min=min(walls),
        ms_max=max(walls), reps=reps, first_call_s=first, **note)
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--keys", type=int, default=4096)
    ap.add_argument("--lanes", type=int, default=128)
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--seed", type=int, default=27)
    a = ap.parse_args()
    n, C = a.keys, a.lanes
    dev0 = jax.devices()[0]
    say(probe="device", platform=dev0.platform, kind=dev0.device_kind,
        keys=n, lanes=C)

    rng = random.Random(a.seed)
    sks = [rng.randrange(1, 1 << 20) for _ in range(n)]
    pks = [BN254PublicKey(p) for p in nat.g2_mul_batch([bn.G2_GEN] * n, sks)]
    dev = BN254Device(pks, batch_size=C)
    g2 = dev.curves.g2
    t0 = time.perf_counter()
    jax.block_until_ready(dev._prefix)
    say(probe="prefix_table", seconds=time.perf_counter() - t0)

    reqs = failing_requests(rng, n, C, set(rng.sample(range(n), n // 4)))
    plan = dev._pack_requests(reqs)
    if plan.kind != "range":
        say(probe="plan", kind=plan.kind, note="this registry has no wide class")
        return 1
    wide = plan.miss_k
    holes = np.asarray(plan.miss_ok).sum(axis=0)
    say(probe="plan", kind=plan.kind, miss_k=wide, holes_min=int(holes.min()),
        holes_mean=float(holes.mean()), holes_max=int(holes.max()))
    stage = lambda: dev._stage_plan(plan)[:4]

    agg = timed(f"range_agg{wide}", dev._range_agg_kernel(wide), stage, a.reps)

    # the two halves of the patch, each alone
    take = lambda arr, idx: jnp.take(arr, idx, axis=1)
    gather = jax.jit(_named(
        lambda idx, rx, ry: ((take(rx[0], idx), take(rx[1], idx)),
                             (take(ry[0], idx), take(ry[1], idx))),
        f"gather{wide}"))
    bank = (dev._reg_x, dev._reg_y)
    keys = timed("gather", gather, lambda: (stage()[2], *bank), a.reps,
                 columns=wide * C)
    patch_sum = jax.jit(_named(
        lambda ok, kx, ky: g2.masked_sum(g2.from_affine(kx, ky), ok, wide),
        f"patch_sum{wide}"))
    timed("patch_sum", patch_sum, lambda: (stage()[3], *keys), a.reps,
          blocks=wide, lanes=C)

    # the dense class's stage for the same candidates: same points
    dense_fn = jax.jit(_named(dev._dense_aggregate, "dense_agg"))
    words = lambda: (*bank, dev._dput(plan.words.view(np.uint32)),
                     dev._dput(plan.valid))
    dense = timed("dense_agg", dense_fn, words, a.reps)
    to_affine = jax.jit(g2.to_affine)
    same = all(
        bool(jnp.array_equal(p, q))
        for p, q in zip(jax.tree_util.tree_leaves(to_affine(agg)),
                        jax.tree_util.tree_leaves(to_affine(dense))))
    say(probe="same_points", range_equals_dense=same)

    # the narrow class on whole ranges, for scale
    full = []
    for bs, sig in reqs:
        b = BitSet(n)
        idx = list(bs.indices())
        b.set_range(idx[0], idx[-1] + 1)
        full.append((b, sig))
    plan8 = dev._pack_requests(full)
    timed(f"range_agg{plan8.miss_k}", dev._range_agg_kernel(plan8.miss_k),
          lambda: dev._stage_plan(plan8)[:4], a.reps)
    return 0 if same else 1


if __name__ == "__main__":
    sys.exit(main())
