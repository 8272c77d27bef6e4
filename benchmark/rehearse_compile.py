#!/usr/bin/env python3
"""Rehearsal, NOT a chip run: compile each cell's launch class for a
described TPU v5e (`v5e:2x2`, chip 0) at full width, with the chip's own
compiler, in the sandbox. What the compiler refuses here (a tile, a kernel's
fast memory, a program too large for the device) costs no chip time. Nothing
runs, so this says nothing about results or times.

    JAX_PLATFORMS=cpu python benchmark/rehearse_compile.py [--workload <cell>]

Minutes per class on a sandbox core. The launch classes are the program's
own jitted bodies (`BN254Device._verify_batch_range` / `_verify_batch`),
lowered with the shapes a launch of the cell has: registry bank, prefix
table and `lanes` candidates. Under JAX_PLATFORMS=cpu the program would take
its CPU branches, so `ops.fp.device_platform` is steered to "tpu" here, in
the rehearsal and not through an option of the program.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from functools import partial

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)
sys.path.insert(1, os.path.dirname(BENCH_DIR))

import spec  # noqa: E402


def compile_class(launch_class: str, n_keys: int, lanes: int) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from handel_tpu.models.bn254 import BN254PublicKey
    from handel_tpu.models.bn254_jax import BN254Device
    from handel_tpu.ops import bn254_ref as bn
    from handel_tpu.ops import fp

    jax.config.update("jax_enable_compilation_cache", False)
    fp.device_platform = lambda: "tpu"  # what the code picks on the chip
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    U32, I32, BOOL = jnp.uint32, jnp.int32, jnp.bool_
    shape = lambda dims, dt: jax.ShapeDtypeStruct(dims, dt, sharding=chip)
    f2 = lambda n: (shape((16, n), U32), shape((16, n), U32))
    sig, h = shape((16, lanes), U32), shape((16, 1), U32)
    valid = shape((lanes,), BOOL)
    if launch_class.startswith("range"):
        miss_k = int(launch_class[len("range"):])
        dev = BN254Device([BN254PublicKey(bn.G2_GEN)] * 2, batch_size=lanes)
        fn = jax.jit(partial(dev._verify_batch_range, miss_k=miss_k),
                     donate_argnums=(0, 1, 2, 3, 4, 5, 8))
        prefix = (f2(n_keys + 1), f2(n_keys + 1), shape((n_keys + 1,), BOOL))
        args = (
            shape((lanes,), I32), shape((lanes,), I32),
            shape((miss_k * lanes,), I32), shape((miss_k * lanes,), BOOL),
            sig, sig, h, h, valid, prefix, f2(n_keys), f2(n_keys),
        )
    elif launch_class == "dense":
        dev = BN254Device([BN254PublicKey(bn.G2_GEN)] * n_keys, batch_size=lanes)
        fn = jax.jit(dev._verify_batch, donate_argnums=(2, 3, 4, 7))
        args = (
            f2(n_keys), f2(n_keys), shape((lanes, 2 * (n_keys // 64)), U32),
            sig, sig, h, h, valid,
        )
    else:
        raise SystemExit(f"no rehearsal for launch class {launch_class!r}")
    assert dev.curves.F.use_pallas
    t0 = time.perf_counter()
    lowered = fn.lower(*args)
    t1 = time.perf_counter()
    compiled = lowered.compile()
    ma = compiled.memory_analysis()
    return {
        "launch_class": launch_class, "keys": n_keys, "lanes": lanes,
        "lower_s": t1 - t0, "compile_s": time.perf_counter() - t1,
        "mosaic_calls": compiled.as_text().count("tpu_custom_call"),
        "temp_bytes": ma.temp_size_in_bytes,
        "argument_bytes": ma.argument_size_in_bytes,
        "code_bytes": ma.generated_code_size_in_bytes,
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", action="append",
                    help="cell(s) to rehearse; default: every cell")
    args = ap.parse_args()
    bench = json.load(open(os.path.join(spec.ROOT, "BENCHMARK.json")))
    names = args.workload or [w["name"] for w in bench["workloads"]]
    print("REHEARSAL for a described v5e:2x2 — not a chip run, no time or "
          "result below is a measurement of the system", flush=True)
    for name in names:
        cell = spec.Cell(name)
        if cell.config["scheme"] != "bn254-jax":
            raise SystemExit(f"{name}: rehearsal knows the bn254-jax launches only")
        out = compile_class(cell.traffic["launch_class"],
                            int(cell.config["registry_keys"]),
                            int(cell.config["lanes"]))
        print(json.dumps({"cell": name, "accepted_by_compiler": True, **out}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
