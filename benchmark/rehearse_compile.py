#!/usr/bin/env python3
"""Rehearsal, NOT a chip run: compile the launch classes each cell's traffic
names (`launch_classes`) for a described TPU v5e (`v5e:2x2`, chip 0) at full
width, with the chip's own compiler, in the sandbox. What the compiler refuses
here (a tile, a kernel's fast memory, a program too large for the device)
costs no chip time. Nothing runs, so this says nothing about results or times.

    JAX_PLATFORMS=cpu python benchmark/rehearse_compile.py [--workload <cell>]
        [--benchmark <another BENCHMARK.json>]

Minutes per class on a sandbox core. The launch classes are the jitted
bodies of the engine class the cell's scheme prepares
(`_verify_batch_range` / `_verify_batch` of `BN254Device` or a binding of
it), lowered with the shapes a launch of the cell has: registry bank, prefix
table and `lanes` candidates in the scheme's field and key group. Under
JAX_PLATFORMS=cpu the program would take its CPU branches, so
`ops.fp.device_platform` is steered to "tpu" here, in the rehearsal and not
through an option of the program.
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


def compile_class(cfg: dict, launch_class: str) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from handel_tpu.models.bn254_jax import _named
    from handel_tpu.models.registry import new_scheme
    from handel_tpu.ops import fp
    from run import resolve

    jax.config.update("jax_enable_compilation_cache", False)
    fp.device_platform = lambda: "tpu"  # what the code picks on the chip
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    U32, I32, BOOL = jnp.uint32, jnp.int32, jnp.bool_
    shape = lambda dims, dt: jax.ShapeDtypeStruct(dims, dt, sharding=chip)
    n_keys, lanes = int(cfg["registry_keys"]), int(cfg["lanes"])
    cons = new_scheme(cfg["scheme"], batch_size=lanes, warmup=False,
                      **cfg.get("device_options", {})).constructor
    Device = cons.Device
    gen = Device.ref.G2_GEN if Device.key_group == 2 else Device.ref.G1_GEN
    # the range classes take the bank as an argument; the dense class reads
    # the registry's size off the engine
    dense = launch_class == "dense"
    dev = cons.prepare(
        [resolve(cfg["program"]["public_key"])(gen)] * (n_keys if dense else 2))
    nl = dev.curves.F.nlimbs

    def coord(cols: int, n: int):
        """One packed coordinate over n lanes: a limb array in G1 (cols 1),
        an Fp2 pair of them in G2 (cols 2)."""
        col = shape((nl, n), U32)
        return col if cols == 1 else (col,) * cols

    key = lambda n: coord(dev.kg.ops.COLS, n)
    sig, h = coord(dev.sg.ops.COLS, lanes), coord(dev.sg.ops.COLS, 1)
    valid = shape((lanes,), BOOL)
    if dense:
        fn = jax.jit(_named(dev._verify_batch, "verify_dense"),
                     donate_argnums=(2, 3, 4, 7))
        args = (
            key(n_keys), key(n_keys), shape((lanes, 2 * (n_keys // 64)), U32),
            sig, sig, h, h, valid,
        )
    elif launch_class.startswith("range"):
        miss_k = int(launch_class[len("range"):])
        fn = jax.jit(
            _named(partial(dev._verify_batch_range, miss_k=miss_k),
                   f"verify_range{miss_k}"),
            donate_argnums=(0, 1, 2, 3, 4, 5, 8))
        prefix = (key(n_keys + 1), key(n_keys + 1), shape((n_keys + 1,), BOOL))
        args = (
            shape((lanes,), I32), shape((lanes,), I32),
            shape((miss_k * lanes,), I32), shape((miss_k * lanes,), BOOL),
            sig, sig, h, h, valid, prefix, key(n_keys), key(n_keys),
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
        "launch_class": launch_class, "engine": Device.__name__,
        "limbs": nl, "key_group": dev.key_group, "keys": n_keys, "lanes": lanes,
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
    ap.add_argument("--benchmark", default="",
                    help="another BENCHMARK.json (a cell not in the committed one)")
    args = ap.parse_args()
    path = args.benchmark or os.path.join(spec.ROOT, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    names = args.workload or [w["name"] for w in bench["workloads"]]
    print("REHEARSAL for a described v5e:2x2 — not a chip run, no time or "
          "result below is a measurement of the system", flush=True)
    for name in names:
        cell = spec.Cell(name, args.benchmark)
        for launch_class in cell.traffic["launch_classes"]:
            out = compile_class(cell.config, launch_class)
            print(json.dumps({"cell": name, "accepted_by_compiler": True, **out}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
