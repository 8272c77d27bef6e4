"""BLS12-381 device-verify benchmark — the second curve family on chip.

Mirror of bench.py's headline measurement for the `bls12-381-jax` scheme
(same launch engine, 381-bit field / M-type twist / |z|-bit Miller loop):
the SAME `bench.build_problem` candidate generator, parameterized with the
BLS12-381 oracle and pure-Python host keygen (the native C++ path is
BN254-only), a device-resident registry, one fused multi-pairing launch,
p50 over trials. Persists chiprun_out/bench_bls12.json. Registry is smaller
than the BN254 headline's (pure-Python keygen cost; launch cost is
registry-size independent on the range path).

    python scripts/bench_bls12.py [trials]
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from handel_tpu.utils.jaxenv import apply_platform_env

apply_platform_env()

import jax
import numpy as np


def main() -> int:
    trials = int(sys.argv[1]) if len(sys.argv) > 1 else 9
    from bench import build_problem
    from handel_tpu.models.bls12_381 import BLS12381PublicKey
    from handel_tpu.models.bls12_381_jax import BLS12381Device
    from handel_tpu.ops import bls12_381_ref as bls
    from handel_tpu.ops.curve import BLS12Curves

    n_registry, lanes, n_cands = 1024, 64, 32
    curves = BLS12Curves()
    pks, miss_k, args = build_problem(
        curves,
        n_registry,
        lanes,
        n_cands,
        ref=bls,
        g1_mul_batch=lambda pts, ks: [
            bls.g1_mul(p, k) for p, k in zip(pts, ks)
        ],
        g2_mul_batch=lambda pts, ks: [
            bls.g2_mul(p, k) for p, k in zip(pts, ks)
        ],
        miss_k=4,
        seed=7,
    )
    dev = BLS12381Device(
        [BLS12381PublicKey(p) for p in pks], batch_size=lanes, curves=curves
    )
    kern = dev._range_kernel(miss_k)
    verdicts = np.asarray(jax.device_get(kern(*args)))
    assert verdicts[:n_cands].all(), f"verification failed: {verdicts[:n_cands]}"
    assert not verdicts[n_cands:].any(), "padding lanes must not verify"

    times = []
    for _ in range(trials):
        t0 = time.perf_counter()
        jax.device_get(kern(*args))
        times.append((time.perf_counter() - t0) * 1e3)
    p50 = float(np.median(times))
    out = {
        "metric": f"bls12_381_{n_registry}reg_{lanes}lane_verify_p50_ms",
        "value": round(p50, 3),
        "unit": "ms",
        "backend": jax.default_backend(),
        "device": str(jax.devices()[0]),
        "registry": n_registry,
        "lanes": lanes,
        "candidates": n_cands,
        "trials_ms": [round(t, 3) for t in times],
        "captured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }
    print(json.dumps(out))
    path = os.path.normpath(
        os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "..", "chiprun_out",
            "bench_bls12.json",
        )
    )
    if out["backend"] != "cpu":
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            json.dump(out, f, indent=1)
            f.write("\n")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
