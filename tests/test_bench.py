"""bench.py plumbing tests: the measurement path (persist with provenance,
vs_baseline ratio) must work before its first run on the chip (round-3
verdict "What's weak" #1: the TPU measurement path was itself untested
code). Runs bench.py as a subprocess — the real driver surface — on the CPU
backend with tiny forced sizes."""

import json
import os
import subprocess
import sys

import pytest

# slow tier: each test runs bench.py as a subprocess that compiles the
# verify kernel from scratch (XLA-compile-bound, ~10 min on one core) —
# runs in test-slow/test-all (nightly/CI)
pytestmark = pytest.mark.slow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "bench.py")


def _run_bench(tmp_path, extra_env):
    env = dict(
        os.environ,
        HANDEL_TPU_PLATFORM="cpu",
        HANDEL_TPU_BENCH_ARTIFACT=str(tmp_path / "bench_device.json"),
        HANDEL_TPU_BENCH_FP_ARTIFACT=str(tmp_path / "fp.json"),
        HANDEL_TPU_BENCH_FP_BATCH=str(1 << 10),
        # tiny host-pipeline shape: the packing/dedup metrics plumbing is
        # exercised without the full 1024-key keygen per bench subprocess
        HANDEL_TPU_BENCH_HOST_SHAPE="64,8,3",
        **extra_env,
    )
    r = subprocess.run(
        [sys.executable, BENCH],
        capture_output=True,
        text=True,
        timeout=1500,
        env=env,
        cwd=REPO,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    lines = [l for l in r.stdout.strip().splitlines() if l.strip()]
    assert len(lines) == 1, f"exactly one JSON line expected: {r.stdout!r}"
    return json.loads(lines[0]), r


def test_accel_measurement_path_persists_artifact(tmp_path):
    """Forced accel shape on CPU: the headline line carries a real
    vs_baseline ratio and the persisted artifact carries provenance +
    per-trial times; the fp microbench artifact is written too."""
    line, _ = _run_bench(
        tmp_path,
        {"HANDEL_TPU_BENCH_FORCE_ACCEL_SHAPE": "16,4,4,2"},
    )
    assert line["metric"] == "16sig_batch_verify_p50_ms"
    assert line["unit"] == "ms"
    # a forced tiny-CPU run must not present a baseline ratio or read as
    # a real accelerator measurement
    assert line["vs_baseline"] is None
    assert line["forced_shape"] is True
    assert line["backend"] == "cpu"
    # host half of the pipeline rides the same line: packing p50 for the
    # vectorized packer and the old loop, and the dedup-trace hit rate
    assert line["host_pack_ms"] > 0
    assert line["host_pack_loop_ms"] > 0
    assert 0.0 <= line["dedup_hit_rate"] <= 1.0

    art = json.load(open(tmp_path / "bench_device.json"))
    assert art["backend"] == "cpu"  # provenance is honest about the force
    assert art["registry"] == 16 and art["lanes"] == 4
    assert len(art["trials_ms"]) == 2
    assert "captured_at" in art

    fp = json.load(open(tmp_path / "fp.json"))
    assert fp["metric"] == "fp254_mont_mul_throughput_marginal"
    # at the forced tiny CPU batch the chain-delta slope can be lost to
    # timing noise; a 0.0 capture is then persisted with the honest
    # invalid_measurement flag — accept either outcome (advisor, r04)
    assert fp["value"] > 0 or fp.get("invalid_measurement") is True
    assert fp["dispatch_floor_ms"] >= 0
