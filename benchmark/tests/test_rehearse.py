"""`run.py --rehearse` end to end under JAX_PLATFORMS=cpu: the two BN254 cells,
and the two traffic files that no committed cell uses (the open loop and the
mixed stream of three launch classes) — added the way a later PR adds a cell:
one data file plus one BENCHMARK.json entry (`extra_cell.py`), no harness
edit."""

import json
import os
import subprocess
import sys

import pytest

import extra_cell
import spec

RUN = os.path.join(spec.BENCH_DIR, "run.py")
KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def rehearse(workload, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, RUN, "--workload", workload, "--seed", str(2**31 + 11),
         "--seconds", "4", "--rehearse", *extra],
        capture_output=True, text=True, env=env, cwd=spec.ROOT, timeout=3000,
    )
    assert p.returncode == 0, p.stderr[-3000:]
    lines = p.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert KEYS <= set(last) and last["correct"] is False and last["metrics"] == {}
    assert last["device"]["platform"] == "cpu" and last["attempted"] > 0
    assert p.stderr.strip().splitlines()[-1].startswith("compared ")
    phases = [json.loads(l) for l in lines[:-1] if l.startswith("{")]
    assert list(last)[-1] == "compared"  # each number beside its limit, last
    compared = {name: value for name, (value, limit) in last["compared"].items()}
    return last, {ph["phase"]: ph for ph in phases if "phase" in ph}, compared


def launched(phases) -> set:
    """The classes the window launched. The engine counts a launch by class
    when it dispatches and the service when it has fetched, so the two totals
    differ by the launches in flight at the window's edges (max_inflight 2)."""
    by_class = phases["window"]["launches_by_class"]
    assert abs(sum(by_class.values()) - phases["window"]["launches"]) <= 2
    return {name for name, n in by_class.items() if n}


@pytest.mark.slow
@pytest.mark.parametrize("trace", ["0", "1"])
def test_range_cell(trace):
    last, phases, compared = rehearse(
        "handel4096-99thr.closed256", "--trace", trace)
    assert phases["rehearsal"]["would_be_correct"] is True
    assert phases["window"]["compile_events_in_window"] == 0
    assert phases["pool"]["launch_classes"] == ["range8"]
    assert phases["prefix_table"]["seconds"] > 0.5  # the scan, not a re-read
    assert {"warm_launch_range8_0", "warm_launch_range8_2"} <= set(phases)
    assert (compared["warm_launches_range8"], compared["warm_launches_range64"],
            compared["unwarmed_class_launches"]) == (3, 0, 0)
    assert launched(phases) == {"range8"}
    if trace == "1":
        assert {"busy_s", "window_s"} <= set(last["device"])
        assert "service.fill" in phases["rehearsal"]["host_clock_values"]


@pytest.mark.slow
def test_wide_cell():
    last, phases, compared = rehearse(
        "handel4096-51thr-failing.closed256", "--trace", "0")
    assert phases["rehearsal"]["would_be_correct"] is True
    assert phases["pool"]["launch_classes"] == ["range1024"]
    assert "prefix_table" in phases and "warm_launch_range1024_2" in phases
    assert (compared["warm_launches_range1024"], compared["warm_launches_dense"],
            compared["unwarmed_class_launches"]) == (3, 0, 0)


@pytest.mark.slow
def test_open_loop_cell_added_as_data(tmp_path):
    path = extra_cell.write_benchmark(
        tmp_path / "BENCHMARK.json", extra_cell.OPEN_BURST)
    last, phases, _ = rehearse(
        extra_cell.OPEN_BURST["name"], "--trace", "0", "--benchmark", path)
    assert phases["setup_done"]["loop"] == "open"
    assert phases["rehearsal"]["would_be_correct"] is True


@pytest.mark.slow
def test_mixed_stream_added_as_data(tmp_path):
    """Three launch classes in one stream: each is warmed (nine launches on
    the one engine), nothing compiles in the window, and the per-layer
    metrics of the configuration's committed cell are read."""
    path = extra_cell.write_benchmark(
        tmp_path / "BENCHMARK.json", extra_cell.MIXED_LEVELS)
    last, phases, compared = rehearse(
        extra_cell.MIXED_LEVELS["name"], "--trace", "1", "--benchmark", path)
    assert phases["rehearsal"]["would_be_correct"] is True
    assert phases["pool"]["launch_classes"] == ["range8", "range64", "range1024"]
    warmed = [p for p in phases if p.startswith("warm_launch_")]
    assert warmed == [f"warm_launch_{c}_{k}"
                      for c in ("range8", "range64", "range1024") for k in range(3)]
    assert phases["window"]["compile_events_in_window"] == 0
    assert [compared[f"warm_launches_{c}"]
            for c in ("range8", "range64", "range1024", "dense")] == [3, 3, 3, 0]
    assert compared["unwarmed_class_launches"] == 0
    assert launched(phases) <= {"range8", "range64", "range1024"}
    assert "agg.patch_fill" in phases["rehearsal"]["host_clock_values"]


def test_no_tpu_no_result():
    """Without --rehearse the sandbox's CPU is refused: non-zero, no result."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, RUN, "--workload", "handel4096-99thr.closed256",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=spec.ROOT, timeout=600,
    )
    assert p.returncode != 0
    assert '"correct"' not in p.stdout and '"metrics"' not in p.stdout
