"""`run.py --rehearse` end to end under JAX_PLATFORMS=cpu: both cells, and an
open-loop traffic file that no committed cell uses — added the way a later PR
adds a cell: one data file plus one BENCHMARK.json entry, no harness edit."""

import json
import os
import subprocess
import sys

import pytest

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
    return last, {ph["phase"]: ph for ph in phases if "phase" in ph}


@pytest.mark.slow
@pytest.mark.parametrize("trace", ["0", "1"])
def test_range_cell(trace):
    last, phases = rehearse("handel4096-99thr.closed256", "--trace", trace)
    assert phases["rehearsal"]["would_be_correct"] is True
    assert phases["window"]["compile_events_in_window"] == 0
    assert phases["pool"]["launch_class"] == "range8"
    if trace == "1":
        assert {"busy_s", "window_s"} <= set(last["device"])
        assert "service.fill" in phases["rehearsal"]["host_clock_values"]


@pytest.mark.slow
def test_dense_cell():
    last, phases = rehearse("handel4096-51thr-failing.closed256", "--trace", "0")
    assert phases["rehearsal"]["would_be_correct"] is True
    assert phases["pool"]["launch_class"] == "dense"
    assert "prefix_table" not in phases


@pytest.mark.slow
def test_open_loop_cell_added_as_data(tmp_path):
    bench = json.load(open(os.path.join(spec.ROOT, "BENCHMARK.json")))
    bench["workloads"].append({
        "name": "handel4096-99thr.open-burst", "config": "handel4096-99thr",
        "traffic": "open-poisson-levels", "chips": 1, "why": "rehearsed only",
    })
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    last, phases = rehearse(
        "handel4096-99thr.open-burst", "--trace", "0", "--benchmark", str(path))
    assert phases["setup_done"]["loop"] == "open"
    assert phases["rehearsal"]["would_be_correct"] is True


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
