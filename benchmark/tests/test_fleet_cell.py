"""The four-chip cell (ISSUE 45), by hand: its three metric files and two
readers on made-up contexts — a reader that finds no lanes returns `None`,
the parent's rule — and, slow, the cell's rehearsal on four forced host
devices."""

import importlib
import json
import os
import subprocess
import sys
from types import SimpleNamespace

import pytest

import spec
from spans import SpanSink

CELL = "handel4096-99thr-host4.closed1024"
NEW_METRICS = {
    "plane.lane_wait_ms": "counter_if_present",
    "plane.lane_launch_spread": "lane_spread",
    "device.idle_max_chip": "trace_idle_max",
}


def reader(name):
    return importlib.import_module(f"readers.{name}")


def context(**kw):
    base = dict(
        cell=SimpleNamespace(name=CELL), sink=None, trace=None,
        result=SimpleNamespace(t0_epoch=100.0, t1_epoch=130.0),
        counters0={}, counters1={},
    )
    base.update(kw)
    ctx = SimpleNamespace(**base)
    ctx.delta = lambda key: ctx.counters1[key] - ctx.counters0[key]
    return ctx


def sink_of(launches_by_lane: dict, **args) -> SpanSink:
    """`launch_on_device` spans that end inside the window, by lane."""
    sink = SpanSink()
    for lane, n in launches_by_lane.items():
        for k in range(n):
            sink.span("launch_on_device", 101.0 + k, 101.5 + k,
                      tid=-(2 + lane), args=dict(args, lane=lane, seq=k))
    return sink


@pytest.mark.parametrize("name", NEW_METRICS)
def test_metric_file_loads_and_is_the_new_cells_alone(name):
    m = spec.load_metric(name)
    assert m["reader"] == NEW_METRICS[name]
    assert callable(reader(m["reader"]).read)
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        entry = next(e for e in json.load(f)["per_layer"] if e["name"] == name)
    assert entry["workloads"] == [CELL] and "bound" not in entry
    assert name in [e["name"] for e in spec.Cell(CELL).per_layer()]
    assert name not in [
        e["name"] for e in spec.Cell("handel4096-99thr.closed256").per_layer()]


def test_the_cell_is_cell_1_on_four_chips():
    cell, twin = spec.Cell(CELL), spec.Cell("handel4096-99thr.closed256")
    assert cell.chips == 4 and twin.chips == 1
    same = lambda a, b, but: {k: v for k, v in a.items() if k not in but} == {
        k: v for k, v in b.items() if k not in but}
    assert same(cell.traffic, twin.traffic, {"clients", "pool_requests"})
    assert cell.traffic["clients"] == 4 * twin.traffic["clients"] == 1024
    assert cell.traffic["pool_requests"] == 4 * twin.traffic["pool_requests"]
    assert same(cell.config, twin.config,
                {"name", "source", "deployment", "program", "device_options",
                 "assumed"})
    assert cell.config["guarantees"] == twin.config["guarantees"]
    assert cell.config["reduced"] == [] and cell.config["service_options"] == {}
    assert cell.config["deployment"]["chips"] == 4
    # a factory the parent's program does not have: it fails there at once
    assert cell.config["program"]["plane"].endswith(":scheme_plane")
    assert cell.config["device_options"] == {"scheme": cell.config["scheme"]}
    reports = {e["name"] for e in cell.per_layer()}
    assert set(NEW_METRICS) <= reports and "agg.patch_fill" not in reports
    assert [e["name"] for e in cell.end_to_end()] == [
        e["name"] for e in twin.end_to_end()]


def test_lane_spread_on_made_up_sinks():
    read = reader("lane_spread").read
    four = {"devicesTotal": 4.0}
    # equal lanes: 0
    assert read(context(sink=sink_of({0: 5, 1: 5, 2: 5, 3: 5}),
                        counters1=four)) == 0.0
    # 100 x (max - min) / mean: (8 - 2) / 5
    assert read(context(sink=sink_of({0: 8, 1: 6, 2: 4, 3: 2}),
                        counters1=four)) == pytest.approx(120.0)
    # a lane that never launched has no span and counts as 0: (6 - 0) / 3
    assert read(context(sink=sink_of({0: 6, 1: 3, 2: 3}),
                        counters1=four)) == pytest.approx(200.0)
    # ... also without the counter, by the lanes the spans name
    assert read(context(sink=sink_of({0: 6, 3: 2}))) == pytest.approx(100.0)
    # spans outside the window are not the window's
    late = sink_of({0: 5, 1: 5})
    late.span("launch_on_device", 140.0, 141.0, args={"lane": 0, "seq": 9})
    assert read(context(sink=late, counters1={"devicesTotal": 2.0})) == 0.0


def test_readers_find_nothing_without_lanes():
    spread, idle = reader("lane_spread").read, reader("trace_idle_max").read
    assert spread(context()) is None                          # untraced run
    assert spread(context(sink=SpanSink())) is None           # no launch
    assert spread(context(sink=sink_of({0: 9}),               # one lane
                          counters1={"devicesTotal": 1.0})) is None
    bare = SpanSink()                                         # spans with no lane
    bare.span("launch_on_device", 101.0, 102.0, args={"seq": 0})
    assert spread(context(sink=bare, counters1={"devicesTotal": 4.0})) is None
    assert idle(context()) is None
    one = SimpleNamespace(window_ns=1e9, planes=[SimpleNamespace(busy_ns=4e8)])
    assert idle(context(trace=one)) is None                   # one chip
    assert idle(context(trace=SimpleNamespace(window_ns=0.0, planes=[]))) is None
    # the counter's reader on a program without the counter (the parent)
    wait = reader("counter_if_present").read
    args = spec.load_metric("plane.lane_wait_ms")["args"]
    assert wait(context(counters0={"verifierLaunches": 1.0},
                        counters1={"verifierLaunches": 9.0}), **args) is None
    assert wait(context(
        counters0={"laneWaitMs": 10.0, "laneWaitLaunches": 4.0},
        counters1={"laneWaitMs": 70.0, "laneWaitLaunches": 16.0}), **args) == 5.0


def test_idle_max_is_the_idlest_chips():
    planes = [SimpleNamespace(busy_ns=b) for b in (9e8, 6e8, 8e8, 7e8)]
    tr = SimpleNamespace(window_ns=1e9, planes=planes)
    assert reader("trace_idle_max").read(context(trace=tr)) == pytest.approx(40.0)


@pytest.mark.slow
def test_the_cell_rehearses_on_four_host_devices():
    """`--rehearse` over four forced host devices: twelve warm launches (three
    an engine), nothing compiles in the window, every served verdict equals
    the reference's, and the new metrics read something."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run(
        [sys.executable, os.path.join(spec.BENCH_DIR, "run.py"), "--workload",
         CELL, "--seed", str(2**31 + 11), "--seconds", "6", "--trace", "1",
         "--rehearse"],
        capture_output=True, text=True, env=env, cwd=spec.ROOT, timeout=3400,
    )
    assert p.returncode == 0, p.stderr[-3000:]
    lines = p.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    phases = {ph["phase"]: ph for ph in
              (json.loads(l) for l in lines[:-1] if l.startswith("{"))
              if "phase" in ph}
    assert last["correct"] is False and last["metrics"] == {}  # a rehearsal
    assert phases["rehearsal"]["would_be_correct"] is True
    assert phases["engines"]["engines"] == 4
    assert last["device"]["count"] == 4
    compared = {name: value for name, (value, _) in last["compared"].items()}
    assert compared["warm_launches_range8"] == 12
    assert compared["unwarmed_class_launches"] == 0
    assert compared["engine_launches_minus_service_launches_minus_warmup"] == 0
    assert phases["window"]["compile_events_in_window"] == 0
    read = phases["rehearsal"]["host_clock_values"]
    assert {"plane.lane_wait_ms", "plane.lane_launch_spread"} <= set(read)
