"""The readers of the launch stages (ISSUE 26) on synthetic contexts: spans
at a known cadence, a small XSpace with host annotations and program
executions at a known clock ratio, one idle gap an annotation covers and one
none does — and every new metric file loads and names a reader that is there."""

import importlib
import json
import os
from types import SimpleNamespace

import pytest

import spec
from spans import SpanSink

CELL = "handel4096-99thr.closed256"
NEW_METRICS = [
    "fence.wait_ms_per_launch", "pack.work_ms_per_launch",
    "pack.cpu_ms_per_launch", "stage.ms_per_launch", "enqueue.ms_per_launch",
    "fetch.copy_ms_per_launch", "service.queue_wait_ms", "launch.interval_ms",
    "trace.clock_skew", "idle.under_host_stages", "idle.no_launch_ready",
]


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


@pytest.mark.parametrize("name", NEW_METRICS)
def test_metric_file_loads_and_is_listed(name):
    m = spec.load_metric(name)
    assert callable(reader(m["reader"]).read)
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        entry = next(e for e in json.load(f)["per_layer"] if e["name"] == name)
    assert entry["workloads"] and "bound" not in entry
    cell = spec.Cell(CELL)
    assert name in [e["name"] for e in cell.per_layer()]


def test_counter_if_present_reads_like_counter_and_skips_a_missing_key():
    read = reader("counter_if_present").read
    ctx = context(counters0={"hostFenceWaitMs": 10.0, "hostPackLaunches": 3.0},
                  counters1={"hostFenceWaitMs": 80.0, "hostPackLaunches": 5.0})
    assert read(ctx, "hostFenceWaitMs", per="hostPackLaunches") == 35.0
    assert read(ctx, "hostFenceWaitMs") == 70.0
    assert read(ctx, "hostFenceWaitMs", per="hostPackLaunches", scale=2.0) == 70.0
    # a program from before the counter (the PR's parent): nothing, no raise
    assert read(ctx, "queueWaitMs", per="queueWaitCandidates") is None
    assert read(ctx, "hostFenceWaitMs", per="hostFetchLaunches") is None
    still = context(counters0={"a": 1.0, "n": 2.0}, counters1={"a": 4.0, "n": 2.0})
    assert read(still, "a", per="n") is None  # nothing to divide by


def test_launch_interval_is_the_median_gap_per_lane():
    read = reader("launch_interval").read
    sink = SpanSink()
    for k in range(20):  # lane -2: one launch every 39 ms, one stall of 80
        end = 101.0 + 0.039 * k + (0.041 if k >= 10 else 0.0)
        sink.span("launch_on_device", end - 0.07, end, tid=-2, args={"seq": k})
    for k in range(20):  # lane -3 runs beside it, offset by 10 ms
        end = 101.010 + 0.039 * k
        sink.span("launch_on_device", end - 0.07, end, tid=-3, args={"seq": k})
    sink.span("launch_on_device", 50.0, 50.1, tid=-2)   # before the window
    sink.span("launch_staged", 101.0, 101.001, tid=-2)  # another span
    assert read(context(sink=sink)) == pytest.approx(39.0, abs=1e-6)
    assert read(context(sink=None)) is None
    few = SpanSink()
    few.span("launch_on_device", 101.0, 101.1, tid=-2)
    few.span("launch_on_device", 101.1, 101.2, tid=-2)
    assert read(context(sink=few)) is None


# -- a small XSpace ----------------------------------------------------------

PROGRAM = "jit_verify_range8(1234)"
LAUNCH_NS = 40_000_000      # one launch every 40 ms of the host's clock
DEVICE_RATIO = 0.9          # ... which the device's line counts as 36 ms
CALLBACK_NS = 1_000_000     # the host learns of an execution's end 1 ms late


def xspace_text(first_seq=5, launches=6, annotate=True) -> str:
    ps = lambda ns: int(ns * 1000)
    runs, waits, packs = [], [], []
    for k in range(launches):
        host_end = 10_000_000 + LAUNCH_NS * (k + 1)
        dev_end = 10_000_000 + DEVICE_RATIO * LAUNCH_NS * (k + 1)
        runs.append(
            f"events {{ metadata_id: 1 offset_ps: {ps(dev_end - 30_000_000)} "
            f"duration_ps: {ps(30_000_000)} }}")
        stats = (f"stats {{ metadata_id: 1 int64_value: {first_seq + k} }} "
                 f"stats {{ metadata_id: 2 int64_value: 0 }}")
        waits.append(
            f"events {{ metadata_id: 1 offset_ps: {ps(host_end - 35_000_000)} "
            f"duration_ps: {ps(35_000_000 + CALLBACK_NS)} {stats} }}")
        packs.append(  # 3 ms of packing right after each fetch
            f"events {{ metadata_id: 2 offset_ps: {ps(host_end + CALLBACK_NS)} "
            f"duration_ps: {ps(3_000_000)} {stats} }}")
    # the profiler clips the program running at the session's start and the
    # one running at its stop to the session: two short events, the first
    # with a real end (its fetch_wait began before the session: not there),
    # the second with the session's end for its own
    last_end = 10_000_000 + DEVICE_RATIO * LAUNCH_NS * launches
    runs.insert(0, f"events {{ metadata_id: 1 offset_ps: {ps(2_000_000)} "
                   f"duration_ps: {ps(8_000_000)} }}")
    runs.append(f"events {{ metadata_id: 1 offset_ps: {ps(last_end + 6_000)} "
                f"duration_ps: {ps(7_000_000)} }}")
    host_lines = (
        f'lines {{ id: 7 name: "fetcher/7" {" ".join(waits)} }} '
        f'lines {{ id: 8 name: "dispatcher/8" {" ".join(packs)} }}'
    ) if annotate else 'lines { id: 7 name: "fetcher/7" }'
    return f"""
planes {{
  id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Modules" {" ".join(runs)}
          events {{ metadata_id: 2 offset_ps: 1000 duration_ps: 5000 }} }}
  lines {{ id: 2 name: "XLA Ops" events {{ metadata_id: 3 offset_ps: 0 duration_ps: 1 }} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "{PROGRAM}" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "jit_prefix_table(9)" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "%fusion.1 = u32[16] fusion()" }} }}
}}
planes {{
  id: 2 name: "/host:CPU"
  {host_lines}
  event_metadata {{ key: 1 value {{ id: 1 name: "handel/fetch_wait" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "handel/pack" }} }}
  stat_metadata {{ key: 1 value {{ id: 1 name: "seq" }} }}
  stat_metadata {{ key: 2 value {{ id: 2 name: "lane" }} }}
}}
"""


@pytest.fixture
def trace_dir(tmp_path, monkeypatch):
    """Where `run.py` leaves a cell's XSpace, under a temporary BENCH_DIR."""
    from jax.profiler import ProfileData

    def write(**kw):
        out = tmp_path / "_out" / "trace" / CELL / "plugins" / "profile" / "run"
        out.mkdir(parents=True, exist_ok=True)
        (out / "bench.xplane.pb").write_bytes(
            ProfileData.text_proto_to_serialized_xspace(xspace_text(**kw)))
    monkeypatch.setattr(spec, "BENCH_DIR", str(tmp_path))
    return write


def test_xspace_stages_are_parsed_without_the_ops_line(trace_dir):
    trace_dir()
    stages = reader("_xspace").load(context())
    assert [a[1] for a in stages.of("fetch_wait")] == [5, 6, 7, 8, 9, 10]
    assert [a[1] for a in stages.of("pack", lane=0)] == [5, 6, 7, 8, 9, 10]
    assert stages.of("pack", lane=1) == []
    (runs,) = stages.executions.values()
    assert [n for _, _, n in runs].count(PROGRAM) == 8 and len(runs) == 9
    assert reader("_xspace").lane_of("/device:TPU:3") == 3


def test_trace_clock_reads_the_ratio_of_the_two_clocks(trace_dir, capsys):
    trace_dir()
    sink = SpanSink()
    for k in range(12):  # the service's spans of the same launches, epoch clock
        end = 120.0 + 0.040 * k
        sink.span("launch_on_device", end - 0.07, end, tid=-2, args={"seq": k})
    skew = reader("trace_clock").read(context(sink=sink))
    assert skew == pytest.approx(100.0 * (1.0 - DEVICE_RATIO), abs=1e-6)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    (plane,) = line["planes"]
    assert line["phase"] == "trace_clock" and plane["program"] == PROGRAM
    assert (plane["seq_first"], plane["seq_last"], plane["matched"]) == (5, 10, 6)
    assert (plane["executions"], plane["whole"]) == (8, 6)  # two were clipped
    assert plane["device_over_profiler"] == pytest.approx(DEVICE_RATIO)
    assert plane["device_over_epoch"] == pytest.approx(DEVICE_RATIO)


def test_trace_readers_find_nothing_in_a_trace_without_annotations(trace_dir):
    for make in (lambda: trace_dir(annotate=False), lambda: None):
        make()
        ctx = context(trace=SimpleNamespace(window_ns=1.0, planes=[]))
        assert reader("trace_clock").read(ctx) is None
        assert reader("trace_stage_gaps").read(ctx, covered=True) is None
        import shutil
        shutil.rmtree(os.path.join(spec.BENCH_DIR, "_out"), ignore_errors=True)


def test_idle_gaps_split_by_host_stage_cover(trace_dir):
    trace_dir()
    read = reader("trace_stage_gaps").read
    # handel/pack k covers [51+40k, 54+40k] ms, fetch_wait k+1 [55+40k,
    # 91+40k]: take a gap under a pack, one half under it and half under
    # nothing, one far out past the last annotation, and a short one
    gaps = [(51_500_000, 52_500_000),      # 1 ms, all under pack 5
            (53_500_000, 54_500_000),      # 1 ms: 0.5 under pack, 0.5 bare
            (400_000_000, 402_000_000),    # 2 ms, nothing covers it
            (60_000_000, 60_050_000),      # 0.05 ms: inside a program
            (40_000_000, 41_000_000),      # from the interval's start: no gap
            (440_000_000, 450_000_000)]    # the tail after the last operation
    plane = SimpleNamespace(name="/device:TPU:0", gaps=gaps)
    edges = dict(window_ns=400_000_000.0, t0_ns=40_000_000, t1_ns=450_000_000)
    ctx = context(trace=SimpleNamespace(planes=[plane], **edges))
    assert read(ctx, covered=True) == pytest.approx(100.0 * 1.5 / 400.0)
    assert read(ctx, covered=False) == pytest.approx(100.0 * 2.5 / 400.0)
    other = SimpleNamespace(name="/device:TPU:1", gaps=gaps[:1])  # lane 1: bare
    ctx = context(trace=SimpleNamespace(planes=[other], **edges))
    assert read(ctx, covered=True) == 0.0
    assert read(ctx, covered=False) == pytest.approx(100.0 * 1.0 / 400.0)
