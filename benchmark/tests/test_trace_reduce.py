"""The reduction from trace to metrics: interval arithmetic on hand-made
events, then known busy / idle / per-name sums on the small trace recorded on
the chip and committed beside this file (tests/data/README.md says how it
was cut)."""

import gzip
import json
import os
import shutil

import pytest

import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_union_and_gaps():
    evs = [(10, 20, "a"), (15, 30, "b"), (40, 50, "a"), (41, 42, "c")]
    busy, gaps = tr._union_and_gaps(evs, 0, 60)
    assert busy == 30
    assert gaps == [(0, 10), (30, 40), (50, 60)]


def test_self_time_of_nested_events():
    # a loop of 100 holding two kernels of 30 and 20: 50 of its own
    evs = [(0, 100, "while"), (10, 40, "kernel"), (50, 70, "kernel"),
           (120, 130, "copy")]
    self_ns, total_ns, count = tr._self_times(evs)
    assert self_ns == {"while": 50, "kernel": 50, "copy": 10}
    assert total_ns == {"while": 100, "kernel": 50, "copy": 10}
    assert count == {"while": 1, "kernel": 2, "copy": 1}
    assert sum(self_ns.values()) == tr._union_and_gaps(evs, 0, 130)[0]


def test_clip():
    assert list(tr._clip([(0, 10, "a"), (20, 30, "b"), (8, 25, "c")], 5, 22)) == [
        (5, 10, "a"), (20, 22, "b"), (8, 22, "c")]


def test_gap_labels():
    spans = [("dispatch_pack", 100.0, 100.4, 0, {}),
             ("launch_fetched", 100.5, 101.0, 0, {})]
    out = tr.label_gaps(
        [(0, 3e8), (6e8, 9e8), (20e8, 21e8)], spans,
        lambda ns: 100.0 + ns / 1e9,
        ("dispatch_pack", "launch_queued", "launch_fetched"))
    assert dict(out) == pytest.approx(
        {"dispatch_pack": 0.3, "launch_fetched": 0.3, "none": 0.1})


def test_execution_cut_by_the_session_is_not_whole():
    """The profiler cuts the launch that is running when its session ends to
    the session: 40 of a 100-long execution. It is no whole execution, and
    counts as 0.4 of one, not as 1 (PERF.md section 7, request 1)."""
    ops = [(k * 100 + 1, k * 100 + 99, "op") for k in range(3)] + [(301, 340, "op")]
    mods = [(k * 100, k * 100 + 100, "jit_verify_range8") for k in range(3)] + [
        (300, 340, "jit_verify_range8"), (10, 12, "jit_other")]
    red = tr.reduce_loaded(tr.Loaded("made", [("/device:TPU:0", ops, mods)], None, None),
                           0, 340)
    plane = red.planes[0]
    assert plane.modules["jit_verify_range8"] == (3, 300)
    assert plane.executions["jit_verify_range8"] == pytest.approx(3.4)
    assert plane.modules["jit_other"] == (1, 2)  # alone, it is its own longest
    # the interval's own edge still cuts by the share inside, as before
    half = tr.reduce_loaded(tr.Loaded("made", [("/device:TPU:0", ops, mods)], None, None),
                            50, 340).planes[0]
    assert half.executions["jit_verify_range8"] == pytest.approx(2.9)
    assert half.modules["jit_verify_range8"] == (2, 200)


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    src = os.path.join(DATA, "chip_small.xplane.pb.gz")
    dst = tmp_path_factory.mktemp("trace") / "chip_small.xplane.pb"
    with gzip.open(src, "rb") as f, open(dst, "wb") as g:
        shutil.copyfileobj(f, g)
    with open(os.path.join(DATA, "chip_small.expected.json")) as f:
        return str(dst), json.load(f)


def test_recorded_trace(recorded):
    path, want = recorded
    red = tr.reduce_trace(path)
    assert len(red.planes) == want["planes"]
    assert red.window_s == pytest.approx(want["window_s"], rel=1e-9)
    assert red.busy_s == pytest.approx(want["busy_s"], rel=1e-9)
    assert 0 < red.busy_s <= red.window_s
    selfs = red.self_seconds()
    for name, seconds in want["self_seconds"].items():
        assert selfs[name] == pytest.approx(seconds, rel=1e-9)
    assert sum(selfs.values()) == pytest.approx(red.busy_s * len(red.planes), rel=1e-6)
    modules = {n: v[0] for p in red.planes for n, v in p.modules.items()}
    assert modules == want["module_executions"]  # one whole launch ...
    for name, runs in want["executions"].items():  # ... and a piece of the next
        assert red.planes[0].executions[name] == pytest.approx(runs, rel=1e-9)


def test_readers_on_recorded_trace(recorded):
    """The three trace readers on the recorded launch: 39 ms a launch, 57.6 %
    of the device's time in Mosaic kernels, under 1 % idle."""
    from types import SimpleNamespace

    from readers import trace_busy_per_launch, trace_idle, trace_share

    path, want = recorded
    ctx = SimpleNamespace(trace=tr.reduce_trace(path))
    runs = sum(want["executions"].values())
    assert trace_busy_per_launch.read(ctx) == pytest.approx(
        1e3 * want["busy_s"] / runs, rel=1e-9)
    assert 38.0 < trace_busy_per_launch.read(ctx) < 40.0
    assert trace_share.read(ctx, pattern="tpu_custom_call") == pytest.approx(
        want["mosaic_share_percent"], rel=1e-9)
    assert trace_share.read(ctx, pattern="no_such_kernel") is None
    assert trace_idle.read(ctx) == pytest.approx(
        100 * (1 - want["busy_s"] / want["window_s"]), rel=1e-9)
    assert trace_busy_per_launch.read(SimpleNamespace(trace=None)) is None


def test_recorded_trace_sub_interval(recorded):
    """Half the trace: busy and idle add up to the half."""
    path, want = recorded
    whole = tr.reduce_trace(path)
    mid = (whole.t0_ns + whole.t1_ns) / 2
    a = tr.reduce_trace(path, whole.t0_ns, mid)
    b = tr.reduce_trace(path, mid, whole.t1_ns)
    assert a.busy_s + b.busy_s == pytest.approx(whole.busy_s, rel=1e-9)
    for red in (a, b):
        gaps = sum(e - s for p in red.planes for s, e in p.gaps) / 1e9
        assert gaps / len(red.planes) + red.busy_s == pytest.approx(red.window_s)
