"""Flight-recorder + trace-pipeline tests (ISSUE 4).

Covers: the bounded ring and its disabled-mode overhead budget (<1 us per
span call — the contract that lets the hooks live in the hot path
permanently), Packet.sent_ts wire transport, the end-to-end traced
LocalCluster (every contribution's recv -> queue -> verify -> merge chain
reconstructable with >= 95% wall coverage), and the trace-analysis CLI.
"""

import asyncio
import json
import os
import time

import pytest

from handel_tpu.core.net import Packet
from handel_tpu.core.test_harness import run_cluster
from handel_tpu.core.trace import FlightRecorder, LogHistogram, merge_traces
from handel_tpu.sim import trace_cli


# -- ring mechanics ----------------------------------------------------------


def test_ring_bound_and_order():
    rec = FlightRecorder(capacity=8)
    for i in range(20):
        rec.span(f"s{i}", float(i), float(i) + 0.5, tid=1)
    evs = rec.events()
    assert len(evs) == 8
    assert rec.dropped == 12
    # oldest events were overwritten; the survivors are the newest, in order
    assert [e[0] for e in evs] == [f"s{i}" for i in range(12, 20)]
    assert rec.values()["traceDropped"] == 12.0


def test_export_chrome_shape():
    rec = FlightRecorder(capacity=16, pid=7)
    rec.name_thread(3, "node-3")
    rec.span("verify", 1.0, 1.002, tid=3, cat="pipeline", args={"origin": 5})
    rec.instant("level_complete", ts=1.01, tid=3, args={"level": 2})
    ex = rec.export()
    assert ex["traceEvents"]
    meta = [e for e in ex["traceEvents"] if e["ph"] == "M"]
    assert meta[0]["args"]["name"] == "node-3"
    span = next(e for e in ex["traceEvents"] if e["ph"] == "X")
    assert span["pid"] == 7 and span["tid"] == 3
    assert span["ts"] == pytest.approx(1.0e6)
    assert span["dur"] == pytest.approx(2000.0, rel=1e-6)
    inst = next(e for e in ex["traceEvents"] if e["ph"] == "i")
    assert inst["args"]["level"] == 2
    json.dumps(ex)  # serializable as-is


def test_disabled_overhead_below_1us():
    """The acceptance budget: with tracing disabled, a span hook costs under
    1 us — so the per-contribution instrumentation (a handful of calls)
    stays compiled into the hot path unconditionally."""
    rec = FlightRecorder(capacity=8, enabled=False)
    n = 200_000
    t0 = time.perf_counter()
    for _ in range(n):
        rec.span("recv", 0.0, 0.0, tid=1)
    per_call = (time.perf_counter() - t0) / n
    assert per_call < 1e-6, f"disabled span() costs {per_call * 1e9:.0f} ns"
    assert rec.events() == []  # nothing recorded


def test_merge_traces_sorts_by_ts():
    a = FlightRecorder(pid=1)
    b = FlightRecorder(pid=2)
    a.span("x", 2.0, 3.0)
    b.span("y", 1.0, 2.0)
    merged = merge_traces([a.export(), b.export()])
    names = [e["name"] for e in merged["traceEvents"] if e["ph"] == "X"]
    assert names == ["y", "x"]


def test_flow_events_export_chrome_shape():
    """Flow links (ph s/t/f) share an id and bind to the enclosing slice
    (bp: "e" on t/f) — the Chrome/Perfetto contract that draws one arrow
    per contribution across process rows."""
    rec = FlightRecorder(capacity=16, pid=3)
    rec.span("send", 1.0, 1.001, tid=1, cat="pipeline")
    rec.flow("contrib", 0xBEEF, "s", 1.0, tid=1)
    rec.span("recv", 1.002, 1.003, tid=2, cat="pipeline")
    rec.flow("contrib", 0xBEEF, "t", 1.003, tid=2)
    rec.flow("contrib", 0xBEEF, "f", 1.004, tid=2)
    ex = rec.export()
    flows = [e for e in ex["traceEvents"] if e["ph"] in ("s", "t", "f")]
    assert [e["ph"] for e in flows] == ["s", "t", "f"]
    assert all(e["id"] == 0xBEEF for e in flows)
    assert all(e["name"] == "contrib" for e in flows)
    assert "bp" not in flows[0]
    assert flows[1]["bp"] == "e" and flows[2]["bp"] == "e"
    json.dumps(ex)


def test_flow_disabled_is_noop():
    rec = FlightRecorder(capacity=8, enabled=False)
    rec.flow("contrib", 7, "s", 1.0)
    assert rec.events() == []


def test_merge_traces_applies_clock_offset():
    """A per-process clockOffset (seconds to add to land on the master's
    clock) shifts every non-metadata event at merge, so cross-process
    arrows point forward in time."""
    a = FlightRecorder(pid=1)
    b = FlightRecorder(pid=2)
    a.name_thread(0, "a")
    a.span("send", 1.0, 1.001, tid=0)
    b.span("recv", 1.0, 1.002, tid=0)
    b.clock_offset = 0.5  # b's clock runs half a second behind the master
    merged = merge_traces([a.export(), b.export()])
    spans = {
        (e["pid"], e["name"]): e["ts"]
        for e in merged["traceEvents"]
        if e["ph"] == "X"
    }
    assert spans[(1, "send")] == pytest.approx(1.0e6)
    assert spans[(2, "recv")] == pytest.approx(1.5e6)
    # metadata rows are clock-independent and must not shift
    meta = [e for e in merged["traceEvents"] if e["ph"] == "M"]
    assert all(e.get("ts", 0) == 0 for e in meta)


def test_span_rate_gauge_in_values():
    rec = FlightRecorder(capacity=64)
    for i in range(10):
        rec.span("s", float(i), float(i) + 0.1)
    vals = rec.values()
    assert vals["traceEvents"] == 10.0
    assert vals["traceSpanRate"] > 0.0
    assert "traceSpanRate" in rec.gauge_keys()


def test_sync_slave_offset_sample_keeps_min_rtt():
    """The NTP-style estimator keeps the minimum-RTT sample (tightest
    ±rtt/2 error bound) and rejects backwards clocks."""
    from handel_tpu.sim.sync import SyncSlave

    s = SyncSlave("127.0.0.1:0", 1)
    now = time.time()
    s._offset_sample(now - 0.010, now - 0.005 + 0.3)  # rtt ~10ms, offset ~.3
    assert s.clock_rtt == pytest.approx(0.010, abs=0.005)
    first = s.clock_offset
    assert first == pytest.approx(0.3, abs=0.01)
    # a noisier (larger-rtt) sample must not displace the kept one
    s._offset_sample(now - 0.200, now + 1.0)
    assert s.clock_offset == first
    # a tighter sample wins
    s.clock_rtt = 1.0
    s._offset_sample(time.time() - 1e-4, time.time() + 0.25)
    assert s.clock_offset == pytest.approx(0.25, abs=0.01)
    # negative rtt (clock stepped back) is discarded
    before = s.clock_offset, s.clock_rtt
    s._offset_sample(time.time() + 5.0, 0.0)
    assert (s.clock_offset, s.clock_rtt) == before


# -- wire transport of the cross-node stamp ----------------------------------


def test_packet_sent_ts_roundtrip():
    p = Packet(origin=3, level=2, multisig=b"ms", individual_sig=b"i",
               sent_ts=1234.5678)
    q = Packet.decode(p.encode())
    assert q.sent_ts == pytest.approx(1234.5678)
    assert (q.origin, q.level, q.multisig, q.individual_sig) == (
        3, 2, b"ms", b"i",
    )


def test_packet_corrupt_sent_ts_degrades_to_zero():
    import struct

    p = Packet(origin=1, level=1, multisig=b"m", sent_ts=float("inf"))
    assert Packet.decode(p.encode()).sent_ts == 0.0
    wire = bytearray(Packet(origin=1, level=1, multisig=b"m").encode())
    # force a NaN into the stamp field (bytes 9-16 of the header)
    wire[9:17] = struct.pack(">d", float("nan"))
    assert Packet.decode(bytes(wire)).sent_ts == 0.0


# -- end-to-end traced cluster ----------------------------------------------


@pytest.fixture(scope="module")
def traced_run(tmp_path_factory):
    """One traced 16-node LocalCluster run shared by the e2e assertions."""
    rec = FlightRecorder(capacity=1 << 16)
    finals = asyncio.run(run_cluster(16, recorder=rec))
    d = tmp_path_factory.mktemp("trace")
    rec.dump(str(d / "trace_0.json"))
    return rec, finals, str(d)


def test_traced_cluster_exports_pipeline_spans(traced_run):
    rec, finals, _ = traced_run
    assert len(finals) == 16
    names = {e[0] for e in rec.events()}
    for span in ("recv", "queue", "verify", "merge", "net_transit"):
        assert span in names, f"missing {span} spans"
    assert "level_complete" in names


def test_traced_contribution_coverage(traced_run):
    """Acceptance: spans cover >= 95% of a sampled contribution's
    recv -> merge wall time (and the median chain stays attributable)."""
    _, _, d = traced_run
    events = trace_cli.load_traces([d])
    chains = trace_cli.contribution_chains(events)
    assert chains, "no complete contribution chains reconstructed"
    cov = sorted(c["coverage"] for c in chains.values())
    assert cov[-1] >= 0.95, f"best chain coverage {cov[-1]:.1%}"
    assert cov[len(cov) // 2] >= 0.80, f"median coverage {cov[len(cov) // 2]:.1%}"
    # every chain decomposes into the pipeline stages
    sample = next(iter(chains.values()))
    assert {"recv", "queue", "verify", "merge"} <= set(sample["stages"])


def test_level_timeline_is_monotonic(traced_run):
    _, _, d = traced_run
    events = trace_cli.load_traces([d])
    wave = trace_cli.level_timeline(events)
    assert wave, "no level_complete events"
    for lvl, (first, med, last) in wave.items():
        assert first <= med <= last
    # higher levels complete no earlier than level 1 started (the wave moves up)
    firsts = [wave[lvl][0] for lvl in sorted(wave)]
    assert firsts == sorted(firsts)


def test_traced_cluster_flow_linkage(traced_run):
    """Every traced contribution's recv resolves its packet span id back to
    a send span — in-process, linkage must be total."""
    _, _, d = traced_run
    events = trace_cli.load_traces([d])
    frac, linked, total = trace_cli.flow_linkage(events)
    assert total > 0
    assert frac >= 0.95, f"flow linkage {frac:.1%} ({linked}/{total})"


def test_critical_path_covers_time_to_threshold(traced_run):
    """Acceptance: the backwards walk from the first threshold_reached
    instant yields ONE causal chain whose spans cover >= 90% of the wall
    time-to-threshold, with per-stage attribution."""
    _, _, d = traced_run
    events = trace_cli.load_traces([d])
    cp = trace_cli.critical_path(events)
    assert cp is not None, "no threshold_reached anchor"
    assert cp["chain"], "empty causal chain"
    assert cp["wall_ms"] > 0
    assert cp["coverage"] >= 0.90, f"coverage {cp['coverage']:.1%}"
    names = {e["name"] for e in cp["chain"]}
    # the chain decomposes into the pipeline stages, net hops included
    assert {"recv", "verify", "merge", "net_transit"} <= names
    assert cp["hops"] >= 1
    # stage attribution is sane: non-negative, and no stage alone exceeds
    # the wall (adjacent chain spans may overlap, so the SUM can slightly)
    assert all(v >= 0.0 for v in cp["stages_ms"].values())
    assert max(cp["stages_ms"].values()) <= cp["wall_ms"] * 1.001
    # the chain is causally ordered: event starts never move backwards
    starts = [e["t_ms"] for e in cp["chain"]]
    assert starts == sorted(starts)


def test_build_report_is_bench_record(traced_run):
    """trace_report.json carries its headline (metric/value/backend) and
    every headline figure flat on the record, as numbers."""
    _, _, d = traced_run
    exports = trace_cli.load_exports([d])
    events = merge_traces(exports)["traceEvents"]
    report = trace_cli.build_report(events, exports)
    assert report["backend"] == "trace"
    assert report["metric"] == "trace_time_to_threshold_s"
    assert report["value"] > 0
    for key in ("time_to_threshold_s", "critical_path_coverage",
                "flow_linkage", "lane_occupancy"):
        assert isinstance(report[key], (int, float)), key
    assert report["time_to_threshold_s"] == report["value"]
    assert report["critical_path_coverage"] >= 0.90
    json.dumps(report)


def test_trace_cli_smoke(traced_run, tmp_path, capsys):
    _, _, d = traced_run
    merged = str(tmp_path / "merged.json")
    report = str(tmp_path / "trace_report.json")
    assert trace_cli.main(
        [d, "--merged", merged, "--top", "3",
         "--critical-path", "--report", report]
    ) == 0
    out = capsys.readouterr().out
    assert "aggregation wave" in out
    assert "slowest-span attribution" in out
    assert "contribution chains" in out
    assert "critical path to threshold" in out
    with open(merged) as f:
        data = json.load(f)
    assert len(data["traceEvents"]) > 0
    with open(report) as f:
        rep = json.load(f)
    assert rep["backend"] == "trace" and rep["critical_path"]["chain"]


def test_trace_cli_plot(traced_run, tmp_path):
    pytest.importorskip("matplotlib")
    _, _, d = traced_run
    png = str(tmp_path / "wave.png")
    assert trace_cli.main([d, "--plot", png]) == 0
    assert os.path.getsize(png) > 0


def test_untraced_cluster_has_no_recorder_cost_path():
    """Default config: recorder is None — the protocol still converges and
    per-node histograms (always-on distributional plane) are populated."""
    async def go():
        from handel_tpu.core.test_harness import LocalCluster

        cluster = LocalCluster(8)
        cluster.start()
        try:
            await cluster.wait_complete_success(10.0)
        finally:
            cluster.stop()
        h = next(iter(cluster.handels.values()))
        assert h.rec is None
        hists = h.histograms()
        assert hists["levelCompleteS"].count > 0
        assert hists["verifyLatencyS"].count > 0
        assert hists["queueWaitS"].count > 0

    asyncio.run(go())


def test_localhost_platform_traced_run(tmp_path):
    """The full subprocess path: `trace = true` makes every node process
    record a flight recorder and dump Chrome JSON into the run's trace dir;
    the stats CSV carries the _p50/_p90/_p99 columns for the
    level-completion and device-verify latency keys (acceptance criteria)."""
    import csv

    from handel_tpu.sim.config import RunConfig, SimConfig
    from handel_tpu.sim.platform import LocalhostPlatform

    cfg = SimConfig(
        network="udp",
        scheme="fake",
        trace=True,
        max_timeout_s=60.0,
        runs=[RunConfig(nodes=8, threshold=5, processes=2)],
    )

    async def go():
        plat = LocalhostPlatform(cfg, str(tmp_path))
        return await plat.start_run(0)

    res = asyncio.run(go())
    if not res.ok:
        for out, err in res.outputs:
            print(out.decode(errors="replace"))
            print(err.decode(errors="replace"))
    assert res.ok
    # one dump per node process, each a valid non-empty Chrome trace
    dumps = sorted(os.listdir(res.trace_dir))
    assert len(dumps) == 2
    exports = trace_cli.load_exports([res.trace_dir])
    events = merge_traces(exports)["traceEvents"]
    assert len(events) > 0
    assert trace_cli.level_timeline(events)  # the wave is reconstructable
    chains = trace_cli.contribution_chains(events)
    assert chains
    assert max(c["coverage"] for c in chains.values()) >= 0.95
    # cross-process causality (acceptance): >= 95% of traced recvs resolve
    # their packet span id to the sending process's send span
    frac, linked, total = trace_cli.flow_linkage(events)
    assert total > 0
    assert frac >= 0.95, f"cross-process flow linkage {frac:.1%} ({linked}/{total})"
    # each process dump carries a clock-offset estimate from the sync
    # handshake; on one host the skew must be tiny (well under a second)
    offsets = [float(ex.get("clockOffset", 0.0) or 0.0) for ex in exports]
    assert len(offsets) == 2
    assert all(abs(o) < 1.0 for o in offsets), f"clock offsets {offsets}"
    # the merged trace yields a critical path across processes
    cp = trace_cli.critical_path(events)
    assert cp is not None and cp["chain"]
    # distribution columns next to the classic stats
    rows = list(csv.DictReader(open(res.csv_path)))
    for key in ("levelCompleteS", "verifyLatencyS", "queueWaitS"):
        for s in ("p50", "p90", "p99"):
            assert float(rows[0][f"sigs_{key}_{s}"]) > 0.0
    assert float(rows[0]["sigs_levelCompleteS_n"]) > 0.0


def test_histogram_quantile_accuracy():
    """LogHistogram quantiles land within one bucket (<= 19% relative) of
    the exact sample quantiles, clamped to the observed range."""
    import random

    rng = random.Random(7)
    h = LogHistogram()
    samples = [rng.uniform(1e-4, 2.0) for _ in range(5000)]
    for s in samples:
        h.add(s)
    samples.sort()
    for q in (0.5, 0.9, 0.99):
        exact = samples[int(q * len(samples)) - 1]
        est = h.quantile(q)
        assert est == pytest.approx(exact, rel=0.25)
    assert h.quantile(0.99) >= h.quantile(0.5)
    assert h.lo <= h.quantile(0.5) <= h.hi
