"""Live telemetry plane tests (ISSUE 5): exposition format, health/readiness
transitions, registry scrapes over a traced LocalCluster, port hygiene,
and explicit gauge declarations."""

from __future__ import annotations

import asyncio
import json
import os
import time
import urllib.error
import urllib.request

import pytest

from handel_tpu.core.metrics import (
    MetricsRegistry,
    MetricsServer,
    is_gauge_key,
    merged_histogram,
    metric_name,
    parse_exposition,
    snake,
)
from handel_tpu.core.test_harness import LocalCluster
from handel_tpu.core.trace import FlightRecorder, LogHistogram


def _get(addr: str, path: str, timeout: float = 3.0):
    """(status, body) even for non-2xx replies."""
    try:
        with urllib.request.urlopen(
            f"http://{addr}{path}", timeout=timeout
        ) as r:
            return r.status, r.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


# -- naming + classification --------------------------------------------------


def test_snake_and_metric_name():
    assert snake("msgSentCt") == "msg_sent_ct"
    assert snake("levelCompleteS") == "level_complete_s"
    assert snake("dedupHitRate") == "dedup_hit_rate"
    assert snake("xlaCompileCt") == "xla_compile_ct"
    assert metric_name("sigs", "msgSentCt") == "handel_sigs_msg_sent_ct"
    assert (
        metric_name("device_verifier", "breakerState")
        == "handel_device_verifier_breaker_state"
    )


def test_gauge_classification_explicit_then_suffix():
    # explicit declaration wins even without a magic suffix...
    assert is_gauge_key("bestCardinality", {"bestCardinality"})
    # ...and the suffix heuristic stays as fallback only
    assert is_gauge_key("dedupHitRate", None)
    assert is_gauge_key("breakerState", set())
    assert not is_gauge_key("msgSentCt", set())


# -- exposition golden --------------------------------------------------------


def test_exposition_golden():
    reg = MetricsRegistry()
    c = reg.counter("handel_test_events", "events seen")
    g = reg.gauge("handel_test_depth", "queue depth")
    h = reg.histogram("handel_test_latency_s")
    c.inc()
    c.inc(2)
    g.set(7)
    for v in (0.001, 0.002, 0.002, 0.5):
        h.observe(v)

    text = reg.exposition()
    lines = text.splitlines()
    assert "# TYPE handel_test_events counter" in lines
    assert "# HELP handel_test_events events seen" in lines
    assert "# TYPE handel_test_depth gauge" in lines
    assert "# TYPE handel_test_latency_s histogram" in lines
    assert "handel_test_events 3.0" in lines
    assert "handel_test_depth 7.0" in lines
    # histogram carries cumulative buckets, +Inf, _sum and _count
    assert any(
        l.startswith("handel_test_latency_s_bucket{le=") for l in lines
    )
    assert 'handel_test_latency_s_bucket{le="+Inf"} 4.0' in lines
    assert any(l.startswith("handel_test_latency_s_count") for l in lines)
    assert any(l.startswith("handel_test_latency_s_sum") for l in lines)
    # exactly one TYPE header per family
    assert sum(1 for l in lines if l.startswith("# TYPE")) == len(
        {l.split()[2] for l in lines if l.startswith("# TYPE")}
    )

    fams = parse_exposition(text)
    assert fams["handel_test_events"]["type"] == "counter"
    assert fams["handel_test_events"]["samples"][0][1] == 3.0
    assert fams["handel_test_latency_s"]["type"] == "histogram"
    rebuilt = merged_histogram(fams, "handel_test_latency_s")
    assert rebuilt is not None and rebuilt.count == 4
    # quantiles survive the round trip to within the log-bucket error
    assert rebuilt.quantile(0.5) == pytest.approx(
        h.hist.quantile(0.5), rel=0.25
    )


def test_histogram_exposition_roundtrip_exact_quantiles():
    """`sim watch` quantile-reconstruction bias fix (ISSUE 19 satellite):
    the exposition carries the observed min/max as _min/_max pseudo-
    samples, so a merged histogram's quantile() matches the original
    EXACTLY — not just to within the log-bucket error — because the
    clamp to [lo, hi] uses the true observed extrema, not bucket edges."""
    import random

    rng = random.Random(7)
    h = LogHistogram()
    for _ in range(500):
        h.add(rng.lognormvariate(-3.0, 1.2))

    class Rep:
        def histograms(self):
            return {"verifyLatencyS": h}

    reg = MetricsRegistry()
    reg.register_histograms("sigs", Rep())
    fams = parse_exposition(reg.exposition())
    rebuilt = merged_histogram(fams, "handel_sigs_verify_latency_s")
    assert rebuilt is not None and rebuilt.count == h.count
    assert rebuilt.lo == h.lo and rebuilt.hi == h.hi
    for q in (0.001, 0.5, 0.9, 0.99, 0.999):
        assert rebuilt.quantile(q) == h.quantile(q), q

    # single-sample edge case: the reconstruction must return the sample
    h1 = LogHistogram()
    h1.add(0.00103)

    class Rep1:
        def histograms(self):
            return {"verifyLatencyS": h1}

    reg1 = MetricsRegistry()
    reg1.register_histograms("sigs", Rep1())
    fams1 = parse_exposition(reg1.exposition())
    r1 = merged_histogram(fams1, "handel_sigs_verify_latency_s")
    assert r1.quantile(0.5) == h1.quantile(0.5) == 0.00103


def test_obs_plane_declares_every_gauge():
    """ISSUE 19 satellite: every obs/ reporter key classifies explicitly
    — a declared gauge or a *Ct counter — so the metrics plane never
    falls back to the suffix heuristic on the alerts/incidents families."""
    from handel_tpu.obs import BurnRateEvaluator, DetectorBank, IncidentLog

    for rep in (BurnRateEvaluator(), DetectorBank(), IncidentLog()):
        vals = rep.values()
        gauges = rep.gauge_keys()
        assert gauges <= set(vals), type(rep).__name__
        for key in vals:
            assert key in gauges or key.endswith("Ct"), (
                f"{type(rep).__name__}.{key} is neither a declared gauge "
                f"nor a *Ct counter — the suffix heuristic would guess"
            )
        # labeled planes declare explicitly too, and never call a
        # counter a gauge
        for key in rep.labeled_gauge_keys():
            assert not key.endswith("Ct"), (
                f"{type(rep).__name__} labeled gauge {key} looks like "
                f"a counter"
            )


def test_reporter_collector_uses_gauge_keys():
    class Rep:
        def values(self):
            return {"fooCt": 3.0, "liveLanes": 5.0}

        def gauge_keys(self):
            return {"liveLanes"}  # no magic suffix — explicit only

    reg = MetricsRegistry()
    reg.register_values("sigs", Rep(), labels={"node": "2"})
    fams = parse_exposition(reg.exposition())
    assert fams["handel_sigs_foo_ct"]["type"] == "counter"
    assert fams["handel_sigs_live_lanes"]["type"] == "gauge"
    labels, v = fams["handel_sigs_live_lanes"]["samples"][0]
    assert labels["node"] == "2" and v == 5.0


def test_scrape_survives_dying_reporter():
    class Dying:
        def values(self):
            raise RuntimeError("reporter died")

    reg = MetricsRegistry()
    reg.register_values("sigs", Dying())
    reg.gauge("handel_ok_gauge").set(1)
    fams = parse_exposition(reg.exposition())
    assert "handel_ok_gauge" in fams
    assert reg.scrape_errors >= 1


# -- health + readiness -------------------------------------------------------


def test_healthz_readyz_transition_warmup_and_breaker():
    from handel_tpu.utils.breaker import CircuitBreaker

    state = {"warmed": False}
    breaker = CircuitBreaker(threshold=1, cooldown_s=3600)
    reg = MetricsRegistry()
    reg.add_readiness("scheme_warmed", lambda: state["warmed"])
    reg.add_readiness("breaker_closed", lambda: breaker.state != "open")
    srv = MetricsServer(reg, port=0).start()
    try:
        addr = srv.address
        assert _get(addr, "/healthz")[0] == 200  # alive from the start
        code, body = _get(addr, "/readyz")
        assert code == 503
        checks = json.loads(body)["checks"]
        assert checks == {"scheme_warmed": False, "breaker_closed": True}

        breaker.record_failure()  # forces the breaker open
        state["warmed"] = True  # warmup done, but breaker now open
        code, body = _get(addr, "/readyz")
        assert code == 503
        assert json.loads(body)["checks"]["breaker_closed"] is False

        breaker.record_success()  # device recovered
        code, body = _get(addr, "/readyz")
        assert code == 200 and json.loads(body)["ready"] is True

        assert _get(addr, "/nope")[0] == 404
    finally:
        srv.stop()


def test_debug_profile_endpoint():
    reg = MetricsRegistry()
    srv = MetricsServer(reg, port=0).start()
    try:
        # no profiler wired: 501, never a crash
        req = urllib.request.Request(
            f"http://{srv.address}/debug/profile?seconds=0.1", method="POST"
        )
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=3)
        assert ei.value.code == 501

        captured = []
        srv.set_profiler(lambda s: captured.append(s) or "/tmp/prof_dir")
        with urllib.request.urlopen(req, timeout=3) as r:
            out = json.loads(r.read())
        assert out["trace"] == "/tmp/prof_dir"
        assert captured == [0.1]
    finally:
        srv.stop()


# -- registry scrape over a traced LocalCluster -------------------------------


class _StubDevice:
    batch_size = 8

    def dispatch(self, msg, reqs):
        return len(reqs)

    def fetch(self, handle):
        return [True] * handle


def test_traced_localcluster_scrape():
    """The acceptance-shaped run: a traced 8-node in-process cluster with a
    shared verifier service serves >= 20 metric families spanning the
    sigs / net / penalty / device_verifier planes, and /readyz flips only
    after the cluster starts."""
    from handel_tpu.parallel.batch_verifier import BatchVerifierService

    async def run():
        svc = BatchVerifierService(_StubDevice(), max_delay_ms=0.1)
        rec = FlightRecorder(capacity=1 << 14)
        cluster = LocalCluster(
            8, recorder=rec, metrics_port=0, verifier_service=svc
        )
        addr = cluster.metrics_server.address
        assert _get(addr, "/healthz")[0] == 200
        assert _get(addr, "/readyz")[0] == 503  # not started yet
        cluster.start()
        assert _get(addr, "/readyz")[0] == 200
        finals = await cluster.wait_complete_success(10)
        assert len(finals) == 8
        code, text = _get(addr, "/metrics")
        assert code == 200
        svc.stop()
        cluster.stop()
        return text, cluster

    text, cluster = asyncio.run(run())
    fams = parse_exposition(text)
    handel_fams = {n for n in fams if n.startswith("handel_")}
    assert len(handel_fams) >= 20, sorted(handel_fams)
    planes = {n.split("_")[1] for n in handel_fams}
    assert {"sigs", "net", "penalty", "device", "metrics"} <= planes
    assert any(n.startswith("handel_device_verifier_") for n in fams)

    # per-node labels survive: 8 samples for a sigs counter
    sent = fams["handel_sigs_msg_sent_ct"]["samples"]
    assert len(sent) == 8
    assert {l["node"] for l, _ in sent} == {str(i) for i in range(8)}
    # scraped totals agree with the live reporters
    assert sum(v for _, v in sent) == sum(
        h.values()["msgSentCt"] for h in cluster.handels.values()
    )
    # histogram plane made it through with real observations
    wave = merged_histogram(fams, "handel_sigs_level_complete_s")
    assert wave is not None and wave.count >= 8
    # after stop() the endpoint is down (zero leaked sockets)
    with pytest.raises((urllib.error.URLError, OSError)):
        urllib.request.urlopen(
            f"http://{cluster.metrics_server.address}/healthz", timeout=0.5
        )


def test_metrics_disabled_is_fully_off():
    cluster = LocalCluster(4)
    assert cluster.metrics is None and cluster.metrics_server is None
    # the sim platform allocates zero ports when metrics = false
    from handel_tpu.sim.config import SimConfig, dump_config, load_config
    from handel_tpu.sim.platform import metrics_port_plan

    cfg = SimConfig()
    assert cfg.metrics is False  # off by default, like trace
    assert metrics_port_plan(cfg, nodes=8, nprocs=2) == []
    # TOML round trip for the new keys
    cfg.metrics = True
    cfg.metrics_linger_s = 1.5
    import tempfile

    with tempfile.NamedTemporaryFile("w", suffix=".toml", delete=False) as f:
        f.write(dump_config(cfg))
        path = f.name
    try:
        loaded = load_config(path)
        assert loaded.metrics is True
        assert loaded.metrics_linger_s == 1.5
    finally:
        os.unlink(path)


def test_metrics_port_plan_hygiene():
    """Per-process ports never collide with the node block or the
    master/monitor/verifier slots below base_port."""
    from handel_tpu.sim.config import SimConfig
    from handel_tpu.sim.platform import metrics_port_plan, port_plan

    cfg = SimConfig(metrics=True, base_port=21000)
    nodes = 16
    node_ports, master_p, monitor_p, verifier_p = port_plan(cfg, nodes)
    mports = metrics_port_plan(cfg, nodes, nprocs=4)
    assert len(mports) == len(set(mports)) == 4
    taken = set(node_ports) | {master_p, monitor_p, verifier_p}
    assert not (set(mports) & taken)
    # ephemeral plan: real, distinct, bindable ports
    cfg2 = SimConfig(metrics=True)
    mports2 = metrics_port_plan(cfg2, nodes, nprocs=3)
    assert len(set(mports2)) == 3


# -- explicit gauges through the monitor plane --------------------------------


class _CaptureSink:
    def __init__(self):
        self.recorded = {}

    def record(self, name, values):
        self.recorded.setdefault(name, {}).update(values)


def test_counterio_honors_declared_gauges():
    from handel_tpu.sim.monitor import CounterIO

    class Rep:
        def __init__(self):
            self.base = {"evCt": 10.0, "liveLanes": 4.0, "hitRate": 0.5}

        def values(self):
            return dict(self.base)

        def gauge_keys(self):
            return {"liveLanes"}

    sink = _CaptureSink()
    rep = Rep()
    cio = CounterIO(sink, "sigs", rep)
    rep.base = {"evCt": 25.0, "liveLanes": 6.0, "hitRate": 0.8}
    cio.record()
    got = sink.recorded["sigs"]
    assert got["evCt"] == 15.0  # counter: delta'd against the base
    assert got["liveLanes"] == 6.0  # declared gauge: recorded as-is
    assert got["hitRate"] == 0.8  # suffix fallback still catches Rate


def test_stats_declare_gauge():
    from handel_tpu.sim.monitor import Stats

    s = Stats()
    s.declare("sigen_wall")
    s.declare("verifier_liveLanes", gauge=True)
    assert s.is_gauge("verifier_liveLanes")
    assert not s.is_gauge("sigen_wall")
    assert s.is_gauge("anything_dedupHitRate")  # suffix fallback intact
    assert s.gauge_keys() == {"verifier_liveLanes"}
    # declared keys still pin the NaN schema
    assert "verifier_liveLanes_avg" in s.columns()


# -- device telemetry ---------------------------------------------------------


def test_device_telemetry_values_shape():
    """The collector reports every key with jax absent-or-present and never
    imports jax itself (a scrape must not initialize a backend)."""
    from handel_tpu.parallel.telemetry import DeviceTelemetry

    tel = DeviceTelemetry(service=None)
    vals = tel.values()
    for key in (
        "xlaCompileCt", "liveArrays", "liveArrayBytes", "memBytesInUse",
        "dispatchQueueDepth", "inflightLaunches", "breakerState",
    ):
        assert key in vals
    assert tel.gauge_keys() <= set(vals)
    assert not is_gauge_key("xlaCompileCt", tel.gauge_keys())
    assert is_gauge_key("dispatchQueueDepth", tel.gauge_keys())


# -- watch dashboard ----------------------------------------------------------


def test_watch_aggregate_and_render():
    from handel_tpu.sim import watch_cli

    class Node:
        def __init__(self, levels, sent):
            self._levels = levels
            self._sent = sent

        def values(self):
            return {
                "levelsCompletedCt": float(self._levels),
                "bestCardinality": 6.0,
                "msgSentCt": float(self._sent),
            }

        def gauge_keys(self):
            return {"bestCardinality"}

        def histograms(self):
            h = LogHistogram()
            h.add(0.01)
            h.add(0.04)
            return {"levelCompleteS": h}

    reg = MetricsRegistry()
    for i, lv in enumerate((3, 3, 2, 1)):
        n = Node(lv, 10 * (i + 1))
        reg.register_values("sigs", n, labels={"node": str(i)})
        reg.register_histograms("sigs", n, labels={"node": str(i)})
    fams = parse_exposition(reg.exposition())
    model = watch_cli.aggregate([fams])
    assert model["nodes"] == 4
    assert model["levels"] == {"0": 3.0, "1": 3.0, "2": 2.0, "3": 1.0}
    assert model["wave_p50"] is not None
    frame = watch_cli.render(model, ["127.0.0.1:1"], up=1, tick=3)
    assert "aggregation wave (4 nodes reporting)" in frame
    assert "level  1 complete" in frame
    assert "4/4" in frame  # every node finished level 1
    assert "2/4" in frame  # two nodes reached level 3


def test_watch_discovers_endpoints(tmp_path):
    from handel_tpu.sim import watch_cli

    (tmp_path / "metrics_ports.json").write_text(
        json.dumps({"run": 0, "addresses": {"0": "127.0.0.1:9100",
                                            "1": "127.0.0.1:9101"}})
    )
    (tmp_path / "metrics_5.addr").write_text("127.0.0.1:9102\n")
    eps = watch_cli.discover_endpoints(str(tmp_path))
    assert eps == ["127.0.0.1:9100", "127.0.0.1:9101", "127.0.0.1:9102"]


# -- localhost platform end to end --------------------------------------------


def test_sim_metrics_end_to_end(tmp_path):
    """A 2-process localhost run with `metrics = true` serves /metrics and
    /readyz on every node process (distinct allocated ports, plan written
    to the run dir), and the endpoints are gone after the run."""
    from handel_tpu.sim.config import RunConfig, SimConfig, dump_config
    from handel_tpu.sim.platform import run_simulation
    from handel_tpu.sim import watch_cli

    cfg = SimConfig(
        network="udp",
        scheme="fake",
        metrics=True,
        metrics_linger_s=3.0,
        max_timeout_s=30.0,
        runs=[RunConfig(nodes=8, threshold=8, processes=2)],
    )
    workdir = str(tmp_path / "run")

    async def run_and_scrape():
        task = asyncio.create_task(run_simulation(cfg, workdir))
        plan_path = os.path.join(workdir, "metrics_ports.json")
        deadline = time.monotonic() + 25
        scraped = {}
        ready_codes = {}
        while time.monotonic() < deadline and not task.done():
            eps = watch_cli.discover_endpoints(workdir)
            if len(eps) >= 2:
                for addr in eps:
                    got = await asyncio.to_thread(watch_cli.scrape, addr)
                    if got is None:
                        continue
                    # the server comes up before the node registers its
                    # reporters — keep re-scraping until this endpoint is
                    # warm, or the first boot-window scrape freezes a
                    # 3-family snapshot the assertions below reject
                    fams = {n for n in got[0] if n.startswith("handel_")}
                    if len(fams) < 20:
                        continue
                    scraped[addr] = got
                    code, _ = await asyncio.to_thread(
                        _get, addr, "/readyz"
                    )
                    ready_codes[addr] = code
                if len(scraped) >= 2:
                    break
            await asyncio.sleep(0.2)
        results = await task
        assert os.path.exists(plan_path)
        return results, scraped, ready_codes

    results, scraped, ready_codes = asyncio.run(run_and_scrape())
    assert len(results) == 1 and results[0].ok, results[0].outputs
    assert len(scraped) == 2, "both node processes must serve /metrics"
    assert set(ready_codes.values()) == {200}
    for fams, _text in scraped.values():
        handel_fams = {n for n in fams if n.startswith("handel_")}
        assert len(handel_fams) >= 20
        assert any(n.startswith("handel_sigs_") for n in handel_fams)
        assert any(n.startswith("handel_net_") for n in handel_fams)
        assert any(n.startswith("handel_penalty_") for n in handel_fams)
    # distinct ports per process
    with open(os.path.join(workdir, "metrics_ports.json")) as f:
        plan = json.load(f)
    addrs = list(plan["addresses"].values())
    assert len(addrs) == len(set(addrs)) == 2
    # endpoints die with the run
    for addr in addrs:
        with pytest.raises((urllib.error.URLError, OSError)):
            urllib.request.urlopen(f"http://{addr}/healthz", timeout=0.5)
