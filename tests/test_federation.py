"""Geo-federation + open-loop load tests (ISSUE 18).

The failure lattice for service/federation.py and sim/load.py: RTT
lookup against the planet presets, deterministic nearest-first routing,
capped-exponential retry backoff, spill-over with one region down,
bounded attributed shed with every region refusing, recovery
re-admission through the probe map and the epoch path, `[load]` /
`[federation]` TOML round-trips, the `sim watch` federation row, the
seeded arrival models, and a short end-to-end LoadRun with the kill
drill — plus a regression for the shared report-check specs
(sim/report_checks.py) the soak and federation reports both stamp.
"""

from __future__ import annotations

import asyncio

import pytest

from handel_tpu.core.metrics import MetricsRegistry, parse_exposition
from handel_tpu.network.geo import GeoConfig
from handel_tpu.scenario.planets import planet_preset
from handel_tpu.service.federation import Federation, RegionShedding
from handel_tpu.sim.config import (
    FederationParams,
    LoadParams,
    SimConfig,
    dump_config,
    load_config,
)


def run(coro):
    return asyncio.run(coro)


def _fast_params(**kw) -> FederationParams:
    """CI-speed federation: tiny RTTs, tiny retry waits, small registry."""
    base = dict(
        planet="planet-3region-fast",
        retry_base_ms=5.0,
        retry_cap_ms=20.0,
        probe_interval_s=0.05,
        session_ttl_s=10.0,
        registry=16,
        trace_capacity=1 << 12,
    )
    base.update(kw)
    return FederationParams(**base)


# -- satellite 1: the public RTT lookup --------------------------------------


def test_geo_rtt_lookup_matches_presets():
    for planet in ("planet-3region", "planet-5region"):
        regions, rtt = planet_preset(planet)
        geo = GeoConfig(regions=regions, rtt_ms=rtt).validate()
        for i, a in enumerate(regions):
            for j, b in enumerate(regions):
                # by name, by index, and mixed all read the same cell
                assert geo.rtt(a, b) == rtt[i][j]
                assert geo.rtt(i, j) == rtt[i][j]
                assert geo.rtt(a, j) == rtt[i][j]
                # the presets are symmetric matrices
                assert geo.rtt(a, b) == geo.rtt(b, a)


def test_geo_rtt_lookup_validation():
    regions, rtt = planet_preset("planet-3region")
    geo = GeoConfig(regions=regions, rtt_ms=rtt).validate()
    with pytest.raises(ValueError, match="unknown region"):
        geo.rtt("atlantis", "eu-west")
    with pytest.raises(ValueError, match="out of range"):
        geo.rtt(0, 7)
    with pytest.raises(ValueError, match="out of range"):
        geo.rtt(-1, 0)


# -- routing + backoff --------------------------------------------------------


def test_route_order_nearest_first_and_deterministic():
    fed = Federation(_fast_params())
    fd = fed.front_door
    # planet-3region-fast RTTs: eu<->us 8ms, eu<->ap 22ms, us<->ap 17ms
    assert fd.route_order("eu-west") == ["eu-west", "us-east", "ap-east"]
    assert fd.route_order("us-east") == ["us-east", "eu-west", "ap-east"]
    assert fd.route_order("ap-east") == ["ap-east", "us-east", "eu-west"]
    # a second build from the same params routes identically
    fed2 = Federation(_fast_params())
    for origin in fed.region_names():
        assert (fed.front_door.route_order(origin)
                == fed2.front_door.route_order(origin))
    # marking a region down removes it; marking up restores the order
    fd.mark("us-east", False)
    assert fd.route_order("eu-west") == ["eu-west", "ap-east"]
    fd.mark("us-east", True)
    assert fd.route_order("eu-west") == ["eu-west", "us-east", "ap-east"]


def test_backoff_capped_exponential():
    fed = Federation(
        _fast_params(retry_base_ms=50.0, retry_cap_ms=400.0)
    )
    fd = fed.front_door
    assert [fd.backoff_ms(a) for a in range(6)] == [
        50.0, 100.0, 200.0, 400.0, 400.0, 400.0
    ]


# -- the failure lattice ------------------------------------------------------


def test_spillover_when_nearest_region_down():
    async def go():
        fed = Federation(_fast_params())
        fed.start()
        try:
            fed.kill_region("eu-west")
            outcome, s, plane, _ = await fed.submit(
                "eu-west", nodes=4, tier="gold", seed=1
            )
            assert outcome == "admitted"
            # spilled to the next region by RTT from eu-west
            assert plane.name == "us-east"
            assert fed.front_door.spillovers == 1
            assert plane.spill_in == 1
            # the misroute marked the dead region down passively —
            # no probe round needed
            assert fed.front_door.health["eu-west"] is False
            while not s.finished:
                await asyncio.sleep(0.01)
        finally:
            await fed.stop()

    run(go())


def test_all_regions_dead_fails_with_attribution():
    async def go():
        p = _fast_params(retry_budget=2)
        fed = Federation(p)
        fed.start()
        try:
            for name in fed.region_names():
                fed.kill_region(name)
            outcome, s, plane, attempts = await fed.submit(
                "us-east", nodes=4, tier="gold", seed=2
            )
            assert outcome == "failed" and s is None and plane is None
            assert attempts == p.retry_budget
            assert fed.front_door.failures == 1
            assert fed.front_door.retries == p.retry_budget
        finally:
            await fed.stop()

    run(go())


def test_all_regions_shedding_classified_as_shed(monkeypatch):
    async def go():
        p = _fast_params(retry_budget=2)
        fed = Federation(p)
        fed.start()
        try:
            monkeypatch.setattr(
                "handel_tpu.service.federation.RegionPlane.shedding",
                lambda self, tier: True,
            )
            outcome, s, _, attempts = await fed.submit(
                "ap-east", nodes=4, tier="bronze", seed=3
            )
            # every region at its shed bound through the whole retry
            # budget is a SHED, not a failure — bounded, attributed
            assert outcome == "shed" and s is None
            assert attempts == p.retry_budget
            assert fed.front_door.sheds == 1
            assert fed.front_door.failures == 0
        finally:
            await fed.stop()

    run(go())


def test_region_shed_bound_refuses_session(monkeypatch):
    fed = Federation(_fast_params())
    plane = fed.by_name["eu-west"]
    monkeypatch.setattr(
        type(plane.cluster.service.queue), "__len__", lambda self: 10**6
    )
    with pytest.raises(RegionShedding):
        plane.admit(nodes=4, tier="gold", seed=4)
    assert plane.sheds == 1


def test_kill_recover_readmission_via_epoch_path():
    async def go():
        fed = Federation(_fast_params())
        fed.start()
        try:
            fd = fed.front_door
            assert fed.epoch == 0
            fed.kill_region("ap-east")
            fd.probe_now()
            assert fd.health["ap-east"] is False
            assert "ap-east" not in fd.route_order("ap-east")
            assert fed.values()["regionsHealthy"] == 2.0

            stall = await fed.recover_region("ap-east")
            assert stall >= 0.0
            # the rejoin IS an epoch rotation: every healthy region
            # flipped together and the federation epoch advanced
            assert fed.epoch == 1
            for plane in fed.planes:
                assert plane.cluster.manager.epoch == 1
            fd.probe_now()
            assert fd.health["ap-east"] is True
            assert fd.route_order("ap-east")[0] == "ap-east"
            # and the revived region ADMITS again
            outcome, s, plane, _ = await fed.submit(
                "ap-east", nodes=4, tier="gold", seed=5
            )
            assert outcome == "admitted" and plane.name == "ap-east"
            while not s.finished:
                await asyncio.sleep(0.01)
        finally:
            await fed.stop()

    run(go())


def test_kill_returns_interrupted_live_sids():
    async def go():
        fed = Federation(_fast_params())
        fed.start()
        try:
            outcome, s, plane, _ = await fed.submit(
                "eu-west", nodes=64, tier="gold", seed=6
            )
            assert outcome == "admitted" and plane.name == "eu-west"
            live = fed.kill_region("eu-west")
            assert s.sid in live
            assert plane.stats()["regionHealthy"] == 0.0
            assert plane.stats()["sessionsLive"] == 0.0
        finally:
            await fed.stop()

    run(go())


# -- TOML round-trips ---------------------------------------------------------


def test_load_federation_toml_round_trip(tmp_path):
    cfg = SimConfig()
    cfg.load = LoadParams(
        rate_sps=7.5, duration_s=33.0, model="diurnal", seed=9,
        nodes=12, deadline_s=4.0, tiers="gold,silver",
        diurnal_amplitude=0.3, diurnal_period_s=20.0,
    )
    cfg.federation = FederationParams(
        planet="planet-3region-fast", devices=2, batch_size=16,
        queue_capacity=128, kill_region="us-east",
        kill_at_frac=0.25, recover_at_frac=0.5,
        retry_base_ms=10.0, retry_cap_ms=80.0, retry_budget=3,
    )
    path = tmp_path / "load.toml"
    path.write_text(dump_config(cfg))
    back = load_config(str(path))
    assert back.load == cfg.load
    assert back.federation == cfg.federation


def test_load_toml_validation(tmp_path):
    bad_model = tmp_path / "bad_model.toml"
    bad_model.write_text("[load]\nrate_sps = 1.0\nmodel = \"lunar\"\n")
    with pytest.raises(ValueError, match="load.model"):
        load_config(str(bad_model))
    bad_kill = tmp_path / "bad_kill.toml"
    bad_kill.write_text(
        "[federation]\nkill_region = \"us-east\"\n"
        "kill_at_frac = 0.8\nrecover_at_frac = 0.4\n"
    )
    with pytest.raises(ValueError, match="kill_at_frac"):
        load_config(str(bad_kill))
    bad_retry = tmp_path / "bad_retry.toml"
    bad_retry.write_text(
        "[federation]\nretry_base_ms = 100.0\nretry_cap_ms = 10.0\n"
    )
    with pytest.raises(ValueError, match="retry_cap_ms"):
        load_config(str(bad_retry))


# -- satellite 2: the `sim watch` federation row ------------------------------


def test_watch_federation_row():
    from handel_tpu.sim import watch_cli

    fed = Federation(_fast_params())
    fed.by_name["us-east"].killed = True
    reg = MetricsRegistry()
    reg.register_values("federation", fed)
    reg.register_labeled_values(
        "federation", fed, label="region",
        gauges=fed.labeled_gauge_keys(),
    )
    fams = parse_exposition(reg.exposition())
    model = watch_cli.aggregate([fams])
    assert model["fed_regions_total"] == 3.0
    assert model["fed_regions_healthy"] == 2.0
    assert set(model["regions"]) == {"eu-west", "us-east", "ap-east"}
    assert model["regions"]["us-east"]["healthy"] == 0.0
    frame = watch_cli.render(model, ["127.0.0.1:1"], up=1, tick=1)
    assert "federation  regions 2/3 healthy" in frame
    assert "us-east DOWN" in frame
    assert "eu-west up" in frame


# -- arrival models -----------------------------------------------------------


def test_arrival_offsets_seeded_and_in_window():
    from handel_tpu.sim.load import arrival_offsets

    p = LoadParams(rate_sps=20.0, duration_s=10.0, seed=3)
    a = arrival_offsets(p)
    assert a == arrival_offsets(p)  # same seed, same clock
    assert a != arrival_offsets(
        LoadParams(rate_sps=20.0, duration_s=10.0, seed=4)
    )
    assert all(0.0 <= t < p.duration_s for t in a)
    assert a == sorted(a)
    # LLN at 200 expected arrivals: within a loose band
    assert 120 < len(a) < 300


def test_rate_at_models():
    from handel_tpu.sim.load import peak_rate, rate_at

    diurnal = LoadParams(
        rate_sps=10.0, model="diurnal", diurnal_amplitude=0.5,
        diurnal_period_s=40.0,
    )
    assert rate_at(diurnal, 0.0) == pytest.approx(10.0)
    assert rate_at(diurnal, 10.0) == pytest.approx(15.0)  # sin peak
    assert rate_at(diurnal, 30.0) == pytest.approx(5.0)  # trough
    assert peak_rate(diurnal) == pytest.approx(15.0)

    burst = LoadParams(
        rate_sps=10.0, model="burst", burst_every_s=10.0,
        burst_x=4.0, burst_len_s=2.0,
    )
    assert rate_at(burst, 1.0) == pytest.approx(40.0)  # inside the window
    assert rate_at(burst, 5.0) == pytest.approx(10.0)  # between bursts
    assert rate_at(burst, 11.5) == pytest.approx(40.0)  # next window
    assert peak_rate(burst) == pytest.approx(40.0)


def test_burst_model_concentrates_arrivals():
    from handel_tpu.sim.load import arrival_offsets

    p = LoadParams(
        rate_sps=10.0, duration_s=40.0, model="burst", seed=11,
        burst_every_s=10.0, burst_x=6.0, burst_len_s=2.0,
    )
    a = arrival_offsets(p)
    in_burst = sum(1 for t in a if (t % 10.0) < 2.0)
    # burst windows are 20% of the wall but 6x the rate: they must carry
    # well over half the arrivals
    assert in_burst / len(a) > 0.5


# -- end-to-end: a short open-loop run with the kill drill --------------------


def test_load_run_e2e_with_kill_drill(tmp_path):
    from handel_tpu.sim.load import run_load

    lp = LoadParams(
        rate_sps=6.0, duration_s=6.0, nodes=4, seed=2, deadline_s=5.0
    )
    fp = _fast_params(
        kill_region="us-east", kill_at_frac=0.3, recover_at_frac=0.6,
        # the session spans of even a short run outnumber the smoke ring;
        # keep the early kill instants resident for the trace assertions
        trace_capacity=1 << 16,
    )
    report = run(run_load(lp, fp, str(tmp_path)))
    assert report["ok"], report["checks"]
    fed = report["federation"]
    assert fed["unaccounted"] == 0 and fed["unresolved"] == 0
    assert fed["arrivals"] == (
        fed["completed"] + fed["shed"] + fed["failed"] + fed["expired"]
    )
    kill = fed["kill"]
    assert kill["killed_at_s"] is not None
    assert kill["unhealthy_detected_s"] >= kill["killed_at_s"]
    assert kill["recovery_s"] is not None
    assert kill["post_recovery_completed"] > 0
    # the headline figures sit flat on the record
    for key in ("open_loop_p99_s", "region_recovery_s", "spillover_rate"):
        assert isinstance(report[key], (int, float))
    assert (tmp_path / "federation_report.json").exists()
    assert (tmp_path / "trace_federation.json").exists()
    # the trace carries region-tagged federation spans for
    # `sim trace --critical-path` attribution
    import json

    events = json.loads(
        (tmp_path / "trace_federation.json").read_text()
    )["traceEvents"]
    fed_events = {
        e["name"] for e in events if e.get("cat") == "federation"
    }
    assert "region_kill" in fed_events
    assert "region_recover" in fed_events
    assert "frontdoor_route" in fed_events


# -- the shared report-check specs (rode-along refactor) ----------------------


def test_report_checks_helper():
    from handel_tpu.sim.report_checks import (
        Check,
        assert_checks,
        attach,
        evaluate,
    )

    checks = [
        Check("has_x", lambda r: r.get("x", 0) > 0, lambda r: "x > 0"),
        Check("has_y", lambda r: "y" in r, lambda r: "y present"),
    ]
    good = attach({"x": 1, "y": 2}, checks)
    assert good["checks"] == {"has_x": True, "has_y": True}
    assert good["ok"] is True
    assert_checks(good, checks)

    bad = attach({"x": 0}, checks)
    assert bad["ok"] is False
    assert evaluate(bad, checks) == {"has_x": False, "has_y": False}
    with pytest.raises(AssertionError, match="has_x"):
        assert_checks(bad, checks)


def test_federation_checks_vacuous_without_kill():
    from handel_tpu.sim.report_checks import FEDERATION_CHECKS, evaluate

    report = {
        "shed_rate": 0.0,
        "federation": {
            "unaccounted": 0, "unresolved": 0, "spillovers": 0,
            "shed_ceiling": 0.15, "tiers": {"gold": {"met": 1.0}},
            "kill": None,
        },
    }
    got = evaluate(report, FEDERATION_CHECKS)
    # no kill drill configured: the kill-lattice checks pass vacuously,
    # the always-on invariants still bind
    assert all(got.values()), got
