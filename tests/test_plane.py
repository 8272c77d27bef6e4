"""Fleet-of-chips verify plane (parallel/plane.py + the per-lane service
pipeline): scheduling, degradation, per-device metrics rows, and the
`devices` config knob. Host-math engines only — no jax, no kernels."""

import asyncio

import pytest

from handel_tpu.core.bitset import BitSet
from handel_tpu.models.fake import FakePublic, FakeSignature
from handel_tpu.parallel.batch_verifier import BatchVerifierService
from handel_tpu.parallel.plane import DeviceLane, DevicePlane, host_plane
from handel_tpu.utils.breaker import CircuitBreaker


class _Engine:
    batch_size = 4

    def __init__(self):
        self.dispatched = 0

    def dispatch_multi(self, items):
        self.dispatched += 1
        return [True] * len(items)

    def fetch(self, handle):
        return handle


def _plane(k, breakers=None):
    return DevicePlane([_Engine() for _ in range(k)], breakers=breakers)


def _req(tag: int, n: int = 16):
    bs = BitSet(n)
    bs.set(tag % n, True)
    return (bs, FakeSignature(True))


PKS = [FakePublic(True) for _ in range(16)]


def test_pick_prefers_idle_lane():
    plane = _plane(3)
    # lane 0 busy dispatching, lane 1 has one launch awaiting fetch
    plane.lanes[0].dispatching = ["x"]
    plane.lanes[1].fetching = ["y"]
    lane = plane.pick()
    assert lane is plane.lanes[2]  # the only zero-load lane
    assert plane.idle_violations == 0


def test_pick_least_loaded_then_lowest_index():
    plane = _plane(3)
    plane.lanes[0].fetching = ["a"]
    plane.lanes[1].fetching = ["b"]
    # all free for dispatch, loads 1/1/1 after giving lane 2 one too
    plane.lanes[2].fetching = ["c"]
    assert plane.pick() is plane.lanes[0]  # tie -> lowest index


def test_pick_skips_breaker_open_lane():
    breakers = [CircuitBreaker(cooldown_s=600.0) for _ in range(2)]
    plane = _plane(2, breakers=breakers)
    for _ in range(breakers[0].threshold):
        breakers[0].record_failure()
    assert plane.pick() is plane.lanes[1]
    assert plane.values()["devicesAvailable"] == 1.0
    assert len(plane.allowed()) == 1


def test_pick_none_when_all_occupied():
    plane = _plane(2)
    for lane in plane.lanes:
        lane.dispatching = ["x"]
    assert plane.pick() is None


def test_host_cost_sums_over_engines():
    plane = _plane(2)
    for i, lane in enumerate(plane.lanes):
        lane.engine.host_pack_ms = 2.0 + i
        lane.engine.host_pack_launches = 1 + i
        lane.engine.host_dispatch_ms = 10.0
        lane.engine.host_dispatch_launches = 2
    hc = plane.host_cost()
    assert hc["pack_ms"] == 5.0
    assert hc["pack_launches"] == 3.0
    assert hc["dispatch_ms"] == 20.0
    assert hc["dispatch_launches"] == 4.0


def test_labeled_values_one_row_per_device():
    plane = _plane(3)
    plane.lanes[1].launches = 4
    plane.lanes[1].fill_sum = 3.0
    rows = plane.labeled_values()
    assert set(rows) == {"0", "1", "2"}
    assert rows["1"]["launches"] == 4.0
    assert rows["1"]["fillRatio"] == 0.75
    assert plane.labeled_gauge_keys() <= set(rows["0"])


def test_plane_requires_engines_and_matched_breakers():
    with pytest.raises(ValueError, match="at least one"):
        DevicePlane([])
    with pytest.raises(ValueError, match="1:1"):
        DevicePlane([_Engine()], breakers=[])


def test_lane_values_shape():
    lane = DeviceLane(0, _Engine())
    vals = lane.values()
    assert vals["breakerState"] == 0.0
    assert vals["load"] == 0.0


@pytest.mark.parametrize("k", [1, 4, 8])
def test_service_fleet_uses_every_lane(k):
    """A flood of distinct aggregates over a k-lane plane must reach every
    lane (least-loaded spreads; no lane starves) and keep the scheduler
    audit clean: no pick left an idle lane while another queued."""
    plane = _plane(k)

    async def go():
        svc = BatchVerifierService(plane, max_delay_ms=0.1)
        try:
            out = await asyncio.gather(
                *(
                    svc.verify(
                        i.to_bytes(2, "big"), PKS, [_req(i)], session="s"
                    )
                    for i in range(16 * k)
                )
            )
            return out, svc.values()
        finally:
            svc.stop()

    out, vals = asyncio.run(go())
    assert all(v == [True] for v in out)
    assert all(lane.engine.dispatched >= 1 for lane in plane.lanes)
    assert vals["devicesTotal"] == float(k)
    assert plane.idle_violations == 0
    assert vals["schedIdleViolations"] == 0.0
    assert sum(lane.launches for lane in plane.lanes) == vals[
        "verifierLaunches"
    ]


def test_service_fleet_degrades_to_healthy_lanes():
    """Breaker-open on one lane: the run completes on the others and the
    tripped lane never dispatches."""
    breakers = [CircuitBreaker(cooldown_s=600.0) for _ in range(3)]
    plane = _plane(3, breakers=breakers)
    for _ in range(breakers[1].threshold):
        breakers[1].record_failure()

    async def go():
        svc = BatchVerifierService(plane, max_delay_ms=0.1)
        try:
            out = await asyncio.gather(
                *(
                    svc.verify(
                        i.to_bytes(2, "big"), PKS, [_req(i)], session="s"
                    )
                    for i in range(24)
                )
            )
            return out, svc.values()
        finally:
            svc.stop()

    out, vals = asyncio.run(go())
    assert all(v == [True] for v in out)
    assert plane.lanes[1].engine.dispatched == 0
    assert plane.lanes[0].engine.dispatched >= 1
    assert plane.lanes[2].engine.dispatched >= 1
    assert vals["devicesAvailable"] == 2.0
    assert vals["failoverBatches"] == 0.0


def test_single_device_wrap_keeps_identities():
    """A bare engine (no plane) wraps into a plane of 1 and the legacy
    `service.device` / `service.breaker` surfaces stay the lane's."""
    eng = _Engine()
    br = CircuitBreaker()
    svc = BatchVerifierService(eng, breaker=br)
    assert len(svc.plane) == 1
    assert svc.device is eng
    assert svc.breaker is br
    assert svc.plane.lanes[0].breaker is br


def test_host_plane_builds_k_host_devices():
    from handel_tpu.core.test_harness import FakeScheme

    plane = host_plane(FakeScheme().constructor, 3, batch_size=8)
    assert len(plane) == 3
    assert plane.batch_size == 8


def test_devices_knob_roundtrip(tmp_path):
    """[service] devices flows through load_config and dump_config."""
    from handel_tpu.sim.config import dump_config, load_config

    p = tmp_path / "sim.toml"
    p.write_text(
        "[sim]\nnodes = 8\n\n[service]\nsessions = 2\ndevices = 4\n"
    )
    cfg = load_config(str(p))
    assert cfg.service.devices == 4
    dumped = dump_config(cfg)
    assert "devices = 4" in dumped
    # default stays 1 when the key is absent
    p.write_text("[sim]\nnodes = 8\n")
    assert load_config(str(p)).service.devices == 1


def test_watch_aggregates_device_rows():
    """sim watch: `device`-labeled families aggregate into per-device rows
    and render as a devices block."""
    from handel_tpu.sim.watch_cli import aggregate, parse_exposition, render

    text = (
        'handel_device_verifier_launches{device="0"} 5\n'
        'handel_device_verifier_launches{device="1"} 7\n'
        'handel_device_verifier_fill_ratio{device="1"} 0.5\n'
        'handel_device_verifier_inflight{device="1"} 2\n'
        'handel_device_verifier_breaker_state{device="0"} 1\n'
    )
    model = aggregate([parse_exposition(text)])
    assert model["devices"]["1"]["launches"] == 7.0
    assert model["devices"]["1"]["fill"] == 0.5
    assert model["devices"]["0"]["breaker"] == 1.0
    out = render(model, ["x"], 1, 1)
    assert "dev   1" in out
    assert "breaker open" in out
