"""Multi-tenant aggregation service tests (handel_tpu/service/).

Coverage per ISSUE 7's satellite list: session lifecycle transitions
(spawn/threshold/expire), eviction under the live-session cap,
deficit-round-robin starvation resistance (hot tenant + 15 cold tenants
all make progress), per-tenant dedup isolation (the same aggregate in two
sessions is NOT cross-deduped), per-launch fill-ratio accounting, the
session-labeled metrics plane, and the 2-process multi-session e2e through
the `sim serve` driver.
"""

from __future__ import annotations

import asyncio
import threading
import time

import pytest

from handel_tpu.core.bitset import BitSet
from handel_tpu.core.metrics import MetricsRegistry, parse_exposition
from handel_tpu.core.penalty import SessionScorers
from handel_tpu.core.store import VerifiedAggCache
from handel_tpu.parallel.batch_verifier import BatchVerifierService
from handel_tpu.service import (
    STATE_DONE,
    STATE_EXPIRED,
    STATE_RUNNING,
    AdmissionRefused,
    SessionManager,
    TenantQueue,
)
from handel_tpu.service.driver import (
    HostDevice,
    MultiSessionCluster,
    merge_summaries,
    run_service,
)
from handel_tpu.sim.config import (
    ServiceParams,
    SimConfig,
    dump_config,
    load_config,
)


def run(coro):
    return asyncio.run(coro)


class _Sig:
    """Marshal-able stand-in signature with identity-distinct bytes."""

    def __init__(self, tag: int = 0):
        self.tag = tag

    def marshal(self) -> bytes:
        return self.tag.to_bytes(4, "big")


def _req(tag: int, n: int = 16):
    bs = BitSet(n)
    bs.set(tag % n, True)
    return (bs, _Sig(tag))


class StubDevice:
    """Single-message device (no dispatch_multi): per-msg launch groups."""

    batch_size = 16

    def __init__(self, gate: threading.Event | None = None):
        self.dispatched = 0
        self.lanes: list[int] = []
        self.gate = gate

    def dispatch(self, msg, reqs):
        if self.gate is not None:
            self.gate.wait(5.0)
        self.dispatched += 1
        self.lanes.append(len(reqs))
        return len(reqs)

    def fetch(self, handle):
        return [True] * handle


class MultiStubDevice:
    """dispatch_multi-capable stub: whole mixed batches as one launch."""

    def __init__(self, batch_size: int = 16, launch_s: float = 0.0):
        self.batch_size = batch_size
        self.launch_s = launch_s
        self.dispatched = 0
        self.lanes: list[int] = []

    def dispatch_multi(self, items):
        if self.launch_s:
            time.sleep(self.launch_s)
        self.dispatched += 1
        self.lanes.append(len(items))
        return [True] * len(items)

    def fetch(self, handle):
        return handle


# -- queue wait measured where it happens (ISSUE 26) -------------------------


def test_queue_wait_counts_launched_candidates_only():
    """`queueWaitMs`/`queueWaitCandidates` cover exactly the candidates a
    lane's dispatcher took: dedup hits and coalesced duplicates never enter
    the queue and are not counted."""
    dev = MultiStubDevice(batch_size=4, launch_s=0.02)

    async def go():
        svc = BatchVerifierService(dev, max_delay_ms=1.0)
        try:
            reqs = [_req(t) for t in range(6)]
            # the same six twice at once (the second six coalesce onto the
            # first's lanes), then again afterwards (verdict-cache hits)
            both = await asyncio.gather(
                svc.verify(b"m", None, reqs, session="s"),
                svc.verify(b"m", None, reqs, session="s"),
            )
            again = await svc.verify(b"m", None, reqs, session="s")
            assert both == [[True] * 6] * 2 and again == [True] * 6
            return svc.values()
        finally:
            svc.stop()

    v = run(go())
    assert v["queueWaitCandidates"] == sum(dev.lanes) == 6
    assert v["verifierCandidates"] == 6 and v["dedupHits"] == 12
    # two launches of 4 and 2: the second waited out the first's 20 ms
    assert v["queueWaitMs"] >= 2 * 20.0 * 0.5
    assert v["queueWaitMs"] / v["queueWaitCandidates"] < 1000.0


# -- TenantQueue: deficit round robin ----------------------------------------


def test_drr_single_tenant_fifo():
    q = TenantQueue(quantum=4)
    for i in range(10):
        assert q.push("a", i)
    assert q.take(6) == [0, 1, 2, 3, 4, 5]
    assert q.take(10) == [6, 7, 8, 9]
    assert len(q) == 0


def test_drr_fair_share_across_tenants():
    q = TenantQueue(quantum=2)
    for i in range(6):
        q.push("a", f"a{i}")
        q.push("b", f"b{i}")
    got = q.take(8)
    # quantum-2 alternation: neither tenant gets more than quantum ahead
    assert got == ["a0", "a1", "b0", "b1", "a2", "a3", "b2", "b3"]


def test_drr_hot_tenant_cannot_starve_cold():
    """Hot session + 15 cold sessions: every cold tenant's work drains
    within two 64-lane takes while the hot backlog waits its turns."""
    q = TenantQueue(quantum=8)
    for i in range(2000):
        q.push("hot", ("hot", i))
    for c in range(15):
        for i in range(8):
            q.push(f"cold{c}", (f"cold{c}", i))
    first = q.take(64)
    second = q.take(64)
    served = first + second
    cold_served = [it for it in served if it[0] != "hot"]
    assert len(cold_served) == 15 * 8, "a cold tenant was starved"
    hot_served = [it for it in served if it[0] == "hot"]
    # the hot tenant still progresses (no lockout), just fairly
    assert 0 < len(hot_served) <= 2 * 8
    assert q.depth("hot") == 2000 - len(hot_served)


def test_drr_deficit_continues_across_takes():
    """A lane budget exhausted mid-quantum must not reset whose turn it
    is: the head tenant finishes its quantum on the next take."""
    q = TenantQueue(quantum=4)
    for i in range(8):
        q.push("a", f"a{i}")
        q.push("b", f"b{i}")
    assert q.take(2) == ["a0", "a1"]
    # a's quantum (4) is half spent; it continues before b starts
    assert q.take(4) == ["a2", "a3", "b0", "b1"]


def test_tenant_bound_refuses_push():
    q = TenantQueue(quantum=4, max_pending=3)
    assert all(q.push("a", i) for i in range(3))
    assert not q.push("a", 99)
    assert q.refused == 1
    assert q.push("b", 0)  # other tenants unaffected


def test_drop_tenant_returns_items():
    q = TenantQueue()
    q.push("a", 1)
    q.push("b", 2)
    assert q.drop_tenant("a") == [1]
    assert q.depths() == {"b": 1}
    assert q.take(4) == [2]


# -- service: per-tenant dedup isolation + fill accounting -------------------


def test_per_tenant_dedup_isolation():
    """The same aggregate content in two sessions is TWO verifications;
    within one session the second copy is a cache hit."""

    async def go():
        svc = BatchVerifierService(StubDevice(), max_delay_ms=0.1)
        await svc.verify(b"m", [], [_req(1)], session="A")
        await svc.verify(b"m", [], [_req(1)], session="B")  # not cross-dedup
        await svc.verify(b"m", [], [_req(1)], session="A")  # intra-session hit
        svc.stop()
        return svc

    svc = run(go())
    assert svc.device.dispatched == 2
    assert svc.cache.hits == 1
    assert svc.tenant_dedup_hits == {"A": 1}


def test_forget_session_drops_state_and_fails_queued():
    async def go():
        gate = threading.Event()
        gate.set()
        svc = BatchVerifierService(StubDevice(gate=gate), max_delay_ms=0.1)
        await svc.verify(b"m", [], [_req(1)], session="A")  # cached verdict
        # gate the device so the NEXT batch parks the collector in its
        # dispatch executor, leaving later requests in the tenant queue
        gate.clear()
        blocker = asyncio.ensure_future(
            svc.verify(b"mC", [], [_req(9)], session="C")
        )
        await asyncio.sleep(0.05)
        t_a = asyncio.ensure_future(
            svc.verify(b"m", [], [_req(2)], session="A")
        )
        t_b = asyncio.ensure_future(
            svc.verify(b"m", [], [_req(3)], session="B")
        )
        await asyncio.sleep(0.02)
        assert any(k[0] == "A" for k in svc.cache._map)
        dropped = svc.forget_session("A")
        cache_clean = not any(k[0] == "A" for k in svc.cache._map)
        gate.set()
        with pytest.raises(RuntimeError, match="evicted"):
            await asyncio.wait_for(t_a, 2.0)
        assert await asyncio.wait_for(t_b, 2.0) == [True]
        assert await asyncio.wait_for(blocker, 2.0) == [True]
        svc.stop()
        return svc, dropped, cache_clean

    svc, dropped, cache_clean = run(go())
    assert dropped == 1
    assert cache_clean, "A's cached verdicts survived the evict"
    assert "A" not in svc.tenant_candidates


def test_launch_fill_ratio_coalesced():
    """4 sessions' 4 candidates each fill one 16-lane launch end to end."""

    async def go():
        svc = BatchVerifierService(MultiStubDevice(16), max_delay_ms=5.0)
        results = await asyncio.gather(
            *(
                svc.verify(
                    f"m{s}".encode(),
                    [],
                    [_req(s * 10 + i) for i in range(4)],
                    session=f"s{s}",
                )
                for s in range(4)
            )
        )
        svc.stop()
        return svc, results

    svc, results = run(go())
    assert all(r == [True] * 4 for r in results)
    assert svc.device.dispatched == 1
    assert svc.fill_launches == 1
    assert svc.values()["launchFillRatio"] == 1.0
    assert svc.values()["lastLaunchFill"] == 1.0
    assert svc.coalesced_launches == 1


def test_single_msg_device_groups_by_msg():
    """Without dispatch_multi, distinct messages still split (pre-service
    behavior), and each split launch records its own fill."""

    async def go():
        svc = BatchVerifierService(StubDevice(), max_delay_ms=5.0)
        await asyncio.gather(
            svc.verify(b"m1", [], [_req(1)], session="A"),
            svc.verify(b"m2", [], [_req(2)], session="B"),
        )
        svc.stop()
        return svc

    svc = run(go())
    assert svc.device.dispatched == 2
    assert svc.fill_launches == 2
    assert svc.coalesced_launches == 0
    assert svc.values()["launchFillRatio"] == pytest.approx(1 / 16)


def test_admission_bound_fails_future_immediately():
    async def go():
        svc = BatchVerifierService(
            MultiStubDevice(4, launch_s=0.05),
            max_delay_ms=0.1,
            max_pending_per_session=2,
        )
        reqs = [_req(i) for i in range(8)]
        with pytest.raises(RuntimeError, match="queue full"):
            await svc.verify(b"m", [], reqs, session="hot")
        vals = svc.values()
        svc.stop()
        return vals

    vals = run(go())
    assert vals["admissionRefused"] >= 1


# -- service: hot tenant vs cold tenants under load --------------------------


def test_service_hot_session_no_starvation():
    """500 hot candidates + 15 cold sessions x 4: every cold session
    resolves while most of the hot backlog is still queued."""

    async def go():
        svc = BatchVerifierService(
            MultiStubDevice(64, launch_s=0.002),
            max_delay_ms=0.5,
            quantum=8,
        )
        hot = [
            asyncio.ensure_future(
                svc.verify(b"hot", [], [_req(i, 1024)], session="hot")
            )
            for i in range(500)
        ]
        await asyncio.sleep(0)  # hot backlog enqueues first
        cold = [
            asyncio.ensure_future(
                svc.verify(
                    f"c{c}".encode(),
                    [],
                    [_req(c * 100 + i, 1024) for i in range(4)],
                    session=f"cold{c}",
                )
            )
            for c in range(15)
        ]
        await asyncio.wait_for(asyncio.gather(*cold), 10.0)
        hot_unresolved = sum(1 for f in hot if not f.done())
        await asyncio.wait_for(asyncio.gather(*hot), 20.0)
        svc.stop()
        return hot_unresolved

    hot_unresolved = run(go())
    # all cold done while the hot tenant still holds most of its backlog
    assert hot_unresolved > 250, (
        f"cold tenants waited for the hot backlog ({hot_unresolved} left)"
    )


# -- dedup cache scope drops --------------------------------------------------


def test_cache_drop_scope_plain_and_tuple():
    c = VerifiedAggCache()
    ms_key_a = ("A", b"m", b"w", b"s")
    ms_key_b = ("B", b"m", b"w", b"s")
    node_key = (("A", 3), b"w", b"s")
    plain_key = (3, b"w", b"s")
    for k in (ms_key_a, ms_key_b, node_key, plain_key):
        c.put(k, True)
    assert c.drop_scope("A") == 2
    assert ms_key_b in c._map and plain_key in c._map
    assert ms_key_a not in c._map and node_key not in c._map


# -- per-session penalty keying ----------------------------------------------


def test_session_scorers_isolated_and_dropped():
    scorers = SessionScorers()
    a = scorers.for_session("A")
    b = scorers.for_session("B")
    assert a is not b
    assert scorers.for_session("A") is a
    for _ in range(10):
        a.report(7)
    assert a.banned(7) and not b.banned(7)
    assert scorers.labeled_values()["A"]["peersBanned"] == 1.0
    assert scorers.drop("A")
    assert scorers.for_session("A") is not a  # fresh trust domain


def test_session_scorers_bounded():
    scorers = SessionScorers(capacity=2)
    s1 = scorers.for_session("s1")
    scorers.for_session("s2")
    scorers.for_session("s3")  # evicts s1 (LRU)
    assert len(scorers) == 2
    assert scorers.evicted == 1
    assert scorers.for_session("s1") is not s1


# -- session lifecycle --------------------------------------------------------


def test_session_lifecycle_spawn_running_threshold():
    async def go():
        svc = BatchVerifierService(MultiStubDevice(32), max_delay_ms=0.2)
        mgr = SessionManager(service=svc, max_sessions=4)
        s = mgr.spawn(8)
        assert s.state == "spawned"
        mgr.start(s.sid)
        assert s.state == STATE_RUNNING
        await mgr.wait_all(20.0)
        svc.stop()
        return mgr, s

    mgr, s = run(go())
    assert s.state == STATE_DONE
    assert s.completion_s() is not None and s.completion_s() > 0
    assert mgr.completed_ct == 1
    assert mgr.values()["sessionCompletionP50S"] > 0
    # tenant state released at completion
    assert s.sid not in mgr.service.tenant_candidates


def test_session_expires_at_ttl():
    async def go():
        mgr = SessionManager(max_sessions=2, session_ttl_s=0.3)
        # threshold 8 over a committee with one offline node: unreachable
        s = mgr.spawn(8, threshold=8, offline=(3,))
        mgr.start(s.sid)
        await mgr.wait_all(10.0)
        return mgr, s

    mgr, s = run(go())
    assert s.state == STATE_EXPIRED
    assert mgr.expired_ct == 1 and mgr.completed_ct == 0


def test_admission_cap_refuses_then_evicts_finished():
    async def go():
        mgr = SessionManager(max_sessions=2)
        s1 = mgr.spawn(4)
        mgr.spawn(4)
        # both live: a third spawn is refused outright
        with pytest.raises(AdmissionRefused):
            mgr.spawn(4)
        assert mgr.refused_ct == 1
        # finish s1: still HELD (results retained) — the next spawn at the
        # cap reclaims exactly that slot by evicting the finished session
        mgr.start(s1.sid)
        await mgr.wait_all(10.0)
        assert s1.state == STATE_DONE
        assert s1.sid in mgr.sessions
        s3 = mgr.spawn(4)
        assert s1.sid not in mgr.sessions
        assert s3.sid in mgr.sessions
        # both held sessions live again: refuse
        with pytest.raises(AdmissionRefused):
            mgr.spawn(4)
        return mgr, s1

    mgr, s1 = run(go())
    assert (s1.sid, STATE_DONE, s1.completion_s()) in list(mgr.retired)


def test_evict_vs_threshold_same_tick_settles_once():
    """The evict-vs-threshold race: evicting a session in the same event-loop
    tick its threshold future resolves must settle the session exactly once
    — never both completed AND evicted — with no late `_finish` after the
    eviction, and must still `forget_session` the tenant's shared-plane
    state. Deterministic via a hand-held completion future: the watcher is
    parked on it, then resolution and eviction happen with no await between
    them."""

    async def go():
        svc = BatchVerifierService(MultiStubDevice(32), max_delay_ms=0.2)
        forgotten: list[str] = []
        orig_forget = svc.forget_session
        svc.forget_session = lambda sid: (forgotten.append(sid),
                                          orig_forget(sid))[1]
        mgr = SessionManager(service=svc, max_sessions=4)

        # interleaving A: future resolves, evict lands BEFORE the watcher
        # gets to run — the session must settle as evicted, not completed
        s = mgr.spawn(4)
        gate = asyncio.get_running_loop().create_future()
        s.cluster.wait_complete_success = lambda ttl: gate
        mgr.start(s.sid)
        await asyncio.sleep(0)  # watcher parks on the gate
        gate.set_result({})  # threshold reached...
        assert mgr.evict(s.sid)  # ...and evicted, same tick, no await between
        await asyncio.sleep(0.01)  # any stray watcher wakeup fires here

        # interleaving B: the watcher settles DONE first, the evict of the
        # still-held finished session lands in the same tick — terminal
        # state must stick and the second tenant release must be idempotent
        s2 = mgr.spawn(4)
        gate2 = asyncio.get_running_loop().create_future()
        s2.cluster.wait_complete_success = lambda ttl: gate2
        mgr.start(s2.sid)
        await asyncio.sleep(0)
        gate2.set_result({})
        await asyncio.sleep(0)  # watcher runs _finish(DONE)
        assert s2.state == STATE_DONE
        assert mgr.evict(s2.sid)  # held-but-finished: bookkeeping only
        await asyncio.sleep(0.01)
        svc.stop()
        return mgr, s, s2, forgotten

    mgr, s, s2, forgotten = run(go())
    assert s.state == "evicted"
    assert s2.state == STATE_DONE  # eviction never rewrites a terminal state
    # each session settled exactly once: A evicted, B completed
    assert mgr.evicted_ct == 1 and mgr.completed_ct == 1
    assert mgr.expired_ct == 0
    assert s.sid not in mgr.sessions and s2.sid not in mgr.sessions
    # tenant state released for both (idempotent on B's double release)
    assert forgotten.count(s.sid) == 1
    assert forgotten.count(s2.sid) >= 1
    assert s.sid not in mgr.tiers and s2.sid not in mgr.tiers
    states = {sid: state for sid, state, _ in mgr.retired}
    assert states[s.sid] == "evicted" and states[s2.sid] == STATE_DONE


def test_evict_running_session():
    async def go():
        svc = BatchVerifierService(MultiStubDevice(32), max_delay_ms=0.2)
        mgr = SessionManager(service=svc, max_sessions=4)
        s = mgr.spawn(16)
        mgr.start(s.sid)
        await asyncio.sleep(0.01)
        assert mgr.evict(s.sid)
        svc.stop()
        return mgr, s

    mgr, s = run(go())
    assert s.state == "evicted"
    assert mgr.evicted_ct == 1
    assert s.sid not in mgr.sessions


# -- session-labeled metrics plane -------------------------------------------


def test_labeled_metrics_carry_session_dimension():
    async def go():
        cluster = MultiSessionCluster(
            2, 8, batch_size=32, metrics_port=0
        )
        summary = await cluster.run(30.0)
        text = cluster.metrics.exposition()
        cluster.stop()
        return summary, text

    summary, text = run(go())
    assert summary["completed"] == 2
    fams = parse_exposition(text)
    pending = fams.get("handel_service_pending")
    assert pending is not None and pending["type"] == "gauge"
    sids = {lb.get("session") for lb, _ in pending["samples"]}
    assert len(sids) == 2
    assert fams["handel_service_sessions_completed"]["samples"][0][1] == 2.0
    # every completed session reports the terminal state + its completion
    # latency on the labeled plane
    states = [v for _, v in fams["handel_service_state"]["samples"]]
    assert states == [2.0, 2.0]  # threshold-reached
    assert all(
        v > 0 for _, v in fams["handel_service_completion_s"]["samples"]
    )
    fill = fams["handel_device_verifier_launch_fill_ratio"]
    assert fill["type"] == "gauge"


def test_registry_labeled_values_collector_unit():
    class R:
        def labeled_values(self):
            return {"a": {"depth": 3.0, "hits": 1.0}}

        def gauge_keys(self):
            return {"depth"}

    reg = MetricsRegistry()
    reg.register_labeled_values("svc", R(), label="session")
    fams = parse_exposition(reg.exposition())
    assert fams["handel_svc_depth"]["type"] == "gauge"
    assert fams["handel_svc_hits"]["type"] == "counter"
    labels, v = fams["handel_svc_depth"]["samples"][0]
    assert labels["session"] == "a" and v == 3.0


# -- drivers ------------------------------------------------------------------


def test_multi_session_cluster_all_reach_threshold():
    async def go():
        cluster = MultiSessionCluster(4, 8, batch_size=32)
        try:
            return await cluster.run(30.0), cluster
        finally:
            cluster.stop()

    (summary, cluster) = run(go())
    assert summary["completed"] == 4 and summary["expired"] == 0
    assert summary["aggregates_per_s"] > 0
    assert summary["coalesced_launches"] > 0
    assert 0 < summary["launch_fill_ratio"] <= 1.0
    # per-session dedup never crossed tenants: every session completed with
    # its OWN message, so any cross-dedup would have corrupted verdicts
    assert cluster.service.values()["dedupHitRate"] >= 0


def test_host_device_verdicts_honest():
    """HostDevice must verify, not rubber-stamp: an invalid fake sig in
    one lane fails only that lane."""
    from handel_tpu.core.test_harness import FakeScheme
    from handel_tpu.models.fake import FakePublic, FakeSignature

    scheme = FakeScheme()
    dev = HostDevice(scheme.constructor, batch_size=8)
    pks = [FakePublic(True) for _ in range(4)]
    good, bad = BitSet(4), BitSet(4)
    good.set(0, True)
    bad.set(1, True)
    verdicts = dev.fetch(
        dev.dispatch_multi(
            [
                (b"m1", pks, good, FakeSignature(True)),
                (b"m2", pks, bad, FakeSignature(False)),
            ]
        )
    )
    assert verdicts == [True, False]


def test_serve_driver_two_processes(tmp_path):
    """2-process multi-session e2e: the `sim serve` fleet path."""
    cfg = SimConfig(
        scheme="fake",
        service=ServiceParams(
            sessions=4, nodes=8, processes=2, session_ttl_s=30.0,
            batch_size=32,
        ),
        max_timeout_s=60.0,
    )
    summary = run(run_service(cfg, str(tmp_path)))
    assert summary["ok"]
    assert summary["workers"] == 2
    assert summary["completed"] == 4
    assert (tmp_path / "service_summary.json").exists()


def test_merge_summaries_weighting():
    a = {
        "sessions": 2, "nodes_per_session": 8, "completed": 2, "expired": 0,
        "wall_s": 1.0, "aggregates_per_s": 2.0, "session_p50_s": 0.2,
        "session_p99_s": 0.5, "verifier_launches": 10,
        "verifier_candidates": 100, "coalesced_launches": 5,
        "launch_fill_ratio": 0.5, "dedup_hit_rate": 0.5,
        "admission_refused": 0,
    }
    b = dict(a, wall_s=2.0, session_p99_s=0.9, verifier_launches=30,
             launch_fill_ratio=0.9, verifier_candidates=300,
             dedup_hit_rate=0.7)
    m = merge_summaries([a, b])
    assert m["sessions"] == 4 and m["completed"] == 4
    assert m["wall_s"] == 2.0
    assert m["session_p99_s"] == 0.9  # worst worker
    assert m["launch_fill_ratio"] == pytest.approx(0.8)  # launch-weighted
    assert m["aggregates_per_s"] == pytest.approx(4.0)


def test_service_toml_round_trip(tmp_path):
    cfg = SimConfig(
        scheme="fake",
        service=ServiceParams(
            sessions=64, nodes=128, processes=4, max_sessions=64,
            session_ttl_s=300.0, quantum=16, max_pending_per_session=2048,
            batch_size=128, spawn_stagger_ms=5.0, period_ms=20.0,
        ),
    )
    p = tmp_path / "serve.toml"
    p.write_text(dump_config(cfg))
    got = load_config(str(p)).service
    assert got == cfg.service
    # default config: service mode off
    q = tmp_path / "plain.toml"
    q.write_text(dump_config(SimConfig()))
    assert not load_config(str(q)).service.enabled()


def test_soak_toml_round_trip(tmp_path):
    from handel_tpu.sim.config import SoakParams

    cfg = SimConfig(
        soak=SoakParams(
            duration_s=12.0, nodes=8, concurrency=4, devices=3,
            max_lanes=6, queue_capacity=512, tiers="gold,bronze",
            swap_at_frac=0.3, lane_loss_at_frac=0.7,
        ),
    )
    p = tmp_path / "soak.toml"
    p.write_text(dump_config(cfg))
    assert load_config(str(p)).soak == cfg.soak
    # a default config dumps no [soak] table and loads back to defaults
    q = tmp_path / "plain.toml"
    q.write_text(dump_config(SimConfig()))
    assert "[soak]" not in q.read_text()
    assert load_config(str(q)).soak == SoakParams()


# -- sim watch session rows ---------------------------------------------------


def test_watch_renders_session_rows():
    from handel_tpu.sim.watch_cli import aggregate, render

    text = "\n".join(
        [
            "# TYPE handel_service_state gauge",
            'handel_service_state{session="s1"} 1',
            'handel_service_state{session="s2"} 2',
            "# TYPE handel_service_pending gauge",
            'handel_service_pending{session="s1"} 40',
            'handel_service_pending{session="s2"} 0',
            "# TYPE handel_service_nodes_done gauge",
            'handel_service_nodes_done{session="s1"} 3',
            'handel_service_nodes_done{session="s2"} 8',
            "# TYPE handel_service_nodes gauge",
            'handel_service_nodes{session="s1"} 8',
            'handel_service_nodes{session="s2"} 8',
            "# TYPE handel_service_sessions_live gauge",
            "handel_service_sessions_live 1",
            "# TYPE handel_service_sessions_completed counter",
            "handel_service_sessions_completed 1",
        ]
    )
    model = aggregate([parse_exposition(text)])
    assert model["sessions"]["s1"]["pending"] == 40.0
    frame = render(model, ["x"], 1, 1)
    assert "sessions" in frame
    assert "running" in frame and "done" in frame
    # top-K orders by pending: the hot session leads
    assert frame.index("s1") < frame.index("s2")


def test_watch_renders_lifecycle_row():
    from handel_tpu.sim.watch_cli import aggregate, render

    text = "\n".join(
        [
            "# TYPE handel_device_verifier_epoch gauge",
            "handel_device_verifier_epoch 2",
            "# TYPE handel_device_verifier_quiesce_ct counter",
            "handel_device_verifier_quiesce_ct 2",
            "# TYPE handel_device_verifier_last_quiesce_stall_ms gauge",
            "handel_device_verifier_last_quiesce_stall_ms 65.2",
            "# TYPE handel_device_verifier_admission_shed counter",
            "handel_device_verifier_admission_shed 12",
            "# TYPE handel_device_verifier_shed_rate gauge",
            "handel_device_verifier_shed_rate 0.03",
            "# TYPE handel_device_verifier_lanes_added counter",
            "handel_device_verifier_lanes_added 3",
            "# TYPE handel_device_verifier_lanes_removed counter",
            "handel_device_verifier_lanes_removed 1",
        ]
    )
    model = aggregate([parse_exposition(text)])
    assert model["epoch"] == 2.0 and model["shed_rate"] == 0.03
    frame = render(model, ["x"], 1, 1)
    assert "lifecycle epoch 2" in frame
    assert "65.2ms" in frame and "lanes +3/-1" in frame
    # no lifecycle plane scraped -> the row stays absent entirely
    bare = aggregate([parse_exposition("")])
    assert "lifecycle" not in render(bare, ["x"], 1, 1)
