"""Fault injection + resilience: ChaosNetwork, adversarial rounds, failover.

The tentpole integration contracts (ISSUE 3):
  * a 16-node round with 2 invalid-signer adversaries and 10% seeded packet
    loss completes to threshold (fake + bn254 schemes, CPU), and
  * a BN254-style device failure mid-run trips the verifier circuit breaker
    and fails over to the host reference verifier with the round still
    completing (breaker/failover counters > 0).

Unit layers: seeded determinism of the chaos fault pattern, per-fault
counters, TOML plumbing for the chaos section and the adversary matrix, and
the localhost-platform adversarial smoke run. The long adversarial sweep is
slow-tier.
"""

import asyncio
import csv
import random

import pytest

from handel_tpu.core.identity import Identity
from handel_tpu.core.net import Packet
from handel_tpu.network.chaos import ChaosConfig, ChaosNetwork


class RecordingNet:
    """Minimal inner transport: remembers every (address, packet) delivery."""

    def __init__(self):
        self.delivered = []
        self.listeners = []

    def send(self, identities, packet):
        for ident in identities:
            self.delivered.append((ident.address, packet))

    def register_listener(self, listener):
        self.listeners.append(listener)

    def values(self):
        return {"innerSent": float(len(self.delivered))}


def ident(i):
    return Identity(i, f"peer-{i}", None)


def packet(i=0, payload=b"\x00\x08\xaa" + b"\x01" * 8):
    return Packet(origin=i, level=1, multisig=payload)


def test_chaos_config_validates_rates():
    with pytest.raises(ValueError):
        ChaosConfig(drop_rate=1.5).validate()
    with pytest.raises(ValueError):
        ChaosConfig(corrupt_rate=-0.1).validate()
    ChaosConfig(drop_rate=1.0, reorder_rate=0.0).validate()
    assert not ChaosConfig().any()
    assert ChaosConfig(delay_rate=0.1).any()


def test_chaos_drop_is_seeded_and_per_link():
    """The same seed reproduces the same fault pattern; different seeds (or
    links) fault independently."""

    def pattern(seed):
        inner = RecordingNet()
        net = ChaosNetwork(inner, ChaosConfig(drop_rate=0.5, seed=seed))
        for k in range(64):
            net.send([ident(0), ident(1)], packet(k))
        return [addr for addr, _ in inner.delivered], net.dropped

    a, dropped_a = pattern(7)
    b, _ = pattern(7)
    c, _ = pattern(8)
    assert a == b  # deterministic
    assert a != c  # seed-dependent
    assert 0 < dropped_a < 128  # some but not all of 2*64 deliveries


def test_chaos_corruption_flips_payload_bytes():
    inner = RecordingNet()
    net = ChaosNetwork(inner, ChaosConfig(corrupt_rate=1.0, seed=3))
    original = packet()
    net.send([ident(0)], original)
    assert net.corrupted == 1
    (_, delivered), = inner.delivered
    assert delivered is not original  # corrupts a copy
    assert delivered.multisig != original.multisig
    assert len(delivered.multisig) == len(original.multisig)
    assert original.multisig == b"\x00\x08\xaa" + b"\x01" * 8  # untouched


def test_chaos_duplicate_and_counters():
    inner = RecordingNet()
    net = ChaosNetwork(inner, ChaosConfig(duplicate_rate=1.0, seed=1))
    net.send([ident(0)], packet())
    assert net.duplicated == 1
    assert len(inner.delivered) == 2
    vals = net.values()
    assert vals["chaosDuplicated"] == 1.0
    assert vals["innerSent"] == 2.0  # inner counters merged


def test_chaos_reorder_releases_after_next_send():
    async def go():
        inner = RecordingNet()
        net = ChaosNetwork(inner, ChaosConfig(reorder_rate=0.5, seed=0))
        first, second = packet(1, b"\x00\x08\xaa" + b"A" * 8), packet(
            2, b"\x00\x08\xaa" + b"B" * 8
        )
        for _ in range(32):  # enough traffic to trigger holds at rate 0.5
            net.send([ident(0)], first)
            net.send([ident(0)], second)
        # whatever the seeded pattern chose, every packet must eventually
        # arrive (flush timer covers a held packet with no successor)
        await asyncio.sleep(0.1)
        assert len(inner.delivered) == 64  # nothing lost to reordering
        assert net.reordered > 0

    asyncio.run(go())


def test_chaos_delay_defers_delivery():
    async def go():
        inner = RecordingNet()
        net = ChaosNetwork(
            inner, ChaosConfig(delay_rate=1.0, delay_ms=20.0, seed=2)
        )
        net.send([ident(0)], packet())
        assert inner.delivered == []  # not yet
        await asyncio.sleep(0.08)
        assert len(inner.delivered) == 1
        assert net.delayed == 1

    asyncio.run(go())


# -- the acceptance integration round ---------------------------------------


def _adversarial_round(scheme=None, n=16, threshold=9, timeout=30.0):
    from handel_tpu.core.test_harness import LocalCluster

    async def go():
        cluster = LocalCluster(
            n,
            scheme=scheme,
            threshold=threshold,
            adversaries={n - 1: "invalid_signer", n - 2: "invalid_signer"},
            chaos=ChaosConfig(drop_rate=0.10, seed=42),
        )
        cluster.start()
        try:
            res = await cluster.wait_complete_success(timeout=timeout)
        finally:
            cluster.stop()
        return cluster, res

    return asyncio.run(go())


def test_adversarial_round_fake_16_nodes():
    """16 honest-majority nodes + 2 invalid signers + 10% seeded loss reach
    threshold; adversary contributions never enter a final signature."""
    cluster, res = _adversarial_round()
    assert len(res) == 14
    for sig in res.values():
        assert sig.cardinality() >= 9
        assert not sig.bitset.get(15) and not sig.bitset.get(14)
    # at least one honest node caught and attributed a bad signature
    fails = sum(h.proc.sig_verify_failed for h in cluster.handels.values())
    reports = sum(
        h.scorer.reports for h in cluster.handels.values() if h.scorer
    )
    assert fails > 0 and reports > 0


def test_adversarial_round_bn254_real_crypto():
    """Same adversarial round over real BN254 host crypto (smaller committee
    to stay in the fast tier): forged signatures fail real pairing checks."""
    from handel_tpu.models.bn254 import BN254Scheme

    cluster, res = _adversarial_round(
        scheme=BN254Scheme(), n=8, threshold=5, timeout=60.0
    )
    assert len(res) == 6
    for sig in res.values():
        assert sig.cardinality() >= 5
        assert not sig.bitset.get(7) and not sig.bitset.get(6)
    fails = sum(h.proc.sig_verify_failed for h in cluster.handels.values())
    assert fails > 0


def test_device_failover_midrun():
    """A verifier device that dies mid-run trips the circuit breaker and
    fails over to the host reference verifier; the round still completes
    and the breaker/failover counters prove the path was taken."""
    from handel_tpu.core.config import Config
    from handel_tpu.core.test_harness import FakeScheme, LocalCluster
    from handel_tpu.parallel.batch_verifier import BatchVerifierService

    scheme = FakeScheme()
    pubs = {}

    class DyingDevice:
        """BN254Device-shaped stub: verifies host-side for `good` launches,
        then raises like a lost accelerator on every later dispatch."""

        batch_size = 8

        def __init__(self, good):
            self.good = good
            self.launches = 0

        def dispatch(self, msg, reqs):
            if self.launches >= self.good:
                raise RuntimeError("device lost: simulated XLA failure")
            self.launches += 1
            return scheme.constructor.batch_verify(msg, pubs["k"], reqs)

        def fetch(self, handle):
            return handle

    def host_fallback(msg, reqs):
        return scheme.constructor.batch_verify(msg, pubs["k"], reqs)

    async def go():
        service = BatchVerifierService(
            DyingDevice(good=2),
            fallback=host_fallback,
            backoff_base_s=0.005,
            backoff_cap_s=0.02,
        )

        def cfg_factory(i):
            c = Config()
            c.rand = random.Random(5 + i)
            c.verifier = service.verify
            return c

        cluster = LocalCluster(
            16, threshold=9, scheme=scheme, config_factory=cfg_factory
        )
        pubs["k"] = cluster.registry.public_keys()
        cluster.start()
        try:
            res = await cluster.wait_complete_success(timeout=30.0)
        finally:
            cluster.stop()
            service.stop()
        return service, res

    service, res = asyncio.run(go())
    assert len(res) == 16
    vals = service.values()
    assert vals["breakerOpenCt"] > 0
    assert vals["failoverBatches"] > 0 and vals["failoverCandidates"] > 0
    assert vals["verifierLaunches"] > 0  # the device did work before dying


def test_failover_without_fallback_still_fails_futures():
    """No fallback configured: a dead device fails the verify futures (the
    pre-breaker contract BatchProcessing's requeue depends on)."""
    from handel_tpu.core.bitset import BitSet
    from handel_tpu.parallel.batch_verifier import BatchVerifierService
    from handel_tpu.models.fake import FakeSignature

    class DeadDevice:
        batch_size = 4

        def dispatch(self, msg, reqs):
            raise RuntimeError("dead")

        def fetch(self, handle):
            raise AssertionError("unreachable")

    async def go():
        service = BatchVerifierService(
            DeadDevice(), backoff_base_s=0.001, backoff_cap_s=0.002
        )
        bs = BitSet(4)
        bs.set(1)
        with pytest.raises(RuntimeError):
            await service.verify(b"m", [], [(bs, FakeSignature())])
        service.stop()
        assert service.values()["breakerState"] in (0.5, 1.0)

    asyncio.run(go())


def test_constructor_level_host_failover():
    """The per-node default-verifier path (no shared service): a device that
    cannot even prepare — e.g. XLA compile failure — makes
    BN254JaxConstructor.batch_verify fall back to the inherited host-side
    serial verify with correct verdicts, and the breaker opens."""
    from handel_tpu.models.bn254 import BN254Scheme
    from handel_tpu.models.bn254_jax import BN254JaxConstructor

    class BrokenDeviceConstructor(BN254JaxConstructor):
        def _device_of(self, pubkeys):
            raise RuntimeError("XLA compile failed: simulated")

    host = BN254Scheme()
    keys = [host.keygen(i) for i in range(4)]
    pubkeys = [pk for _, pk in keys]
    cons = BrokenDeviceConstructor(batch_size=4, warmup=False)

    from handel_tpu.core.bitset import BitSet

    bs = BitSet(4)
    bs.set(0)
    bs.set(2)
    agg = keys[0][0].sign(b"m").combine(keys[2][0].sign(b"m"))
    forged = keys[1][0].sign(b"other")
    for _ in range(3):  # three batches: breaker threshold reached
        verdicts = cons.batch_verify(b"m", pubkeys, [(bs, agg), (bs, forged)])
        assert verdicts == [True, False]  # host fallback verdicts are real
    assert cons.failover_batches == 3
    assert cons.breaker.state in ("open", "half-open")
    # request bugs are NOT device failures: they propagate, untouched
    with pytest.raises(ValueError):
        BN254JaxConstructor(batch_size=4, warmup=False).batch_verify(
            b"m", pubkeys, [(BitSet(9), agg)]
        )


def test_breaker_recloses_after_probe_success():
    from handel_tpu.parallel.batch_verifier import CircuitBreaker

    t = [0.0]
    br = CircuitBreaker(threshold=2, cooldown_s=5.0, clock=lambda: t[0])
    assert br.allow() and br.state == "closed"
    br.record_failure()
    assert br.allow()  # one failure: still closed
    br.record_failure()
    assert br.state == "open" and not br.allow()
    t[0] = 6.0
    assert br.state == "half-open" and br.allow()  # cooldown elapsed: probe
    br.record_failure()  # probe failed: re-open, no new open_count
    assert br.state == "open" and br.open_count == 1
    t[0] = 12.0
    br.record_success()  # probe succeeded: fully closed
    assert br.state == "closed" and br.allow()


# -- sim plumbing ------------------------------------------------------------


def test_chaos_and_adversaries_toml_roundtrip(tmp_path):
    from handel_tpu.sim.config import (
        AdversaryParams,
        RunConfig,
        SimConfig,
        dump_config,
        load_config,
    )

    cfg = SimConfig(
        scheme="fake",
        chaos=ChaosConfig(drop_rate=0.1, corrupt_rate=0.05, seed=9),
        runs=[
            RunConfig(
                nodes=16,
                threshold=9,
                adversaries=AdversaryParams(
                    invalid_signer=2, flooder=1, flood_pps=50.0
                ),
            )
        ],
    )
    path = tmp_path / "sim.toml"
    path.write_text(dump_config(cfg))
    back = load_config(str(path))
    assert back.chaos == cfg.chaos
    assert back.runs[0].adversaries == cfg.runs[0].adversaries
    assert back.runs[0].adversaries.total() == 3


def test_localhost_platform_adversarial_chaos_run(tmp_path):
    """run_node_process builds the adversaries and wraps transports in
    ChaosNetwork from the TOML matrix: real processes, UDP, seeded loss,
    one invalid signer — the run completes and the chaos/byzantine counters
    ride the monitor CSV."""
    from handel_tpu.sim.config import AdversaryParams, RunConfig, SimConfig
    from handel_tpu.sim.platform import run_simulation

    # The chaos RNG of a link is seeded by (seed, source node, destination
    # ADDRESS), so with probed ports the fault pattern changed every run,
    # and a 5-of-8 round is only ~47 packets: at drop_rate 0.05 about one
    # run in eleven dropped nothing and failed the counter assertion below.
    # Fixed ports make the pattern a function of the seed, and seed 2199 is
    # one whose FIRST draw on three honest level-1 links (0->1, 2->3, 4->5
    # side) is a drop: every node's first packet goes to its level-1 peer,
    # so at least one packet is dropped however fast the round finishes.
    base = 13600  # beside test_sim's 13500 block, below the probed range
    cfg = SimConfig(
        network="udp",
        scheme="fake",
        base_port=base,
        max_timeout_s=120.0,  # generous: CI cores are shared and slow
        chaos=ChaosConfig(drop_rate=0.05, seed=2199),
        runs=[
            RunConfig(
                nodes=8,
                threshold=5,
                processes=2,
                adversaries=AdversaryParams(invalid_signer=1),
            )
        ],
    )
    first_draw_drops = sum(
        random.Random(
            f"{cfg.chaos.for_node(src).seed}|127.0.0.1:{base + (src ^ 1)}"
        ).random() < cfg.chaos.drop_rate
        for src in range(6)
    )
    assert first_draw_drops == 3  # the seed still means what it says above
    results = asyncio.run(run_simulation(cfg, str(tmp_path)))
    res = results[0]
    if not res.ok:
        for out, err in res.outputs:
            print(out.decode(errors="replace"))
            print(err.decode(errors="replace"))
    assert res.ok
    rows = list(csv.DictReader(open(res.csv_path)))
    assert float(rows[0]["adversaries"]) == 1.0
    assert float(rows[0]["net_chaosDropped_sum"]) > 0
    # somebody verified (and rejected) the forged contribution
    assert float(rows[0]["sigs_sigVerifyFailed_sum"]) > 0


@pytest.mark.slow
def test_real_bn254_device_failover_midrun():
    """The literal acceptance wiring: a REAL BN254Device (JAX kernels on
    CPU) whose dispatch is severed mid-run — the shared BatchVerifierService
    trips its breaker and completes the round through the host reference
    verifier."""
    from handel_tpu.core.config import Config
    from handel_tpu.core.crypto import Constructor, verify_multisignature
    from handel_tpu.core.test_harness import LocalCluster
    from handel_tpu.models.bn254_jax import BN254JaxScheme
    from handel_tpu.parallel.batch_verifier import BatchVerifierService

    scheme = BN254JaxScheme(batch_size=4)
    msg = b"hello world"

    async def go():
        # keygen is seeded per index, so these ARE the cluster's keys
        pubkeys = [scheme.keygen(i)[1] for i in range(8)]
        device = scheme.constructor.prepare(pubkeys)

        real_dispatch = device.dispatch
        seen = {"n": 0}

        def dying_dispatch(m, reqs):
            seen["n"] += 1
            if seen["n"] > 2:  # two good launches, then the device is gone
                raise RuntimeError("device lost: simulated mid-run failure")
            return real_dispatch(m, reqs)

        device.dispatch = dying_dispatch

        def host_fallback(m, reqs):
            return Constructor.batch_verify(scheme.constructor, m, pubkeys, reqs)

        service = BatchVerifierService(
            device,
            fallback=host_fallback,
            backoff_base_s=0.005,
            backoff_cap_s=0.02,
        )

        def cfg_factory(i):
            c = Config()
            c.rand = random.Random(31 + i)
            c.verifier = service.verify
            return c

        cluster = LocalCluster(
            8, scheme=scheme, msg=msg, config_factory=cfg_factory
        )
        cluster.start()
        try:
            res = await cluster.wait_complete_success(timeout=900.0)
        finally:
            cluster.stop()
            service.stop()
        return cluster, service, res

    cluster, service, results = asyncio.run(go())
    assert len(results) == 8
    for sig in results.values():
        assert verify_multisignature(
            msg, sig, cluster.registry, scheme.constructor
        )
    vals = service.values()
    assert vals["breakerOpenCt"] > 0
    assert vals["failoverCandidates"] > 0


@pytest.mark.slow
def test_adversarial_sweep_64_nodes(tmp_path):
    """The long adversarial sweep: 64 nodes, mixed roles (4 invalid signers,
    2 stale replayers, 1 flooder), loss + corruption + duplication — the
    protocol still reaches a 51% threshold on every honest node."""
    from handel_tpu.sim.config import (
        AdversaryParams,
        HandelParams,
        RunConfig,
        SimConfig,
    )
    from handel_tpu.sim.platform import run_simulation

    cfg = SimConfig(
        network="udp",
        scheme="fake",
        max_timeout_s=300.0,
        chaos=ChaosConfig(
            drop_rate=0.10,
            corrupt_rate=0.05,
            duplicate_rate=0.05,
            seed=1234,
        ),
        runs=[
            RunConfig(
                nodes=64,
                threshold=33,
                processes=4,
                adversaries=AdversaryParams(
                    invalid_signer=4,
                    stale_replayer=2,
                    flooder=1,
                    flood_pps=100.0,
                ),
                handel=HandelParams(period_ms=50.0, timeout_ms=100.0),
            )
        ],
    )
    results = asyncio.run(run_simulation(cfg, str(tmp_path)))
    res = results[0]
    assert res.ok, [e.decode(errors="replace")[-2000:] for _, e in res.outputs]
    rows = list(csv.DictReader(open(res.csv_path)))
    assert float(rows[0]["adversaries"]) == 7.0
    assert float(rows[0]["net_chaosCorrupted_sum"]) > 0
    assert float(rows[0]["sigs_peerPenaltyReports_sum"]) > 0
