"""Byzantine roles, peer penalties, and the bounded verification queue.

Units for the hardening layers ISSUE 3 added around the adversaries:
role assignment determinism (sim/adversary.py), decaying penalty scores with
demote/ban semantics (core/penalty.py), packet-validation hardening
(core/handel.py), and the drop-oldest pending-queue bound
(core/processing.py).
"""

import asyncio
import random

import pytest

from handel_tpu.core.bitset import BitSet
from handel_tpu.core.crypto import MultiSignature
from handel_tpu.core.identity import ArrayRegistry, Identity
from handel_tpu.core.net import Packet
from handel_tpu.core.partitioner import BinomialPartitioner, IncomingSig
from handel_tpu.core.penalty import PeerScorer
from handel_tpu.core.processing import BatchProcessing
from handel_tpu.models.fake import (
    FakeConstructor,
    FakePublic,
    FakeSecret,
    FakeSignature,
)
from handel_tpu.sim.adversary import (
    adversary_roles,
    check_threshold_reachable,
    forged_signature,
)


def run(coro):
    return asyncio.run(coro)


# -- role assignment ---------------------------------------------------------


def test_adversary_roles_deterministic_and_skips_offline():
    counts = {"invalid_signer": 2, "flooder": 1}
    a = adversary_roles(counts, 16, offline={15, 13})
    b = adversary_roles(counts, 16, offline={15, 13})
    assert a == b  # every process derives the same mapping
    assert a == {14: "invalid_signer", 12: "invalid_signer", 11: "flooder"}


def test_adversary_roles_overflow_raises():
    with pytest.raises(ValueError):
        adversary_roles({"invalid_signer": 4}, 4, offline={0, 3})


def test_threshold_reachability_check():
    roles = adversary_roles({"invalid_signer": 3}, 8)
    with pytest.raises(ValueError):
        check_threshold_reachable(6, 8, 0, roles)  # only 5 honest sigs exist
    check_threshold_reachable(5, 8, 0, roles)
    # stale replayers still contribute valid signatures
    roles2 = adversary_roles({"stale_replayer": 3}, 8)
    check_threshold_reachable(8, 8, 0, roles2)


def test_forged_signature_fails_verification():
    # fake scheme: message-independent, so the forgery is the explicit
    # invalid construction
    fake = forged_signature(FakeSecret(1), b"msg")
    assert not FakePublic(True).verify(b"msg", fake)
    # bn254: a wrong-message signature over a real key
    from handel_tpu.models.bn254 import BN254Scheme

    scheme = BN254Scheme()
    sk, pk = scheme.keygen(1)
    forged = forged_signature(sk, b"msg")
    assert not pk.verify(b"msg", forged)
    assert pk.verify(b"msg", sk.sign(b"msg"))


# -- penalty scoring ---------------------------------------------------------


def test_scorer_demotes_then_bans():
    t = [0.0]
    s = PeerScorer(
        demote_threshold=2.0, ban_threshold=4.0, half_life_s=10.0,
        clock=lambda: t[0],
    )
    assert not s.demoted(3) and not s.banned(3)
    s.report(3)
    s.report(3)
    assert s.demoted(3) and not s.banned(3)
    s.report(3)
    s.report(3)
    assert s.banned(3)
    assert not s.demoted(3)  # banned dominates demoted
    assert s.values()["peersBanned"] == 1.0


def test_scorer_decay_forgives():
    t = [0.0]
    s = PeerScorer(
        demote_threshold=2.0, ban_threshold=50.0, half_life_s=1.0,
        clock=lambda: t[0],
    )
    s.report(1)
    s.report(1)
    assert s.demoted(1)
    t[0] = 10.0  # ten half-lives: score ~2/1024
    assert not s.demoted(1)
    assert s.score(1) < 0.01


def test_scorer_ban_set_is_bounded():
    s = PeerScorer(ban_threshold=1.0, demote_threshold=0.5, ban_capacity=2)
    for peer in range(5):
        s.report(peer, weight=2.0)
    assert s.values()["peersBanned"] == 2.0
    assert s.values()["peerBanRefused"] > 0


def test_level_selection_skips_banned_and_halves_demoted():
    from handel_tpu.core.handel import Level

    idents = [Identity(i, f"x-{i}", None) for i in range(4)]
    scorer = PeerScorer(demote_threshold=2.0, ban_threshold=10.0)
    lvl = Level(1, idents, 4, scorer)
    scorer.report(2, weight=3.0)  # demoted
    picked = [p.id for p in lvl.select_next_peers(8)]
    assert 2 not in picked  # first encounter skipped (window refills past it)
    assert lvl.demote_skips == 1
    picked_next = [p.id for p in lvl.select_next_peers(8)]
    assert 2 in picked_next  # every OTHER encounter goes through

    banned = PeerScorer(demote_threshold=5.0, ban_threshold=5.0)
    lvl2 = Level(1, idents, 4, banned)
    banned.report(1, weight=6.0)
    picked2 = [p.id for p in lvl2.select_next_peers(8)]
    assert 1 not in picked2
    assert lvl2.banned_skips > 0
    # all-banned level degrades to empty selection, not a spin
    for i in range(4):
        banned.report(i, weight=6.0)
    assert lvl2.select_next_peers(4) == []


# -- packet validation hardening ---------------------------------------------


def _one_node_cluster(n=8):
    from handel_tpu.core.test_harness import LocalCluster

    return LocalCluster(n, seed=3)


def test_validate_rejects_own_origin_before_parsing():
    cluster = _one_node_cluster()
    h = cluster.handels[0]
    bs = BitSet(len(h.levels[1].nodes))
    bs.set(0)
    good = MultiSignature(bs, FakeSignature()).marshal()
    h.new_packet(Packet(origin=0, level=1, multisig=good))  # self-origin
    assert h.invalid_packet_ct == 1
    assert len(h.proc.pending()) == 0


def test_banned_origin_dropped_and_counted():
    cluster = _one_node_cluster()
    h = cluster.handels[0]
    for _ in range(20):  # drive origin 1 over the ban threshold
        h.scorer.report(1)
    assert h.scorer.banned(1)
    bs = BitSet(len(h.levels[1].nodes))
    bs.set(0)
    good = MultiSignature(bs, FakeSignature()).marshal()
    h.new_packet(Packet(origin=1, level=1, multisig=good))
    assert h.banned_packet_ct == 1
    assert len(h.proc.pending()) == 0


def test_parse_failures_attributed_to_origin():
    cluster = _one_node_cluster()
    h = cluster.handels[0]
    before = h.scorer.score(2)
    h.new_packet(Packet(origin=2, level=1, multisig=b"\xff"))  # unparseable
    assert h.invalid_packet_ct == 1
    assert h.scorer.score(2) > before


def test_invalid_signer_gets_banned_end_to_end():
    """A node fed a stream of garbage aggregates from one origin penalizes
    it into the ban set; subsequent packets die at validation."""

    async def go():
        cluster = _one_node_cluster()
        h = cluster.handels[0]
        h.proc.start()
        bs = BitSet(len(h.levels[1].nodes))
        bs.set(0)
        rng = random.Random(9)
        sent = 0
        for _ in range(100):
            if h.scorer.banned(1):
                break
            # content-distinct invalid multisigs (random sig bytes)
            wire = bs.marshal() + rng.randbytes(8)
            h.new_packet(Packet(origin=1, level=1, multisig=wire))
            sent += 1
            await asyncio.sleep(0.01)
        assert h.scorer.banned(1), "origin 1 never banned"
        before = h.banned_packet_ct
        h.new_packet(Packet(origin=1, level=1, multisig=bs.marshal() + b"\x00" * 8))
        assert h.banned_packet_ct == before + 1
        h.proc.stop()

    run(go())


# -- bounded pending queue ---------------------------------------------------


def _make_proc(**kwargs):
    reg = ArrayRegistry(
        [Identity(i, f"x-{i}", FakePublic(True)) for i in range(8)]
    )
    part = BinomialPartitioner(0, reg)
    verified = []

    async def never(msg, pubkeys, requests):  # pipeline never runs in these
        return [True] * len(requests)

    proc = BatchProcessing(
        part,
        FakeConstructor(),
        b"m",
        [None] * 8,
        type("E", (), {"evaluate": staticmethod(lambda sp: 1)})(),
        verified.append,
        verifier=never,
        **kwargs,
    )
    return proc, verified


def _sig(origin, marker=0):
    bs = BitSet(1)
    bs.set(0)
    return IncomingSig(
        origin=origin, level=1, ms=MultiSignature(bs, FakeSignature())
    )


def test_pending_queue_drop_oldest():
    proc, _ = _make_proc(max_pending=4)
    sigs = [_sig(origin=i % 7 + 1) for i in range(6)]
    for sp in sigs:
        proc.add(sp)
    assert proc.sig_dropped_overflow == 2
    assert proc.pending() == sigs[2:]  # oldest two evicted
    # the heap's dead entries are skipped, not selected
    batch = proc._select_batch()
    assert batch == sigs[2:]
    assert proc.pending() == []


def test_pending_queue_bound_in_fifo_pipeline():
    from handel_tpu.core.processing import FifoProcessing

    reg = ArrayRegistry(
        [Identity(i, f"x-{i}", FakePublic(True)) for i in range(8)]
    )
    part = BinomialPartitioner(0, reg)
    proc = FifoProcessing(
        part,
        FakeConstructor(),
        b"m",
        [None] * 8,
        type("E", (), {"evaluate": staticmethod(lambda sp: 1)})(),
        lambda sp: None,
        max_pending=3,
    )
    sigs = [_sig(origin=i + 1) for i in range(5)]
    for sp in sigs:
        proc.add(sp)
    assert proc.sig_dropped_overflow == 2
    assert proc.pending() == sigs[2:]


def test_overflow_counter_reported():
    proc, _ = _make_proc(max_pending=1)
    proc.add(_sig(1))
    proc.add(_sig(2))
    assert proc.values()["sigDroppedOverflow"] == 1.0


# -- RLC batch-check culprit attribution -------------------------------------


def test_rlc_bisection_isolates_culprits_and_matches_per_candidate_penalties():
    """A forged aggregate inside an RLC combined launch (models/rlc.py via
    service/driver.py HostDevice) is isolated by bisection to exactly the
    per-candidate culprit set, so PeerScorer penalties attributed off the
    verdicts are bit-for-bit identical to per_candidate mode."""
    from handel_tpu.models.bn254 import BN254Scheme
    from handel_tpu.service.driver import HostDevice

    scheme = BN254Scheme()
    keys = [scheme.keygen(i) for i in range(8)]
    pubs = [pk for _, pk in keys]

    def agg(msg, idxs, forge=False):
        bs = BitSet(8)
        sig = None
        for i in idxs:
            bs.set(i)
            s = forged_signature(keys[i][0], msg) if forge else keys[i][0].sign(msg)
            sig = s if sig is None else sig.combine(s)
        return (msg, pubs, bs, sig)

    # six candidates over two messages; 1 and 4 are forged aggregates
    items = [
        agg(b"m1", [0, 1]),
        agg(b"m1", [2, 3], forge=True),
        agg(b"m1", [4, 5, 6]),
        agg(b"m2", [1, 2]),
        agg(b"m2", [3, 7], forge=True),
        agg(b"m2", [5]),
    ]
    origins = [3, 4, 5, 6, 7, 2]  # packet origin of each candidate

    pc = HostDevice(scheme.constructor)
    v_pc = pc.fetch(pc.dispatch_multi(items))
    assert v_pc == [True, False, True, True, False, True]

    dev = HostDevice(
        scheme.constructor, batch_check="rlc", rlc_rng=random.Random(7)
    )
    v_rlc = dev.fetch(dev.dispatch_multi(items))
    assert v_rlc == v_pc  # bisection reached the exact culprit set
    st = dev.rlc_stats
    assert st.rlc_launches == 1
    assert st.bisection_ct > 0 and st.bisection_depth_max >= 1

    # attribute each failed verdict to its packet origin, as
    # Handel._on_verify_failed does — identical verdicts give identical
    # scorer state in both modes
    def attribute(verdicts):
        scorer = PeerScorer(clock=lambda: 0.0)
        for origin, ok in zip(origins, verdicts):
            if not ok:
                scorer.report(origin)
        return scorer

    a, b = attribute(v_rlc), attribute(v_pc)
    assert a.reports == b.reports == 2
    for origin in origins:
        assert a.score(origin) == b.score(origin), origin
    assert a.score(4) > 0 and a.score(7) > 0


@pytest.mark.parametrize("c,m", [(8, 1), (8, 4)])
def test_rlc_combined_check_costs_m_plus_one_miller_lanes(c, m):
    """The RLC pairing-work contract (models/rlc.py): one combined check of
    C candidates over M distinct messages issues M + 1 Miller lanes and one
    final exponentiation — per candidate it would be 2C and C — whether it
    accepts or, with one forged aggregate among them, rejects."""
    from handel_tpu.models import rlc
    from handel_tpu.models.bn254 import BN254Scheme

    scheme = BN254Scheme()
    ops = rlc.host_ops_for(scheme.constructor)
    keys = [scheme.keygen(i) for i in range(c)]
    msgs = [b"rlc-count-%d" % (j % m) for j in range(c)]
    cands = [
        (msg, pk.point, sk.sign(msg).point)
        for msg, (sk, pk) in zip(msgs, keys)
    ]
    for forged, want in ((False, True), (True, False)):
        if forged:
            sk, pk = keys[c - 1]
            cands[c - 1] = (
                msgs[c - 1], pk.point, forged_signature(sk, msgs[c - 1]).point
            )
        st = rlc.RlcStats()
        assert rlc.host_rlc_check(ops, cands, random.Random(c + m), st) is want
        assert (st.miller_lanes, st.final_exp_lanes) == (m + 1, 1)
