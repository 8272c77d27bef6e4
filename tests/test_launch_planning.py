"""The collector plans a launch for ONE launch class (ISSUE 38).

The packer gives a launch the class of its largest hole count, so one wide
candidate among 128 makes every lane run the wide program. `verify` queues a
candidate without a class; before each plan the collector asks the engine,
in one call, which class everything new needs (`launch_class`) and moves it
under that class as the key of `TenantQueue` (`rekey`); `_take_launch` then
plans: the class the queue's turn ring names (the oldest candidate of the
session whose turn it is: among sessions without a backlog, the oldest
queued), its candidates in deficit-round-robin order, narrower ones as
riders in lanes that would stay empty, wider only where the launch could
not be filled otherwise.

No kernel compiles here: the engine is a stub with a three-class ladder
that reads a candidate's class off its bitset, and the queue is driven
alone.
"""

import asyncio
import threading

import pytest

from handel_tpu.core.bitset import BitSet
from handel_tpu.parallel.batch_verifier import _CLASS, BatchVerifierService
from handel_tpu.parallel.plane import DevicePlane
from handel_tpu.service.fairness import ANY, TenantQueue

LANES = 8
LADDER = (8, 64, 1024)  # as `BN254Device.patch_widths` at 4096 keys
N = 2048


class _Sig:
    def __init__(self, tag: int):
        self.tag = tag

    def marshal(self) -> bytes:
        return self.tag.to_bytes(4, "big")


def _cand(cls: int, tag: int):
    """A candidate the stub engine classes as `cls`: a range with as many
    holes as the class is wide (0 holes -> 8), tagged by its signature."""
    holes = {8: 0, 64: 9, 1024: 65, 0: 1025}[cls]
    bs = BitSet(N)
    bs.set_range(0, holes + 2)
    for i in range(1, holes + 1):
        bs.set(i, False)
    return (bs, _Sig(tag))


def _ladder_class(bitsets) -> list[int]:
    out = []
    for bs in bitsets:
        ids = bs.indices()
        holes = ids[-1] + 1 - ids[0] - len(ids)
        out.append(next((k for k in LADDER if holes <= k), 0))
    return out


class ClassedEngine:
    """`dispatch_multi` stub with the three-class ladder: it records, a
    launch, the (class, tag) of every lane, and can hold its launches at a
    gate so that a test fills the queue first."""

    batch_size = LANES
    launch_class = staticmethod(_ladder_class)

    def __init__(self, gate: threading.Event | None = None):
        self.gate = gate
        self.launches: list[list[tuple[int, int]]] = []

    def dispatch_multi(self, items):
        if self.gate is not None:
            self.gate.wait(5.0)
        classes = _ladder_class([it[2] for it in items])
        self.launches.append(
            [(c, it[3].tag) for c, it in zip(classes, items)])
        return [True] * len(items)

    def fetch(self, handle):
        return handle


class SingleMsgEngine:
    """The same ladder behind `dispatch` alone: one message a launch."""

    batch_size = LANES
    launch_class = staticmethod(_ladder_class)

    def __init__(self):
        self.launches: list[tuple[bytes, list[int]]] = []

    def dispatch(self, msg, reqs):
        self.launches.append((msg, [sig.tag for _, sig in reqs]))
        return [True] * len(reqs)

    def fetch(self, handle):
        return handle


def _service(engine=None, **kw) -> BatchVerifierService:
    return BatchVerifierService(engine or ClassedEngine(), **kw)


def _put(q: TenantQueue, tenant: str, item, key) -> None:
    """One item under `key`, as the service gets it there: pushed without
    a key, then moved by `rekey` (None: it stays where it was pushed)."""
    assert q.push(tenant, item)
    if key is not None:
        assert q.rekey(None, lambda items: [key] * len(items)) == 1


def _push(svc, cls: int, tag: int, tenant: str = "t") -> None:
    """Queue one candidate as `verify` and `_classify` do between them,
    without a waiter or a bitset."""
    _put(svc.queue, tenant, (tenant, b"m", None, None, tag, 0.0, cls, None),
         cls)


def _classes(batch) -> list[int]:
    return [it[_CLASS] for it in batch]


def _tags(batch) -> list[int]:
    return [it[4] for it in batch]


def test_stub_ladder_reads_holes():
    eng = ClassedEngine()
    assert eng.launch_class(
        [_cand(c, 0)[0] for c in (8, 64, 1024, 0)]) == [8, 64, 1024, 0]


# -- the plan, on the queue alone --------------------------------------------


@pytest.mark.parametrize("mix", [
    (8, 64, 1024), (1024, 8, 64), (64, 64, 8, 1024), (0, 8), (1024, 0, 64, 8),
])
def test_launches_are_class_pure_at_saturation(mix):
    """With at least a launch's worth queued in every class, every launch
    holds one class, whatever order the classes arrived in."""
    svc = _service()
    tag = 0
    for _ in range(3 * LANES):
        for cls in mix:
            _push(svc, cls, tag)
            tag += 1
    while len(svc.queue) >= LANES and all(
            n >= LANES for n in svc.queue.counts().values()):
        batch = svc._take_launch()
        assert len(batch) == LANES and len(set(_classes(batch))) == 1
    assert svc.class_widened_launches == 0


@pytest.mark.parametrize("tenants", [1, 4, 3 * LANES])
def test_oldest_candidates_class_is_served_next(tenants):
    """Among sessions that hold no more than a quantum each, the launch's
    class is the oldest queued candidate's: classes leave in the order
    their heads arrived, so a candidate waits for no more launches than
    were ahead of it in one FIFO. One session alone is such a session."""
    svc = _service()
    tag = 0
    for cls in (64, 1024, 8, 64, 8, 1024):  # six launches' worth, in blocks
        for _ in range(LANES):
            _push(svc, cls, tag, f"t{tag * tenants // (6 * LANES)}")
            tag += 1
    served = []
    while len(svc.queue):
        batch = svc._take_launch()
        served.append((_classes(batch)[0], min(_tags(batch))))
    # each launch starts at the oldest tag left: FIFO over the blocks
    assert served == [(64, 0), (1024, 8), (8, 16), (64, 24), (8, 32), (1024, 40)]


@pytest.mark.parametrize("wide, narrow", [(1024, 8), (64, 8), (0, 1024)])
def test_no_class_waits_behind_a_hot_one(wide, narrow):
    """A class that keeps more than a launch's worth queued cannot hold a
    launch of another class back: the other's candidates leave by the time
    the launches pushed before them have."""
    svc = _service()
    for tag in range(4 * LANES):
        _push(svc, wide, tag)
    for tag in range(100, 100 + LANES):
        _push(svc, narrow, tag)
    for tag in range(200, 200 + 4 * LANES):
        _push(svc, wide, tag)
    seen = []
    for _ in range(5):
        seen.append(_classes(svc._take_launch())[0])
    assert seen == [wide] * 4 + [narrow]


def test_riders_fill_left_over_lanes_and_are_counted():
    """The oldest's class cannot fill the launch, the narrower ones can:
    its candidates first, then the OLDEST narrower candidates as riders;
    nothing widens. The riders are counted when the launch is handed over
    (`classRiderCandidates`)."""
    eng = ClassedEngine(threading.Event())
    reqs = ([_cand(8, t) for t in range(6)]
            + [_cand(64, 10 + t) for t in range(3)]
            + [_cand(8, 20 + t) for t in range(7)]
            + [_cand(1024, 30 + t) for t in range(LANES)])

    async def go():
        svc = _service(eng, max_delay_ms=1.0)
        try:
            # one launch is held at the gate while the rest queue up
            first = asyncio.ensure_future(
                svc.verify(b"m", None, [_cand(64, 99)], session="s"))
            await asyncio.sleep(0.05)
            rest = asyncio.ensure_future(
                svc.verify(b"m", None, reqs, session="s"))
            await asyncio.sleep(0.05)
            eng.gate.set()
            assert await first == [True] and await rest == [True] * len(reqs)
            return svc.values()
        finally:
            svc.stop()

    v = asyncio.run(go())
    assert eng.launches == [
        [(64, 99)],
        # the oldest is class 8 and its 13 fill a launch: pure
        [(8, t) for t in (0, 1, 2, 3, 4, 5, 20, 21)],
        # the oldest is class 64: its three, then the five class-8 left
        # ride (at or below 64 there are exactly eight)
        [(64, 10), (64, 11), (64, 12)] + [(8, t) for t in range(22, 27)],
        [(1024, 30 + t) for t in range(LANES)],
    ]
    assert v["classRiderCandidates"] == 5.0
    assert v["classWidenedLaunches"] == 0.0


def test_riders_are_the_oldest_narrower_candidates():
    svc = _service()
    for tag, cls in enumerate([64] * 3 + [8] * 2 + [64] * 2 + [8] * 9):
        _push(svc, cls, tag)
    batch = svc._take_launch()
    # class 64 first (tags 0-2, 5, 6), then the three oldest of class 8
    assert _tags(batch) == [0, 1, 2, 5, 6, 3, 4, 7]
    assert _classes(batch) == [64] * 5 + [8] * 3
    assert svc.class_widened_launches == 0


def test_widening_takes_the_narrowest_class_that_fills():
    """Candidates at or below the oldest's class cannot fill the launch:
    it widens to the narrowest class at or below which they can, takes the
    oldest's class whole (so the oldest never waits), that class next, and
    is counted."""
    svc = _service()
    for tag, cls in enumerate([8] * 3 + [64] * 2 + [1024] * 20):
        _push(svc, cls, tag)
    batch = svc._take_launch()
    # ... and the two of class 64 wait for the next launch: they are the
    # oldest then, and a launch that pays for the wide program anyway is
    # filled with candidates nothing narrower can serve
    assert _tags(batch) == [0, 1, 2, 5, 6, 7, 8, 9]
    assert _tags(svc._take_launch()) == [3, 4, 10, 11, 12, 13, 14, 15]
    assert svc.class_widened_launches == 2
    # class 64 could have filled it with class 8: then no wide candidate
    svc2 = _service()
    for tag, cls in enumerate([8] * 3 + [64] * 6 + [1024] * 20):
        _push(svc2, cls, tag)
    assert _classes(svc2._take_launch()) == [8] * 3 + [64] * 5
    assert svc2.class_widened_launches == 1


def test_widening_falls_back_to_todays_launch():
    """No class fills a launch at or below itself but the widest: the
    launch takes everything the queue holds, as `take(batch_size)` did."""
    svc, plain = _service(), TenantQueue()
    for tag, cls in enumerate([8, 1024, 64, 8, 64, 1024, 8, 1024]):
        _push(svc, cls, tag)
        plain.push("t", tag)
    batch = svc._take_launch()
    assert sorted(_tags(batch)) == sorted(plain.take(LANES)) == list(range(8))
    assert svc.class_widened_launches == 1 and not len(svc.queue)


@pytest.mark.parametrize("queued", [1, 3, LANES - 1])
def test_a_queue_below_one_launch_is_taken_whole(queued):
    svc = _service()
    for tag in range(queued):
        _push(svc, LADDER[tag % 3], tag)
    batch = svc._take_launch()
    assert sorted(_tags(batch)) == list(range(queued))
    assert svc.class_widened_launches == 0 and not len(svc.queue)


def test_the_plane_asks_the_throughput_engine():
    """`launch_class` is the throughput lanes' engine's, wherever a mesh
    lane sits and whatever wraps the engine; an engine without the method
    gives one class, and a wrapper does not grow one."""
    from handel_tpu.swarm.pager import PagedDevice

    class Plain:
        batch_size = LANES

    bitsets = [_cand(c, 0)[0] for c in LADDER]
    plane = DevicePlane([Plain()])
    plane.lanes[0].mesh = True
    plane.add_lane(PagedDevice(ClassedEngine(), pager=None))
    assert plane.launch_class(bitsets) == list(LADDER)
    assert plane.batch_size == LANES
    assert not hasattr(PagedDevice(Plain(), pager=None), "launch_class")
    assert DevicePlane([PagedDevice(Plain(), pager=None)]).launch_class(
        bitsets) == [0, 0, 0]


def test_one_class_is_todays_take():
    """An engine without classes (every host stub) gives one class, and
    the plan is `queue.take(batch_size)`, order and deficits included."""
    class Plain:
        batch_size = LANES

    assert DevicePlane([Plain()]).launch_class([None] * 3) == [0, 0, 0]
    svc, plain = _service(Plain()), TenantQueue()
    for tag in range(40):
        tenant = "abc"[tag % 3] if tag % 5 else "a"
        _push(svc, 0, tag, tenant)
        plain.push(tenant, tag)
    while len(plain):
        assert _tags(svc._take_launch()) == plain.take(LANES)
    assert svc.class_rider_candidates == svc.class_widened_launches == 0


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_closed_loop_sessions_see_one_fifo_over_classes(seed):
    """The benchmark's traffic: 256 sessions, each with one request of 1-8
    candidates of mixed classes queued at a time. None holds more than a
    quantum, so the turn ring's order is the requests' arrival and every
    launch carries the OLDEST queued candidate, as under the rule "the
    oldest names the class"; full launches are class-pure."""
    import random

    rng = random.Random(seed)
    svc = _service(WideEngine())
    queued: dict[int, str] = {}  # tag -> session
    tag = 0

    def request(tenant):
        nonlocal tag
        for _ in range(rng.randint(1, 8)):
            _push(svc, rng.choices(LADDER, (43, 27, 30))[0], tag, tenant)
            queued[tag] = tenant
            tag += 1

    for c in range(256):
        request(f"c{c}")
    pure = 0
    for _ in range(200):
        batch = svc._take_launch()
        assert len(batch) == 128 and min(queued) in _tags(batch)
        pure += len(set(_classes(batch))) == 1
        for t in _tags(batch):
            del queued[t]
        waiting = set(queued.values())
        for tenant in sorted({it[0] for it in batch} - waiting):
            request(tenant)  # answered: the client's next request
    assert pure >= 190 and svc.class_widened_launches <= 2


# -- tenant isolation across classes ------------------------------------------


class WideEngine(ClassedEngine):
    """The ladder at the served width: sixteen quanta a launch."""

    batch_size = 128


def _hot_and_cold(svc, hot_cls: int, cold: list[tuple[str, int, int]]):
    """A session with the per-tenant bound's worth queued in one class,
    then the cold sessions' (tenant, class, how many); returns the cold
    tags and a one-ring queue, the parent's, holding the same."""
    plain = TenantQueue()
    for tag in range(svc.queue.max_pending):
        _push(svc, hot_cls, tag, "hot")
        plain.push("hot", tag)
    tag, cold_tags = 10_000, set()
    for tenant, cls, n in cold:
        for _ in range(n):
            _push(svc, cls, tag, tenant)
            plain.push(tenant, tag)
            cold_tags.add(tag)
            tag += 1
    return cold_tags, plain


def _launches_until_gone(take, tags: set, limit: int = 64) -> int:
    left = set(tags)
    for n in range(1, limit + 1):
        left -= set(take())
        if not left:
            return n
    return limit + 1


@pytest.mark.parametrize("hot_cls, cold_cls", [
    (1024, 8), (8, 1024), (64, 8), (0, 64), (8, 0),
])
def test_a_hot_session_alone_in_a_class_cannot_starve_cold_ones(
        hot_cls, cold_cls):
    """A session whose backlog sits alone in a class (4 096 queued, the
    admission bound) names a launch once a ring pass, not once per launch
    of its backlog: fifteen cold sessions in another class leave within the
    two launches one ring, the parent's, gave them — and so does every cold
    request that arrives while the backlog lasts."""
    svc = _service(WideEngine())
    cold, plain = _hot_and_cold(
        svc, hot_cls, [(f"cold{c}", cold_cls, 8) for c in range(15)])
    assert _launches_until_gone(lambda: plain.take(128), cold) <= 2
    assert _launches_until_gone(
        lambda: _tags(svc._take_launch()), cold) <= 2
    for r in range(6):  # ... and in the steady state
        fresh = set(range(20_000 + 8 * r, 20_008 + 8 * r))
        for tag in fresh:
            _push(svc, cold_cls, tag, f"cold{r}")
        assert _launches_until_gone(
            lambda: _tags(svc._take_launch()), fresh) <= 2
    assert svc.queue.depth("hot") > 128  # the backlog did last


def test_fifteen_hot_sessions_in_one_class_cannot_starve_a_cold_one():
    """Every lane a session gets costs it turn credit, in launches it did
    not name too: fifteen backlogged sessions sharing one class have had
    their quantum by the first launch, and the turn passes them all."""
    svc = _service(WideEngine())
    for tag in range(15 * 256):
        _push(svc, 1024, tag, f"hot{tag % 15}")
    cold = set(range(10_000, 10_008))
    for tag in cold:
        _push(svc, 8, tag, "cold")
    assert _launches_until_gone(lambda: _tags(svc._take_launch()), cold) <= 2


def test_cold_sessions_wait_a_launch_a_class_at_most():
    """Cold sessions spread over three classes beside a narrow backlog: a
    class leaves when a session holding it has the turn, so the bound is
    the backlog's launch and one launch a class."""
    svc = _service(WideEngine())
    cold, _ = _hot_and_cold(
        svc, 8, [("c64", 64, 5), ("c1024", 1024, 5), ("c0", 0, 5)])
    assert _launches_until_gone(lambda: _tags(svc._take_launch()), cold) <= 4


def test_a_backlog_names_one_launch_a_ring_pass():
    """Two backlogs in two classes and cold requests in a third, arriving
    every launch: the launches' classes alternate between the backlogs,
    one launch each a pass, with the cold class between them."""
    svc = _service(WideEngine())
    for tag in range(2048):
        _push(svc, 1024, tag, "hot-wide")
        _push(svc, 8, 5000 + tag, "hot-narrow")
    named = []
    for r in range(8):
        for tag in range(10_000 + 4 * r, 10_004 + 4 * r):
            _push(svc, 64, tag, f"cold{r}")
        named.append(svc._planned_class(svc._take_launch()))
    # a cold request rides the wide launch when it is next, and names a
    # launch of its own (filled up with narrow riders) when it is not
    assert named.count(1024) >= 3 and named.count(1024) <= 4
    assert not svc.queue.depths().keys() - {"hot-wide", "hot-narrow", "cold7"}


# -- the queue alone: keys, DRR, FIFO ----------------------------------------


@pytest.mark.parametrize("key", ["x", 8, 0])
def test_keyed_take_is_drr_with_per_tenant_fifo(key):
    """Within a key the tenants' shares are DRR's and a tenant's items
    leave in FIFO order, as in a keyless queue, whatever else is queued
    under other keys between them."""
    q, plain = TenantQueue(quantum=2), TenantQueue(quantum=2)
    for i in range(6):
        for t in "ab":
            _put(q, t, f"{t}{i}", key)
            _put(q, t, f"other-{t}{i}", "other")
            plain.push(t, f"{t}{i}")
    assert q.take(8, key) == plain.take(8) == [
        "a0", "a1", "b0", "b1", "a2", "a3", "b2", "b3"]
    # the deficit continues across takes, per key
    assert q.take(3, key) == plain.take(3) == ["a4", "a5", "b4"]
    assert q.counts() == {key: 1, "other": 12}
    assert q.depth("a") == 6 and len(q) == 13


def test_hot_tenant_cannot_starve_cold_within_a_key():
    q = TenantQueue(quantum=8)
    for i in range(500):
        _put(q, "hot", ("hot", i), 64)
        _put(q, "hot", ("hot", -i), 8)
    for c in range(7):
        for i in range(8):
            _put(q, f"cold{c}", (f"cold{c}", i), 64)
    served = q.take(64, 64)
    assert sum(it[0] != "hot" for it in served) == 7 * 8
    assert [it[1] for it in served if it[0] == "hot"] == list(range(8))


def test_queue_takes_oldest_first_and_names_the_next_key():
    q = TenantQueue(quantum=2)
    for i, (t, key) in enumerate(
            [("a", 64), ("b", 8), ("a", 8), ("c", 1024), ("b", 64), ("c", 8)]):
        _put(q, t, i, key)
    assert q.turn_key() == 64  # a's turn, its oldest
    assert q.take_oldest(3, [8, 1024]) == [1, 2, 3]  # no deficit, by age
    assert q.take(1, 64) == [0] and q.turn_key() == 64  # a is gone: b's 4
    assert q.take(8, ANY) == [4, 5]  # the key the turn names first
    assert q.turn_key() is None and not q.counts() and q.tenants() == 0
    assert q.taken == q.pushed == 6


def test_the_turn_passes_with_the_credit():
    """The head of the turn ring names the key (its oldest item's) while it
    has lane credit: `quantum` a visit, one an item of its own that leaves
    under whatever key and by whatever call. Out of credit the turn passes;
    a debt stops at one visit's grant, so an overdrawn tenant sits out one
    pass; a tenant with nothing queued leaves the ring and forfeits."""
    q = TenantQueue(quantum=2)
    for i in range(4):
        _put(q, "a", f"a{i}", "x")
    _put(q, "b", "b0", "y")
    _put(q, "c", "c0", "x")
    _put(q, "c", "c1", "z")
    _put(q, "a", "a4", "x")
    _put(q, "a", "a5", "x")
    assert q.turn_key() == q.turn_key() == "x"  # asking changes nothing
    assert q.take(1, "x") == ["a0"] and q.turn_key() == "x"  # 1 credit left
    # a overdraws: a1 from its ring, two more as riders; c pays for c0
    assert q.take(1, "x") == ["a1"]
    assert q.take_oldest(3, ["x"]) == ["a2", "a3", "c0"]
    assert q.turn_key() == "y"  # b's turn
    assert q.take(1, "y") == ["b0"]  # ... and b is gone
    assert q.turn_key() == "z"  # c: a visit's 2 less the 1 it owed
    assert q.take(1, "z") == ["c1"]
    # a alone: it sat out the pass, and names again
    assert q.turn_key() == "x" and q.take(8, "x") == ["a4", "a5"]
    assert q.turn_key() is None and q.tenants() == 0


def test_a_tier_weight_scales_the_turn():
    q = TenantQueue(quantum=1)
    q.set_tier("g", "gold")  # weight 4
    for i in range(8):
        _put(q, "g", f"g{i}", "x")
        _put(q, "s", f"s{i}", "y")
    named = []
    for _ in range(10):
        key = q.turn_key()
        named.append(key)
        q.take(1, key)
    assert named == ["x"] * 4 + ["y"] + ["x"] * 4 + ["y"]


def test_rekey_moves_in_bulk_and_keeps_age_and_fifo():
    """What was pushed without a key moves to the keys ONE call names;
    push numbers, a tenant's order and the depth books stay; a `keys_of`
    that raises moves nothing."""
    q = TenantQueue(quantum=2)
    _put(q, "a", "old", 8)  # classed by an earlier rekey
    for i, t in enumerate("abab"):
        q.push(t, f"{t}{i}")
    asked = []

    def keys_of(items):
        asked.append(list(items))
        return [8 if it in ("a0", "b3") else 64 for it in items]

    def refuse(items):
        raise ValueError("no")

    with pytest.raises(ValueError):
        q.rekey(None, refuse)
    assert q.counts() == {8: 1, None: 4}
    assert q.rekey(None, keys_of) == 4 and q.rekey(None, keys_of) == 0
    assert asked == [["a0", "a2", "b1", "b3"]]  # one call, ring order
    assert q.counts() == {8: 3, 64: 2} and len(q) == 5
    assert q.depths() == {"a": 3, "b": 2}
    assert q.turn_key() == 8
    assert q.take(8, 8) == ["old", "a0", "b3"]  # a tenant's FIFO, by age
    assert q.take_oldest(8, [64]) == ["b1", "a2"]  # push order survived


@pytest.mark.parametrize("lengths", [(N, N, N), (N, 64, N)])
def test_collector_classes_what_verify_queued(lengths):
    """`verify` queues without a class; `_take_launch` classes everything
    new in one call to the engine. A bitset the engine refuses gives the
    whole call the widest class: the packer refuses it at dispatch."""
    calls = []

    class Engine(ClassedEngine):
        @staticmethod
        def launch_class(bitsets):
            calls.append(len(bitsets))
            if len({len(bs) for bs in bitsets}) > 1:
                raise ValueError("bitset length != registry size")
            return _ladder_class(bitsets)

    svc = _service(Engine())
    for tag, (cls, n) in enumerate(zip((8, 64, 1024), lengths)):
        bs = _cand(cls, tag)[0] if n == N else BitSet(n)
        assert svc.queue.push(
            "t", ["t", b"m", None, bs, tag, 0.0, None, None])
    assert svc.queue.counts() == {None: 3}
    batch = svc._take_launch()
    assert calls == [3] and sorted(_tags(batch)) == [0, 1, 2]
    want = [8, 64, 1024] if len(set(lengths)) == 1 else [0, 0, 0]
    assert sorted(_classes(batch), key=lambda c: (c == 0, c)) == want


def test_admission_and_drop_span_every_key():
    q = TenantQueue(max_pending=3, capacity=0)
    for i, k in enumerate((8, 64, 8)):
        _put(q, "a", i, k)
    assert not q.push("a", 9) and q.refused == 1
    _put(q, "b", 7, 8)
    assert q.drop_tenant("a") == [0, 1, 2]  # oldest first, every key
    assert q.counts() == {8: 1} and q.depths() == {"b": 1} and len(q) == 1
    assert list(q.drain()) == [7] and not q.counts()


# -- the service around the plan ---------------------------------------------


def test_forget_session_and_quiesce_reach_every_queued_candidate():
    """Candidates stay in the tenant queue, under whatever class, until a
    lane is reserved: `forget_session` fails all of a session's, and a
    quiesce runs with the rest still queued, then they launch."""
    eng = ClassedEngine(threading.Event())
    flipped = []

    async def go():
        svc = _service(eng, max_delay_ms=1.0, max_inflight=1)
        try:
            held = asyncio.ensure_future(
                svc.verify(b"m", None, [_cand(8, 99)], session="held"))
            await asyncio.sleep(0.05)  # ... its launch waits at the gate
            mixed = [_cand(c, 10 * i + j) for i, c in enumerate((8, 64, 1024))
                     for j in range(LANES)]
            gone = asyncio.ensure_future(
                svc.verify(b"m", None, mixed, session="gone"))
            tags = [100 + t for t in range(3 * LANES)]
            kept = asyncio.ensure_future(svc.verify(
                b"m", None,
                [_cand(LADDER[t % 3], t) for t in tags], session="kept"))
            await asyncio.sleep(0.05)
            # one launch may sit in the hand-off cell; the rest is queued
            queued = svc.queue.depth("gone")
            assert queued >= 2 * LANES
            assert svc.forget_session("gone") == queued
            assert svc.queue.depth("gone") == 0
            quiesce = asyncio.ensure_future(
                svc.quiesce_and(lambda: flipped.append(len(svc.queue))))
            await asyncio.sleep(0.02)
            eng.gate.set()
            await quiesce
            assert await held == [True] and await kept == [True] * len(tags)
            got = await asyncio.gather(gone, return_exceptions=True)
            assert isinstance(got[0], RuntimeError)
        finally:
            svc.stop()

    asyncio.run(go())
    # the quiesce ran with `kept` still in the queue, across all classes
    assert flipped and flipped[0] >= 2 * LANES
    launched = sorted(t for l in eng.launches for _, t in l if t >= 100)
    assert launched == [100 + t for t in range(3 * LANES)]


def test_two_messages_still_split():
    """`_plan_launches` sees the planned group as it saw a batch: a
    single-message engine gets one launch a message."""
    eng = SingleMsgEngine()

    async def go():
        svc = _service(eng, max_delay_ms=1.0)
        try:
            a, b = await asyncio.gather(
                svc.verify(b"m1", None, [_cand(8, t) for t in range(3)],
                           session="a"),
                svc.verify(b"m2", None, [_cand(8, 10 + t) for t in range(3)],
                           session="b"),
            )
            assert a == b == [True] * 3
        finally:
            svc.stop()

    asyncio.run(go())
    assert sorted(eng.launches) == [
        (b"m1", [0, 1, 2]), (b"m2", [10, 11, 12])]


def test_class_pure_launches_through_verify():
    """End to end through `verify`: 256-client-like requests of mixed
    classes, the queue saturated behind a held launch; every full launch
    holds one class, the span names it, and no candidate is lost."""
    from handel_tpu.core.trace import FlightRecorder

    eng = ClassedEngine(threading.Event())
    rec = FlightRecorder()

    async def go():
        svc = _service(eng, max_delay_ms=1.0, recorder=rec)
        try:
            held = asyncio.ensure_future(
                svc.verify(b"m", None, [_cand(1024, 999)], session="held"))
            await asyncio.sleep(0.05)
            clients = [
                svc.verify(
                    b"m", None,
                    [_cand(LADDER[(c + j) % 3], 8 * c + j) for j in range(6)],
                    session=f"c{c}")
                for c in range(16)
            ]
            waiters = asyncio.gather(*clients)
            await asyncio.sleep(0.05)
            eng.gate.set()
            assert await waiters == [[True] * 6] * 16 and await held == [True]
            return svc.values()
        finally:
            svc.stop()

    v = asyncio.run(go())
    full = [l for l in eng.launches if len(l) == LANES]
    assert len(full) >= 9
    pure = [l for l in full if len({c for c, _ in l}) == 1]
    # 32 candidates a class = four whole launches a class; at most the
    # last launch of the stream mixes what is left
    assert len(pure) >= len(full) - 1
    assert {c for l in pure for c, _ in l} == set(LADDER)
    assert sum(len(l) for l in eng.launches) == 1 + 16 * 6
    assert v["classWidenedLaunches"] <= 1.0
    planned = [args["cls"] for name, *_, args, _ in rec.events()
               if name == "launch_queued"]
    assert len(planned) == len(eng.launches)
    assert planned == [max(c for c, _ in l) for l in eng.launches]
