"""The four-chip verifier host on the CPU (ISSUE 45): a plane of four real
engines from `parallel/plane.py scheme_plane`, one pinned to each of four
host devices (conftest forces eight), behind `BatchVerifierService`.

What a plane shares: the keys are converted once and the bank copied chip
to chip, the prefix table is computed on ONE chip and copied bit for bit,
and each launch class is TRACED once for the plane and its executable
compiled on the chip that asks first and loaded on the others
(models/bn254_jax.py `PlanePrograms`).

Fast tier: everything above and the lanes' counters, with the launch
programs' pairing tail (minutes of XLA on a CPU) replaced by "the aggregate
key is a point" — the aggregation stage, the prefix gather and the hole
patch run for real, and `test_aggregates_equal_the_host_sum_on_every_lane`
holds them to the host's sum of the same keys. The verdicts against the
host scheme's `batch_verify`, forged candidates among them, run the real
pairing: `-m slow` (one cold compile of a pairing class a scheme).

Every engine test runs once per device scheme.
"""

import asyncio
import random

import jax
import numpy as np
import pytest

from handel_tpu.core.bitset import BitSet
from handel_tpu.models.bn254_jax import BN254Device
from handel_tpu.models.registry import new_keygen_scheme, new_scheme
from handel_tpu.parallel.batch_verifier import BatchVerifierService
from handel_tpu.parallel.plane import bn254_plane, host_plane, scheme_plane

N, LANES, CHIPS = 64, 4, 4
MSG = b"handel-tpu fleet reference"
SCHEMES = ["bn254-jax", "bls12-381-jax", "bls12-381-minpk-jax"]


# a launch class is a minute of XLA on this CPU even without its pairing, and
# the 24-limb field's take twice as long: the BLS12-381 planes BUILD in the
# fast tier and run in the slow one
COMPILED = [SCHEMES[0]] + [pytest.param(s, marks=pytest.mark.slow)
                           for s in SCHEMES[1:]]
_KEYS: dict = {}


def _keys(scheme):
    """(secret keys, public keys, one signature a key) of the host scheme."""
    if scheme not in _KEYS:
        host = new_keygen_scheme(scheme)
        pairs = [host.keygen(i) for i in range(N)]
        _KEYS[scheme] = ([sk for sk, _ in pairs], [pk for _, pk in pairs],
                         [sk.sign(MSG) for sk, _ in pairs])
    return _KEYS[scheme]


def _candidate(keys, lo, size, holes=(), forged=False):
    """(bitset, aggregate signature) of the range [lo, lo + size) less
    `holes`; a forged one carries one signer too many."""
    _, _, sigs = keys
    bs = BitSet(N)
    bs.set_range(lo, lo + size)
    for i in holes:
        bs.set(i, False)
    signers = [i for i in range(lo, lo + size) if i not in holes]
    if forged:
        signers.append((lo + size) % N)
    sig = sigs[signers[0]]
    for i in signers[1:]:
        sig = sig.combine(sigs[i])
    return bs, sig


def _requests(keys, rng, count, forged_every=0):
    """`count` requests of 1-4 distinct level ranges with 0-3 holes."""
    seen, out = set(), []
    while len(out) < count:
        req = []
        for _ in range(rng.randrange(1, LANES + 1)):
            size = 1 << rng.randrange(2, 6)
            lo = size * rng.randrange(N // size)
            holes = tuple(sorted(rng.sample(range(lo + 1, lo + size - 1),
                                            rng.randrange(0, 3))))
            if (lo, size, holes) in seen:
                continue
            seen.add((lo, size, holes))
            forged = bool(forged_every) and len(seen) % forged_every == 0
            req.append(_candidate(keys, lo, size, holes, forged))
        if req:
            out.append(req)
    return out


class _Fleet:
    """One plane a scheme for the whole module (a launch class is a minute
    of XLA even without its pairing): built with the pairing tail replaced
    by "the aggregate key is a point", and with the traced launch bodies
    and the prefix scan counted. The tests below run in the order written
    and say what they add to the counts."""

    COUNTED = ("_verify_batch_range", "_range_aggregate", "_build_prefix")

    def __init__(self, scheme, keys, patch):
        self.calls = dict.fromkeys(self.COUNTED, 0)
        for name in self.COUNTED:
            patch.setattr(BN254Device, name,
                          self._counting(name, getattr(BN254Device, name)))
        patch.setattr(
            BN254Device, "_pairing_tail",
            lambda eng, agg, sx, sy, hx, hy, valid:
                valid & ~eng.kg.is_infinity(agg))
        self.scheme, self.keys, self.pubkeys = scheme, keys, keys[1]
        self.plane = scheme_plane(
            self.pubkeys, CHIPS, batch_size=LANES, scheme=scheme)
        self.engines = [lane.engine for lane in self.plane.lanes]
        self.programs = self.engines[0].programs

    def _counting(self, name, inner):
        def counting(eng, *a, **kw):
            self.calls[name] += 1
            return inner(eng, *a, **kw)

        return counting


@pytest.fixture(scope="module", params=COMPILED)
def fleet(request):
    with pytest.MonkeyPatch.context() as patch:
        yield _Fleet(request.param, _keys(request.param), patch)


def _serve(plane, pubkeys, requests, **options):
    """Every request at once through one service; (verdicts, values())."""
    async def go():
        svc = BatchVerifierService(plane, fallback=None, **options)
        try:
            got = await asyncio.gather(*(
                svc.verify(MSG, pubkeys, req, session=f"s{i}",
                           dedup_scope=f"s{i}")
                for i, req in enumerate(requests)))
            return got, svc.values()
        finally:
            svc.stop()

    return asyncio.run(go())


def _held(programs, name) -> tuple:
    """(chips that compiled `name`, chips that loaded it). One compiles and
    the others load its bytes — except that XLA:CPU cannot serialise again
    an executable it READ from the compile cache (the copy loads and then
    misses functions when run: `PlanePrograms` tries a loaded program once
    and lets the chip compile), so with a warm `.jax_cache/` every chip
    compiles, as every chip did before there was a plane."""
    held = programs.compiles[name], programs.loads[name]
    assert held in ((1, CHIPS - 1), (CHIPS, 0)), (name, held)
    return held


def _bits(tree):
    return [np.asarray(a) for a in jax.tree_util.tree_leaves(tree)]


def _devices(tree) -> set:
    return {d for a in jax.tree_util.tree_leaves(tree) for d in a.devices()}


# -- the factory ---------------------------------------------------------------


@pytest.mark.parametrize("scheme", SCHEMES)
def test_every_scheme_builds_a_plane_of_its_own_engines(scheme):
    pubkeys = _keys(scheme)[1]
    plane = scheme_plane(pubkeys, CHIPS, batch_size=LANES, scheme=scheme)
    Device = new_scheme(
        scheme, batch_size=LANES, warmup=False).constructor.Device
    engines = [lane.engine for lane in plane.lanes]
    assert len(engines) == CHIPS and plane.batch_size == LANES
    assert all(type(e) is Device and e.n == N for e in engines)
    # one engine a chip, in device order, keys and all
    devs = jax.devices()[:CHIPS]
    assert [e.jax_device for e in engines] == devs
    for e, dev in zip(engines, devs):
        assert _devices((e._reg_x, e._reg_y)) == {dev}
    # the keys were converted once: the banks are copies, bit for bit
    first = _bits((engines[0]._reg_x, engines[0]._reg_y))
    for e in engines[1:]:
        assert e._plane_of is engines[0]
        assert e.programs is engines[0].programs is not None
        assert all(np.array_equal(a, b) for a, b in
                   zip(_bits((e._reg_x, e._reg_y)), first))
    # the harness's call: what a configuration's `device_options` carry
    asked = scheme_plane(pubkeys, devices=2, batch_size=LANES,
                         **{"scheme": scheme})
    assert [type(l.engine) for l in asked.lanes] == [Device] * 2
    # nothing ran: no table, no executable
    assert all(e._prefix_cache is None for e in engines)
    assert not engines[0].programs.compiles and not engines[0].programs.loads


def test_the_factory_refuses_what_it_cannot_pin():
    for name in ("bn254", "bn254-jaxx"):  # a host scheme, an unknown one
        with pytest.raises(ValueError, match="no device scheme"):
            scheme_plane([], 2, scheme=name)
    with pytest.raises(TypeError):
        scheme_plane([], 2)  # no scheme: nothing is assumed
    pubkeys = [new_keygen_scheme("bn254-jax").keygen(i)[1] for i in range(4)]
    with pytest.raises(ValueError, match="only 8 visible"):
        bn254_plane(pubkeys, 9)
    # an option the scheme does not take is the scheme's error, not ours
    with pytest.raises(TypeError):
        bn254_plane(pubkeys, 2, chips=2)
    first = bn254_plane(pubkeys, 1, batch_size=2).lanes[0].engine
    with pytest.raises(ValueError, match="plane_of wants"):
        BN254Device(pubkeys, batch_size=4, jax_device=jax.devices()[1],
                    plane_of=first)
    with pytest.raises(ValueError, match="plane_of wants"):
        BN254Device(pubkeys, batch_size=2, plane_of=first)  # not pinned


# -- set-up that shares --------------------------------------------------------


def test_prefix_table_is_scanned_on_one_chip_and_copied(fleet):
    # asked in any order: the last lane first
    for e in reversed(fleet.engines):
        jax.block_until_ready(e._prefix)
    assert fleet.calls["_build_prefix"] == 1
    first = _bits(fleet.engines[0]._prefix)
    for e in fleet.engines:
        assert _devices(e._prefix) == {e.jax_device}
        for a, b in zip(_bits(e._prefix), first):
            assert a.dtype == b.dtype and np.array_equal(a, b)


def test_aggregates_equal_the_host_sum_on_every_lane(fleet):
    """The aggregation stage alone, through the plane's one program, on each
    chip: the executable loaded from another chip's gives the host's sum of
    the same public keys."""
    rng = random.Random(11)
    affine = jax.jit(fleet.engines[0].kg.to_affine)
    for eng in fleet.engines:
        reqs = _requests(fleet.keys, rng, 1)[0]
        plan = eng._pack_requests(reqs)
        agg = eng._range_agg_kernel(plan.miss_k)(*eng._stage_plan(plan)[:4])
        assert _devices(agg) == {eng.jax_device}
        x, y, inf = affine(agg)
        xs, ys = eng.kg.ops.unpack(x), eng.kg.ops.unpack(y)
        for j, (bs, _) in enumerate(reqs):
            want = None
            for i in range(N):
                if bs.get(i):
                    pk = fleet.pubkeys[i]
                    want = pk if want is None else want.combine(pk)
            assert not np.asarray(inf)[j]
            assert (xs[j], ys[j]) == want.point, (eng.jax_device, j)
    assert fleet.calls["_range_aggregate"] == 1  # one trace for the plane
    _held(fleet.programs, "range_agg8")


def test_each_launch_class_is_traced_once_and_every_lane_launches(fleet):
    requests = _requests(fleet.keys, random.Random(7), 24)
    got, v = _serve(fleet.plane, fleet.pubkeys, requests)
    assert got == [[True] * len(r) for r in requests]
    # every lane launched, and the service's sums are the lanes' sums
    engines = fleet.engines
    launches = [lane.launches for lane in fleet.plane.lanes]
    assert min(launches) >= 1 and sum(launches) == v["verifierLaunches"]
    assert v["hostDispatchLaunches"] == sum(
        e.host_dispatch_launches for e in engines) == sum(launches)
    assert v["launchesRange8"] == sum(
        e.class_launches["range8"] for e in engines) == sum(launches)
    assert v["hostPackLaunches"] == v["hostFetchLaunches"] == sum(launches)
    assert v["devicesTotal"] == CHIPS
    # one trace for the plane (the aggregation test's program is another),
    # and every chip holds the class
    assert fleet.calls["_verify_batch_range"] == 1
    _held(fleet.programs, "verify_range8")
    assert v["programCompiles"] == fleet.programs.compiles.total()
    assert v["programLoads"] == fleet.programs.loads.total()
    # every launch planned went through `_acquire_lane`
    assert v["laneWaitLaunches"] >= sum(launches)
    # the table was there: no lane scanned again
    assert fleet.calls["_build_prefix"] == 1


def test_rotation_keeps_working_a_lane(fleet):
    """A lane that stages and activates another registry scans its own
    table on its own chip and goes on running the plane's programs. (Last:
    it leaves the module's plane with one lane on other keys.)"""
    a, b = fleet.engines[0], fleet.engines[-1]
    held = (fleet.programs.compiles.total(), fleet.programs.loads.total())
    reqs = _requests(fleet.keys, random.Random(3), 1)[0]
    b.stage_registry(fleet.pubkeys[1:] + fleet.pubkeys[:1])
    assert b.activate_staged() == 1 and fleet.calls["_build_prefix"] == 2
    assert _devices(b._prefix) == {b.jax_device}
    assert not all(np.array_equal(x, y)
                   for x, y in zip(_bits(b._prefix), _bits(a._prefix)))
    assert b.fetch(b.dispatch(MSG, reqs)) == [True] * len(reqs)
    # an equal-size flip reaches the executable the lane already holds
    assert (fleet.programs.compiles.total(),
            fleet.programs.loads.total()) == held
    assert fleet.calls["_verify_batch_range"] == 1
    # a lane that joins a source which has flipped would scan for itself
    late = type(a)(fleet.pubkeys, batch_size=LANES,
                   jax_device=jax.devices()[CHIPS], plane_of=b)
    assert late._plane_of is b and b.epoch != late.epoch


# -- the programs of a plane, alone --------------------------------------------


def _square_programs():
    """A plane's programs over a program of this run alone: the nonce keeps
    it out of the compile cache, so its executable is one XLA:CPU made
    here and can serialise (see `_held`)."""
    from handel_tpu.models.bn254_jax import PlanePrograms

    traces = []
    nonce = np.uint32(random.SystemRandom().randrange(1 << 31))
    progs = PlanePrograms()
    progs.jit("square", lambda: jax.jit(
        lambda x, bank: (traces.append(1), x * x + bank + nonce - nonce)[1]))
    return progs, traces


def _on(dev, n=8):
    return (jax.device_put(np.arange(n, dtype=np.uint32), dev),
            jax.device_put(np.ones((n,), np.uint32), dev))


def test_programs_compile_once_and_load_on_the_other_chips():
    progs, traces = _square_programs()
    devs = jax.devices()[:CHIPS]
    for _ in range(2):  # the second round finds every executable held
        for dev in devs:
            out = progs.run("square", dev, *_on(dev))
            assert out.devices() == {dev}
            assert np.array_equal(np.asarray(out), np.arange(8) ** 2 + 1)
    assert len(traces) == 1
    assert progs.compiles == {"square": 1} and progs.loads == {"square": 3}
    # other shapes are another executable of the same program
    progs.run("square", devs[1], *_on(devs[1], 16))
    progs.run("square", devs[0], *_on(devs[0], 16))
    assert len(traces) == 2
    assert progs.compiles == {"square": 2} and progs.loads == {"square": 4}
    # an executable runs where it was loaded, and says so otherwise
    with pytest.raises(Exception):
        progs._loaded["square", ((8,), (8,)), devs[1]](*_on(devs[2]))


def test_a_chip_that_cannot_load_compiles_for_itself(monkeypatch):
    """Where the runtime binds a compiled program to the chip it was made
    for, a lane falls back to what every lane did before there was a
    plane: its own compile, from the plane's one trace."""
    from handel_tpu.models import bn254_jax

    def refuse(se, blob, device):
        raise RuntimeError("executable is bound to its device")

    monkeypatch.setattr(bn254_jax, "_load_on", refuse)
    progs, traces = _square_programs()
    for dev in jax.devices()[:3]:
        out = progs.run("square", dev, *_on(dev))
        assert out.devices() == {dev}
        assert np.array_equal(np.asarray(out), np.arange(8) ** 2 + 1)
    assert progs.compiles == {"square": 3} and not progs.loads
    assert len(traces) == 1  # still one trace


# -- the lane wait -------------------------------------------------------------


def test_lane_wait_grows_only_when_every_lane_is_occupied():
    """Host stubs with a launch wall (`host_plane`): a burst that outruns
    two lanes waits in `_acquire_lane`; a request to an idle plane is
    counted and not timed."""
    host = new_scheme("fake")
    cons = host.constructor
    pairs = [host.keygen(i) for i in range(16)]
    pubkeys = [pk for _, pk in pairs]

    def request(j, k=4):
        out = []
        for c in range(k):
            bs = BitSet(16)
            bs.set((j + c) % 16, True)
            out.append((bs, pairs[(j + c) % 16][0].sign(MSG)))
        return out

    async def go():
        plane = host_plane(cons, devices=2, batch_size=4, launch_ms=30.0)
        svc = BatchVerifierService(plane, fallback=None, max_delay_ms=0.5)
        try:
            # idle plane, one launch: a lane is free at once
            await svc.verify(MSG, pubkeys, request(0), session="a",
                             dedup_scope="a")
            idle = svc.values()
            assert idle["laneWaitLaunches"] >= 1.0
            assert idle["laneWaitMs"] == 0.0
            # twelve launches' worth at once over two lanes of 30 ms
            await asyncio.gather(*(
                svc.verify(MSG, pubkeys, request(j), session=f"b{j}",
                           dedup_scope=f"b{j}") for j in range(12)))
            busy = svc.values()
            assert busy["laneWaitMs"] > 30.0
            assert busy["laneWaitLaunches"] > idle["laneWaitLaunches"]
            # ... and idle again: counted, not timed
            await svc.verify(MSG, pubkeys, request(5), session="c",
                             dedup_scope="c")
            after = svc.values()
            assert after["laneWaitMs"] == busy["laneWaitMs"]
            assert after["laneWaitLaunches"] == busy["laneWaitLaunches"] + 1
            return after
        finally:
            svc.stop()

    v = asyncio.run(go())
    assert v["programCompiles"] == v["programLoads"] == 0.0  # host stubs
    assert v["failoverBatches"] == 0.0


# -- the verdicts against the plain reference (the real pairing) ---------------


@pytest.mark.slow
@pytest.mark.parametrize("scheme", SCHEMES)
def test_fleet_verdicts_equal_the_host_reference(scheme):
    """Four real engines behind the service, seeded requests with forged
    ones among them: every verdict equals the host scheme's `batch_verify`.
    Slow tier: one cold compile of a pairing class (minutes on this CPU)."""
    keys = _keys(scheme)
    _, pubkeys, _ = keys
    rng = random.Random(45)
    requests = _requests(keys, rng, 12, forged_every=5)
    host = new_keygen_scheme(scheme).constructor
    want = [host.batch_verify(MSG, pubkeys, req) for req in requests]
    flat = [w for ws in want for w in ws]
    assert False in flat and True in flat
    plane = scheme_plane(pubkeys, CHIPS, batch_size=LANES, scheme=scheme)
    got, v = _serve(plane, pubkeys, requests)
    assert got == want
    launches = [lane.launches for lane in plane.lanes]
    assert min(launches) >= 1, launches
    assert v["hostDispatchLaunches"] == v["launchesRange8"] == sum(launches)
    assert (v["programCompiles"], v["programLoads"]) == _held(
        plane.lanes[0].engine.programs, "verify_range8")
    assert v["failoverBatches"] == v["deviceRetryCt"] == 0.0
