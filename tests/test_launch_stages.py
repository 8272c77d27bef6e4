"""The launch lifecycle measured inside the program (ISSUE 26).

One stage clock (core/trace.py StageClock) times every host stage of a
device launch — fence_wait, pack, stage, enqueue on the dispatch side,
fetch_wait, fetch_copy on the fetch side — as a counter (always), a
`launch/<stage>` recorder span (when a recorder listens) and a
`handel/<stage>` profiler annotation, all carrying the engine's launch
number `seq`, which the service puts in its own launch spans too.

Fast-tier by design, like tests/test_device_residency.py: the engines here
pack, stage and fetch for real, but the pairing kernels (minutes of XLA on a
CPU) are replaced by a jitted echo of the `valid` mask — the lifecycle is
what is under test, not the verdicts.

Every engine test runs once per device class (`curve`): BN254Device, its
BLS12-381 binding and that curve's other group binding (keys in G1,
signatures in G2) share the launch engine, so each emits the same stages,
counters, `seq` and names — over 16 limbs or 24 (`fieldLimbs`), with the
registry in G2 or G1 (`keyGroup`). The G1-keyed class has no RLC launch
class: its `rlc` cases assert the refusal.
"""

import asyncio
import glob
import os
import random
from functools import partial

import jax
import jax.numpy as jnp
import pytest

from handel_tpu import native as nat
from handel_tpu.core.bitset import BitSet
from handel_tpu.core.trace import LAUNCH_STAGES, FlightRecorder, StageClock
from handel_tpu.models.bls12_381 import (
    BLS12381PublicKey,
    BLS12381Signature,
    MinPkPublicKey,
    MinPkSignature,
)
from handel_tpu.models.bls12_381_jax import BLS12381Device, BLS12381MinPkDevice
from handel_tpu.models.bn254 import BN254PublicKey, BN254Signature
from handel_tpu.models.bn254_jax import BN254Device, _named
from handel_tpu.ops import bls12_381_ref as bls
from handel_tpu.ops import bn254_ref as bn
from handel_tpu.parallel.batch_verifier import BatchVerifierService

N = 12
C = 4
STAGE_COUNTERS = (
    "hostFenceWaitMs", "hostPackWorkMs", "hostPackCpuMs", "hostStageMs",
    "hostEnqueueMs", "hostFetchWaitMs", "hostFetchCopyMs",
    "hostFetchLaunches", "hostPackMs", "hostDispatchMs",
)

_echo = jax.jit(lambda valid: jnp.logical_and(valid, True))
_accept = jax.jit(lambda: jnp.ones((1,), bool))


class _BN254:
    Device, limbs, key_group = BN254Device, 16, 2
    # 64 steps of 6u+2 and the two tail additions; 36 bits are set, and
    # only their steps add
    miller = (66, 38)
    # 64 squarings (36) and 64 + 38 sparse line products (39)
    acc_fp_muls = 6282
    sig = BN254Signature(bn.G1_GEN)

    @staticmethod
    def pubkeys(n):
        rng = random.Random(5)
        sks = [rng.randrange(1, 1 << 20) for _ in range(n)]
        return [BN254PublicKey(p)
                for p in nat.g2_mul_batch([bn.G2_GEN] * n, sks)]


class _BLS12381:
    Device, limbs, key_group = BLS12381Device, 24, 2
    # |z|: 63 steps, no tail addition; 5 bits set
    miller = (63, 5)
    acc_fp_muls = 4920  # 63 x (36 + 39) + 5 x 39
    sig = BLS12381Signature(bls.G1_GEN)

    @staticmethod
    def pubkeys(n):
        # k B2, (k + 1) B2, ...: valid keys by n additions (the host scheme
        # of this curve is pure Python: 25 ms a scalar multiplication)
        pt = bls.g2_mul(bls.G2_GEN, random.Random(5).randrange(1, 1 << 20))
        out = []
        for _ in range(n):
            out.append(BLS12381PublicKey(pt))
            pt = bls.g2_add(pt, bls.G2_GEN)
        return out


class _BLS12381MinPk(_BLS12381):
    """The same pairing (63 steps, 5 additions) over keys in G1."""
    Device, key_group = BLS12381MinPkDevice, 1
    sig = MinPkSignature(bls.G2_GEN)

    @staticmethod
    def pubkeys(n):
        pt = bls.g1_mul(bls.G1_GEN, random.Random(5).randrange(1, 1 << 20))
        out = []
        for _ in range(n):
            out.append(MinPkPublicKey(pt))
            pt = bls.g1_add(pt, bls.G1_GEN)
        return out


@pytest.fixture(params=[_BN254, _BLS12381, _BLS12381MinPk],
                ids=["bn254", "bls12_381", "bls12_381_minpk"])
def curve(request):
    return request.param


def _device(curve, n=N, **kw) -> BN254Device:
    """A small engine whose launches run an echo instead of a pairing."""
    dev = curve.Device(curve.pubkeys(n), batch_size=C, **kw)
    dev._run_plan = lambda plan, staged, h_x, h_y: _echo(staged[-1])
    dev._rlc_msm_kernel = lambda kind, miss_k, G: (lambda *args: ())
    dev._rlc_check_kernel = lambda G: (lambda *args: _accept())
    return dev


def _no_rlc(curve, how) -> bool:
    """True where `how` is the RLC path and the class has none (keys in
    G1): the engine must refuse the option at construction."""
    if how != "rlc" or curve.key_group == 2:
        return False
    with pytest.raises(ValueError, match='does not support batch_check="rlc"'):
        curve.Device(curve.pubkeys(2), batch_size=C, batch_check="rlc")
    return True


def _requests(rng, curve, k=C):
    """k distinct range candidates (distinct content: no dedup hit)."""
    sig = curve.sig
    seen, reqs = set(), []
    while len(reqs) < k:
        size = rng.randrange(2, N)
        lo = rng.randrange(0, N - size + 1)
        if (lo, size) in seen:
            continue
        seen.add((lo, size))
        bs = BitSet(N)
        bs.set_range(lo, lo + size)
        reqs.append((bs, sig))
    return reqs


def _launch(dev, how: str, reqs):
    if how == "dispatch_multi":  # two messages: the per-lane-h path
        items = [(b"m%d" % (j % 2), None, bs, sig)
                 for j, (bs, sig) in enumerate(reqs)]
        return dev.fetch(dev.dispatch_multi(items))
    return dev.fetch(dev.dispatch(b"m", reqs))


# -- (a) the stage counters add up, whichever path built the launch ----------


@pytest.mark.parametrize("how", ["dispatch", "dispatch_multi", "rlc"])
def test_stage_counters_add_up(curve, how):
    if _no_rlc(curve, how):
        return
    launches = 5
    dev = _device(curve, batch_check="rlc", rlc_rng=random.Random(1)) \
        if how == "rlc" else _device(curve)
    svc = BatchVerifierService(dev)  # values() only: never started
    # which field the process serves, without parsing a kernel's name
    assert svc.values()["fieldLimbs"] == curve.limbs == dev.field_limbs
    # ... and which group holds the registry keys
    assert svc.values()["keyGroup"] == curve.key_group == dev.key_group
    # ... and which form of the multiplication kernel it compiled: a limb row
    # fills the 8 sublanes of a register, 1 024 lanes a pass, in every field
    assert svc.values()["fpMulRowSublanes"] == 8 == dev.fp_mul_row_sublanes
    assert svc.values()["fpMulStepLanes"] == 1024 == dev.fp_mul_step_lanes
    assert {"fieldLimbs", "keyGroup", "fpMulStepLanes",
            "fpMulRowSublanes"} <= svc.gauge_keys()
    rng = random.Random(7)
    before = svc.values()
    for _ in range(launches):
        assert _launch(dev, how, _requests(rng, curve)) == [True] * C
        now = svc.values()
        for key in STAGE_COUNTERS:
            assert now[key] >= before[key], key  # monotone
        before = now
    v = svc.values()
    assert v["hostPackLaunches"] == v["hostDispatchLaunches"] == launches
    assert v["hostFetchLaunches"] == launches
    # the old totals are the sums of their stages: nothing between two
    # stages of a launch goes untimed (0.1 ms a launch)
    assert abs(v["hostFenceWaitMs"] + v["hostPackWorkMs"]
               - v["hostPackMs"]) <= 0.1 * launches
    assert abs(v["hostStageMs"] + v["hostEnqueueMs"]
               - v["hostDispatchMs"]) <= 0.1 * launches
    assert v["hostPackMs"] == dev.host_pack_ms > 0.0
    assert v["hostDispatchMs"] == dev.host_dispatch_ms > 0.0
    assert 0.0 < v["hostPackCpuMs"] <= v["hostPackWorkMs"] + 1.0 * launches
    assert v["hostFetchCopyMs"] > 0.0 and v["hostFetchWaitMs"] >= 0.0
    # launches are numbered from 0, and a counter reset keeps the numbering
    assert dev._next_seq == launches
    dev.reset_host_counters()
    assert dev.host_pack_ms == 0.0 and dev.host_fetch_launches == 0
    assert dev.launch_seq(dev.dispatch(b"m", _requests(rng, curve))) == launches


CLASS_COUNTERS = ("launchesRange8", "launchesRange64", "launchesRangeWide",
                  "launchesDense", "patchSlots", "patchHoles")


@pytest.mark.parametrize("how", ["dispatch", "dispatch_multi"])
def test_class_counters_count_each_launch_once(curve, how):
    """How often each launch class engages, counted where every dispatch
    path passes (`_launch`) and summed into the service's values(): one
    launch of each class of a 520-key registry (patch widths 8, 64 and
    n // 4 = 130; dense past that), and for the wide one its patch slots
    (width x valid lanes) and the holes really patched."""
    n = 520
    dev = _device(curve, n)
    assert dev.patch_widths == (8, 64, 130)
    ran = []
    echo = dev._run_plan
    dev._run_plan = lambda plan, *rest: (
        ran.append((plan.kind, plan.miss_k)), echo(plan, *rest))[1]
    svc = BatchVerifierService(dev)  # values() only: never started
    sig = curve.sig

    def candidate(lo, size, n_holes):
        bs = BitSet(n)
        bs.set_range(lo, lo + size)
        for i in range(lo + 1, lo + 1 + n_holes):
            bs.set(i, False)
        return (bs, sig)

    zero = svc.values()
    assert all(zero[k] == 0.0 for k in CLASS_COUNTERS)
    launches = {
        "launchesRange8": [candidate(0, 260, 0), candidate(260, 130, 8)],
        "launchesRange64": [candidate(0, 260, 9), candidate(260, 130, 64)],
        # the launch's class is its LARGEST hole count; an empty bitset is
        # an invalid lane and brings no patch slots
        "launchesRangeWide": [candidate(0, 260, 70), candidate(260, 130, 100),
                              candidate(390, 65, 3), (BitSet(n), sig)],
        "launchesDense": [candidate(0, 260, 131), candidate(0, 520, 4)],
    }
    done = 0
    for key, reqs in launches.items():
        _launch(dev, how, reqs)
        done += 1
        v = svc.values()
        assert v[key] == 1.0, key
        assert sum(v[k] for k in CLASS_COUNTERS[:4]) == done
        assert v["hostDispatchLaunches"] == done
    assert ran == [("range", 8), ("range", 64), ("range", 130), ("dense", 0)]
    assert v["patchSlots"] == 130 * 3 and v["patchHoles"] == 70 + 100 + 3
    dev.reset_host_counters()
    assert all(svc.values()[k] == 0.0 for k in CLASS_COUNTERS)


_WIDE_DEVICES: dict = {}


@pytest.mark.parametrize("holes", [0, 8, 9, 64, 65, 1024, 1025])
def test_launch_class_is_the_class_the_packer_gives_the_candidate_alone(
        curve, holes):
    """`launch_class`, what the service plans launches by, and the packer
    read a candidate's holes in one place (`_hull`): at every edge of the
    4096-key ladder (8 | 64 | n // 4 | dense) the class named for a bitset
    is the class `_pack_into` gives a launch that holds it alone, and the
    class of a launch is that of its widest candidate — so no candidate is
    ever verified by a narrower program than its holes need."""
    n = 4096
    dev = _WIDE_DEVICES.get(curve)
    if dev is None:  # 16 real keys, repeated: only the packer runs
        dev = _WIDE_DEVICES[curve] = curve.Device(
            curve.pubkeys(16) * (n // 16), batch_size=C)
    assert dev.patch_widths == (8, 64, 1024)

    def candidate(lo, n_holes):
        bs = BitSet(n)
        bs.set_range(lo, lo + n_holes + 2)
        for i in range(lo + 1, lo + 1 + n_holes):
            bs.set(i, False)
        return bs

    def packed(bitsets):
        plan = dev._pack_requests([(bs, curve.sig) for bs in bitsets])
        return plan.miss_k if plan.kind == "range" else 0

    # across a word boundary, at the registry's start and at its end
    own = [candidate(lo, holes) for lo in (0, 61, n - holes - 2)]
    named = dev.launch_class(own)
    assert named == [packed([bs]) for bs in own]
    assert len(set(named)) == 1
    assert named[0] == next((k for k in (8, 64, 1024) if holes <= k), 0)
    # beside narrower candidates the launch still takes this one's class
    assert packed([candidate(7, 0), own[1], candidate(130, 3)]) == named[0]
    assert dev.launch_class([]) == [] and dev.launch_class(
        [BitSet(n)]) == [8]  # an empty bitset: no hull, no hole


@pytest.mark.parametrize("how", ["dispatch", "dispatch_multi", "rlc"])
def test_miller_step_counters_follow_the_loop_bits(curve, how):
    """Per launch, values() gains the Miller loop's steps and those whose
    addition executes, read off the pairing that built the program: the
    loop adds on its set bits only, so the two differ by the zero bits (a
    program that computed both and selected would report them equal)."""
    if _no_rlc(curve, how):
        return
    dev = _device(curve, batch_check="rlc", rlc_rng=random.Random(1)) \
        if how == "rlc" else _device(curve)
    steps, adds = curve.miller
    assert (dev.pairing.miller_steps, dev.pairing.miller_add_steps) == (
        steps, adds)
    svc = BatchVerifierService(dev)  # values() only: never started
    v = svc.values()
    assert v["millerSteps"] == v["millerAddSteps"] == 0.0
    rng = random.Random(11)
    for done in (1, 2, 3):
        _launch(dev, how, _requests(rng, curve))
        v = svc.values()
        assert v["hostDispatchLaunches"] == done
        assert v["millerSteps"] == steps * done
        assert v["millerAddSteps"] == adds * done
    dev.reset_host_counters()
    v = svc.values()
    assert v["millerSteps"] == v["millerAddSteps"] == 0.0


@pytest.mark.parametrize("how", ["dispatch", "dispatch_multi", "rlc"])
def test_miller_accumulator_multiplications_a_launch(curve, how):
    """values()["millerAccFpMuls"] gains, a launch, the base-field
    multiplications a pair that the Miller loop's accumulator updates run,
    as the pairing adds them up from the tower's product costs
    (tests/test_miller_products.py holds those to the lanes handed to
    `Field.mul`): a squaring of 36 and a sparse line product of 39 a
    doubling, one line product an addition. A program that squared and
    multiplied the padded line by the general product would read
    54 x (2 x bits + additions): 8 964 / 7 074."""
    if _no_rlc(curve, how):
        return
    dev = _device(curve, batch_check="rlc", rlc_rng=random.Random(1)) \
        if how == "rlc" else _device(curve)
    steps, adds = curve.miller
    bits = steps - dev.pairing._TAIL_ADDS
    assert dev.pairing.miller_acc_fp_muls == curve.acc_fp_muls \
        == bits * (36 + 39) + adds * 39 < 54 * (2 * bits + adds)
    svc = BatchVerifierService(dev)  # values() only: never started
    assert svc.values()["millerAccFpMuls"] == 0.0
    rng = random.Random(13)
    for done in (1, 2):
        _launch(dev, how, _requests(rng, curve))
        v = svc.values()
        assert v["hostDispatchLaunches"] == done
        assert v["millerAccFpMuls"] == curve.acc_fp_muls * done
        # what the benchmark's `miller.acc_fp_muls` divides
        assert round(v["millerAccFpMuls"] / v["millerSteps"], 2) == round(
            curve.acc_fp_muls / steps, 2)
    dev.reset_host_counters()
    assert svc.values()["millerAccFpMuls"] == 0.0


def test_agg_multiplications_are_the_lanes_handed_to_field_mul(curve):
    """values()["aggFpMuls"] gains, a launch, the base-field multiplications
    of the launch program's `agg` stage as the key group's cost attributes
    add them up (ops/curve.py `add_fp_muls`, `sum_fp_muls`). Held here to
    the lanes the stage REALLY hands to `Field.mul`, for every class of a
    520-key registry (tracing only: nothing compiles): 12 a G1 addition, 42
    a G2 one, so the G1-keyed class reads two sevenths of the G2-keyed."""
    n = 520
    dev = _device(curve, n)
    assert dev.kg.add_fp_muls == (42 if curve.key_group == 2 else 12)
    F = dev.curves.F
    handed = []
    mul = F.mul
    F.mul = lambda a, b: (handed.append(a.shape[1]), mul(a, b))[1]
    sds = lambda a: jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), a)
    dev._prefix_cache = jax.tree_util.tree_map(  # shapes only: no scan here
        lambda a: jnp.zeros((*a.shape[:-1], n + 1), a.dtype),
        (dev._reg_x, dev._reg_y, jnp.zeros((n,), bool)))
    svc = BatchVerifierService(dev)  # values() only: never started
    sig = curve.sig

    def candidate(n_holes):
        bs = BitSet(n)
        bs.set_range(0, 260)
        for i in range(1, 1 + n_holes):
            bs.set(i, False)
        return (bs, sig)

    want = 0
    for n_holes, (kind, miss_k) in {
        2: ("range", 8), 30: ("range", 64), 100: ("range", 130),
        200: ("dense", 0),
    }.items():
        plan = dev._pack_requests([candidate(n_holes)])
        assert (plan.kind, plan.miss_k) == (kind, miss_k)
        staged = dev._stage_plan(plan)
        del handed[:]
        if kind == "range":
            jax.eval_shape(
                partial(dev._range_aggregate, miss_k=miss_k),
                *sds(staged[:4]), sds(dev._prefix), sds(dev._reg_x),
                sds(dev._reg_y))
        else:
            jax.eval_shape(dev._dense_aggregate, sds(dev._reg_x),
                           sds(dev._reg_y), sds(staged[0]), sds(staged[-1]))
        assert dev._agg_fp_muls(plan) == sum(handed) > 0, (kind, miss_k)
        want += sum(handed)
        before = svc.values()["aggFpMuls"]
        _launch(dev, "dispatch", [candidate(n_holes)])
        assert svc.values()["aggFpMuls"] - before == dev._agg_fp_muls(plan)
    assert svc.values()["aggFpMuls"] == want
    # the wide class of the failing-committee cells, a launch of 128 lanes:
    # 1 023 additions of the patch's tree and the two subtractions
    big = type("Plan", (), {"kind": "range", "miss_k": 1024})
    dev.batch_size = 128
    assert dev._agg_fp_muls(big) == 1025 * 128 * dev.kg.add_fp_muls
    dev.reset_host_counters()
    assert svc.values()["aggFpMuls"] == 0.0


def test_second_use_of_a_staging_set_waits_on_its_fence(curve):
    """fence_wait is the block on the launch that last read the staging set:
    with two sets, the third launch's fence is the first launch's verdicts."""
    dev = _device(curve)
    rng = random.Random(3)
    handles = [dev.dispatch(b"m", _requests(rng, curve)) for _ in range(3)]
    assert [dev.launch_seq(h) for h in handles] == [0, 1, 2]
    assert dev._stage[dev._stage_idx].fence is handles[2][0]
    for h in handles:
        dev.fetch(h)
    assert dev.host_fetch_launches == dev.host_pack_launches == 3


# -- (b) one seq through the engine's spans and the service's ----------------


def test_launch_spans_share_seq_in_order_per_lane(curve):
    dev = _device(curve)
    rec = FlightRecorder()
    rng = random.Random(11)
    launches = 3

    async def go():
        svc = BatchVerifierService(dev, max_delay_ms=1.0, recorder=rec)
        try:
            for _ in range(launches):
                got = await svc.verify(
                    b"m", None, _requests(rng, curve), session="s")
                assert got == [True] * C
        finally:
            svc.stop()
        return svc.plane.lanes[0]

    lane = asyncio.run(go())
    by_seq: dict = {}
    for name, ph, ts, dur, tid, cat, args, _ in rec.events():
        if ph == "X" and args and "seq" in args:
            assert tid == lane.trace_tid and args["lane"] == lane.index, name
            by_seq.setdefault(args["seq"], {})[name] = (ts, ts + dur)
    assert sorted(by_seq) == list(range(launches))
    engine = [f"launch/{s}" for s in LAUNCH_STAGES]
    service = ["launch_queued", "launch_staged", "launch_on_device",
               "launch_fetched"]
    last_end = 0.0
    for seq in range(launches):
        spans = by_seq[seq]
        assert sorted(spans) == sorted(engine + service)
        starts = [spans[n][0] for n in engine]
        assert starts == sorted(starts)  # the stages of a launch, in order
        # the engine's dispatch stages lie inside the service's staging
        # span, its fetch stages inside the service's fetch span
        s0, s1 = spans["launch_staged"]
        for n in engine[:4]:
            assert s0 - 1e-3 <= spans[n][0] and spans[n][1] <= s1 + 1e-3, n
        f0, f1 = spans["launch_fetched"]
        for n in engine[4:]:
            assert f0 - 1e-3 <= spans[n][0] and spans[n][1] <= f1 + 1e-3, n
        assert spans["launch/fence_wait"][0] >= last_end - 1e-3  # lane order
        last_end = spans["launch/enqueue"][1]
    assert not any(e[0] == "device_verify" for e in rec.events())


def test_stub_engine_launches_read_seq_none():
    """An engine that does not number its launches: the service's spans
    carry `seq: None` and nothing else changes."""
    class Stub:
        batch_size = C

        def dispatch(self, msg, reqs):
            return len(reqs)

        def fetch(self, handle):
            return [True] * handle

    rec = FlightRecorder()

    async def go():
        svc = BatchVerifierService(Stub(), max_delay_ms=1.0, recorder=rec)
        try:
            return await svc.verify(
                b"m", None, _requests(random.Random(2), _BN254))
        finally:
            svc.stop()

    assert asyncio.run(go()) == [True] * C
    seqs = {e[0]: e[6]["seq"] for e in rec.events()
            if e[0].startswith("launch_")}
    assert seqs == dict.fromkeys(
        ["launch_queued", "launch_staged", "launch_on_device",
         "launch_fetched"])


# -- (c) the same stages on the profiler's host plane ------------------------


def test_profiler_annotations_carry_seq(curve, tmp_path):
    from jax.profiler import ProfileData

    dev = _device(curve)
    dev.stage_clock.bind(None, lane=3, tid=0)
    rng = random.Random(13)
    # warm the echo; seq 0 untraced
    _launch(dev, "dispatch", _requests(rng, curve))
    dev._next_seq = 0
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        for _ in range(3):
            _launch(dev, "dispatch", _requests(rng, curve))
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(
        os.path.join(str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    seen: dict = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("handel/"):
                    assert plane.name.startswith("/host:")
                    st = dict(ev.stats)
                    assert st["lane"] == 3
                    seen.setdefault(ev.name[len("handel/"):], []).append(
                        (st["seq"], ev.start_ns))
    assert sorted(seen) == sorted(LAUNCH_STAGES)
    for stage, evs in seen.items():
        assert [seq for seq, _ in sorted(evs, key=lambda e: e[1])] == [0, 1, 2]


# -- (d) nothing listens: the clock only counts ------------------------------


class _CountingRecorder:
    def __init__(self, enabled):
        self.enabled = enabled
        self.calls = []

    def span(self, *args, **kw):
        self.calls.append((args, kw))


def test_stage_clock_without_a_listener_calls_nothing():
    clock = StageClock()
    with clock.stage("pack", 0, cpu=True):
        pass
    assert clock.ms["pack"] > 0.0 and clock.cpu_ms["pack"] >= 0.0
    assert clock.ms["stage"] == 0.0

    off = _CountingRecorder(enabled=False)
    clock.bind(off, lane=1, tid=-3)
    for seq in range(4):
        with clock.stage("enqueue", seq):
            pass
    assert off.calls == []  # no span, so no args dict was built for one

    on = _CountingRecorder(enabled=True)
    clock.bind(on, lane=1, tid=-3)
    with clock.stage("enqueue", 7):
        pass
    ((args, kw),) = on.calls
    assert args[0] == "launch/enqueue" and args[2] >= args[1]
    assert kw["tid"] == -3 and kw["args"] == {"seq": 7, "lane": 1}
    before = clock.ms["enqueue"]
    with pytest.raises(ValueError):
        with clock.stage("enqueue", 8):
            raise ValueError("a stage that fails is still timed")
    assert clock.ms["enqueue"] > before and len(on.calls) == 1
    clock.reset()
    assert not any(clock.ms.values()) and not any(clock.cpu_ms.values())


# -- (f) names on the device side ---------------------------------------------


def test_jitted_programs_have_stable_names(curve):
    rlc = {"batch_check": "rlc"} if curve.key_group == 2 else {}
    dev = _device(curve, **rlc)
    assert dev._kernel.__name__ == "verify_dense"
    assert dev._combine_kernel(4).__name__ == "combine4"
    assert dev._prefix_table_kernel().__name__ == "prefix_table"
    real = curve.Device(curve.pubkeys(N), batch_size=C, **rlc)
    jitted = lambda fn: fn.__defaults__[0]  # the bank-injection wrappers
    if rlc:
        assert real._rlc_check_kernel(2).__name__ == "rlc_check2"
        assert jitted(
            real._rlc_msm_kernel("dense", 0, 1)).__name__ == "rlc_msm_dense"
        assert jitted(
            real._rlc_msm_kernel("range", 8, 2)).__name__ == "rlc_msm_range8"
    # the range classes build the prefix table first: its program too
    assert jitted(real._range_agg_kernel(8)).__name__ == "range_agg8"
    assert jitted(real._range_kernel(8)).__name__ == "verify_range8"
    assert jitted(real._range_kernel(64)).__name__ == "verify_range64"
    # what the profiler's "XLA Modules" line will print
    fn = jax.jit(_named(lambda x: x + 1, "verify_range8"))
    assert "jit_verify_range8" in fn.lower(1.0).as_text()


def test_launch_phases_are_named_scopes(curve):
    """agg / to_affine / miller_loop / final_exp reach the lowered program
    as name-stack metadata (the aggregation stage alone: seconds)."""
    dev = curve.Device(curve.pubkeys(N), batch_size=C)
    plan = dev._pack_requests(_requests(random.Random(17), curve))
    args = dev._stage_plan(plan)[:4]
    jitted = dev._range_agg_kernel(plan.miss_k).__defaults__[0]
    text = jitted.lower(
        *args, dev._prefix, dev._reg_x, dev._reg_y
    ).as_text(debug_info=True)
    assert "jit(range_agg8)/agg/" in text
    # the pairing's two phases are scoped where both curve families pass
    from handel_tpu.ops.pairing import BLS12Pairing, BN254Pairing

    for cls in (BN254Pairing, BLS12Pairing):
        assert cls.final_exp.__wrapped__.__name__ == "final_exp"
        assert cls._miller_loop_res.__wrapped__.__name__ == "_miller_loop_res"
