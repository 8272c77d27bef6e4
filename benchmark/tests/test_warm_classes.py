"""What PR 37 made the harness do, without a launch: the generator survives a
range with no live signer and leaves the committed pools as they were, the
warm-up selects class-pure launches for every class the traffic names, and the
class checks fail when the program's counters say another class ran. No
pairing program compiles here (a stub engine records what it is handed)."""

import hashlib
import json
from collections import Counter

import pytest

import correct
import run
import spec
import traffic as tg

ORDER = 21888242871839275222246405745257275088548364400416034343698204186575808495617
N_KEYS, LANES = 4096, 128
LADDER = spec.Cell("handel4096-51thr-failing.closed256").config[
    "guarantees"]["launch_classes"]
MIXED = "closed256-mixed-levels"

# sha256 of the pool's (lo, size, holes, forged), taken on the parent commit
# (969fef7) with this file's `digest`: (traffic file, failing ids, seed)
PARENT_POOLS = {
    ("closed256-levels", 0, 1):
        "1df0a4063dc7bc6974738b86fbcca0326adfac3b22f56ffd192d52897216804f",
    ("closed256-levels", 0, 2**31 + 12345):
        "1c20c1eda07c946a6fcdf35c52594c6a38ff6b3bba0214a5025a2cd49005dbc2",
    ("closed256-upper-levels", 1024, 1):
        "7fba95566b5f526c18030cf322ab983d24e133a7088a822f6319340c35087e93",
    ("closed256-upper-levels", 1024, 2**31 + 12345):
        "bff9aef2b658800b74f11f1785004648d5a247b30087d174647599f953e9b496",
    ("open-poisson-levels", 0, 1):
        "1df0a4063dc7bc6974738b86fbcca0326adfac3b22f56ffd192d52897216804f",
    ("open-poisson-levels", 0, 2**31 + 12345):
        "1c20c1eda07c946a6fcdf35c52594c6a38ff6b3bba0214a5025a2cd49005dbc2",
}


def build(traffic_name: str, failing: int, seed: int, **over):
    tr = dict(spec.load_traffic(traffic_name), **over)
    sks = [tg.stream(seed, tg.KEYS).randrange(1, ORDER) for _ in range(N_KEYS)]
    return tg.build_pool(tr, seed, sks, tg.failing_ids(seed, N_KEYS, failing), ORDER)


def digest(pool) -> str:
    shape = [[(c.lo, c.size, list(c.holes), c.forged) for c in r] for r in pool]
    return hashlib.sha256(json.dumps(shape).encode()).hexdigest()


@pytest.mark.parametrize("traffic_name,failing,seed", PARENT_POOLS)
def test_committed_pools_are_the_parents(traffic_name, failing, seed):
    assert digest(build(traffic_name, failing, seed)) == PARENT_POOLS[
        traffic_name, failing, seed]


def class_of(c) -> str:
    holes = c.hull_holes()
    return next(cls["name"] for cls in LADDER
                if cls["hull_holes"][0] <= holes <= cls["hull_holes"][1])


def test_hull_holes_counts_between_the_outer_signers():
    """Against the plain definition (the hull's width less its signers), on
    a pool whose level-1 to level-4 ranges lose ids at their ends."""
    flat = [c for r in build(MIXED, 1024, 11, pool_requests=512) for c in r]
    for c in flat:
        s = c.signers()
        assert c.hull_holes() == (s[-1] - s[0] + 1) - len(s)
    assert any(c.hull_holes() < len(c.holes) for c in flat)
    assert tg.Candidate(8, 4, (8, 9, 10, 11), False, 0).hull_holes() == 0


@pytest.mark.parametrize("seed", [1, 2, 3, 4, 5])
def test_mixed_pool_builds(seed):
    """Levels 1-12 of the failing committee: one level-1 range in four has no
    live id (the parent's generator raised ValueError there); every candidate
    has a signer, every seed gets the same sizes, and the three classes hold
    0.43 / 0.27 / 0.30 of the candidates."""
    flat = [c for r in build(MIXED, 1024, seed) for c in r]
    assert len(flat) == 9216 and all(c.signers() for c in flat)
    assert Counter(c.size for c in flat) == {1 << l: 768 for l in range(12)}
    share = Counter(class_of(c) for c in flat)
    for name, want in (("range8", 0.43), ("range64", 0.27), ("range1024", 0.30)):
        assert abs(share[name] / len(flat) - want) < 0.02, share
    assert share["dense"] == 0


class StubEngine:
    """Records the launches it is handed; a request IS its candidate."""

    def __init__(self):
        self.launches = []

    def dispatch(self, msg, requests):
        self.launches.append(list(requests))
        return requests

    def fetch(self, handle):
        return [not c.forged for c in handle]


class NoMeter:
    def take(self):
        return {}


def warm(pool, named, engines=1):
    stubs = [StubEngine() for _ in range(engines)]
    made, wrong = run.warm(stubs, b"m", pool, pool, LANES, LADDER, named, NoMeter())
    return stubs, made, wrong


@pytest.mark.parametrize("traffic_name,failing,named", [
    ("closed256-levels", 0, ["range8"]),
    ("closed256-upper-levels", 1024, ["range1024"]),
])
def test_one_class_cell_warms_as_the_parent_did(traffic_name, failing, named, capsys):
    """The parent's rule: launch k is flat[(k * lanes + j) % len(flat)]."""
    pool = build(traffic_name, failing, 7, pool_requests=64)
    assert spec.load_traffic(traffic_name)["launch_classes"] == named
    flat = [c for r in pool for c in r]
    (stub,), made, wrong = warm(pool, named)
    assert made == run.WARM_LAUNCHES and wrong == 0
    assert stub.launches == [
        [flat[(k * LANES + j) % len(flat)] for j in range(LANES)]
        for k in range(run.WARM_LAUNCHES)]
    phases = [json.loads(l)["phase"] for l in capsys.readouterr().out.splitlines()]
    assert phases == [f"warm_launch_{named[0]}_{k}" for k in range(run.WARM_LAUNCHES)]


def test_mixed_pool_warms_class_pure(capsys):
    """Narrowest class first, three launches a class and an engine, each of
    one class's candidates only, in pool order."""
    pool = build(MIXED, 1024, 7, pool_requests=256)
    named = spec.load_traffic(MIXED)["launch_classes"]
    stubs, made, wrong = warm(pool, named, engines=2)
    assert made == 2 * 3 * run.WARM_LAUNCHES and wrong == 0
    flat = [c for r in pool for c in r]
    for stub in stubs:
        got = [{class_of(c) for c in launch} for launch in stub.launches]
        assert got == [{n} for n in named for _ in range(run.WARM_LAUNCHES)]
        assert all(len(launch) == LANES for launch in stub.launches)
        own = [c for c in flat if class_of(c) == "range64"]
        assert stub.launches[run.WARM_LAUNCHES] == own[:LANES]
    assert stubs[0].launches == stubs[1].launches
    phases = [json.loads(l)["phase"] for l in capsys.readouterr().out.splitlines()]
    assert phases[:4] == ["warm_launch_range8_0", "warm_launch_range8_1",
                          "warm_launch_range8_2", "warm_launch_range64_0"]


def test_forged_verdicts_are_counted():
    pool = build("closed256-levels", 0, 7, pool_requests=64)

    class Accepting(StubEngine):
        def fetch(self, handle):
            return [True] * len(handle)

    stub = Accepting()
    made, wrong = run.warm([stub], b"m", pool, pool, LANES, LADDER, ["range8"], NoMeter())
    assert wrong == sum(c.forged for launch in stub.launches for c in launch) > 0


@pytest.mark.parametrize("named,says", [
    (["range8", "range64"], "'range64'"),   # the 99 % pool never leaves range8
    (["dense"], "'dense'"),
    (["range8", "range512"], "range512"),   # not a class of the ladder
])
def test_a_class_the_pool_cannot_fill_fails(named, says):
    pool = build("closed256-levels", 0, 7, pool_requests=64)
    with pytest.raises(run.BenchFailure, match=says):
        warm(pool, named)


def counters(**ran) -> dict:
    return {cls["counter"]: ran.get(cls["name"], 0) for cls in LADDER}


def test_class_checks_hold_the_counters():
    named = ["range8", "range64", "range1024"]
    sound = counters(range8=3, range64=3, range1024=3)
    checks = correct.warmed_classes(LADDER, named, sound, 3)
    assert [c.name for c in checks] == [
        "warm_launches_range8", "warm_launches_range64",
        "warm_launches_range1024", "warm_launches_dense"]
    assert all(c.ok for c in checks)
    assert correct.unwarmed_class_launches(
        LADDER, named, counters(range8=40, range64=3, range1024=900)).ok


@pytest.mark.parametrize("named,ran,failing", [
    # the interval of a data file is wrong: range64's launches packed wide
    (["range8", "range64", "range1024"], dict(range8=3, range1024=6),
     ["warm_launches_range64", "warm_launches_range1024"]),
    # the traffic file says dense, the engine ran range1024 (the stale label)
    (["dense"], dict(range1024=3), ["warm_launches_range1024", "warm_launches_dense"]),
    # one launch short
    (["range8"], dict(range8=2), ["warm_launches_range8"]),
])
def test_class_checks_fail_on_another_class(named, ran, failing):
    checks = correct.warmed_classes(LADDER, named, counters(**ran), 3)
    assert [c.name for c in checks if not c.ok] == failing


def test_a_launch_of_an_unwarmed_class_fails():
    check = correct.unwarmed_class_launches(
        LADDER, ["range8"], counters(range8=500, range64=2))
    assert (check.name, check.value, check.limit, check.ok) == (
        "unwarmed_class_launches", 2, 0, False)
    assert "NOT OK" in check.line()
