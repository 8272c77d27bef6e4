"""The traffic generator: deterministic in the seed, the same work for every
seed, and class-pure in the four cells — every launch packs to the one class
the traffic file names (range miss_k=8 in cells 1 and 3, range miss_k=1024 in
cells 2 and 4) — while the mixed stream reaches three classes, which the
warm-up's selection separates. Checked with the program's own
`_pack_requests` on the CPU (dummy keys: packing reads bitsets and signature
points only)."""

import random
from collections import Counter

import pytest

import spec
import traffic as tg

ORDER = 21888242871839275222246405745257275088548364400416034343698204186575808495617
N_KEYS, LANES = 4096, 128
CELLS = [
    "handel4096-99thr.closed256",
    "handel4096-51thr-failing.closed256",
    "bls12-381-4096.closed256",
    "bls12-381-minpk-4096-failing.closed256",
]
MIXED = "closed256-mixed-levels"


def packs_to(class_name: str) -> tuple[str, int]:
    """A ladder class's name as the packer's (kind, miss_k)."""
    return ("dense", 0) if class_name == "dense" else (
        "range", int(class_name[len("range"):]))


def make_pool(cell_name: str, seed: int, pool_requests: int = 256, traffic=None):
    cell = spec.Cell(cell_name)
    tr = dict(spec.load_traffic(traffic) if traffic else cell.traffic,
              pool_requests=pool_requests)
    sks = [tg.stream(seed, tg.KEYS).randrange(1, ORDER) for _ in range(N_KEYS)]
    failing = tg.failing_ids(seed, N_KEYS, cell.config["deployment"]["failing"])
    return cell, tg.build_pool(tr, seed, sks, failing, ORDER)


def shape(pool):
    return [[(c.lo, c.size, c.holes, c.forged, c.agg_sk) for c in r] for r in pool]


@pytest.mark.parametrize("cell_name", CELLS)
def test_deterministic_in_seed(cell_name):
    big = 2**31 + 12345  # the driver's seeds are large
    assert shape(make_pool(cell_name, big)[1]) == shape(make_pool(cell_name, big)[1])
    assert shape(make_pool(cell_name, big)[1]) != shape(make_pool(cell_name, 7)[1])


@pytest.mark.parametrize("cell_name", CELLS)
def test_every_seed_gets_the_same_work(cell_name):
    """Sizes of requests and of ranges are one multiset, in another order."""
    def work(seed):
        pool = make_pool(cell_name, seed)[1]
        return (Counter(len(r) for r in pool),
                Counter(c.size for r in pool for c in r),
                sum(c.forged for r in pool for c in r))
    assert work(1) == work(2**31 + 5) == work(99)


def test_failing_ids_are_exact():
    ids = tg.failing_ids(5, N_KEYS, 1024)
    assert len(ids) == 1024 and ids == tg.failing_ids(5, N_KEYS, 1024)
    assert tg.failing_ids(5, N_KEYS, 0) == frozenset()


@pytest.fixture(scope="module")
def engine():
    from handel_tpu.models.bn254 import BN254PublicKey
    from handel_tpu.models.bn254_jax import BN254Device
    from handel_tpu.ops import bn254_ref as bn

    return BN254Device([BN254PublicKey(bn.G2_GEN)] * N_KEYS, batch_size=LANES)


def request(c):
    from handel_tpu.core.bitset import BitSet
    from handel_tpu.models.bn254 import BN254Signature
    from handel_tpu.ops import bn254_ref as bn

    bs = BitSet(N_KEYS)
    bs.set_range(c.lo, c.lo + c.size)
    for i in c.holes:
        bs.set(i, False)
    return bs, BN254Signature(bn.G1_GEN)


@pytest.mark.parametrize("cell_name", CELLS)
def test_class_pure(cell_name, engine):
    """Whatever candidates the collector puts side by side, the launch packs
    to the cell's one class: full launches, a partial one, a single one.
    (The packer is one for every scheme; the BN254 engine stands for all.)"""
    cell, pool = make_pool(cell_name, 31337)
    (name,) = cell.traffic["launch_classes"]
    ladder = {c["name"]: c for c in cell.config["guarantees"]["launch_classes"]}
    lo, hi = ladder[name]["hull_holes"]
    flat = [c for r in pool for c in r]
    assert all(lo <= c.hull_holes() <= hi for c in flat)
    rng = random.Random(1)
    for width in (LANES, LANES, LANES, 37, 1):
        plan = engine._pack_requests([request(c) for c in rng.sample(flat, width)])
        assert (plan.kind, plan.miss_k) == packs_to(name)


def test_ladder_is_the_engines(engine):
    """The intervals the configurations state are the packer's thresholds."""
    for cell_name in CELLS:
        ladder = spec.Cell(cell_name).config["guarantees"]["launch_classes"]
        assert [c["hull_holes"][0] for c in ladder] == [
            0, *(c["hull_holes"][1] + 1 for c in ladder[:-1])]
        assert ladder[-1]["hull_holes"][1] == N_KEYS - 1
        for c in ladder:
            for holes in c["hull_holes"]:
                k = engine._patch_width(holes)
                assert (("range", k) if k else ("dense", 0)) == packs_to(c["name"])


def test_mixed_stream_classes(engine):
    """The mixed traffic holds whole launches of each of its three classes;
    a launch made by the warm-up's selection packs to the class it is made
    for (full, partial, single), and a random launch of the stream packs
    wide: one candidate over 64 holes decides for all 128 lanes."""
    import run

    cell, pool = make_pool("handel4096-51thr-failing.closed256", 31337,
                           pool_requests=512, traffic=MIXED)
    named = spec.load_traffic(MIXED)["launch_classes"]
    assert named == ["range8", "range64", "range1024"]
    flat = [(None, c) for r in pool for c in r]
    batches = run.warm_batches(
        flat, cell.config["guarantees"]["launch_classes"], named, LANES)
    assert [(n, k) for n, k, _ in batches] == [
        (n, k) for n in named for k in range(run.WARM_LAUNCHES)]
    for name, k, batch in batches:
        cands = [c for _, c in batch]
        assert len({id(c) for c in cands}) == LANES  # a whole launch, no repeat
        for width in (LANES, 37, 1):
            plan = engine._pack_requests([request(c) for c in cands[:width]])
            assert (plan.kind, plan.miss_k) == packs_to(name)
    rng = random.Random(2)
    everything = [c for _, c in flat]
    for _ in range(5):
        plan = engine._pack_requests(
            [request(c) for c in rng.sample(everything, LANES)])
        assert (plan.kind, plan.miss_k) == ("range", 1024)


def test_arrival_clock():
    a = spec.load_traffic("open-poisson-levels")["arrival"]
    one = tg.arrival_offsets(a, 30.0, 2**31 + 9)
    assert one == tg.arrival_offsets(a, 30.0, 2**31 + 9)
    assert one != tg.arrival_offsets(a, 30.0, 3)
    assert one == sorted(one) and 0 < one[0] and one[-1] < 30.0
    # burst x2 for 0.5 s of every 5 s: mean rate 1.1 x the floor
    mean = a["rate_rps"] * (1 + (a["burst_x"] - 1) * a["burst_len_s"] / a["burst_every_s"])
    assert abs(len(one) / 30.0 - mean) < 0.1 * mean
    in_burst = sum((t % a["burst_every_s"]) < a["burst_len_s"] for t in one)
    assert in_burst / len(one) > 0.15  # 0.1 of the time, twice the rate
