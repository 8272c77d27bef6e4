"""The traffic generator: deterministic in the seed, the same work for every
seed, and class-pure — every launch of cell 1 packs to range miss_k=8 and of
cell 2 to dense, checked with the program's own `_pack_requests` on the CPU
(dummy keys: packing reads bitsets and signature points only)."""

import random
from collections import Counter

import pytest

import spec
import traffic as tg

ORDER = 21888242871839275222246405745257275088548364400416034343698204186575808495617
N_KEYS, LANES = 4096, 128
CELLS = {
    "handel4096-99thr.closed256": ("range", 8),
    "handel4096-51thr-failing.closed256": ("dense", 0),
}


def make_pool(cell_name: str, seed: int, pool_requests: int = 256):
    cell = spec.Cell(cell_name)
    tr = dict(cell.traffic, pool_requests=pool_requests)
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


@pytest.mark.parametrize("cell_name", CELLS)
def test_class_pure(cell_name, engine):
    """Whatever candidates the collector puts side by side, the launch packs
    to the cell's one class: full launches, a partial one, a single one."""
    from handel_tpu.core.bitset import BitSet
    from handel_tpu.models.bn254 import BN254Signature
    from handel_tpu.ops import bn254_ref as bn

    cell, pool = make_pool(cell_name, 31337)
    kind, miss_k = CELLS[cell_name]
    assert cell.traffic["launch_class"] == (f"range{miss_k}" if miss_k else "dense")
    flat = [c for r in pool for c in r]
    for c in flat:
        holes = c.hull_holes()
        assert holes <= 8 if kind == "range" else holes > engine.MISS_CAP

    def request(c):
        bs = BitSet(N_KEYS)
        bs.set_range(c.lo, c.lo + c.size)
        for i in c.holes:
            bs.set(i, False)
        return bs, BN254Signature(bn.G1_GEN)

    rng = random.Random(1)
    for width in (LANES, LANES, LANES, 37, 1):
        plan = engine._pack_requests([request(c) for c in rng.sample(flat, width)])
        assert (plan.kind, plan.miss_k) == (kind, miss_k)


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
