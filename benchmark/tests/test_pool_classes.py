"""The pool of a traffic file that names more than one launch class holds
the same multiset of (level, class) for every seed (PR 44: cell 5's rate
followed the seed's failing set through its class mix), and the files that
name ONE class draw exactly what they drew before: their pools, at three
seeds, hash to what the parent commit's `build_pool` gave
(golden/one_class_pools.json, made at the parent)."""

import hashlib
import json
import os
from collections import Counter

import pytest

import spec
import traffic as tg

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "golden", "one_class_pools.json")) as f:
    GOLDEN = json.load(f)
ORDER, N_KEYS = GOLDEN["order"], 4096
MIXED = "closed256-mixed-levels"
SIX_SEEDS = [1, 7, 99, 2**31 + 12345, 4400000001, 4400000002]


def config(name: str) -> dict:
    with open(os.path.join(spec.BENCH_DIR, "configs", f"{name}.json")) as f:
        return json.load(f)


def pool_of(traffic_name, cfg, seed, stats=None, **changed):
    tr = dict(spec.load_traffic(traffic_name), **changed)
    sks = [tg.stream(seed, tg.KEYS).randrange(1, ORDER) for _ in range(N_KEYS)]
    failing = tg.failing_ids(seed, N_KEYS, cfg["deployment"]["failing"])
    return tg.build_pool(tr, seed, sks, failing, ORDER,
                         cfg["guarantees"]["launch_classes"], stats)


@pytest.mark.parametrize("traffic_name,seed", [
    (name, int(seed)) for name, entry in GOLDEN["pools"].items()
    for seed in entry["sha256_by_seed"]])
def test_one_class_pools_are_the_parents(traffic_name, seed):
    entry = GOLDEN["pools"][traffic_name]
    pool = pool_of(traffic_name, config(entry["config"]), seed)
    shape = [[(c.lo, c.size, list(c.holes), c.forged, c.agg_sk) for c in r]
             for r in pool]
    assert hashlib.sha256(json.dumps(shape).encode()).hexdigest() == \
        entry["sha256_by_seed"][str(seed)]


def level_classes(pool, ladder) -> Counter:
    return Counter((c.size.bit_length(), ladder[tg.class_of(c.hull_holes(), ladder)]["name"])
                   for r in pool for c in r)


def test_mixed_pool_is_one_multiset_of_level_and_class():
    cfg = config("handel4096-51thr-failing")
    ladder = cfg["guarantees"]["launch_classes"]
    seen = []
    for seed in SIX_SEEDS:
        stats = {}
        pool = pool_of(MIXED, cfg, seed, stats)
        assert stats == {"class_target_misses": 0}
        seen.append(level_classes(pool, ladder))
        sizes = Counter(len(r) for r in pool)
        assert sizes == Counter({k: 256 for k in range(1, 9)})
    assert all(s == seen[0] for s in seen)
    by_class = Counter()
    for (_, name), n in seen[0].items():
        by_class[name] += n
    assert set(by_class) == {"range8", "range64", "range1024"}
    # whole launches of each class for the warm-up, and the stream's own mix:
    # levels 10-12 and most of level 9 are wide, three tenths of the candidates
    assert min(by_class.values()) >= 3 * 128
    assert by_class["range1024"] / sum(by_class.values()) == pytest.approx(0.304, abs=0.002)
    # and the pool is still the seed's own: other ranges, other holes
    a, b = (pool_of(MIXED, cfg, s, pool_requests=64) for s in SIX_SEEDS[:2])
    assert [(c.lo, c.holes) for r in a for c in r] != [(c.lo, c.holes) for r in b for c in r]


def test_class_shares_against_draws():
    """The expectation the targets are made from is what the draws give:
    level 6 of the failing committee (32 ids, 8 failing on the mean, 0-8
    more absent), a thousand seeded ranges."""
    cfg = config("handel4096-51thr-failing")
    ladder = cfg["guarantees"]["launch_classes"]
    shares = tg.class_shares(32, N_KEYS, 1024, 8, ladder)
    assert sum(shares) == pytest.approx(1.0) and shares[2] == shares[3] == 0
    drawn = Counter()
    for seed in range(40):
        pool = pool_of("closed256-upper-levels", cfg, seed, levels=[6, 6],
                       holes={"rule": "failing", "max": 8}, pool_requests=8)
        drawn.update(tg.class_of(c.hull_holes(), ladder) for r in pool for c in r)
    assert drawn[0] / sum(drawn.values()) == pytest.approx(shares[0], abs=0.04)
    # one id: it is live or the range is redrawn, so never a hole
    assert tg.class_shares(1, N_KEYS, 1024, 8, ladder)[0] == 1.0


def test_target_a_seed_cannot_fill_is_counted():
    """No failing id and no extra hole: every draw is `range8`, so targets of
    a ladder whose narrow class wants 1-8 hull holes cannot be met; the draw
    nearest to the target is taken and the miss counted."""
    cfg = config("handel4096-51thr-failing")
    ladder = [dict(cfg["guarantees"]["launch_classes"][0], name="none", hull_holes=[0, 0]),
              dict(cfg["guarantees"]["launch_classes"][1], name="some", hull_holes=[1, 4095])]
    tr = dict(spec.load_traffic(MIXED), pool_requests=8, levels=[4, 4],
              launch_classes=["none", "some"])
    stats = {}
    failing = tg.failing_ids(3, N_KEYS, 1024)
    pool = tg.build_pool(tr, 3, [1] * N_KEYS, failing, ORDER, ladder, stats)
    n = sum(len(r) for r in pool)
    got = Counter(tg.class_of(c.hull_holes(), ladder) for r in pool for c in r)
    want = tg.class_shares(8, N_KEYS, 1024, 8, ladder)
    assert stats["class_target_misses"] <= 2  # reached but for a rare draw
    assert got[0] == pytest.approx(want[0] * n, abs=2.5)
