"""The one general traffic generator: a request pool and its arrivals.

A traffic mix is a data file (traffic/<name>.json); this module turns it,
a configuration and a seed into

  * a POOL of verify requests — each 1..k candidates, a candidate being an
    aligned level range of the binomial tree minus its holes, with an
    aggregate BLS signature over exactly its signers (a seeded share of them
    forged: a well-formed G1 point that signs nothing);
  * for an open loop, the arrival clock (offsets into the run).

Every seed gets the SAME multiset of request sizes, levels and hole counts
(stratified, then shuffled by the seed), so a seed changes the order and
the keys, not the amount of work.

The arrival arithmetic (`rate_at`, `peak_rate`, `arrival_offsets`,
Lewis-Shedler thinning) is copied from handel_tpu/sim/load.py, which drives
fake-scheme sessions and is not imported.

Traffic file keys:
  loop                     "closed" | "open"
  clients                  closed: concurrent clients; open: sessions
  candidates_per_request   [lo, hi], uniform
  levels                   [lo, hi] for the configuration's registry size;
                           level l is an aligned range of 2**(l-1) ids
  holes                    {"rule": "uniform", "max": h}: 0..min(h, size-1)
                           seeded ids absent (signers not yet aggregated);
                           {"rule": "failing", "max": h, "min": m}: the
                           configuration's failing ids are absent as well,
                           and the hull keeps at least m holes (no "min":
                           0); a drawn range with no live id is redrawn
  forged_share             share of candidates forged (at least one)
  dedup                    "fresh_scope": every request its own dedup scope;
                           "shared_scope": all clients share one
  pool_requests            distinct requests generated (replayed in order)
  launch_classes           the launch classes the mix's candidates reach, by
                           names of the configuration's ladder
                           (guarantees.launch_classes): run.py warms each of
                           them and fails a launch of any other
  arrival                  open only: {"model": "poisson"|"burst",
                           "rate_rps", "burst_x", "burst_every_s",
                           "burst_len_s"}
"""

from __future__ import annotations

import random
from dataclasses import dataclass

# seed streams: one generator per purpose, so adding a draw to one never
# shifts another
KEYS, FAILING, POOL, ARRIVALS, SAMPLE = range(5)


def stream(seed: int, which: int) -> random.Random:
    return random.Random(int(seed) * 1_000_003 + 17 * which + 1)


@dataclass
class Candidate:
    lo: int           # aligned range [lo, lo + size)
    size: int
    holes: tuple      # absent ids inside the range
    forged: bool
    agg_sk: int       # aggregate secret the signature was made from
    sig: tuple | None = None  # G1 point

    def signers(self) -> list[int]:
        gone = set(self.holes)
        return [i for i in range(self.lo, self.lo + self.size) if i not in gone]

    def hull_holes(self) -> int:
        """Absent ids between the first and the last signer: the holes less
        the runs of them at the range's two ends (`holes` is ascending)."""
        n, first, last = len(self.holes), self.lo, self.lo + self.size - 1
        head = 0
        while head < n and self.holes[head] == first + head:
            head += 1
        tail = 0
        while tail < n - head and self.holes[n - 1 - tail] == last - tail:
            tail += 1
        return n - head - tail


def _cycle(values, n: int, rng: random.Random) -> list:
    """n values covering `values` evenly, in seeded order."""
    out = [values[i % len(values)] for i in range(n)]
    rng.shuffle(out)
    return out


def failing_ids(seed: int, n_keys: int, failing: int) -> frozenset:
    """The deployment's failing nodes: exactly `failing` seeded ids."""
    if not failing:
        return frozenset()
    return frozenset(stream(seed, FAILING).sample(range(n_keys), failing))


def build_pool(traffic: dict, seed: int, sks: list[int], failing: frozenset,
               order: int) -> list[list[Candidate]]:
    """The request pool, unsigned (see `sign_pool`). `order` is the group
    order the aggregate secrets are reduced by."""
    rng = stream(seed, POOL)
    n_keys = len(sks)
    n_req = int(traffic["pool_requests"])
    lo_c, hi_c = traffic["candidates_per_request"]
    sizes = _cycle(list(range(lo_c, hi_c + 1)), n_req, rng)
    n_cand = sum(sizes)
    l_lo, l_hi = traffic["levels"]
    levels = list(range(l_lo, l_hi + 1))
    if l_lo < 1 or (1 << (l_hi - 1)) > n_keys // 2:
        raise ValueError(f"levels {traffic['levels']} do not fit {n_keys} ids")
    cand_levels = _cycle(levels, n_cand, rng)
    rule = traffic["holes"]
    if rule["rule"] not in ("uniform", "failing"):
        raise ValueError(f"unknown hole rule {rule['rule']!r}")
    hole_counts = _cycle(list(range(rule["max"] + 1)), n_cand, rng)
    n_forged = max(1, round(traffic["forged_share"] * n_cand))
    forged = set(rng.sample(range(n_cand), n_forged))
    # prefix sums of the secrets: a range's aggregate secret in O(holes)
    pre = [0]
    for sk in sks:
        pre.append(pre[-1] + sk)

    by_failing = rule["rule"] == "failing"
    split: dict = {}  # (lo, size) -> (failing ids of the range, live ids)

    def range_split(lo: int, size: int):
        if (lo, size) not in split:
            ids = range(lo, lo + size)
            split[lo, size] = (
                [i for i in ids if i in failing],
                [i for i in ids if i not in failing],
            ) if by_failing else ([], ids)
        return split[lo, size]

    pool, c = [], 0
    for k in sizes:
        req: list[Candidate] = []
        # a node never sends one aggregate twice in a batch: signer sets in
        # a request are distinct (the service would coalesce equal ones)
        seen: set = set()
        for _ in range(k):
            size = 1 << (cand_levels[c] - 1)
            for attempt in range(100):
                lo = rng.randrange(n_keys // size) * size
                gone, live = range_split(lo, size)
                # a level with few ranges runs out of fresh candidates at a
                # given hole count: later attempts move the count on. A
                # range with no live id (a level-1 range whose one id fails)
                # draws no hole and, having no signer, is redrawn below
                extra = max(0, min(
                    (hole_counts[c] + attempt // 4) % (rule["max"] + 1),
                    len(live) - 1))
                holes = tuple(sorted(gone + rng.sample(live, extra)))
                drawn = Candidate(lo, size, holes, False, 0)
                signers = tuple(drawn.signers())
                if (signers and signers not in seen
                        and drawn.hull_holes() >= rule.get("min", 0)):
                    break
            else:
                raise ValueError(
                    f"no fresh level-{cand_levels[c]} range for a request of "
                    f"{k} under hole rule {rule}"
                )
            seen.add(signers)
            agg = (pre[lo + size] - pre[lo] - sum(sks[i] for i in holes)) % order
            is_forged = c in forged
            req.append(Candidate(
                lo, size, holes, is_forged,
                (agg + 1) % order if is_forged else agg,
            ))
            c += 1
        pool.append(req)
    return pool


def sign_pool(pool, msg: bytes, sign_batch) -> None:
    """Aggregate signatures for every candidate, in one batch call of the
    reference's `sign_batch(msg, scalars)`."""
    flat = [c for req in pool for c in req]
    for c, pt in zip(flat, sign_batch(msg, [c.agg_sk for c in flat])):
        c.sig = pt


def make_pool(cfg: dict, traffic: dict, seed: int, ref):
    """Everything a run draws from the seed, with the reference's arithmetic
    only: the registry's public keys (raw G2 points), the signed request
    pool and the message. `ref` is the configuration's reference module."""
    n_keys = int(cfg["registry_keys"])
    sks, points = ref.keygen(stream(seed, KEYS), n_keys)
    failing = failing_ids(seed, n_keys, int(cfg["deployment"]["failing"]))
    pool = build_pool(traffic, seed, sks, failing, ref.R)
    msg = cfg["message"].encode()
    sign_pool(pool, msg, ref.sign_batch)
    return points, pool, msg


def client_order(n_req: int, clients: int, seed: int) -> list[int]:
    """Where each client starts in the pool: evenly spread, seeded phase."""
    phase = stream(seed, ARRIVALS).randrange(n_req)
    return [(phase + i * n_req // clients) % n_req for i in range(clients)]


# -- arrival models (copied arithmetic, see module docstring) -----------------


def rate_at(a: dict, t: float) -> float:
    """Instantaneous arrival rate (requests/s) at offset t."""
    if a["model"] == "burst":
        in_burst = (t % a["burst_every_s"]) < a["burst_len_s"]
        return a["rate_rps"] * (a["burst_x"] if in_burst else 1.0)
    if a["model"] != "poisson":
        raise ValueError(f"unknown arrival model {a['model']!r}")
    return a["rate_rps"]


def peak_rate(a: dict) -> float:
    if a["model"] == "burst":
        return a["rate_rps"] * max(1.0, a["burst_x"])
    return a["rate_rps"]


def arrival_offsets(a: dict, duration_s: float, seed: int) -> list[float]:
    """Seeded arrival clock: Lewis-Shedler thinning against the peak rate
    keeps the burst model exact; one stream keeps the trace reproducible."""
    rng = stream(seed, ARRIVALS)
    peak = peak_rate(a)
    out: list[float] = []
    t = 0.0
    while True:
        t += rng.expovariate(peak)
        if t >= duration_s:
            return out
        if rng.random() * peak <= rate_at(a, t):
            out.append(t)
