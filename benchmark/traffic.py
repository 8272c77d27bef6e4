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
                           them and fails a launch of any other. Where a
                           file names MORE THAN ONE, every seed's pool holds
                           the same multiset of (level, class): the counts a
                           level's candidates are held to are the
                           expectation under the configuration's failing
                           share (`class_shares`), which no seed moves, and
                           a drawn range whose class misses its target is
                           redrawn (`build_pool`). A one-class file draws as
                           it always did
  arrival                  open only: {"model": "poisson"|"burst",
                           "rate_rps", "burst_x", "burst_every_s",
                           "burst_len_s"}
"""

from __future__ import annotations

import math
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


RARE_CLASS = 0.02  # a class fewer of a level's draws reach is no target there


def class_of(hull_holes: int, ladder: list[dict]) -> int:
    """Index into the configuration's `ladder` of the launch class a
    candidate with that many hull holes takes."""
    for i, cls in enumerate(ladder):
        if cls["hull_holes"][0] <= hull_holes <= cls["hull_holes"][1]:
            return i
    raise ValueError(f"no launch class takes {hull_holes} hull holes")


def _log_comb(n: int, k: int) -> float:
    return math.lgamma(n + 1) - math.lgamma(k + 1) - math.lgamma(n - k + 1)


def class_shares(size: int, n_keys: int, n_failing: int, max_extra: int,
                 ladder: list[dict]) -> list[float]:
    """The share of each ladder class among the candidates of one level (an
    aligned range of `size` ids) under the `failing` hole rule, as the
    EXPECTATION over failing sets — no seed enters. The range's failing ids
    are hypergeometric (`n_failing` of `n_keys`, `size` drawn); 0..max_extra
    more live ids are absent, each count as likely, at most all but one; the
    h absent ids are a uniform subset of the range, and the runs of them at
    its two ends, which the hull leaves out, are t ids long together with
    probability (t + 1) C(size - t - 2, h - t) / C(size, h), taken here by
    its ratio from t to t + 1. A range with no live id is redrawn by the
    generator and is left out here."""
    shares = [0.0] * len(ladder)
    f_lo = max(0, size - (n_keys - n_failing))
    f_hi = min(size - 1, n_failing)  # a live id is left
    for f in range(f_lo, f_hi + 1):
        p_f = math.exp(_log_comb(n_failing, f)
                       + _log_comb(n_keys - n_failing, size - f)
                       - _log_comb(n_keys, size))
        if p_f < 1e-12:
            continue
        for extra in range(max_extra + 1):
            h = f + min(extra, size - f - 1)
            p = p_f / (max_extra + 1)
            if h == size - 1:  # one signer: both runs reach it, no hull hole
                shares[class_of(0, ladder)] += p
                continue
            # runs longer than `reach` ids are rarer than 1e-15: where even
            # they leave the hull in the class of h holes, the class is known
            reach = math.ceil(-36.0 / math.log(h / size)) if h else 0
            if class_of(h, ladder) == class_of(max(0, h - reach), ladder):
                shares[class_of(h, ladder)] += p
                continue
            p_t = (size - h) * (size - h - 1) / (size * (size - 1))
            for t in range(h + 1):
                shares[class_of(h - t, ladder)] += p * p_t
                if t == h or p_t < 1e-15:  # longer runs are rarer still
                    break
                p_t *= (t + 2) / (t + 1) * (h - t) / (size - t - 2)
    total = sum(shares)
    return [x / total for x in shares]


def class_targets(cand_levels: list[int], hole_counts: list[int],
                  traffic: dict, n_keys: int, n_failing: int,
                  ladder: list[dict]) -> list[int]:
    """For every candidate the ladder class its drawn range has to take, so
    that a level's candidates hold `class_shares` of each class whatever the
    seed: the shares become counts by largest remainder (a class the
    traffic does not name, or one that under RARE_CLASS of the level's draws
    reach — a hundred draws would often miss it — gives its share to the
    nearest other), and within a level the candidates with the fewest extra
    holes take the narrowest classes, which is how the draws fall by
    themselves."""
    in_traffic = [i for i, cls in enumerate(ladder)
                  if cls["name"] in traffic["launch_classes"]]
    out = [0] * len(cand_levels)
    for level in sorted(set(cand_levels)):
        own = sorted((c for c, l in enumerate(cand_levels) if l == level),
                     key=lambda c: (hole_counts[c], c))
        drawn = class_shares(1 << (level - 1), n_keys, n_failing,
                             traffic["holes"]["max"], ladder)
        named = [i for i in in_traffic if drawn[i] >= RARE_CLASS] or in_traffic
        shares = [0.0] * len(ladder)
        for i, x in enumerate(drawn):
            shares[min(named, key=lambda j: (abs(j - i), j))] += x
        counts = [int(x * len(own)) for x in shares]
        for i in sorted(named, key=lambda i: counts[i] - shares[i] * len(own)):
            if sum(counts) < len(own):
                counts[i] += 1
        classes = [i for i in named for _ in range(counts[i])]
        for c, i in zip(own, classes):
            out[c] = i
    return out


def build_pool(traffic: dict, seed: int, sks: list[int], failing: frozenset,
               order: int, ladder: list[dict] | None = None,
               stats: dict | None = None) -> list[list[Candidate]]:
    """The request pool, unsigned (see `sign_pool`). `order` is the group
    order the aggregate secrets are reduced by. `ladder` is the
    configuration's launch-class ladder: where the traffic names more than
    one class and the holes follow the failing set, each candidate is drawn
    until it falls in its `class_targets` class (a target that 100 draws do
    not reach takes the nearest class drawn and is counted in
    `stats["class_target_misses"]`)."""
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
    targets = None
    if ladder and by_failing and len(traffic["launch_classes"]) > 1:
        targets = class_targets(cand_levels, hole_counts, traffic, n_keys,
                                len(failing), ladder)
    misses = 0
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
            nearest = None  # (classes off the target, the draw, its signers)
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
                    if targets is None:
                        break
                    off = abs(class_of(drawn.hull_holes(), ladder) - targets[c])
                    if nearest is None or off < nearest[0]:
                        nearest = (off, drawn, signers)
                    if not off:
                        break
            else:
                if nearest is None:
                    raise ValueError(
                        f"no fresh level-{cand_levels[c]} range for a request "
                        f"of {k} under hole rule {rule}"
                    )
                misses += 1
                _, drawn, signers = nearest
                lo, holes = drawn.lo, drawn.holes
            seen.add(signers)
            agg = (pre[lo + size] - pre[lo] - sum(sks[i] for i in holes)) % order
            is_forged = c in forged
            req.append(Candidate(
                lo, size, holes, is_forged,
                (agg + 1) % order if is_forged else agg,
            ))
            c += 1
        pool.append(req)
    if stats is not None and targets is not None:
        stats["class_target_misses"] = misses
    return pool


def sign_pool(pool, msg: bytes, sign_batch) -> None:
    """Aggregate signatures for every candidate, in one batch call of the
    reference's `sign_batch(msg, scalars)`."""
    flat = [c for req in pool for c in req]
    for c, pt in zip(flat, sign_batch(msg, [c.agg_sk for c in flat])):
        c.sig = pt


def make_pool(cfg: dict, traffic: dict, seed: int, ref,
              stats: dict | None = None):
    """Everything a run draws from the seed, with the reference's arithmetic
    only: the registry's public keys (raw G2 points), the signed request
    pool and the message. `ref` is the configuration's reference module."""
    n_keys = int(cfg["registry_keys"])
    sks, points = ref.keygen(stream(seed, KEYS), n_keys)
    failing = failing_ids(seed, n_keys, int(cfg["deployment"]["failing"]))
    pool = build_pool(traffic, seed, sks, failing, ref.R,
                      cfg["guarantees"]["launch_classes"], stats)
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
