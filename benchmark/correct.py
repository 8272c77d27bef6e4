"""The comparison that decides `correct`.

What is compared is what the timed path produced: every verdict that
`service.verify` returned to a client during the run (ramp, window, drain).

  1. Every answer against the CONSTRUCTION: a candidate is forged or not by
     how the generator made it, so its verdict is known. Exact, limit 0.
  2. A SAMPLE of the served candidates, drawn from the seed and holding
     every forged candidate served (they are the rare class), against the
     plain reference — the pairing check of benchmark/reference/, which
     shares nothing with the program. Exact, limit 0. The reference is also
     held against the construction on the sample (a harness fault, not the
     program's, if they differ).
  3. The guarantees the configuration states, from the program's counters:
     no failover, no device retry, no dedup hit (every timed request a real
     launch), as many candidates verified on the device as clients were
     answered, as many engine launches as the service fetched, nothing
     compiled in the window, no request failed.
  4. The launch classes: before the ramp, the program's class counters read
     the warm launches on every class the traffic names and 0 on the
     others; over the service's whole life no launch took a class that was
     not warmed (a compile the cache may have hidden).

Each number is printed beside its limit; `correct` is true only when every
one is inside it.
"""

from __future__ import annotations

from dataclasses import dataclass

from traffic import SAMPLE, stream


@dataclass
class Check:
    name: str
    value: float
    limit: float
    kind: str = "max"   # "max": value <= limit; "min": value >= limit; "eq"

    @property
    def ok(self) -> bool:
        if self.kind == "min":
            return self.value >= self.limit
        if self.kind == "eq":
            return self.value == self.limit
        return self.value <= self.limit

    def line(self) -> str:
        sign = {"max": "<=", "min": ">=", "eq": "=="}[self.kind]
        return (f"compared {self.name} = {self.value} (limit {sign} "
                f"{self.limit}) {'ok' if self.ok else 'NOT OK'}")


def served_verdicts(records, pool):
    """Per pool candidate: the verdicts served for it, over every record."""
    served: dict[tuple[int, int], list[bool]] = {}
    for r in records:
        if r.verdicts is None:
            continue
        for k, v in enumerate(r.verdicts):
            served.setdefault((r.req, k), []).append(bool(v))
        if len(r.verdicts) != len(pool[r.req]):
            served.setdefault((r.req, -1), []).append(False)  # missing verdict
    return served


def draw_sample(served, pool, seed: int, n_valid: int, n_forged: int):
    """Seeded sample of served candidates: up to n_forged forged ones and
    n_valid others."""
    keys = sorted(k for k in served if k[1] >= 0)
    forged = [k for k in keys if pool[k[0]][k[1]].forged]
    valid = [k for k in keys if not pool[k[0]][k[1]].forged]
    rng = stream(seed, SAMPLE)
    return (
        rng.sample(forged, min(n_forged, len(forged)))
        + rng.sample(valid, min(n_valid, len(valid)))
    )


def compare(records, pool, seed, verify_one, sample_valid=512,
            sample_forged=64) -> tuple[list[Check], dict]:
    """Checks 1 and 2. `verify_one(candidate) -> bool` is the reference."""
    served = served_verdicts(records, pool)
    wrong_construction = sum(
        1
        for (req, k), verdicts in served.items()
        for v in verdicts
        if k < 0 or v != (not pool[req][k].forged)
    )
    sample = draw_sample(served, pool, seed, sample_valid, sample_forged)
    wrong_reference = ref_vs_construction = forged_accepted = 0
    for req, k in sample:
        cand = pool[req][k]
        want = verify_one(cand)
        ref_vs_construction += want != (not cand.forged)
        bad = sum(v != want for v in served[(req, k)])
        wrong_reference += bad
        forged_accepted += bad if cand.forged else 0
    n_forged = sum(pool[r][k].forged for r, k in sample)
    answers = sum(len(v) for k, v in served.items() if k[1] >= 0)
    checks = [
        Check("verdicts_differing_from_reference", wrong_reference, 0),
        Check("verdicts_differing_from_construction", wrong_construction, 0),
        Check("reference_differing_from_construction", ref_vs_construction, 0),
        Check("forged_candidates_in_sample", n_forged, 1, "min"),
        Check("answers_compared", answers, 1, "min"),
    ]
    info = {
        "answers_compared_with_construction": answers,
        "distinct_candidates_served": len([k for k in served if k[1] >= 0]),
        "sample_candidates": len(sample),
        "sample_forged": n_forged,
        "sample_answers": sum(len(served[k]) for k in sample),
        "forged_accepted": forged_accepted,
    }
    return checks, info


def guarantees(stated: dict, counters: dict, answered: int,
               warm_launches: int, window_compile_events: int, failed: int,
               fresh_scopes: bool) -> list[Check]:
    """Check 3: the configuration's `guarantees`, held against the service's
    counters over its whole life (ramp, window and drain)."""
    c = counters
    checks = [
        Check(key, c[key], 0) for key in stated["counters_zero"]
    ]
    if fresh_scopes:  # every request its own scope: a hit served no launch
        checks.append(Check(stated["dedup_hits"], c[stated["dedup_hits"]], 0))
    dev = stated["served_by_device"]
    checks += [
        Check("candidates_verified_on_device_minus_answered",
              c[dev["candidates"]] - answered, 0, "eq"),
        Check("engine_launches_minus_service_launches_minus_warmup",
              c[dev["engine_launches"]] - c[dev["launches"]] - warm_launches,
              0, "eq"),
        Check("compile_events_in_window", window_compile_events,
              stated["compile_events_in_window"]),
        Check("failed_requests", failed, 0),
    ]
    return checks


def warmed_classes(ladder: list[dict], named: list[str], counters: dict,
                   per_class: int) -> list[Check]:
    """Check 4 at set-up, one a class of the configuration's ladder: the
    class counter after the warm launches and before the ramp."""
    return [
        Check(f"warm_launches_{cls['name']}", counters[cls["counter"]],
              per_class if cls["name"] in named else 0, "eq")
        for cls in ladder
    ]


def unwarmed_class_launches(ladder: list[dict], named: list[str],
                            counters: dict) -> Check:
    """Check 4 at the end: launches, over the service's life, of classes the
    traffic does not name (they read 0 after the warm-up)."""
    return Check("unwarmed_class_launches",
                 sum(counters[cls["counter"]] for cls in ladder
                     if cls["name"] not in named), 0)
