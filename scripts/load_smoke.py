"""Robustness CI gate: the geo-federation region-kill drill (ISSUE 18).

Runs `sim load`'s open-loop traffic in-process — a seeded Poisson arrival
clock against a 3-region federation (service/federation.py) with a forced
mid-run region kill and epoch-path recovery — then asserts the federation
invariants the report carries:

- zero dropped work: every arrival reached an attributed outcome
  (completed / shed / failed / expired) across the kill, the spillover
  storm and the recovery — nothing vanished silently
- the gold tier's open-loop arrival->verdict p99 stayed inside its SLO
  target with a whole region gone for a third of the run
- shed stayed bounded under the configured ceiling (spill-over and
  retry absorbed the lost capacity; the front door did not give up)
- the kill drill ran end to end: the front door detected the death,
  arrivals spilled to surviving regions, and the revived region rejoined
  via a federation-wide epoch rotation and COMPLETED work again

Usage: python scripts/load_smoke.py [--artifact-dir DIR] [--duration S]
       [--rate SPS]
"""

from __future__ import annotations

import argparse
import asyncio
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from handel_tpu.sim.config import FederationParams, LoadParams  # noqa: E402
from handel_tpu.sim.load import run_load  # noqa: E402
from handel_tpu.sim.report_checks import (  # noqa: E402
    FEDERATION_CHECKS,
    assert_checks,
)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--artifact-dir", default="",
        help="keep federation_report.json here (CI upload)",
    )
    ap.add_argument(
        "--duration", type=float, default=45.0,
        help="load window in seconds (the ~45 s CI drill)",
    )
    ap.add_argument(
        "--rate", type=float, default=5.0,
        help="open-loop arrival rate (sessions/s)",
    )
    args = ap.parse_args(argv)

    lo = LoadParams(
        rate_sps=args.rate, duration_s=args.duration, nodes=6, seed=18
    )
    fe = FederationParams(kill_region="us-east")
    with tempfile.TemporaryDirectory() as tmp:
        d = args.artifact_dir or tmp
        if args.artifact_dir:
            os.makedirs(d, exist_ok=True)
        report = asyncio.run(run_load(lo, fe, d))

        fed = report["federation"]
        kill = fed["kill"]
        print(
            f"load: {fed['completed']}/{fed['arrivals']} arrivals over "
            f"{fed['wall_s']}s, p99 {report['open_loop_p99_s']:.3f}s, "
            f"spillovers {fed['spillovers']}, "
            f"shed {report['shed_rate']}, "
            f"kill->detect "
            f"{kill['unhealthy_detected_s'] - kill['killed_at_s']:.2f}s, "
            f"recovery {report['region_recovery_s']}s "
            f"({kill['post_recovery_completed']} post-recovery completions)"
        )
        for name, ok in report["checks"].items():
            print(f"  check {name}: {'ok' if ok else 'FAILED'}")
        # the SAME predicate specs the report builder stamped `ok` with
        # (sim/report_checks.py): re-evaluated from the report, so the
        # smoke and the artifact can never assert different invariants
        assert_checks(report, FEDERATION_CHECKS)
        assert report["ok"], f"federation checks failed: {report['checks']}"
        # the kill drill must have actually interrupted a live plane,
        # not killed an idle region between arrivals
        assert kill is not None and kill["killed_at_s"] is not None

    print("load smoke: all federation invariants held")
    return 0


if __name__ == "__main__":
    sys.exit(main())
