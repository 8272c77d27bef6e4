"""Lifecycle CI gate: the production soak drill (ISSUE 12 acceptance).

Runs `sim soak`'s continuously-loaded service in-process — sustained
tiered sessions on a multi-lane host plane with a mid-run epoch registry
rotation and a forced lane-0 breaker loss — then asserts the lifecycle
invariants the report carries:

- zero dropped work: every spawned session reached a terminal verdict and
  none by expiry, across both the swap and the lane loss
- the epoch advanced exactly once (stage -> quiesce -> flip completed)
- the swap hid between launches: neither the gate-closed stall nor the
  launch gap straddling the flip exceeded the steady-state cadence bound
- the autoscaler replaced the broken lane (attach-first, so the plane
  never dipped) and per-tenant p99 stayed inside every SLO tier target

Usage: python scripts/soak_smoke.py [--artifact-dir DIR] [--duration S]
"""

from __future__ import annotations

import argparse
import asyncio
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from handel_tpu.sim.config import SoakParams  # noqa: E402
from handel_tpu.sim.report_checks import SOAK_CHECKS, assert_checks  # noqa: E402
from handel_tpu.sim.soak import run_soak  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--artifact-dir", default="",
        help="keep soak_report.json here (CI upload)",
    )
    ap.add_argument(
        "--duration", type=float, default=90.0,
        help="load window in seconds (the ~90 s CI soak)",
    )
    args = ap.parse_args(argv)

    p = SoakParams(duration_s=args.duration)
    with tempfile.TemporaryDirectory() as tmp:
        d = args.artifact_dir or tmp
        if args.artifact_dir:
            os.makedirs(d, exist_ok=True)
        report = asyncio.run(run_soak(p, d))

        soak = report["soak"]
        print(
            f"soak: {soak['completed']} sessions over {soak['wall_s']}s, "
            f"epoch swap stall {report['epoch_swap_stall_ms']}ms "
            f"(bound {soak['swap_gap_bound_ms']}ms), "
            f"p99 {report['soak_p99_s']}s, shed {report['shed_rate']}"
        )
        for name, ok in report["checks"].items():
            print(f"  check {name}: {'ok' if ok else 'FAILED'}")
        # the SAME predicate specs the report builder stamped `ok` with
        # (sim/report_checks.py): re-evaluated from the report, so the
        # smoke and the artifact can never assert different invariants
        assert_checks(report, SOAK_CHECKS)
        assert report["ok"], f"soak checks failed: {report['checks']}"

    print("soak smoke: all lifecycle invariants held")
    return 0


if __name__ == "__main__":
    sys.exit(main())
