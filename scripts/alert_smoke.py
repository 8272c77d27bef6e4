"""Alerting CI gate: the SLO burn-rate + incident-plane chaos drill
(ISSUE 19).

Runs `sim load`'s open-loop traffic in-process TWICE with the alert plane
on (handel_tpu/obs/):

1. **drill** — a forced mid-run region kill. The region-health detector
   must open EXACTLY ONE incident, its causal attribution must name the
   killed region, detection latency must stay under the bound, and the
   incident must close after recovery (hold_while + min-hold, not
   detector adaptation).
2. **clean control** — the identical load with no kill. ZERO incidents
   may open: `false_positive_rate` must be exactly 0.0.

`detection_latency_ms` and `false_positive_rate` ride the report flat.

Usage: python scripts/alert_smoke.py [--artifact-dir DIR] [--duration S]
       [--rate SPS] [--latency-bound-ms MS]
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from handel_tpu.sim.config import (  # noqa: E402
    AlertParams,
    FederationParams,
    LoadParams,
)
from handel_tpu.sim.load import run_load  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--artifact-dir", default="",
        help="keep incident_report.json here (CI upload)",
    )
    ap.add_argument(
        "--duration", type=float, default=30.0,
        help="load window per run in seconds (x2 runs: drill + control)",
    )
    ap.add_argument(
        "--rate", type=float, default=5.0,
        help="open-loop arrival rate (sessions/s)",
    )
    ap.add_argument(
        "--latency-bound-ms", type=float, default=3000.0,
        help="max allowed kill -> incident-open latency",
    )
    args = ap.parse_args(argv)

    lo = LoadParams(
        rate_sps=args.rate, duration_s=args.duration, nodes=6, seed=19
    )
    # window_scale compresses the 1m/15m burn windows to drill scale;
    # min_hold/cooldown tightened so the close lands inside the run
    al = AlertParams(window_scale=0.02, min_hold_s=1.0, cooldown_s=3.0,
                     tick_interval_s=0.25)

    with tempfile.TemporaryDirectory() as tmp:
        d = args.artifact_dir or tmp
        if args.artifact_dir:
            os.makedirs(d, exist_ok=True)

        # -- the drill: forced region kill ------------------------------
        fe = FederationParams(kill_region="us-east")
        report = asyncio.run(run_load(lo, fe, d, alert_p=al))
        al_block = report["alerts"]
        assert al_block is not None, "alert plane did not run"
        incidents = al_block["report"]["incidents"]
        kill = report["federation"]["kill"]
        print(
            f"drill: {len(incidents)} incident(s), "
            f"detection {report['detection_latency_ms']:.1f}ms, "
            f"false-positive rate {report['false_positive_rate']}, "
            f"kill at {kill['killed_at_s']}s"
        )
        assert len(incidents) == 1, (
            f"expected exactly one incident, got {len(incidents)}: "
            f"{json.dumps(incidents, indent=1)}"
        )
        inc = incidents[0]
        # correct attribution: the snapshot captured at open time must
        # name the killed region
        attributed = inc["attribution"].get("unhealthy_regions", [])
        assert "us-east" in attributed, (
            f"incident attribution missed the killed region: {attributed}"
        )
        # bounded detection latency
        assert 0.0 < report["detection_latency_ms"] <= args.latency_bound_ms, (
            f"detection latency {report['detection_latency_ms']}ms "
            f"outside (0, {args.latency_bound_ms}]"
        )
        # the drill's open was expected, so nothing counts as a false pos
        assert report["false_positive_rate"] == 0.0
        # closed after recovery, not left dangling
        assert inc["state"] == "closed", (
            f"incident never closed: {json.dumps(inc, indent=1)}"
        )
        artifact = os.path.join(d, "incident_report.json")
        assert os.path.exists(artifact), "incident_report.json not written"

        # -- the clean control: same load, no kill ----------------------
        with tempfile.TemporaryDirectory() as tmp2:
            clean = asyncio.run(
                run_load(lo, FederationParams(), tmp2, alert_p=al)
            )
        opened = clean["alerts"]["report"]["opened"]
        print(
            f"control: {opened} incident(s), "
            f"false-positive rate {clean['false_positive_rate']}"
        )
        assert opened == 0, (
            f"clean control opened {opened} incident(s): "
            f"{json.dumps(clean['alerts']['report']['incidents'], indent=1)}"
        )
        assert clean["false_positive_rate"] == 0.0

    print("alert smoke: exactly-one-incident drill + clean control held")
    return 0


if __name__ == "__main__":
    sys.exit(main())
