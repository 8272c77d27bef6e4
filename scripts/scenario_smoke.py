"""Scenario-engine CI gate: the composed WAN drill (ISSUE 13 acceptance).

Runs one in-process `sim scenario` round with every axis active at once —
a 32-node committee spread over the fast 3-region planet, ~10% of it
departing mid-round on the seeded membership schedule, a join admitted
through the epoch path, and completion gated on pareto-distributed stake
instead of a contribution count — then asserts the invariants the report
carries:

- the weighted threshold was reached (achieved stake >= the stake gate)
- every survivor marked every churner departed (re-leveling happened)
- the join advanced the epoch at least once (stage -> quiesce -> flip)
- the trace's critical path attributes >= 1 WAN hop to a region pair

Usage: python scripts/scenario_smoke.py [--artifact-dir DIR] [--nodes N]
"""

from __future__ import annotations

import argparse
import asyncio
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from handel_tpu.scenario import run_scenario  # noqa: E402
from handel_tpu.sim.confgen import scenario_geo_weighted  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--artifact-dir", default="",
        help="keep scenario_report.json + trace here (CI upload)",
    )
    ap.add_argument(
        "--nodes", type=int, default=32,
        help="committee size (3-region fast planet, ~10%% churn)",
    )
    args = ap.parse_args(argv)

    cfg = scenario_geo_weighted(args.nodes)
    # CI shape: the fast planet keeps WAN delays ~ms so the drill is quick
    cfg.scenario.planet = "planet-3region-fast"
    cfg.scenario.jitter_ms = 1.0
    cfg.scenario.joins = 1

    with tempfile.TemporaryDirectory() as tmp:
        d = args.artifact_dir or tmp
        if args.artifact_dir:
            os.makedirs(d, exist_ok=True)
        report = asyncio.run(run_scenario(cfg, d))

        s = report["scenario"]
        print(
            f"scenario: {s['nodes']} nodes / {len(s['regions'])} regions, "
            f"{s['churners']} departed, {s['joins']} joined "
            f"({s['epochs_advanced']} epoch advance), stake "
            f"{s['achieved_weight']:.2f}/{s['weight_threshold']:.2f}, "
            f"ttt {report['geo_weighted_ttt_s']}s"
        )
        for name, ok in report["checks"].items():
            print(f"  check {name}: {'ok' if ok else 'FAILED'}")
        assert report["checks"]["threshold_reached"], (
            f"weighted threshold missed: {s['achieved_weight']} < "
            f"{s['weight_threshold']}"
        )
        assert report["checks"]["departures_marked"], (
            f"churners {s['departed_ids']} not marked departed everywhere"
        )
        assert report["checks"]["epoch_advanced"], (
            "join did not advance the epoch"
        )
        assert report["checks"]["region_attributed"], (
            "critical path attributed no WAN hop to a region pair"
        )
        assert s["region_hops"], "trace carried no region-tagged hops"
        assert report["ok"], f"scenario checks failed: {report['checks']}"

    print("scenario smoke: all WAN scenario invariants held")
    return 0


if __name__ == "__main__":
    sys.exit(main())
