"""Swarm CI gate: 4096 virtual nodes reach threshold on one host.

Runs the `sim swarm` orchestrator (handel_tpu/swarm/driver.py run_swarm)
on a 4096-identity committee in <= 2 processes with tracing on, and
asserts the ISSUE 11 acceptance surface: every vnode reaches threshold,
the windowed store actually retired levels (the memory contract), the
merged summary carries its headline figures, and the streamed
trace report shows the per-level completion wave plus a non-trivial
critical path. A swarm regression then fails CI on its own named step
(.github/workflows/ci.yml) before the full tier runs.

Gossip is set sparse (period 10s): the in-memory router is lossless and
the id-staggered fast-path cascade covers every level deterministically,
so the run is fast-path-paced — about a minute on one core.

Usage: python scripts/swarm_smoke.py [--artifact-dir DIR]
       [--identities N] [--processes M]
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from handel_tpu.sim.config import SimConfig, SwarmParams  # noqa: E402
from handel_tpu.swarm.driver import run_swarm  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--artifact-dir", default="",
        help="keep swarm_summary.json + swarm_trace_report.json here",
    )
    ap.add_argument("--identities", type=int, default=4096)
    ap.add_argument("--processes", type=int, default=1)
    args = ap.parse_args(argv)
    assert args.processes <= 2, "the smoke gate is a <=2 process shape"

    cfg = SimConfig(
        trace=True,
        trace_capacity=1 << 20,
        swarm=SwarmParams(
            identities=args.identities,
            processes=args.processes,
            period_ms=10000.0,
            timeout_ms=50.0,
            fast_path=3,
            timeout_s=600.0,
        ),
    )

    with tempfile.TemporaryDirectory() as tmp:
        d = args.artifact_dir or tmp
        if args.artifact_dir:
            os.makedirs(d, exist_ok=True)
        summary = asyncio.run(run_swarm(cfg, d))

        assert summary["ok"], (
            f"only {summary['completed']}/{summary['swarm_identities']} "
            "vnodes reached threshold"
        )
        assert summary["swarm_identities"] == args.identities
        assert summary["mem_bytes_per_identity"] > 0
        assert summary["swarm_time_to_threshold_s"] > 0
        # windowed store must actually retire completed levels — a silent
        # fallback to the unwindowed store would pass completion but leak
        assert summary["retired_level_ct"] > 0, "no levels retired"
        if args.processes == 1:
            assert summary["udp_sent"] == 0.0, "single process sent UDP"
        else:
            assert summary["udp_sent"] > 0, "blocks never crossed the socket"

        rep = summary.get("trace_report") or {}
        wave = rep.get("level_wave") or {}
        assert wave, "trace report has no level-completion wave"
        for lvl, w in wave.items():
            assert w["first"] <= w["median"] <= w["last"], (
                f"level {lvl} wave out of order: {w}"
            )
        assert rep.get("critical_path_len", 0) >= 1

        print(
            f"swarm smoke OK: {summary['swarm_identities']} vnodes / "
            f"{summary['processes']} proc, "
            f"ttt {summary['swarm_time_to_threshold_s']:.1f}s, "
            f"{summary['mem_bytes_per_identity']:.0f} B/identity, "
            f"{summary['retired_level_ct']} levels retired, "
            f"wave levels {sorted(wave, key=int)}"
        )
        if args.artifact_dir:
            print(f"artifacts: {os.path.join(d, 'swarm_summary.json')}")
        else:
            # still show the merged record for the CI log
            print(json.dumps({k: v for k, v in summary.items()
                              if k != "per_process"}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
