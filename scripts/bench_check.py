"""Bench-regression gate: compare the fresh bench artifact to its history.

The driver persists one BENCH_r<NN>.json per round (repo root) and bench.py
keeps the latest chip capture in chiprun_out/bench_device.json — but until
now nobody READ them, so a regression like PR 1's 22.5 -> 6.3 ms pack win
could silently un-happen. This script loads the whole history, compares the
fresh artifact like-for-like — same metric AND same backend, so a chip p50
is never judged against a CPU number — and exits nonzero with a named report when any metric degrades more than
`--threshold` (default 20%) against the trailing median.

Usage:
    python scripts/bench_check.py                 # gate (exit 1 on regression)
    python scripts/bench_check.py --dry-run       # CI self-test: report only
    python scripts/bench_check.py --history 'BENCH_*.json' \
        --fresh chiprun_out/bench_device.json --threshold 0.2 --min-history 2

History records come in two shapes, both accepted: the driver wrapper
({"n": .., "parsed": {<line>}}) and a raw bench line / persisted artifact.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
from statistics import median

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# metric key -> direction ("lower" is better, or "higher"). The headline
# metric's name comes from the record itself (e.g. 4096sig_batch_verify_
# p50_ms); the side metrics ride every accelerator line.
SIDE_METRICS = {
    "pipelined_p50_ms": "lower",
    "host_pack_ms": "lower",
    "host_pack_dense_ms": "lower",
    "host_dispatch_ms": "lower",
    "no_transfer_steady_state": "higher",
    "dedup_hit_rate": "higher",
    # multi-tenant service plane (bench.py service_bench / sim serve):
    # sustained session completions per second, tail completion latency
    # under concurrent load, and coalesced launch lane fill
    "aggregates_per_s": "higher",
    "session_p99_s": "lower",
    "launch_fill_ratio": "higher",
    # fleet-of-chips verify plane (bench.py fleet_bench): K-lane DevicePlane
    # scheduler throughput, its speedup over an identical 1-lane run, and
    # the fleet's per-launch lane fill
    "launches_per_s": "higher",
    "fleet_speedup_x": "higher",
    "fleet_fill_ratio": "higher",
    # mesh latency plane (bench.py small_batch_bench / parallel/
    # mesh_plane.py): p50 wall of a small gold-tier launch riding the
    # whole-mesh lane, and its speedup over the identical-code 1-device
    # run (the dual-mode scheduling contract: > 1x, ~K/2 at batch <= 64)
    "small_batch_verify_p50_ms": "lower",
    "small_batch_speedup_x": "higher",
    # causal-tracing plane (sim trace --report / scripts/trace_smoke.py):
    # wall time from the critical chain's first send to threshold, the
    # fraction of that wall the chain's spans attribute, cross-process
    # flow-link resolution rate, and mean device-lane busy fraction
    "time_to_threshold_s": "lower",
    "critical_path_coverage": "higher",
    "flow_linkage": "higher",
    "lane_occupancy": "higher",
    # virtual-node swarm (bench.py swarm_bench / sim swarm): identities one
    # host carries, summed-RSS bytes per identity (the 1M extrapolation
    # basis), and wall until the LAST member held a threshold signature
    "swarm_identities": "higher",
    "mem_bytes_per_identity": "lower",
    "swarm_time_to_threshold_s": "lower",
    # lifecycle soak (sim soak / scripts/soak_smoke.py): the epoch-swap
    # gate-closed wall, tail session completion under the full drill
    # (swap + forced lane loss), and the SLO admission shed fraction
    "epoch_swap_stall_ms": "lower",
    "soak_p99_s": "lower",
    # WAN scenario engine (sim scenario / scripts/scenario_smoke.py):
    # wall to the weighted threshold under the composed geo + churn +
    # stake-weight drill
    "geo_weighted_ttt_s": "lower",
    "shed_rate": "lower",
    # Fp-backend marginal modmul throughput (bench.py _fp_microbench /
    # ops/fp.py chained_marginal): captured once per Field backend
    # (CIOS, RNS) under the same chained-dispatch methodology
    "mont_muls_per_s": "higher",
    # residue-resident pairing (bench.py _pairing_bench / ops/pairing.py):
    # p50 wall of a batch-4 full pairing per Field backend, and the CRT
    # boundary crossings per pairing trace (resident form: O(line
    # boundaries); legacy: once per tower mul)
    "pairing_p50_ms": "lower",
    "rns_conversions_per_pairing": "lower",
    # RLC batch verification (models/rlc.py / scripts/rlc_smoke.py): p50
    # wall of one combined check over a full batch, and its speedup over
    # the per-candidate check of the same batch (acceptance: >= 3x at
    # batch 64 on the host path)
    "rlc_verify_p50_ms": "lower",
    "rlc_speedup_x": "higher",
    # geo-federation robustness (bench.py federation_bench / sim load /
    # scripts/load_smoke.py): gold-tier open-loop arrival->verdict p99
    # under a mid-run region kill, wall from recovery start to the
    # revived region's first completion, and the fraction of arrivals
    # that spilled to a non-nearest region
    "open_loop_p99_s": "lower",
    "region_recovery_s": "lower",
    "spillover_rate": "lower",
    # SLO alerting + incident plane (handel_tpu/obs/ / sim load /
    # scripts/alert_smoke.py): wall from the forced region kill to the
    # incident opening, and the unexpected-open fraction across the
    # drill (clean control runs must hold this at exactly 0.0)
    "detection_latency_ms": "lower",
    "false_positive_rate": "lower",
    # hierarchical roll-up plane (obs/rollup.py / bench.py rollup_bench /
    # scripts/rollup_smoke.py): master-side merged series count (must
    # stay O(hosts) — flat across identity sweeps), delta wire bytes per
    # host per emission interval, and the master's merge wall
    "fleet_series_count": "lower",
    "rollup_bytes_per_host_s": "lower",
    "fleet_eval_ms": "lower",
}

# Metrics that exist once per Field backend. Their comparison key grows a
# "/<fp_backend>" suffix so a CIOS row is never judged against an RNS row
# (the per-backend like-for-like rule, same spirit as tpu-vs-cpu refusal).
PER_FP_BACKEND = {
    "mont_muls_per_s",
    "pairing_p50_ms",
    "rns_conversions_per_pairing",
    "rlc_verify_p50_ms",
    "rlc_speedup_x",
}


def normalize(obj: dict) -> dict | None:
    """One bench record from either wrapper shape, or None when the round
    produced no parsable line (rc != 0, empty tail)."""
    if not isinstance(obj, dict):
        return None
    if "parsed" in obj or "rc" in obj:  # driver wrapper
        rec = obj.get("parsed")
        return rec if isinstance(rec, dict) else None
    # "records" alone is enough: a container of nested per-fp-backend
    # captures with no headline of its own is still a bench record
    return obj if "metric" in obj or "records" in obj else None


def extract_metrics(rec: dict) -> dict[tuple[str, str], float]:
    """{(metric name, backend): value} for every comparable number in one
    record. Records without a backend tag (old CPU smokes) are keyed under
    "cpu" only when their metric name says so, else skipped entirely —
    an unlabeled number cannot be compared like-for-like. PER_FP_BACKEND
    metrics key as "<backend>/<fp_backend>"; a "records" list of nested
    captures is walked with the same rules."""
    out: dict[tuple[str, str], float] = {}
    # nested per-fp-backend captures (bench.py _fp_microbench "records")
    for sub in rec.get("records") or []:
        if isinstance(sub, dict):
            out.update(extract_metrics(sub))
    backend = rec.get("backend")
    if not backend:
        backend = "cpu" if "cpu_smoke" in str(rec.get("metric", "")) else None
    if not backend:
        return out

    def keyed(metric: str) -> str:
        fp = rec.get("fp_backend")
        if metric in PER_FP_BACKEND and fp:
            return f"{backend}/{fp}"
        return backend

    name, value = rec.get("metric"), rec.get("value")
    if name and isinstance(value, (int, float)):
        if not rec.get("forced_shape") and not rec.get("invalid_measurement"):
            out[(str(name), keyed(str(name)))] = float(value)
    for key in SIDE_METRICS:
        v = rec.get(key)
        if isinstance(v, (int, float)):
            out[(key, keyed(key))] = float(v)
    return out


def direction(metric: str) -> str:
    return SIDE_METRICS.get(metric, "lower")


def load_history(pattern: str) -> list[dict]:
    """Chronologically ordered history records."""
    recs: list[dict] = []
    for path in sorted(glob.glob(pattern)):
        try:
            with open(path) as f:
                rec = normalize(json.load(f))
        except (OSError, ValueError):
            continue
        if rec is None:
            continue
        recs.append(rec)
    return recs


def detect_regressions(
    history: list[dict],
    fresh: dict,
    threshold: float = 0.20,
    min_history: int = 2,
) -> dict:
    """Compare `fresh` against the trailing median of `history`,
    like-for-like. Returns the full report:
    {"regressions": [...], "improved": [...], "ok": [...], "skipped": [...]}.
    Each entry names metric, backend, fresh value, trailing median, delta.
    """
    hist_vals: dict[tuple[str, str], list[float]] = {}
    hist_backends: dict[str, set[str]] = {}
    for rec in history:
        for key, v in extract_metrics(rec).items():
            hist_vals.setdefault(key, []).append(v)
            hist_backends.setdefault(key[0], set()).add(key[1])

    report = {"regressions": [], "improved": [], "ok": [], "skipped": []}
    for (metric, backend), value in extract_metrics(fresh).items():
        past = hist_vals.get((metric, backend), [])
        if len(past) < min_history:
            other = hist_backends.get(metric, set()) - {backend}
            reason = (
                f"history exists only for backend(s) {sorted(other)} — "
                f"cross-backend comparison refused"
                if other
                else f"only {len(past)} comparable record(s) "
                f"(< {min_history})"
            )
            report["skipped"].append(
                {"metric": metric, "backend": backend, "value": value,
                 "reason": reason}
            )
            continue
        med = median(past)
        if med == 0:
            report["skipped"].append(
                {"metric": metric, "backend": backend, "value": value,
                 "reason": "trailing median is 0"}
            )
            continue
        if direction(metric) == "lower":
            delta = (value - med) / med
        else:
            delta = (med - value) / med
        entry = {
            "metric": metric,
            "backend": backend,
            "value": value,
            "trailing_median": med,
            "n_history": len(past),
            "degradation": round(delta, 4),
        }
        if delta > threshold:
            report["regressions"].append(entry)
        elif delta < 0:
            report["improved"].append(entry)
        else:
            report["ok"].append(entry)
    return report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--history", default=os.path.join(REPO, "BENCH_*.json"),
        help="glob of historical bench records (driver wrapper or raw line)",
    )
    ap.add_argument(
        "--fresh",
        default=os.path.join(REPO, "chiprun_out", "bench_device.json"),
        help="the artifact under judgment",
    )
    ap.add_argument("--threshold", type=float, default=0.20,
                    help="fractional degradation that fails the gate")
    ap.add_argument("--min-history", type=int, default=2,
                    help="comparable records required before judging")
    ap.add_argument("--dry-run", action="store_true",
                    help="validate + report, always exit 0 (CI self-test)")
    ap.add_argument("--json", default="", help="also write the report here")
    args = ap.parse_args(argv)

    history = load_history(args.history)
    try:
        with open(args.fresh) as f:
            fresh = normalize(json.load(f))
    except (OSError, ValueError) as e:
        print(f"bench_check: cannot read fresh artifact {args.fresh}: {e}",
              file=sys.stderr)
        return 0 if args.dry_run else 2
    if fresh is None:
        print(f"bench_check: {args.fresh} holds no bench record",
              file=sys.stderr)
        return 0 if args.dry_run else 2

    report = detect_regressions(
        history, fresh, threshold=args.threshold,
        min_history=args.min_history,
    )
    print(
        f"bench_check: {len(history)} history records "
        f"({os.path.basename(args.history)}), fresh = {args.fresh}"
    )
    for entry in report["regressions"]:
        print(
            f"  REGRESSION {entry['metric']} [{entry['backend']}]: "
            f"{entry['value']:g} vs trailing median "
            f"{entry['trailing_median']:g} over {entry['n_history']} runs "
            f"({entry['degradation']:+.1%}, threshold "
            f"{args.threshold:.0%})"
        )
    for entry in report["improved"]:
        print(
            f"  improved   {entry['metric']} [{entry['backend']}]: "
            f"{entry['value']:g} vs median {entry['trailing_median']:g} "
            f"({entry['degradation']:+.1%})"
        )
    for entry in report["ok"]:
        print(
            f"  ok         {entry['metric']} [{entry['backend']}]: "
            f"{entry['value']:g} vs median {entry['trailing_median']:g} "
            f"({entry['degradation']:+.1%})"
        )
    for entry in report["skipped"]:
        print(
            f"  skipped    {entry['metric']} [{entry['backend']}]: "
            f"{entry['reason']}"
        )
    if args.json:
        with open(args.json, "w") as f:
            json.dump(report, f, indent=1)

    if report["regressions"] and not args.dry_run:
        print(
            f"bench_check: FAILED — {len(report['regressions'])} metric(s) "
            f"regressed past {args.threshold:.0%}",
            file=sys.stderr,
        )
        return 1
    if args.dry_run and report["regressions"]:
        print("bench_check: dry-run — regressions reported, exit 0",
              file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
