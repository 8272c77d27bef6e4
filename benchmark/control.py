#!/usr/bin/env python3
"""The CONTROL of the comparison that decides `correct`.

The system runs no model and states no precision, so the control breaks one
guarantee the configuration states: the plain reference is put in the
program's place — behind the same `verify(msg, pubkeys, requests, session=,
dedup_scope=)` entry, driven by the same load generator at the cell's own
clients and pool — with one fault switched on:

  accept_any     the pairing equation is skipped: forged aggregates pass
                 (guarantee broken: "forged aggregates rejected")
  ignore_holes   a range's hole patch is dropped, the whole hull is
                 aggregated (guarantee broken: "every verdict exact")

and the same comparison has to come out NOT correct for each. With no fault
("sound") it has to come out correct: the comparison is not simply always
false. Host only — no jax is imported and no chip is touched.

    python benchmark/control.py --workload <cell> --seeds 1,2,3 --seconds 3

Exit 0 when every control failed the comparison and every sound run passed.
"""

from __future__ import annotations

import argparse
import asyncio
import importlib
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)

import correct  # noqa: E402
import loadgen  # noqa: E402
import spec  # noqa: E402
import traffic as tg  # noqa: E402

FAULTS = ("sound", "accept_any", "ignore_holes")


class ReferenceService:
    """The reference in the program's place. `requests` are pool indices'
    candidates (the control needs no program types); verdicts are computed
    once per candidate and remembered, in a thread, so the loop stays live."""

    def __init__(self, ref, msg, points, fault: str):
        self.ref, self.msg, self.points = ref, msg, points
        self.flags = {} if fault == "sound" else {fault: True}
        self.memo: dict[int, bool] = {}
        self.served = 0

    def _verdicts(self, cands) -> list[bool]:
        out = []
        for c in cands:
            v = self.memo.get(id(c))
            if v is None:
                v = self.memo[id(c)] = self.ref.verify(
                    self.msg, self.points, c.signers(), c.sig, **self.flags
                )
            out.append(v)
        return out

    async def verify(self, msg, pubkeys, requests, session="", dedup_scope=None):
        self.served += len(requests)
        return await asyncio.get_running_loop().run_in_executor(
            None, self._verdicts, requests
        )

    def stop(self) -> None:
        pass


def run_control(cell, seed: int, seconds: float, fault: str,
                pool_requests: int | None = None, clients: int | None = None):
    """One short window of the cell's traffic served by the reference with
    `fault`; returns (checks, info) of the run's own comparison."""
    cfg, tr = cell.config, dict(cell.traffic)
    if pool_requests:
        tr["pool_requests"] = pool_requests
    if clients:
        tr["clients"] = clients
    ref = importlib.import_module(f"reference.{cfg['reference']}")
    points, pool, msg = tg.make_pool(cfg, tr, seed, ref)
    service = ReferenceService(ref, msg, points, fault)
    edge = lambda name: {}
    scope_of = lambda s, j: f"{s}/{j}"

    async def drive():
        if tr["loop"] == "closed":
            starts = tg.client_order(len(pool), tr["clients"], seed)
            return await loadgen.closed_loop(
                service, msg, None, pool, starts, scope_of, 0.0, seconds, edge)
        offsets = tg.arrival_offsets(tr["arrival"], seconds, seed)
        return await loadgen.open_loop(
            service, msg, None, pool, offsets, tr["clients"], scope_of, 0.0,
            seconds, edge)

    res = asyncio.run(drive())
    verify_one = lambda c: ref.verify(msg, points, c.signers(), c.sig)
    return correct.compare(res.records, pool, seed, verify_one)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1,2,3")
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args()
    cell = spec.Cell(args.workload)
    ok = True
    for seed in (int(s) for s in args.seeds.split(",")):
        for fault in FAULTS:
            checks, info = run_control(cell, seed, args.seconds, fault)
            came_out_correct = all(c.ok for c in checks)
            ok &= came_out_correct == (fault == "sound")
            print(json.dumps({
                "workload": cell.name, "seed": seed, "control": fault,
                "correct": came_out_correct,
                "compared": {c.name: [c.value, c.limit] for c in checks},
                **info,
            }), flush=True)
    print(json.dumps({"controls_separate": ok}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
