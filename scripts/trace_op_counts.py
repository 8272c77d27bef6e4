"""Per launch, how often each Mosaic kernel ran and what a call took — read
from a traced benchmark run's .xplane.pb (too large to bring back from the
chip's machine: run this there, after `benchmark/run.py --trace 1
--trace-summary <file>`, which keeps the trace).

    python scripts/trace_op_counts.py benchmark/_out/trace/<cell>

Takes the WHOLE executions of the launch program (`jit_verify_*` on the
"XLA Modules" line) and, over the operations inside them ("XLA Ops"), prints
one JSON line: per kernel name (`fp_mul_<limbs>x<lanes>`) the calls and the
self milliseconds a launch, the microseconds a call, the nanoseconds a lane
and, for the Montgomery multiplication, `sublanes_by_this_checkout` and
`block_by_this_checkout`: the sublanes of a register a limb row fills (S) and
the lanes a grid step moves at that width by `ops/fp.py` `mul_tile` of the
checkout this script runs from. A trace does not record the kernel's grid,
so for a trace another commit's program wrote they are not what the kernel
ran with. Then the executed `conditional`s and `while`s a launch, and the
launch's time outside the kernels (the XLA glue). The second witness of
ops/pairing.py's loop over the runs of its public bits: a 0-bit step leaves
no `fp_mul_<limbs>x3072` call (the addition step's) in the trace.
"""

from __future__ import annotations

import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import trace_reduce  # noqa: E402
from handel_tpu.ops.fp import mul_tile  # noqa: E402

_KERNEL = re.compile(r"%?((?:fp|rns)_mul_(\d+)x(\d+))")


def per_lane(kernel: str, ns_per_call: float) -> dict:
    """A kernel's time a lane, and the tile this checkout's rule gives its
    width (the trace does not say how the traced program walked it)."""
    _, _, lanes = _KERNEL.fullmatch(kernel).groups()
    out = {"ns_per_lane": ns_per_call / int(lanes)}
    if kernel.startswith("fp_"):  # `rns_mul_*` tiles by its own rule (ops/rns.py)
        out["sublanes_by_this_checkout"], out["block_by_this_checkout"] = mul_tile(int(lanes))
    return out


def op_name(hlo: str) -> str:
    """A kernel by the name ops/fp.py gave it, anything else by opcode."""
    m = _KERNEL.match(hlo)
    return m.group(1) if m else trace_reduce.op_group(hlo).split(" ")[0]


def launch_counts(path: str, program: str = "jit_verify_") -> dict:
    loaded = trace_reduce.load_trace(path)
    _, ops, mods = loaded.planes[0]
    runs = sorted((s, e) for s, e, name in mods if name.startswith(program))
    # the profiler clips the first and the last execution to the session,
    # and their operations are missing with the clipped part
    longest = max(e - s for s, e in runs)
    whole = [(s, e) for s, e in runs[1:-1] if e - s >= 0.9 * longest]
    calls: dict[str, int] = {}
    self_ns: dict[str, float] = {}
    for s, e in whole:
        inside = [ev for ev in ops if s <= ev[0] and ev[1] <= e]
        ns, _, count = trace_reduce._self_times(inside)
        for hlo, n in count.items():
            name = op_name(hlo)
            calls[name] = calls.get(name, 0) + n
            self_ns[name] = self_ns.get(name, 0.0) + ns[hlo]
    n = len(whole)
    kernels = {
        k: {"calls": calls[k] / n, "ms": self_ns[k] / n / 1e6,
            "us_per_call": self_ns[k] / calls[k] / 1e3,
            **per_lane(k, self_ns[k] / calls[k])}
        for k in sorted(calls, key=lambda k: -self_ns[k]) if _KERNEL.fullmatch(k)
    }
    kernel_ms = sum(v["ms"] for v in kernels.values())
    launch_ms = sum(e - s for s, e in whole) / n / 1e6
    return {
        "launches": n,
        "launch_ms": launch_ms,
        "kernel_ms": kernel_ms,
        "glue_ms": sum(self_ns.values()) / n / 1e6 - kernel_ms,
        "kernel_calls": sum(v["calls"] for v in kernels.values()),
        "conditionals": calls.get("conditional", 0) / n,
        "whiles": calls.get("while", 0) / n,
        "kernels": kernels,
    }


if __name__ == "__main__":
    print(json.dumps(launch_counts(trace_reduce.find_xplane(sys.argv[1]))))
