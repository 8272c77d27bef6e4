"""How far the device line's clock and the host's disagree, in percent:
100 * |1 - ratio|, where ratio = (last - first execution end of the launch
program on the device's "XLA Modules" line) / (last - first end of the
matching `handel/fetch_wait` annotations on the host plane) — the same
launches, in one trace, the first on the device's clock and the second on
the profiler's host clock. 0 means a millisecond of the trace is a
millisecond of the host. What it can resolve is the jitter of the callback
that ends a fetch_wait, about a millisecond, over the matched span: a few
tenths of a percent over the half second the benchmark traces.

Also prints a `trace_clock` phase line with that ratio and the same against
the service's own `launch_on_device` span ends (the host's epoch clock) of
the launches with the same `seq`."""

import bisect
import json
import statistics

from readers import _xspace


def ratios(stages, plane: str, span_end_of_seq: dict) -> dict | None:
    """{"device_over_profiler", "device_over_epoch", "executions", ...} for
    one device plane, or None below two matched whole executions.

    The profiler clips a program that is running when the session starts or
    stops to the session's bounds: such an event is shorter than a whole
    execution and one of its ends is the session's, not the program's. Only
    whole executions are matched (at least 0.9 of the longest). The first
    match is made by nearness — a launch's fetch_wait ends a callback's
    latency after its execution does, and the profiler lines its clocks up at
    the session's start — and the others by counting: launch `seq + k` is
    the k-th execution after it, however far the clocks drift."""
    runs = stages.executions.get(plane, [])
    if not runs:
        return None
    by_program: dict[str, int] = {}
    for _, _, name in runs:
        by_program[name] = by_program.get(name, 0) + 1
    program = max(by_program, key=by_program.get)
    mine = [(s, e) for s, e, name in runs if name == program]
    longest = max(e - s for s, e in mine)
    ends = sorted(e for s, e in mine if e - s >= 0.9 * longest)
    if len(ends) < 2:
        return None
    near = 0.25 * statistics.median(b - a for a, b in zip(ends, ends[1:]))
    waits = [a for a in stages.of("fetch_wait", _xspace.lane_of(plane))
             if a[1] is not None]
    first = None  # (index of the execution, seq of its launch)
    for a in waits:
        i = bisect.bisect_right(ends, a[4]) - 1
        if i >= 0 and a[4] - ends[i] <= near:
            first = (i, a[1])
            break
    if first is None:
        return None
    pairs = [(ends[i], a) for a in waits
             if 0 <= (i := first[0] + a[1] - first[1]) < len(ends)]
    if len(pairs) < 2:
        return None
    (e0, a0), (e1, a1) = pairs[0], pairs[-1]
    out = {
        "program": program, "executions": len(mine), "whole": len(ends),
        "matched": len(pairs), "seq_first": a0[1], "seq_last": a1[1],
        "device_ms": (e1 - e0) / 1e6, "profiler_host_ms": (a1[4] - a0[4]) / 1e6,
        "device_over_profiler": (e1 - e0) / (a1[4] - a0[4]),
    }
    s0, s1 = span_end_of_seq.get(a0[1]), span_end_of_seq.get(a1[1])
    if s0 is not None and s1 is not None and s1 > s0:
        out["epoch_host_ms"] = 1e3 * (s1 - s0)
        out["device_over_epoch"] = (e1 - e0) / 1e9 / (s1 - s0)
    return out


def read(ctx, span: str = "launch_on_device"):
    stages = _xspace.load(ctx)
    if stages is None:
        return None
    span_end = {}
    if ctx.sink is not None:
        span_end = {s[4].get("seq"): s[2] for s in ctx.sink.named(span)}
    found = [r for r in (ratios(stages, p, span_end) for p in stages.executions)
             if r is not None]
    if not found:
        return None
    print(json.dumps({"phase": "trace_clock", "planes": found}), flush=True)
    return 100.0 * max(abs(1.0 - r["device_over_profiler"]) for r in found)
