"""The device's idle time by what the host path was doing, in percent of the
traced interval, both sides on the profiler's clock (no anchor): every idle
gap of 0.1 ms or more between device operations (shorter ones lie between
the operations of one program) is split into the part that some
`handel/<stage>` annotation of that chip's lane covers — the host was
building, enqueueing or fetching a launch: the host path is too slow — and
the part none covers — no launch was ready to build. `covered` picks which
part is read. Averaged over the chips used.

Only a gap BETWEEN two device operations is an idle gap. The reduction also
lists the stretch from the interval's start to the first operation and from
the last one to the interval's end; where the device's tracing stops a few
milliseconds before the host's window does, that tail is no idleness (PR 26:
9.4 ms of "idle" in a run whose verdict bursts never came 5 ms late), and it
is left out here."""

from readers import _xspace
import trace_reduce


def covered_ns(gap, intervals) -> float:
    """Length of `gap` covered by the union of sorted `intervals`."""
    g0, g1 = gap
    total, cursor = 0.0, g0
    for s, e in intervals:
        if e <= cursor:
            continue
        if s >= g1:
            break
        total += min(e, g1) - max(s, cursor)
        cursor = min(e, g1)
    return total


def read(ctx, covered: bool):
    tr = ctx.trace
    stages = _xspace.load(ctx)
    if tr is None or stages is None or not tr.window_ns:
        return None
    under = bare = 0.0
    for plane in tr.planes:
        lane = _xspace.lane_of(plane.name)
        ivs = sorted((a[3], a[4]) for a in stages.annotations if a[2] == lane)
        for gap in plane.gaps:
            if gap[1] - gap[0] < trace_reduce.SHORT_GAP_NS:
                continue
            if gap[0] <= tr.t0_ns or gap[1] >= tr.t1_ns:
                continue  # an edge of the interval, not two operations
            c = covered_ns(gap, ivs)
            under += c
            bare += (gap[1] - gap[0]) - c
    share = (under if covered else bare) / (tr.window_ns * len(tr.planes))
    return 100.0 * share
