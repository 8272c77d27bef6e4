"""The launch interval, in ms: the median gap between the ends of
consecutive `span` spans (`launch_on_device`: dispatch done to verdicts
fetched) of one lane, over the spans that end inside the window. A lane
fetches its launches in order, so under saturation the gap between two ends
IS the device time of one launch — on the host's clock, which the trace's
`launch.device_ms` is not. The median leaves out the window's last half
second, where the profiler runs. Fewer than three spans on every lane read
as nothing."""

import statistics


def read(ctx, span: str = "launch_on_device"):
    if ctx.sink is None:
        return None
    ends: dict[int, list] = {}
    for s in ctx.sink.named(span, ctx.result.t0_epoch, ctx.result.t1_epoch):
        ends.setdefault(s[3], []).append(s[2])
    gaps = []
    for ts in ends.values():
        ts.sort()
        gaps += [b - a for a, b in zip(ts, ts[1:])]
    return 1e3 * statistics.median(gaps) if len(gaps) >= 2 else None
