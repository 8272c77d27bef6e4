"""How unevenly a plane's lanes were fed, in percent: 100 x (max - min) /
mean of the launches each lane ran in the window, counted from the ends of
the service's `span` spans (`launch_on_device`) by their `lane`. 0 is every
lane the same; a lane the scheduler starved reads as a large number. A lane
that ran nothing has no span: where the program's counters say how many
lanes the service has (`devicesTotal`), the lanes missing from the spans
count as 0 launches. A service of one lane, a program whose spans carry no
`lane`, no sink: nothing to read."""

from collections import Counter


def read(ctx, span: str = "launch_on_device"):
    if ctx.sink is None:
        return None
    by_lane = Counter(
        s[4]["lane"]
        for s in ctx.sink.named(span, ctx.result.t0_epoch, ctx.result.t1_epoch)
        if s[4].get("lane") is not None
    )
    lanes = max(len(by_lane), int(ctx.counters1.get("devicesTotal", 0)))
    if lanes < 2 or not by_lane:
        return None
    counts = sorted(by_lane.values()) + [0] * (lanes - len(by_lane))
    return 100.0 * (max(counts) - min(counts)) * lanes / sum(counts)
