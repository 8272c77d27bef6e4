"""What a request waits outside its launch, in ms: the median request
latency minus the median wall of a launch, dispatch start to verdicts
fetched. A launch's wall is its `launch_staged` span plus its
`launch_on_device` span; `launch_fetched` lies INSIDE `launch_on_device`
(both end when the verdicts land) and is not added a second time. A lane
runs its launches in order, so the k-th staged span of a lane belongs to its
k-th on-device span. Spans are host-clock and come from the benchmark's own
sink."""

import statistics


def read(ctx, staged: str = "launch_staged", on_device: str = "launch_on_device"):
    if ctx.sink is None or not ctx.latencies_s:
        return None
    t0, t1 = ctx.result.t0_epoch, ctx.result.t1_epoch
    staged_of: dict[int, list] = {}
    for s in ctx.sink.named(staged):
        staged_of.setdefault(s[3], []).append(s[2] - s[1])
    seen: dict[int, int] = {}
    walls = []
    for s in ctx.sink.named(on_device):
        k = seen.get(s[3], 0)
        seen[s[3]] = k + 1
        if t0 <= s[2] <= t1 and k < len(staged_of.get(s[3], ())):
            walls.append((s[2] - s[1]) + staged_of[s[3]][k])
    if not walls:
        return None
    return 1e3 * (
        statistics.median(ctx.latencies_s) - statistics.median(walls)
    )
