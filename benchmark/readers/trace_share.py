"""Share of the device-busy time spent in operations whose name matches
`pattern` (a regular expression, searched), in percent — self time, so a
loop that holds the kernel adds nothing. No matching operation reads as
nothing, never as 0."""

import re


def read(ctx, pattern: str):
    tr = ctx.trace
    if tr is None or not tr.busy_s:
        return None
    rx = re.compile(pattern)
    hit = sum(s for name, s in tr.self_seconds().items() if rx.search(name))
    if not hit:
        return None
    return 100.0 * hit / (tr.busy_s * len(tr.planes))
