"""The `counter` reading for counters a program may not have yet: the
difference of `key` over the window, divided by the difference of `per` when
one is named, times `scale`. A program that does not export `key` (or `per`)
— the parent of the PR that brought the counter — reads as nothing, where
`counter` would raise; nothing to divide by reads as nothing too."""


def read(ctx, key: str, per: str | None = None, scale: float = 1.0):
    have = ctx.counters0.keys() & ctx.counters1.keys()
    if key not in have or (per is not None and per not in have):
        return None
    num = ctx.delta(key)
    if per is None:
        return num * scale
    den = ctx.delta(per)
    return num / den * scale if den else None
