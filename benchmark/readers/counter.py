"""A service counter over the window: the difference of `key` between the
window's two edges, divided by the difference of `per` when one is named
(host milliseconds per launch), times `scale`. Nothing to divide by reads
as nothing."""


def read(ctx, key: str, per: str | None = None, scale: float = 1.0):
    num = ctx.delta(key)
    if per is None:
        return num * scale
    den = ctx.delta(per)
    return num / den * scale if den else None
