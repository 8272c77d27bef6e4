"""The device's idle share of the traced interval, in percent: one minus the
union of device-operation intervals over the interval, averaged over the
chips used."""


def read(ctx):
    tr = ctx.trace
    if tr is None or not tr.window_s:
        return None
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)
