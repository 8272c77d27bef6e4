"""The idle share of the idlest chip in the traced interval, in percent: one
minus the union of device-operation intervals of that chip's plane over the
interval. `device.idle` is the mean over the chips; beside it this says
whether one chip waits while the others work. A trace of one chip has no
idlest one: nothing to read."""


def read(ctx):
    tr = ctx.trace
    if tr is None or not tr.window_ns or len(tr.planes) < 2:
        return None
    return 100.0 * (1.0 - min(p.busy_ns for p in tr.planes) / tr.window_ns)
