"""Device-busy milliseconds per launch, from the profiler trace: the union
of device-operation intervals in the traced interval over the launches in
it. The launch program is the one that ran most on the device (line "XLA
Modules"); an execution cut by the interval's edge counts by the share of it
that lies inside."""


def read(ctx):
    tr = ctx.trace
    if tr is None:
        return None
    launches = sum(
        max(p.executions.values()) for p in tr.planes if p.executions
    )
    if not launches:
        return None
    return sum(p.busy_ns for p in tr.planes) / 1e6 / launches
