"""How late the load generator ran: the 95th percentile of (sent - due) over
the window's requests, in ms. In a closed loop a request is due when its
client is free, so this reads the generator's own overhead."""


def read(ctx):
    lags = sorted(r.sent - r.due for r in ctx.result.in_window())
    if not lags:
        return None
    return 1e3 * lags[min(len(lags) - 1, int(0.95 * len(lags)))]
