"""Launch fill over the window, in percent: candidates the device verified
per launch it ran, over the lanes of a launch (what `launchFillRatio`
averages over the service's whole life, taken over the window alone)."""


def read(ctx, candidates: str = "verifierCandidates",
         launches: str = "verifierLaunches"):
    n = ctx.delta(launches)
    return 100.0 * ctx.delta(candidates) / (n * ctx.lanes) if n else None
