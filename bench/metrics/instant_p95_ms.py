"""95th percentile over every simulated instant of the window of its wall
time, from taking its first event to finishing its last (host clock)."""
import statistics


def read(ctx):
    xs = ctx["instant_seconds"]
    if len(xs) < 2:
        return None
    return 1e3 * statistics.quantiles(xs, n=100, method="inclusive")[94]
