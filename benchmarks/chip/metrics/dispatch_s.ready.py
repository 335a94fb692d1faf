"""dispatch_s.ready — Op dispatch: sat_moments dispatch seconds per build, median over the window's builds (s)."""
from yardstick import layers


def read(run):
    return layers.dispatch_per_trace(run, "sat_moments", "engine.compress")
