"""tolerance_s.ready — Core build (host): seconds of the serial shared tolerance (full integral image and greedy k-tree) per build, median over the window's builds (s)."""
from yardstick import phases


def read(run):
    return phases.per_build(run, ("core.shared_tolerance",))
