"""bicriteria_cpu_s.ready — Core build (host): CPU seconds of the bicriteria phase summed over a build's bands, median per build (s)."""
from yardstick import phases


def read(run):
    return phases.per_build(run, ("core.bicriteria",), phases.cpu_s)
