"""partition_cpu_s.ready — Core build (host): CPU seconds of the balanced partition summed over a build's bands, median per build (s)."""
from yardstick import phases


def read(run):
    return phases.per_build(run, ("core.partition",), phases.cpu_s)
