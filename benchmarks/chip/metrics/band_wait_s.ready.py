"""band_wait_s.ready — Core build (host): seconds the band threads were runnable but not running (wall minus CPU), summed over a build's bands, median per build (s)."""
from yardstick import phases


def read(run):
    return phases.per_build(run, ("core.band",), phases.wait_s)
