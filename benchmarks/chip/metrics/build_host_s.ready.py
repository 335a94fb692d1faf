"""build_host_s.ready — Core build (host): engine.compress span minus the ops.dispatch spans beneath it, median per build (s)."""
from yardstick import layers


def read(run):
    return layers.host_self_median(run, "engine.compress", "ops.dispatch")
