"""register_s.ready — SDK + HTTP: median register latency of a fresh record, as the client sees it (s)."""
from yardstick import layers


def read(run):
    return layers.client_median(run, "sent", "registered")
