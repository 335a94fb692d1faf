"""decode_s.ready — SDK + HTTP: server seconds reading and decoding a register body, median over the window's POST /v1/signals requests (s)."""
from yardstick import phases


def read(run):
    return phases.per_root(run, "POST /v1/signals",
                           ("http.read_body", "http.decode"))
