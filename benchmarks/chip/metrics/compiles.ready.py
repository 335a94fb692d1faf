"""compiles.ready — Device: backend compiles inside the window (target: none)."""
from yardstick import layers


def read(run):
    return layers.compiles(run)
