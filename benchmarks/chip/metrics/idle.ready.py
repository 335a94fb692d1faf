"""idle.ready — Device: idle share of the traced window, 100 x (1 - busy / window) (%)."""
from yardstick import layers


def read(run):
    return layers.idle_pct(run)
