"""sat_moments_roofline.ready — Kernels: the compensated integral-image program's share of its roofline (%), with the bound that sets it."""
from yardstick import layers


def read(run):
    return layers.sat_moments_roofline(run)
