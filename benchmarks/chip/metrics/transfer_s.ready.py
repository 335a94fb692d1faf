"""transfer_s.ready — Op dispatch: upload and read-back seconds of a build's compensated dispatches, median per build (s)."""
from yardstick import phases


def read(run):
    return phases.per_build(run, ("dispatch.h2d", "dispatch.d2h"))
