"""blocks.k16 — Core build (host): blocks of the composed coreset (``core.compose``.``blocks``), median over the window's builds (count)."""
from yardstick import phases


def _blocks(s):
    return s.attrs.get("blocks")


def read(run):
    return phases.per_build(run, ("core.compose",), _blocks)
