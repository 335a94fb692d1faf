"""certified_share.k16 — Core build (host): share of the window's builds whose shared sigma rests on the bands' certified bounds, not on the greedy floor (``core.shared_tolerance``.``certified``) (%)."""
from yardstick.layers import _descendants


def read(run):
    said = []
    for b in run.window_spans("engine.compress"):
        got = [d.attrs["certified"] for d in _descendants(run, b)
               if d.name == "core.shared_tolerance" and "certified" in d.attrs]
        if got:
            said.append(all(got))
    return 100.0 * sum(said) / len(said) if said else None
