"""The end-to-end metrics, from the load generator's records.

Every request is timed on the client, from when it was sent (closed loop)
to when its answer arrived; a request that failed or never answered counts
as infinitely late, so a percentile over all requests shows it.
Percentiles are numpy's linear interpolation over all requests of the
window.
"""
from __future__ import annotations

import numpy as np

__all__ = ["END_TO_END", "percentile"]


def percentile(values, q: float) -> float:
    """numpy's linear percentile, where a failure (+inf) above the
    interpolation point makes the percentile infinite instead of NaN."""
    v = np.sort(np.asarray(values, np.float64))
    pos = q / 100.0 * (v.size - 1)
    lo, hi = int(np.floor(pos)), int(np.ceil(pos))
    if hi == lo or v[hi] == v[lo]:
        return float(v[lo])
    return float(v[lo] + (v[hi] - v[lo]) * (pos - lo))


def ready_p50_s(run) -> float:
    """Median seconds from the start of a fresh record's register to the
    answer of its build, over every record whose register began in the
    window (failures count as infinitely late)."""
    r = run.records
    ready = np.where(np.asarray(r["ok"], bool),
                     np.asarray(r["done"]) - np.asarray(r["sent"]), np.inf)
    return percentile(ready, 50)


def setup_s(run) -> float:
    return run.setup_s


END_TO_END = {
    "ready_p50_s": ready_p50_s,
    "setup_s": setup_s,
}
