"""Latency percentiles over every request of a window."""
from __future__ import annotations

import math

import numpy as np


def percentile_ms(record, q: float) -> float | None:
    """The ``q``-th percentile of due-to-delivery latency in ms, counting an
    undelivered request as infinitely late; None when it lands on one."""
    if not record.attempted:
        return None
    lat = np.where(record.delivered, record.latency, np.inf)
    v = float(np.percentile(lat, q, method="higher"))
    return v * 1e3 if math.isfinite(v) else None
