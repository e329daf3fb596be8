"""Whether the timed path served the right logits.

After the window has closed, every delivered request's logits are compared
with the reference's logits for the image it carried (the images come from
a seeded pool, so the reference runs once per distinct image).  The number
compared is the widest gap of any request, relative to that image's
largest reference logit:

    logit_gap = max over requests of  max|served - ref| / max|ref|

and it must not exceed the configuration's limit.  Every request due in the
window must have been delivered: ``undelivered`` has the limit 0.
"""
from __future__ import annotations

import numpy as np


def reference_logits(reference, traffic, keys, precision: str) -> dict:
    """{pool index: logits} for each image in ``keys``."""
    ks = sorted(keys)
    got = reference.logits(traffic.pool[ks], precision)
    return dict(zip(ks, got))


def relative_gap(got: np.ndarray, ref: np.ndarray) -> float:
    return float(np.abs(got - ref).max() / max(float(np.abs(ref).max()),
                                               1e-30))


def compare(record, traffic, ref: dict, limits: dict) -> dict:
    """{name: {"value", "limit"}} for every number compared."""
    gaps = [relative_gap(logits, ref[traffic.image(i)])
            for i, logits in record.logits.items()]
    gap = max(gaps) if gaps else float("nan")
    return {
        "logit_gap": {"value": gap, "limit": float(limits["logit_gap"])},
        "undelivered": {"value": int(record.attempted - record.delivered.sum()),
                        "limit": 0},
    }


def passed(checks: dict) -> bool:
    """Every number at or under its limit (a NaN fails)."""
    return all(c["value"] <= c["limit"] for c in checks.values())
