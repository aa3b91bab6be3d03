"""Skewed activity: each cycle ``active`` tenants, drawn uniformly without
replacement (the hot set moves every cycle), share ``cycle.records``
records by a Zipf law: the tenant of rank k gets a share in proportion to
k ** -exponent, at least one record.  The shares are the same every cycle
and every seed; only which tenant holds which rank changes.

    "tenants": {"pick": "zipf", "active": 4096, "exponent": 1.1}
"""
import numpy as np


def shares(total: int, active: int, exponent: float) -> np.ndarray:
    """Records of ranks 1..active: floor of the Zipf share, at least one,
    the remainder to the top ranks, summing to ``total``."""
    w = np.arange(1, active + 1, dtype=np.float64) ** -exponent
    n = np.maximum(np.floor(total * w / w.sum()).astype(np.int64), 1)
    short = total - int(n.sum())
    n[:abs(short)] += np.sign(short)
    return n


def picks(rng, cycle, *, cycles, tenants, self_tenants, join_pairs):
    who = cycle["tenants"]
    k = int(who["active"])
    n = shares(int(cycle["records"]), k, float(who["exponent"]))
    return [(rng.choice(tenants, k, replace=False), n) for _ in range(cycles)]
