"""Each cycle ``count`` tenants, drawn uniformly without replacement from
all of them, each submit ``cycle.records`` records.

    "tenants": {"pick": "uniform", "count": 256}
"""
import numpy as np


def picks(rng, cycle, *, cycles, tenants, self_tenants, join_pairs):
    k, m = int(cycle["tenants"]["count"]), int(cycle["records"])
    return [(rng.choice(tenants, k, replace=False), np.full(k, m))
            for _ in range(cycles)]
