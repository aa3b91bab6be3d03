"""Each cycle the next ``each`` self-query tenants of every estimator kind
the mix aims at, in a seeded order per kind, each submit
``cycle.records`` records.  The generator numbers the aimed tenants kind
by kind; the driver passes the size of each kind's block as ``blocks``.

    "tenants": {"pick": "rotate_kinds", "each": 4}
"""
import numpy as np


def picks(rng, cycle, *, cycles, tenants, self_tenants, join_pairs):
    who, m = cycle["tenants"], int(cycle["records"])
    each = int(who["each"])
    edges = np.cumsum([0] + list(who["blocks"]))
    queried = np.asarray(self_tenants)
    orders = [rng.permutation(queried[(queried >= lo) & (queried < hi)])
              for lo, hi in zip(edges[:-1], edges[1:])]
    out = []
    for c in range(cycles):
        t = np.concatenate([o[(c * each + np.arange(each)) % len(o)]
                            for o in orders])
        out.append((t, np.full(len(t), m)))
    return out
