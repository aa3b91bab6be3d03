"""Each cycle the next ``self`` of the self-query tenants and the next
``join`` of the join tenants, in a seeded order, each submit
``cycle.records`` records.  Touching one join tenant a cycle re-estimates
exactly one join pair per poll, so the poll's join batch keeps one shape.

    "tenants": {"pick": "rotate_queried", "self": 7, "join": 1}
"""
import numpy as np


def picks(rng, cycle, *, cycles, tenants, self_tenants, join_pairs):
    who, m = cycle["tenants"], int(cycle["records"])
    k_self, k_join = int(who["self"]), int(who["join"])
    s_order = rng.permutation(self_tenants)
    j_order = rng.permutation(np.asarray(join_pairs).reshape(-1))
    out = []
    for c in range(cycles):
        t = np.concatenate([
            s_order[(c * k_self + np.arange(k_self)) % len(s_order)],
            j_order[(c * k_join + np.arange(k_join)) % len(j_order)]])
        out.append((t, np.full(len(t), m)))
    return out
