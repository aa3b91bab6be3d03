"""Helpers the span readers share: pairing span events with their
children by time (a child starts inside its parent and ends by its end)."""


def top(run, name):
    return [e for e in run.spans if e["path"] == name]


def children(run, parent, paths):
    lo = parent["ts"]
    hi = lo + parent["total_ms"] * 1e-3
    return [e for e in run.spans if e["path"] in paths
            and lo <= e["ts"] <= hi]
