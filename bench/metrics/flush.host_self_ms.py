"""Mean self time of a flush on the host: each top-level ``service.flush``
span minus its ``ingest.flush_cohort`` children.  What is left is the
front end and the window commit (coalescing into numpy, ``index_state``,
``absorb_delta`` and the block on every committed window)."""
from bench.metrics import _spans


def read(run):
    flushes = _spans.top(run, "service.flush")
    if not flushes:
        return None
    self_ms = [f["total_ms"] - sum(c["total_ms"] for c in _spans.children(
        run, f, ("service.flush/ingest.flush_cohort",))) for f in flushes]
    return sum(self_ms) / len(self_ms)
