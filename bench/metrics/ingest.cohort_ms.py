"""Mean ``total_ms`` of the ``ingest.flush_cohort`` spans per top-level
flush: coalescing the cohort's rounds, the upload, the fused ingest
dispatch and its device time (the span blocks on its outputs)."""
from bench.metrics import _spans


def read(run):
    flushes = _spans.top(run, "service.flush")
    if not flushes:
        return None
    total = sum(c["total_ms"] for f in flushes for c in _spans.children(
        run, f, ("service.flush/ingest.flush_cohort",)))
    return total / len(flushes)
