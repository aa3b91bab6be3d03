"""Mean summed ``total_ms`` of the ``query.self_batch`` and
``query.join_batch`` spans of a poll: stacking the cohort's states, the
estimate programs on the device and the host bounds tail."""
from bench.metrics import _spans

BATCHES = ("service.poll/query.self_batch", "service.poll/query.join_batch")


def read(run):
    polls = _spans.top(run, "service.poll")
    if not polls:
        return None
    return sum(c["total_ms"] for p in polls
               for c in _spans.children(run, p, BATCHES)) / len(polls)
