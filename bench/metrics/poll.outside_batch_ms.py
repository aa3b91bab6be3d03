"""Mean time of a ``service.poll`` span outside its ``query.self_batch``
and ``query.join_batch`` children: the snapshot, the no-op flush, the
planner and assembling the answers."""
from bench.metrics import _spans

BATCHES = ("service.poll/query.self_batch", "service.poll/query.join_batch")


def read(run):
    polls = _spans.top(run, "service.poll")
    if not polls:
        return None
    out = [p["total_ms"] - sum(c["total_ms"] for c in _spans.children(
        run, p, BATCHES)) for p in polls]
    return sum(out) / len(out)
