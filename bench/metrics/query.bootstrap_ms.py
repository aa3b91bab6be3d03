"""Mean summed ``total_ms`` per poll of the ``query.bootstrap`` spans: a
sample cohort's error bars, the reservoir's resample and its one
``fused_pairs`` launch over every replicate, and LSH-SS's stratified
Dirichlet resample on the host.  The span ends after its own
``device_get``, so it holds the device time too.  None where no poll
opened one."""
from bench.metrics import _spans

PATH = "service.poll/query.self_batch/query.bootstrap"


def read(run):
    polls = _spans.top(run, "service.poll")
    spans = [c for p in polls for c in _spans.children(run, p, (PATH,))]
    if not spans:
        return None
    return sum(c["total_ms"] for c in spans) / len(polls)
