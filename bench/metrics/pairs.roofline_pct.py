"""Share of the HBM roofline reached by the ``fused_pairs`` launches: the
least bytes their operands make them move (``bench.costs_pairs``) over
the device time of the ``fused_pairs_pallas`` programs in the trace (each
one ``sjpc_fused_pairs`` launch with its padding), against the chip's
peak bandwidth.  A launch's shape comes from its span: ``query.pairs``
(streams x slots) and the reservoir's ``query.bootstrap`` (streams x
replicates samples of ``slots``).  None unless every launch has its
span."""
from bench import costs_pairs

PAIRS = "service.poll/query.self_batch/query.pairs"
BOOTSTRAP = "service.poll/query.self_batch/query.bootstrap"


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    seconds, calls = run.trace.program("fused_pairs_pallas")
    launches = ([(e["streams"], e["slots"]) for e in run.spans
                 if e["path"] == PAIRS]
                + [(e["streams"] * e["replicates"], e["slots"])
                   for e in run.spans if e["path"] == BOOTSTRAP
                   and e["method"] == "bootstrap"])
    if not seconds or calls != len(launches):
        return None
    d = run.shapes["d"]
    moved = sum(costs_pairs.pairs_bytes(streams=n, slots=r, d=d)
                for n, r in launches)
    return 100.0 * moved / run.peaks.hbm_bytes_per_s / seconds
