"""Share of the HBM roofline reached by the flush program
(``multi_round_update``): the least bytes its operands make it move
(``bench.costs.ingest_flush_bytes``, from each ``ingest.flush_cohort``
span's streams and rounds) over its device time in the trace, against the
chip's peak bandwidth.  Bound by bytes: the v5e publishes no int32 VPU
peak, so the hashing's operation count sets no bound."""
from bench import costs


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    seconds, calls = run.trace.program("multi_round_update")
    cohorts = [e for e in run.spans if e["name"] == "ingest.flush_cohort"]
    if not seconds or calls != len(cohorts):
        return None
    sh = run.shapes
    moved = sum(costs.ingest_flush_bytes(
        rounds=e["rounds"], streams=e["streams"], batch_rows=sh["batch_rows"],
        d=sh["d"], levels=sh["levels"], depth=sh["depth"], width=sh["width"])
        for e in cohorts)
    return 100.0 * moved / run.peaks.hbm_bytes_per_s / seconds
