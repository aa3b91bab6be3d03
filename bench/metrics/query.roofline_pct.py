"""Share of the HBM roofline reached by the estimate programs
(``_estimate_batch_core``, self and join): the least bytes their operands
make them move (``bench.costs.query_bytes``, from each batch span's
streams or pairs) over their device time in the trace, against the chip's
peak bandwidth."""
from bench import costs


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    seconds, calls = run.trace.program("_estimate_batch_core")
    batches = [e for e in run.spans
               if e["name"] in ("query.self_batch", "query.join_batch")]
    if not seconds or calls != len(batches):
        return None
    sh = run.shapes
    moved = sum(costs.query_bytes(
        streams=e.get("streams", e.get("pairs")), levels=sh["levels"],
        depth=sh["depth"], width=sh["width"],
        join=e["name"] == "query.join_batch") for e in batches)
    return 100.0 * moved / run.peaks.hbm_bytes_per_s / seconds
