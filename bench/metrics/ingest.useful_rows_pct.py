"""Share of the rows handed to the ingest kernel that carried a record:
``ingest_submitted_records_total`` over ``ingest_dispatch_rows_total``,
both exact counts over the window."""


def read(run):
    rows = run.counters.get("ingest_dispatch_rows_total", 0.0)
    if not rows or "flush" not in run.latencies:
        return None
    return 100.0 * run.counters["ingest_submitted_records_total"] / rows
