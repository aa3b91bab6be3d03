"""Share of the traced window in which no operation ran on the device:
1 - busy / window, busy being the union of the device's op intervals
(``bench.trace_reduce``)."""


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    return run.trace.idle_pct
