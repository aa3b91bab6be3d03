"""Records committed to the windows per second: every record whose
``flush()`` returned inside the window, over the whole window."""


def read(run):
    if "flush" not in run.latencies:
        return None
    return run.work["records"] / run.window_s
