"""Seconds from process start to the opening of the measured window:
building the service or model, drawing the traffic, warming every shape
(compiling, or loading from the persistent cache)."""


def read(run):
    return run.setup_s
