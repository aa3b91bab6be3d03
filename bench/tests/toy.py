"""Toy-sized copies of the service cells for the CPU tests: the same
driver, reference and readers, with the deployment and the traffic shrunk
as data."""
from __future__ import annotations

import copy
import dataclasses
import time

from bench import harness

SEED = 2 ** 31 + 12345          # larger than 32 signed bits hold
SECONDS = 1.0

ZIPF = "svc4096-ingest-zipf"
INGEST = "svc4096-ingest-active256"
POLL = "svc4096-poll-standing72"


def cell(name: str) -> harness.Cell:
    c = harness.find_cell(name)
    config = copy.deepcopy(c.config)
    traffic = copy.deepcopy(c.traffic)
    traffic.update(plan_cycles=8)
    if name == INGEST:
        config["tenants"] = 32
        traffic["pool"] = {"records": 65536, "block": 1024}
        traffic["cycle"]["tenants"]["count"] = 4
        traffic["compare"]["tenants"] = 8
    elif name == ZIPF:
        config["tenants"] = 32
        traffic["pool"] = {"records": 65536, "block": 1024}
        traffic["cycle"]["tenants"]["active"] = 8
        traffic["cycle"]["records"] = 4000
        traffic["compare"]["tenants"] = 8
    else:
        config["tenants"] = 24
        traffic["queries"] = {"all_thresholds": 6, "join": 2}
        traffic["prefill_records"] = 1024
        traffic["pool"] = {"records": 81920, "block": 512}
        traffic["cycle"]["tenants"].update(self=2, join=1)
        traffic["compare"]["tenants"] = 4
    return dataclasses.replace(c, config=config, traffic=traffic)


def run(name: str, *, trace: bool = False, seed: int = SEED):
    return harness.run_cell(cell(name), seed=seed, seconds=SECONDS,
                            trace=trace, started=time.time())
