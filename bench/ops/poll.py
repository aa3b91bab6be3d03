"""``EstimationService.poll()``: pending records are committed (the poll
flushes first), then every standing query is answered in host floats."""


def run(svc):
    return {"commit": True, "answers": svc.poll()}
