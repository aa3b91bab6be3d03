"""``EstimationService.flush()``: every record submitted so far is
committed to its tenant's window once it returns."""


def run(svc):
    svc.flush()
    return {"commit": True}
