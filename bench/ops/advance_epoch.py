"""``EstimationService.advance_epoch()``: pending records are committed,
then every tenant's open epoch closes; the oldest expires once the window
holds ``window_epochs``."""


def run(svc):
    svc.advance_epoch()
    return {"commit": True, "advance": True}
