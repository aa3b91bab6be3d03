"""The comparison catches a broken timed path: each fault is planted in
the program underneath a toy run (the harness's look for a chip is not
made), and ``correct`` has to come out false.  One chip has no exchange
between chips to leave out."""
import pytest

from bench.tests import toy


def _state_unchanged(monkeypatch):
    from repro.service import window
    monkeypatch.setattr(window.WindowedSketch, "absorb_delta",
                        lambda self, new_state: None)


def _half_batch(monkeypatch):
    from repro.service import ingest
    real = ingest.multi_round_update

    def half(cfg, params, counters, n, steps, values, row_mask, keys, **kw):
        B = row_mask.shape[-1]
        row_mask = row_mask.at[..., B // 2:].set(0)
        return real(cfg, params, counters, n, steps, values, row_mask, keys,
                    **kw)

    monkeypatch.setattr(ingest, "multi_round_update", half)


def _counter_altered(monkeypatch):
    from repro.service import ingest
    real = ingest.multi_round_update

    def altered(*args, **kw):
        counters, n, steps = real(*args, **kw)
        return counters.at[:, 0, 0, 0].add(1), n, steps

    monkeypatch.setattr(ingest, "multi_round_update", altered)


def _estimate_altered(monkeypatch):
    from repro.core import sjpc
    real = sjpc.estimate_batch

    def altered(*args, **kw):
        est = real(*args, **kw)
        return est._replace(g=est.g * 1.01)

    monkeypatch.setattr(sjpc, "estimate_batch", altered)


FAULTS = {
    "state_unchanged": (_state_unchanged, (toy.INGEST, toy.POLL)),
    "half_batch_left_out": (_half_batch, (toy.INGEST,)),
    "counter_altered": (_counter_altered, (toy.INGEST,)),
    "estimate_altered": (_estimate_altered, (toy.POLL,)),
}


@pytest.mark.parametrize("fault,name", [(f, n) for f, (_, cells)
                                        in FAULTS.items() for n in cells])
def test_planted_fault_is_not_correct(fault, name, monkeypatch):
    FAULTS[fault][0](monkeypatch)
    run = toy.run(name)
    assert not run.correct, run.checks
