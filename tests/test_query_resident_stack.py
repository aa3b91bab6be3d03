"""The query engine's resident stacks (DESIGN.md §12.1): each self
launch keeps its stacked states across polls and restacks only the rows
whose window state changed.  Whatever path a poll takes -- incremental
update, full rebuild, membership change, a stream re-created under its old
name -- the resident stack equals ``stack_states`` over the snapshot's
views bit for bit, and every answer equals a fresh engine's."""
from __future__ import annotations

import logging

import jax
import numpy as np
import pytest

from repro.core.sjpc import SJPCConfig
from repro.estimators import stack_states
from repro.obs import MetricsRegistry, Observability, Tracer
from repro.service import ContinuousQuery, EstimationService, ServiceConfig
from repro.service.query import QueryEngine, update_rows

CFG = SJPCConfig(d=6, s=4, width=256, depth=2, seed=3)
N = 64                     # streams in the group
QUERIED = (0, 3, 17, 40, 63)


def _records(rng, n=96):
    return rng.integers(0, 40, size=(n, CFG.d), dtype=np.uint32)


def _service(kind: str):
    reg = MetricsRegistry()
    obs = Observability(metrics=reg, tracer=Tracer(registry=reg))
    # window_epochs=1: every advance_epoch expires every window
    svc = EstimationService(ServiceConfig(batch_rows=64, window_epochs=1),
                            obs=obs)
    svc.create_group("g", CFG)
    for i in range(N):
        svc.create_stream(f"t{i:02d}", "g", estimator=kind)
    for i in QUERIED:
        svc.register_continuous(ContinuousQuery(
            f"q{i}", "all_thresholds", (f"t{i:02d}",)))
    return svc


def _resident(svc):
    """The one resident stack of group g's cohort, and the views it is
    checked against."""
    views = list(svc.engine.snapshot()._views.values())
    v = views[0]
    return svc.engine._stacks[((v.group_id, id(v.estimator),
                                v.shape_sig),)], views


def _assert_bitwise(a, b):
    for x, y in zip(a, b):
        x, y = np.asarray(x), np.asarray(y)
        assert x.dtype == y.dtype and x.shape == y.shape
        np.testing.assert_array_equal(x, y)


def _assert_same_answer(a, b):
    assert a.keys() == b.keys()
    for k in a:
        ra, rb = a[k], b[k]
        np.testing.assert_array_equal(ra.per_level, rb.per_level)
        assert ra._replace(per_level=None) == rb._replace(per_level=None)


def _recreate(svc, name):
    """Drop ``name`` and register a fresh stream under it in its old
    place: the same name and row, the window version back to 0."""
    reg = svc.registry
    old = reg._streams.pop(name)
    svc.create_stream(name, old.group_id, estimator=old.estimator_kind,
                      uid=old.uid)
    reg._streams = dict(sorted(reg._streams.items(),
                               key=lambda kv: kv[1].uid))
    return old.window.version


def _change(k):
    def step(svc, rng):
        for i in range(k):
            svc.ingest(f"t{(7 * i) % N:02d}", _records(rng))
    return step


def _expire(svc, rng):
    svc.advance_epoch()


def _add(svc, rng):
    svc.create_stream("t64", "g",
                      estimator=svc.registry.stream("t00").estimator_kind)


def _recreate_t03(svc, rng):
    old = _recreate(svc, "t03")
    # bring the new window back to the dropped one's version, so a
    # (name, version) match would take the stale row for current
    for j in range(old):
        svc.ingest("t03", _records(rng))
        if j == old - 1:
            svc.ingest("t05", _records(rng))
        svc.flush()


# (label, step, the query.stack spans' (mode, changed) in the poll after it)
STEPS = [("prefill", _change(N), [("full", N)]),
         ("0", _change(0), []),
         ("1", _change(1), [("incremental", 1)]),
         ("5", _change(5), [("incremental", 5)]),
         ("9", _change(9), [("incremental", 9)]),
         ("expire", _expire, [("full", N)]),
         ("add", _add, [("full", N + 1)]),
         ("recreate", _recreate_t03, [("incremental", 2)])]


@pytest.mark.parametrize("kind", ["sjpc", "reservoir", "lsh_ss"])
def test_resident_stack_equals_a_fresh_stack_after_every_poll(kind):
    svc = _service(kind)
    rng = np.random.default_rng(11)
    for label, step, want in STEPS:
        n0 = len(svc.obs.tracer.events)
        step(svc, rng)
        out = svc.poll()
        got = [(e["mode"], e["changed"])
               for e in list(svc.obs.tracer.events)[n0:]
               if e["name"] == "query.stack"]
        assert got == want, (label, got)
        res, views = _resident(svc)
        assert res.names == tuple(v.name for v in views)
        _assert_bitwise(res.stacked, stack_states([v.state for v in views]))
        fresh = QueryEngine(svc.registry).snapshot()
        for name, q in svc._continuous.items():
            _assert_same_answer(out[name], q.evaluate(fresh))


# -- the mechanism --------------------------------------------------------

def _stack_events(svc, n0):
    return [e for e in list(svc.obs.tracer.events)[n0:]
            if e["name"] == "query.stack"]


def test_changed_rows_share_a_power_of_two_bucket(caplog):
    # a state shape no other test compiles, so every update_rows compile
    # logged here is this test's own.  XLA compiles are counted, not jit
    # cache entries: on the CPU a full rebuild leaves a NumPy stack, which
    # takes its own cache entry for the same executable
    reg = MetricsRegistry()
    svc = EstimationService(ServiceConfig(batch_rows=64), obs=Observability(
        metrics=reg, tracer=Tracer(registry=reg)))
    svc.create_group("g", SJPCConfig(d=6, s=4, width=128, depth=3, seed=7))
    for i in range(20):
        svc.create_stream(f"t{i:02d}", "g")
        svc.register_continuous(ContinuousQuery(
            f"q{i}", "self_join", (f"t{i:02d}",)))
    rng = np.random.default_rng(3)

    def poll(k):
        for i in range(k):
            svc.ingest(f"t{i:02d}", _records(rng, 10))
        n0 = len(svc.obs.tracer.events)
        caplog.clear()
        with jax.log_compiles(True), caplog.at_level(logging.WARNING):
            svc.poll()
        assert [e["mode"] for e in _stack_events(svc, n0)] == \
            ["incremental" if 2 * k <= 20 else "full"]
        return sum("Compiling jit(update_rows)" in r.getMessage()
                   for r in caplog.records)

    assert poll(20) == 0                        # full: stack_states
    assert poll(5) == 1
    assert poll(6) == 0 and poll(8) == 0        # one executable for 5..8
    assert poll(9) == 1
    assert poll(3) == 1
    assert poll(11) == 0                        # more than half: full


def test_stack_rows_counter_and_span_attributes():
    svc = _service("sjpc")
    reg = svc.obs.metrics
    rng = np.random.default_rng(4)

    def rows(mode):
        return reg.counter("query_stack_rows_total", group="g",
                           kind="sjpc", mode=mode)

    want = {"stacked": 0.0, "reused": 0.0}
    for k, stacked in ((N, N), (5, 5), (1, 1), (N // 2 + 1, N)):
        _change(k)(svc, rng)
        svc.poll()
        want["stacked"] += stacked
        want["reused"] += N - stacked
        assert {m: rows(m) for m in want} == want, k

    # a miss with no changed row (another clamp) reuses the stack as is
    snap = svc.engine.snapshot()
    before = svc.engine._stacks.copy()
    n0 = len(svc.obs.tracer.events)
    snap.self_join("t00", clamp=False)
    e, = _stack_events(svc, n0)
    assert (e["mode"], e["changed"], e["streams"]) == ("incremental", 0, N)
    assert all(svc.engine._stacks[k].stacked is before[k].stacked
               for k in before)
    want["reused"] += N
    assert {m: rows(m) for m in want} == want

    # a subset snapshot stacks its own rows and keeps nothing resident
    n0 = len(svc.obs.tracer.events)
    svc.snapshot(["t00"]).self_join("t00", clamp=False)
    e, = _stack_events(svc, n0)
    assert (e["mode"], e["changed"], e["streams"]) == ("full", 1, 1)
    assert svc.engine._stacks.keys() == before.keys()
    assert all(svc.engine._stacks[k].stacked is before[k].stacked
               for k in before)
