"""The sample estimators' child spans inside a poll (DESIGN.md §15.2): a
poll over a reservoir and an LSH-SS cohort opens ``query.pairs``,
``query.bootstrap`` and ``query.strata`` under
``service.poll/query.self_batch``, with their attributes, and the spans
change no answer."""
from __future__ import annotations

import numpy as np

from repro.core.sjpc import SJPCConfig
from repro.estimators.uncertainty import DEFAULT_REPLICATES
from repro.obs import MetricsRegistry, Observability, Tracer
from repro.service import ContinuousQuery, EstimationService, ServiceConfig

CFG = SJPCConfig(d=6, s=4, width=256, depth=2, seed=3)
BATCH = "service.poll/query.self_batch"
KINDS = {"res": "reservoir", "lsh": "lsh_ss"}


def _service(observe: bool):
    obs = None
    if observe:
        reg = MetricsRegistry()
        obs = Observability(metrics=reg, tracer=Tracer(registry=reg))
    svc = EstimationService(ServiceConfig(batch_rows=64, window_epochs=2,
                                          observe=observe), obs=obs)
    svc.create_group("g", CFG)
    for name, kind in KINDS.items():
        for i in range(2):
            svc.create_stream(f"{name}{i}", "g", estimator=kind)
            svc.register_continuous(ContinuousQuery(
                f"q-{name}{i}", "all_thresholds", (f"{name}{i}",)))
    return svc


def _drive(svc):
    """Two polls around an epoch advance; returns every answer served."""
    rng = np.random.default_rng(5)
    out = []
    for _ in range(2):
        for name in svc.registry.streams("g"):
            svc.ingest(name.name, rng.integers(0, 40, size=(300, CFG.d),
                                               dtype=np.uint32))
        out.append(svc.poll())
        svc.advance_epoch()
    return [{q: [(r.s, r.estimate, r.stderr) for r in res.values()]
             for q, res in poll.items()} for poll in out]


def _contains(outer, inner) -> bool:
    slack = 1e-6                       # ts is rounded to the microsecond
    return (outer["ts"] - slack <= inner["ts"]
            and inner["ts"] + inner["total_ms"] * 1e-3
            <= outer["ts"] + outer["total_ms"] * 1e-3 + slack)


def test_poll_opens_the_sample_spans_under_the_batch():
    svc = _service(observe=True)
    _drive(svc)
    events = list(svc.obs.tracer.events)
    batches = {e["kind"]: [b for b in events if b["path"] == BATCH
                           and b["kind"] == e["kind"]]
               for e in events if e["path"] == BATCH}
    assert set(batches) == set(KINDS.values())
    by_path = {}
    for e in events:
        if e["path"].startswith(BATCH + "/"):
            by_path.setdefault(e["path"][len(BATCH) + 1:], []).append(e)
    assert {"query.stack", "query.pairs", "query.bootstrap",
            "query.strata"} <= set(by_path)

    R = svc.registry.group("g").estimator("reservoir").cfg.capacity
    for e in by_path["query.pairs"]:
        assert (e["streams"], e["slots"]) == (2, R)
        assert any(_contains(b, e) for b in batches["reservoir"])
    for e in by_path["query.strata"]:
        assert e["streams"] == 2
        assert any(_contains(b, e) for b in batches["lsh_ss"])
    methods = {}
    for e in by_path["query.bootstrap"]:
        assert (e["streams"], e["replicates"]) == (2, DEFAULT_REPLICATES)
        methods.setdefault(e["method"], []).append(e)
    assert set(methods) == {"bootstrap", "bootstrap_stratified"}
    for e in methods["bootstrap"]:
        assert e["slots"] == min(256, R)
        assert any(_contains(b, e) for b in batches["reservoir"])
    for e in methods["bootstrap_stratified"]:
        assert any(_contains(b, e) for b in batches["lsh_ss"])
    # the stratified bootstrap is not part of the stratum scaling
    for boot in methods["bootstrap_stratified"]:
        assert not any(_contains(s, boot) for s in by_path["query.strata"])
    # one of each span per cache-missing poll of each cohort
    polls = [e for e in events if e["path"] == "service.poll"]
    assert len(by_path["query.pairs"]) == len(by_path["query.strata"]) \
        == len(polls) == 2


def test_answers_are_identical_with_observability_on_and_off():
    on, off = _drive(_service(observe=True)), _drive(_service(observe=False))
    assert on == off
    assert all(a[0][2] > 0 for poll in on for a in poll.values())
