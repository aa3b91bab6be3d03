"""The DESIGN.md §19 extension surface, end to end: two estimator kinds
registered entirely from ``examples/plugins/`` serve through the service,
the planner, the accuracy auditor, the distributed wire format, and the
coordinator -- with zero edits under ``src/repro/{service,distributed,obs}``.

The module-scope import below registers "theta_kmv" and "ipf" before any
other module-scope ``estimators.available()`` enumeration in this test
process evaluates (pytest imports test modules alphabetically during
collection: test_estimators < test_plugins < test_wire), so the generic
conformance and wire suites parametrize over the plugin kinds for free.
"""
from __future__ import annotations

import importlib

import numpy as np
import pytest

import examples.plugins                     # registration side effect
from examples.plugins import inner_product, theta_sketch
from repro import estimators as E
from repro.core import exact
from repro.core.sjpc import SJPCConfig
from repro.distributed import harness
from repro.obs import MetricsRegistry, Observability, Tracer
from repro.service import (ContinuousQuery, EstimationService, ServiceConfig,
                           reference_snapshot)

CFG = SJPCConfig(d=5, s=3, ratio=1.0, width=128, depth=2, seed=31)
PLUGIN_KINDS = ("theta_kmv", "ipf")


def _records(n, rng=None, hi=6):
    rng = rng or np.random.default_rng(0)
    return rng.integers(0, hi, size=(n, CFG.d), dtype=np.uint32)


def _service(**cfg_kw):
    reg = MetricsRegistry()
    obs = Observability(metrics=reg, tracer=Tracer(registry=reg))
    svc = EstimationService(ServiceConfig(batch_rows=64, **cfg_kw), obs=obs)
    svc.create_group("g", CFG)
    return svc, obs


# ---------------------------------------------------------------------------
# registry: completeness, idempotency, conflict diagnostics
# ---------------------------------------------------------------------------

class TestPluginRegistry:
    def test_plugin_kinds_fully_registered(self):
        for kind in PLUGIN_KINDS:
            assert kind in E.available()
            sp = E.spec(kind)
            assert sp.factory is not None and sp.state_cls is not None
            assert sp.linear is not None and sp.join_capable is not None
            assert sp.stderr_kind == "none"
        assert E.spec("ipf").linear and E.spec("ipf").join_capable
        assert E.spec("ipf").wire_mode == "merge"
        assert E.spec("ipf").exact_oracle is not None
        sp = E.spec("theta_kmv")
        assert not sp.linear and not sp.join_capable
        assert sp.wire_mode == "replace" and sp.exact_oracle is None

    def test_reimport_and_reload_are_idempotent(self):
        before = {k: E.spec(k) for k in E.available()}
        import examples.plugins as again                    # noqa: F401
        importlib.reload(theta_sketch)
        importlib.reload(inner_product)
        assert set(E.available()) == set(before)
        for kind in PLUGIN_KINDS:
            assert E.spec(kind).state_cls.__name__ == \
                before[kind].state_cls.__name__

    def test_conflicting_reregistration_names_both_parties(self):
        def other_factory(cfg, *, params=None,
                          estimator_cfg=None):               # pragma: no cover
            raise AssertionError

        with pytest.raises(ValueError) as ei:
            E.register("theta_kmv", other_factory, linear=True)
        msg = str(ei.value)
        assert "theta_kmv" in msg
        assert "examples.plugins.theta_sketch" in msg        # prior claimant
        assert "test_plugins" in msg                         # new claimant
        # the registry survives the refusal untouched
        assert E.spec("theta_kmv").factory.__module__ == \
            "examples.plugins.theta_sketch"

    def test_load_plugins_env_roundtrip(self, monkeypatch):
        monkeypatch.setenv("REPRO_PLUGINS", "examples.plugins")
        E.load_plugins()                 # re-registration: identical, no-op
        assert set(PLUGIN_KINDS) <= set(E.available())


# ---------------------------------------------------------------------------
# service: plugin kinds served side by side with the builtins
# ---------------------------------------------------------------------------

class TestPluginService:
    def test_plugins_serve_alongside_builtins(self):
        svc, _ = _service()
        recs = _records(400)
        for kind in E.available():
            svc.create_stream(kind, "g", estimator=kind)
            svc.ingest(kind, recs)
        snap = svc.snapshot()
        x = np.asarray(exact.exact_pair_counts(recs))
        n = recs.shape[0]
        for kind in PLUGIN_KINDS:
            for s in range(CFG.s, CFG.d + 1):
                r = snap.self_join(kind, s=s)
                truth = float(x[s:].sum() + n)
                assert np.isfinite(r.estimate) and r.estimate >= 0
                assert r.stderr_kind == "none" and r.stderr == 0
                if kind == "ipf":        # a real estimator of the paper's g
                    assert r.estimate == pytest.approx(truth, rel=1.0)
        # theta's constant g column is n + duplicate-pair estimate: at the
        # top threshold (exact duplicates) it should be in the ballpark
        r = snap.self_join("theta_kmv", s=CFG.d)
        assert r.estimate == pytest.approx(float(x[CFG.d:].sum() + n),
                                           rel=0.5)

    def test_ipf_join_fused_matches_ref(self):
        recs_a, recs_b = _records(300), _records(200, np.random.default_rng(4))
        svc, _ = _service()
        svc.create_stream("a", "g", estimator="ipf")
        svc.create_stream("b", "g", estimator="ipf")
        svc.ingest("a", recs_a)
        svc.ingest("b", recs_b)
        results = {}
        for fused, snap in ((True, svc.snapshot()),
                            (False, reference_snapshot(svc.registry))):
            results[fused] = [snap.join("a", "b", s=s).estimate
                              for s in range(CFG.s, CFG.d + 1)]
        assert results[True] == pytest.approx(results[False], rel=1e-6)
        truth = np.asarray(exact.brute_force_join_counts(recs_a, recs_b))
        assert results[True][0] == pytest.approx(float(truth[CFG.s:].sum()),
                                                 rel=0.5)

    def test_theta_join_refused_via_spec(self):
        svc, _ = _service()
        svc.create_stream("a", "g", estimator="theta_kmv")
        svc.create_stream("b", "g", estimator="theta_kmv")
        svc.ingest("a", _records(50))
        svc.ingest("b", _records(50))
        with pytest.raises(ValueError, match="join-capable"):
            svc.snapshot().join("a", "b")

    def test_ipf_linear_window_expires_by_subtraction(self):
        svc, _ = _service(window_epochs=2)
        svc.create_stream("a", "g", estimator="ipf")
        rng = np.random.default_rng(9)
        per_epoch = [_records(60, rng) for _ in range(4)]
        for recs in per_epoch:
            svc.ingest("a", recs)
            svc.flush()
            svc.advance_epoch()
        mid = svc.registry.stream("a").window.total
        assert int(np.asarray(mid.n)) > 0          # window still live
        for _ in range(3):                         # idle epochs: all expire
            svc.advance_epoch()
        # every ingested epoch has rotated out: exact counter subtraction
        # (spec.linear delta-ring expiry) must leave the literal zero state
        total = svc.registry.stream("a").window.total
        assert int(np.asarray(total.n)) == 0
        assert not np.asarray(total.counters).any()


# ---------------------------------------------------------------------------
# observability: kinds without an exact oracle skip honestly
# ---------------------------------------------------------------------------

class TestPluginAudit:
    def test_no_oracle_kind_skips_with_reason(self):
        svc, obs = _service(audit_rate=1.0, window_epochs=4)
        svc.create_stream("t", "g", estimator="theta_kmv")
        svc.register_continuous(ContinuousQuery("q", "self_join", ("t",)))
        svc.ingest("t", _records(80))
        svc.poll()
        m = obs.metrics
        assert m.counter("accuracy_audit_skipped_total",
                         reason="no_exact_oracle") >= 1.0
        assert m.counter_total("accuracy_audits_total") == 0.0

    def test_oracle_bearing_plugin_is_audited(self):
        svc, obs = _service(audit_rate=1.0, window_epochs=4)
        svc.create_stream("p", "g", estimator="ipf")
        svc.register_continuous(ContinuousQuery("q", "self_join", ("p",)))
        svc.ingest("p", _records(80))
        svc.poll()
        m = obs.metrics
        assert m.counter("accuracy_audits_total", kind="ipf") == 1.0
        assert m.counter("accuracy_audit_skipped_total",
                         reason="no_exact_oracle") == 0.0


# ---------------------------------------------------------------------------
# distributed: plugin tenants through LocalWorker + Coordinator
# ---------------------------------------------------------------------------

class TestPluginDistributed:
    def test_plugin_cluster_matches_oracle(self):
        """The e2e proof: a 2-worker cluster whose tenants all run PLUGIN
        kinds syncs wire deltas (MODE_MERGE for ipf, MODE_REPLACE for
        theta) into coordinator replicas that match the single-process
        oracle -- ipf bit-exactly, both kinds to 1e-6 on estimates."""
        spec = harness.make_spec(4, kinds=("ipf", "theta_kmv"),
                                 d=CFG.d, s=CFG.s, width=CFG.width,
                                 depth=CFG.depth, seed=CFG.seed,
                                 window_epochs=3, batch_rows=64)
        cycles = 3
        batches = harness.make_batches(spec, cycles=cycles,
                                       rows_per_cycle=96, seed=5)
        run = harness.run_cluster(spec, batches, n_workers=2, cycles=cycles,
                                  local=True, keep_open=True)
        try:
            assert all(t["deltas"] > 0 for t in run.sync_trace)
            oracle = harness.run_oracle(spec, batches, cycles=cycles)
            agree = harness.compare_to_oracle(run.coordinator, oracle, spec)
            assert agree["linear_exact"], (
                "plugin replica state diverged from the single-process run")
            assert agree["worst_rel_err"] <= 1e-6
        finally:
            run.coordinator.close()
