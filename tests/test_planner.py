"""Query planner + admission control (DESIGN.md §16) and the PR 7 bug
batch: planned polls equal a cold engine's lazy snapshot and the
per-stream reference for every estimator kind (self + join), plans cache
and invalidate on topology changes, throttled tenants get stale=True copies of their last fresh results, and
the three query/ingest-path regressions stay fixed -- join prefetch
buckets by estimator instance, cache eviction is LRU (hot standing-query
entries survive), and a stream's replay coordinate is independent of its
cohort-mates' backlogs."""
import numpy as np
import jax
import pytest

from repro import estimators as est_mod
from repro.estimators import base as est_base
from repro.core.sjpc import SJPCConfig
from repro.estimators.sjpc_backend import SJPCEstimator
from repro.obs import MetricsRegistry, Observability, Tracer
from repro.service import (ContinuousQuery, EstimationService, PlannerConfig,
                           QueryEngine, ServiceConfig, reference_snapshot)

KINDS = ["sjpc", "reservoir", "lsh_ss"]


def _cfg(**kw):
    base = dict(d=6, s=4, ratio=0.5, width=256, depth=2)
    base.update(kw)
    return SJPCConfig(**base)


def _obs():
    """A private metrics registry per test (the default bundle is
    process-global, so counters would accumulate across tests)."""
    m = MetricsRegistry()
    return Observability(metrics=m, tracer=Tracer(registry=m))


def _records(rng, n, d=6, card=6):
    return rng.integers(0, card, size=(n, d)).astype(np.uint32)


def _result_close(a, b, tol=1e-6):
    if isinstance(a, dict):
        assert a.keys() == b.keys()
        for k in a:
            _result_close(a[k], b[k], tol)
        return
    assert a.kind == b.kind and a.streams == b.streams and a.s == b.s
    assert a.estimate == pytest.approx(b.estimate, abs=tol, rel=tol)
    assert a.stderr == pytest.approx(b.stderr, abs=tol, rel=tol)
    assert a.stderr_offline == pytest.approx(b.stderr_offline,
                                             abs=tol, rel=tol)
    np.testing.assert_allclose(np.asarray(a.per_level),
                               np.asarray(b.per_level), atol=tol, rtol=tol)
    assert a.stderr_kind == b.stderr_kind


def _populate(svc, *, groups=2, rng_seed=0):
    """Identical topology + data for twin services: ``groups`` hash groups
    with one stream per estimator kind plus a second sjpc stream (the
    join partner), standing queries over all of it."""
    rng = np.random.default_rng(rng_seed)
    cfg = _cfg()
    for g in range(groups):
        gid = f"g{g}"
        svc.create_group(gid, cfg)
        for kind in KINDS:
            svc.create_stream(f"{gid}-{kind}", gid, estimator=kind)
        svc.create_stream(f"{gid}-sjpc2", gid, estimator="sjpc")
        for name in [f"{gid}-{k}" for k in KINDS] + [f"{gid}-sjpc2"]:
            svc.ingest(name, _records(rng, 300))
        for kind in KINDS:
            svc.register_continuous(ContinuousQuery(
                f"q-{gid}-{kind}", "self_join", (f"{gid}-{kind}",)))
        svc.register_continuous(ContinuousQuery(
            f"qa-{gid}", "all_thresholds", (f"{gid}-sjpc2",)))
        svc.register_continuous(ContinuousQuery(
            f"qj-{gid}", "join", (f"{gid}-sjpc", f"{gid}-sjpc2")))
    return rng


class TestPlannerConformance:
    """Planned polls == the same queries evaluated one by one within 1e-6
    for every served estimate, across all estimator kinds, self +
    all-thresholds + join, over polls that interleave fresh ingest (the
    acceptance criterion).  The oracle is a cold engine's lazy snapshot
    (per-group batches, no planner) or the per-stream reference."""

    @pytest.mark.parametrize("oracle", ["lazy_snapshot", "reference"])
    def test_on_equals_off_all_kinds(self, oracle):
        svc = EstimationService(ServiceConfig(
            batch_rows=64, window_epochs=4), obs=_obs())
        rng = _populate(svc)
        for _ in range(2):
            out = svc.poll()
            snap = (QueryEngine(svc.registry, obs=_obs()).snapshot()
                    if oracle == "lazy_snapshot"
                    else reference_snapshot(svc.registry))
            assert out.keys() == svc._continuous.keys()
            for name, q in svc._continuous.items():
                _result_close(out[name], q.evaluate(snap))
            for g in range(2):
                svc.ingest(f"g{g}-sjpc", _records(rng, 100))

    def test_cross_group_fusion_one_launch(self):
        """N same-config groups' sjpc cohorts must share ONE
        estimate_batch launch (the tentpole's point), with correct
        per-group results."""
        obs = _obs()
        svc = EstimationService(ServiceConfig(batch_rows=64,
                                              window_epochs=4), obs=obs)
        rng = np.random.default_rng(3)
        cfg = _cfg()
        for g in range(4):
            svc.create_group(f"g{g}", cfg)
            svc.create_stream(f"s{g}", f"g{g}")
            svc.ingest(f"s{g}", _records(rng, 200))
            svc.register_continuous(
                ContinuousQuery(f"q{g}", "self_join", (f"s{g}",)))
        out = svc.poll()
        launches = obs.metrics.series("planner_fused_launches_total")
        cohorts = obs.metrics.series("planner_fused_cohorts_total")
        assert launches[(("op", "self"),)] == 1.0
        assert cohorts[(("op", "self"),)] == 4.0
        # unstacked per-group entries match per-group single-service math
        for g in range(4):
            solo = QueryEngine(svc.registry, obs=_obs()) \
                .snapshot([f"s{g}"]).self_join(f"s{g}")
            _result_close(out[f"q{g}"], solo)

    def test_plan_cached_and_invalidated_by_create_stream(self):
        obs = _obs()
        svc = EstimationService(ServiceConfig(batch_rows=64,
                                              window_epochs=4), obs=obs)
        rng = np.random.default_rng(4)
        svc.create_group("g", _cfg())
        svc.create_stream("s0", "g")
        svc.ingest("s0", _records(rng, 200))
        svc.register_continuous(ContinuousQuery("q0", "self_join", ("s0",)))
        svc.poll()
        svc.poll()
        built = obs.metrics.series("planner_plans_built_total")
        reuse = obs.metrics.series("planner_plan_reuse_total")
        assert built[()] == 1.0 and reuse[()] == 1.0
        # a mid-life create_stream changes cohort membership: the plan must
        # rebuild, and the new stream's results must match a cold engine's
        # lazy snapshot
        svc.create_stream("s1", "g")
        svc.ingest("s1", _records(rng, 150))
        svc.register_continuous(ContinuousQuery("q1", "self_join", ("s1",)))
        out = svc.poll()
        assert obs.metrics.series("planner_plans_built_total")[()] == 2.0
        cold = QueryEngine(svc.registry, obs=_obs()).snapshot()
        for name in out:
            _result_close(out[name], cold.self_join(name.replace("q", "s")))


class TestAdmissionControl:
    def _service(self):
        obs = _obs()
        svc = EstimationService(ServiceConfig(batch_rows=64,
                                              window_epochs=4), obs=obs)
        rng = np.random.default_rng(5)
        svc.create_group("g", _cfg())
        for s in ("a", "b"):
            svc.create_stream(s, "g")
            svc.ingest(s, _records(rng, 200))
        return svc, obs, rng

    def test_throttled_tenant_served_stale_last_fresh(self):
        svc, obs, rng = self._service()
        svc.register_continuous(ContinuousQuery("qa", "self_join", ("a",)))
        svc.register_continuous(ContinuousQuery("qb", "self_join", ("b",)))
        first = svc.poll()
        assert not first["qa"].stale and not first["qb"].stale
        svc.set_tenant_budget("a", 0)
        svc.ingest("a", _records(rng, 300))
        svc.ingest("b", _records(rng, 300))
        second = svc.poll()
        # throttled: stale flag set, values frozen at the last fresh serve
        assert second["qa"].stale
        assert second["qa"].estimate == first["qa"].estimate
        assert second["qa"].stderr == first["qa"].stderr
        # the snapshot itself advanced: the funded tenant sees new data
        assert not second["qb"].stale
        assert second["qb"].estimate != first["qb"].estimate
        rej = obs.metrics.series("admission_rejections_total")
        assert rej[(("tenant", "a"),)] == 1.0
        # budget refill restores service with fresh (non-stale) values
        svc.set_tenant_budget("a", 10)
        third = svc.poll()
        assert not third["qa"].stale
        assert third["qa"].estimate != first["qa"].estimate

    def test_priority_orders_throttling_within_tenant(self):
        svc, obs, _ = self._service()
        svc.register_continuous(ContinuousQuery(
            "low", "self_join", ("a",), priority=2, tenant="t"))
        svc.register_continuous(ContinuousQuery(
            "high", "self_join", ("b",), priority=0, tenant="t"))
        first = svc.poll()         # both fresh: never-served is admitted
        assert not first["low"].stale and not first["high"].stale
        svc.set_tenant_budget("t", 1)
        second = svc.poll()
        assert not second["high"].stale      # the critical class is served
        assert second["low"].stale           # the budget ran out below it

    def test_all_thresholds_stale_marks_every_cell(self):
        svc, obs, rng = self._service()
        svc.register_continuous(ContinuousQuery(
            "qt", "all_thresholds", ("a",)))
        first = svc.poll()
        svc.set_tenant_budget("a", 0)
        svc.ingest("a", _records(rng, 300))
        second = svc.poll()
        assert all(r.stale for r in second["qt"].values())
        for k, r in second["qt"].items():
            assert r.estimate == first["qt"][k].estimate


# -- satellite bugfix regressions -------------------------------------


class _ScaledJoinEstimator(SJPCEstimator):
    """A join-capable kind whose estimator_cfg changes the numbers: the
    sharpest probe that mixed-instance join pairs must not share one
    batched launch (the launcher's estimator would silently answer for
    every pair)."""
    kind = "sjpc_scaled"

    def __init__(self, cfg, params=None, *, scale=1.0, **kw):
        super().__init__(cfg, params, **kw)
        self.scale = float(scale)

    def estimate_join_batch(self, states_a, states_b, **kw):
        t = super().estimate_join_batch(states_a, states_b, **kw)
        return t._replace(g=np.asarray(t.g) * self.scale)

    def estimate_join_ref(self, state_a, state_b, **kw):
        t = super().estimate_join_ref(state_a, state_b, **kw)
        return t._replace(g=np.asarray(t.g) * self.scale)


@pytest.fixture
def scaled_kind():
    """Register the probe kind for one test and UNREGISTER on teardown:
    suite-mates enumerate ``estimators.available()`` (e.g. the served
    stderr and equal-space contracts) and must never see it."""
    try:
        est_mod.register(
            "sjpc_scaled",
            lambda sjpc_cfg, *, params=None, estimator_cfg=None:
            _ScaledJoinEstimator(sjpc_cfg, params,
                                 **dict(estimator_cfg or {})))
    except ValueError:
        pass                         # already registered in this process
    yield "sjpc_scaled"
    est_base._REGISTRY.pop("sjpc_scaled", None)


class TestJoinPrefetchCohorts:
    """Regression (ISSUE 7 satellite 1): join pairs must bucket by
    estimator instance + state shapes like the self path, not by group
    alone -- a group mixing estimator_cfg-overridden streams used to
    stack every pair into the first pair's estimator."""

    @pytest.mark.parametrize("path", ["planner_poll", "lazy_snapshot"])
    def test_mixed_instance_pairs_answer_with_their_own_estimator(
            self, path, scaled_kind):
        svc = EstimationService(ServiceConfig(
            batch_rows=64, window_epochs=4), obs=_obs())
        svc.create_group("g", _cfg(ratio=1.0, width=512))
        rng = np.random.default_rng(6)
        for name in ("a1", "b1"):
            svc.create_stream(name, "g", estimator="sjpc_scaled")
        for name in ("a2", "b2"):
            svc.create_stream(name, "g", estimator="sjpc_scaled",
                              estimator_cfg={"scale": 100.0})
        for name in ("a1", "b1", "a2", "b2"):
            svc.ingest(name, _records(rng, 200, card=4))
        svc.register_continuous(
            ContinuousQuery("j1", "join", ("a1", "b1")))
        svc.register_continuous(
            ContinuousQuery("j2", "join", ("a2", "b2")))
        if path == "planner_poll":
            out = svc.poll()
        else:
            snap = svc.snapshot()
            out = {"j1": snap.join("a1", "b1"), "j2": snap.join("a2", "b2")}
        # the oracle: each pair alone, through a fresh engine (single-pair
        # launches always use the pair's own estimator)
        for qname, pair in (("j1", ("a1", "b1")), ("j2", ("a2", "b2"))):
            solo = QueryEngine(svc.registry, obs=_obs()) \
                .snapshot().join(*pair)
            assert solo.estimate > 0
            assert out[qname].estimate == pytest.approx(solo.estimate,
                                                        rel=1e-9)


class TestLRUCacheEviction:
    """Regression (ISSUE 7 satellite 2): cache overflow must evict
    least-recently-used entries, not clear the table -- hot standing
    queries survive an eviction cycle, and the evictions counter counts
    entries actually dropped."""

    def test_hot_entry_survives_churn(self, monkeypatch):
        import repro.service.query as qmod
        monkeypatch.setattr(qmod, "_CACHE_MAX_ENTRIES", 4)
        obs = _obs()
        svc = EstimationService(ServiceConfig(batch_rows=32,
                                              window_epochs=4), obs=obs)
        rng = np.random.default_rng(7)
        svc.create_group("hot", _cfg())
        svc.create_stream("hot-s", "hot")
        svc.ingest("hot-s", _records(rng, 100))
        svc.create_group("churn", _cfg())
        svc.create_stream("churn-s", "churn")
        iters = 10
        for _ in range(iters):
            svc.ingest("churn-s", _records(rng, 64))
            svc.flush()                  # bumps churn-s's window version:
            snap = svc.engine.snapshot()  # a brand-new cache entry per loop
            snap.self_join("hot-s")
            snap.self_join("churn-s")
        misses = obs.metrics.series("query_cache_misses_total")
        hot_key = (("group", "hot"), ("kind", "sjpc"), ("op", "self"))
        # the hot entry was computed exactly once; every later snapshot
        # found it despite 10 churn entries flowing through a 4-entry cache
        assert misses[hot_key] == 1.0
        assert len(svc.engine._cache) <= 4 + 1
        # evictions counter counts entries: the cache exceeds the bound
        # from the 5th churn key on, shedding exactly one stale key per
        # snapshot thereafter
        evicted = sum(obs.metrics.series(
            "query_cache_evictions_total").values())
        assert evicted == float(iters - 4)


class TestReplayCoordinateIndependence:
    """Regression (ISSUE 7 satellite 3): a stream's committed window state
    -- and its ``flushes`` replay coordinate -- must be bit-identical
    whether or not a busier cohort-mate shared its flushes (the ingest.py
    offline-replay contract)."""

    def _run(self, kind: str, with_busy: bool):
        svc = EstimationService(ServiceConfig(batch_rows=32,
                                              window_epochs=4), obs=_obs())
        svc.create_group("g", _cfg())
        svc.create_stream("solo", "g", estimator=kind)   # uid 0 either way
        if with_busy:
            svc.create_stream("busy", "g", estimator=kind)
        rng = np.random.default_rng(8)      # solo's records: shared draw
        busy_rng = np.random.default_rng(99)
        for _ in range(2):
            svc.ingest("solo", _records(rng, 40))     # 2 rounds of 32
            if with_busy:
                svc.ingest("busy", _records(busy_rng, 300))  # 10 rounds
            svc.flush()
        return svc.registry.stream("solo")

    @pytest.mark.parametrize("kind", ["sjpc", "reservoir"])
    def test_state_independent_of_cohort_backlog(self, kind):
        alone = self._run(kind, with_busy=False)
        crowded = self._run(kind, with_busy=True)
        # replay coordinate: only the rounds that carried solo's rows
        assert alone.flushes == crowded.flushes == 4
        la = jax.tree_util.tree_leaves(alone.window.window_state())
        lc = jax.tree_util.tree_leaves(crowded.window.window_state())
        assert len(la) == len(lc)
        for x, y in zip(la, lc):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


class TestLaunchCoalescing:
    """ISSUE 9 satellite: ``PlannerConfig.coalesce_window`` lets identical
    fusion-signature launches from back-to-back sub-second polls reuse the
    in-flight result -- the new version key aliases the last launch's
    cache entry, so no device work runs -- while polls outside the window
    (and the default window of 0) recompute as before."""

    def _svc(self, window):
        svc = EstimationService(
            ServiceConfig(batch_rows=64, window_epochs=4,
                          planner=PlannerConfig(coalesce_window=window)),
            obs=_obs())
        svc.create_group("g", _cfg())
        svc.create_stream("a", "g")
        svc.create_stream("b", "g")
        svc.register_continuous(ContinuousQuery("qa", "self_join", ("a",)))
        svc.register_continuous(ContinuousQuery("qj", "join", ("a", "b")))
        clock = [0.0]
        svc.planner._now = lambda: clock[0]
        return svc, clock

    def _ingest_poll(self, svc, rng):
        for nm in ("a", "b"):
            svc.ingest(nm, _records(rng, 64))
        return svc.poll()

    def test_within_window_reuses_launch(self):
        svc, clock = self._svc(0.5)
        rng = np.random.default_rng(0)
        m = svc.obs.metrics
        r1 = self._ingest_poll(svc, rng)           # t=0: fresh launches
        clock[0] = 0.2
        r2 = self._ingest_poll(svc, rng)           # in-window: coalesced
        assert m.counter("planner_coalesced_launches_total", op="self") == 1.0
        assert m.counter("planner_coalesced_launches_total", op="join") == 1.0
        # served the in-flight result, fresh (not the stale channel)
        assert r2["qa"].estimate == r1["qa"].estimate
        assert r2["qj"].estimate == r1["qj"].estimate
        assert not r2["qa"].stale and not r2["qj"].stale
        clock[0] = 1.0                             # window measured from the
        r3 = self._ingest_poll(svc, rng)           # LAUNCH, not the alias
        assert m.counter_total("planner_coalesced_launches_total") == 2.0
        assert r3["qa"].estimate != r1["qa"].estimate

    def test_zero_window_always_recomputes(self):
        svc, clock = self._svc(0.0)
        rng = np.random.default_rng(1)
        r1 = self._ingest_poll(svc, rng)
        r2 = self._ingest_poll(svc, rng)           # same instant: still fresh
        assert svc.obs.metrics.counter_total(
            "planner_coalesced_launches_total") == 0.0
        assert r1["qa"].estimate != r2["qa"].estimate

    def test_unchanged_versions_hit_cache_not_coalescing(self):
        """A poll with no new data is a plain version-keyed cache hit; the
        coalescing counter must not claim it."""
        svc, clock = self._svc(10.0)
        rng = np.random.default_rng(2)
        r1 = self._ingest_poll(svc, rng)
        clock[0] = 0.1
        r2 = svc.poll()                            # no ingest between polls
        assert svc.obs.metrics.counter_total(
            "planner_coalesced_launches_total") == 0.0
        assert r2["qa"].estimate == r1["qa"].estimate
