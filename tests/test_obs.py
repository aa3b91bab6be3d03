"""repro.obs: metrics registry, tracer spans, service instrumentation,
version-keyed cache accounting, accuracy telemetry, and the disabled-mode
overhead contract (DESIGN.md §15).

Service-level tests inject a private Observability bundle per test, so
they never race the process-global registry (which the kernel dispatch
counters and any default-config service write into).
"""
from __future__ import annotations

import io
import json
import time

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import jax
import jax.numpy as jnp

from repro.core.sjpc import SJPCConfig
from repro.obs import (NULL_SPAN, Histogram, MetricsRegistry, Observability,
                       Tracer, child, default_registry, set_default_registry)
from repro.service import ContinuousQuery, EstimationService, ServiceConfig

CFG = SJPCConfig(d=6, s=4, width=256, depth=2, seed=3)


def _bundle(**tracer_kw) -> Observability:
    reg = MetricsRegistry()
    return Observability(metrics=reg,
                         tracer=Tracer(registry=reg, **tracer_kw))


def _service(cfg: ServiceConfig = None, **bundle_kw):
    obs = _bundle(**bundle_kw)
    svc = EstimationService(cfg or ServiceConfig(batch_rows=64,
                                                 window_epochs=4), obs=obs)
    svc.create_group("g", CFG)
    return svc, obs


def _records(n, rng=None, lo=0, hi=50):
    rng = rng or np.random.default_rng(0)
    return rng.integers(lo, hi, size=(n, CFG.d), dtype=np.uint32)


# ---------------------------------------------------------------------------
# metrics core
# ---------------------------------------------------------------------------

class TestMetricsRegistry:
    def test_counters_labels_and_totals(self):
        m = MetricsRegistry()
        m.inc("req_total", group="a")
        m.inc("req_total", 2.0, group="a")
        m.inc("req_total", group="b")
        assert m.counter("req_total", group="a") == 3.0
        assert m.counter("req_total", group="b") == 1.0
        assert m.counter("req_total", group="zzz") == 0.0
        assert m.counter_total("req_total") == 4.0

    def test_gauge_set_and_high_water(self):
        m = MetricsRegistry()
        m.set("depth", 7, g="x")
        m.set("depth", 3, g="x")
        assert m.gauge("depth", g="x") == 3.0
        m.set_max("peak", 7, g="x")
        m.set_max("peak", 3, g="x")
        assert m.gauge("peak", g="x") == 7.0
        assert m.gauge("peak", g="missing") is None

    def test_label_order_is_canonical(self):
        m = MetricsRegistry()
        m.inc("c", a="1", b="2")
        m.inc("c", b="2", a="1")
        assert m.counter("c", b="2", a="1") == 2.0

    def test_histogram_quantiles(self):
        m = MetricsRegistry()
        for v in (8e-4, 4e-3, 4e-2):
            m.observe("lat", v)
        h = m.histogram("lat")
        assert h.count == 3 and h.total == pytest.approx(8e-4 + 4e-3 + 4e-2)
        # bucket-resolved: the upper bound of the holding bucket
        assert m.quantile("lat", 0.50) == 5e-3
        assert m.quantile("lat", 0.99) == 5e-2
        assert m.quantile("lat", 0.50, missing="y") == 0.0

    def test_histogram_overflow_mass(self):
        h = Histogram(bounds=(1.0, 2.0))
        h.observe(0.5)
        h.observe(99.0)
        assert h.overflow == 1
        assert h.quantile(0.99) == 2.0     # reported at the last finite bound

    def test_disabled_registry_is_inert(self):
        m = MetricsRegistry(enabled=False)
        m.inc("c")
        m.set("g", 1.0)
        m.set_max("p", 1.0)
        m.observe("h", 0.1)
        assert m.collect() == {}
        assert m.to_prometheus() == ""

    def test_prometheus_text_format(self):
        m = MetricsRegistry()
        m.inc("reqs_total", 3, group="g", kind="sjpc")
        m.set("depth", 2.0)
        m.observe("lat_seconds", 4e-3)
        text = m.to_prometheus()
        assert "# TYPE reqs_total counter" in text
        assert 'reqs_total{group="g",kind="sjpc"} 3' in text
        assert "# TYPE depth gauge" in text and "depth 2" in text
        assert "# TYPE lat_seconds histogram" in text
        assert 'lat_seconds_bucket{le="0.005"} 1' in text
        assert 'lat_seconds_bucket{le="+Inf"} 1' in text
        assert "lat_seconds_count 1" in text

    def test_collect_flattens_histograms(self):
        m = MetricsRegistry()
        m.observe("lat", 4e-3, op="x")
        snap = m.collect()
        row = snap["lat"]['{op="x"}']
        assert row["count"] == 1 and row["p50"] == 5e-3

    @settings(max_examples=20, deadline=None)
    @given(st.integers(min_value=1, max_value=60),
           st.integers(min_value=0, max_value=2**32 - 1))
    def test_histogram_quantile_monotone(self, n, seed):
        # quantiles are monotone in q and bound the empirical quantile
        # from above by at most one bucket (the read-out contract)
        rng = np.random.default_rng(seed)
        h = Histogram()
        vals = 10.0 ** rng.uniform(-4.5, 0.5, size=n)
        for v in vals:
            h.observe(float(v))
        qprobs = (0.1, 0.5, 0.9, 0.99)
        qs = [h.quantile(q) for q in qprobs]
        assert qs == sorted(qs)
        # bound from above: the returned bucket bound covers at least
        # ceil(q*n) observations, so it dominates that order statistic
        svals = np.sort(vals)
        for q, got in zip(qprobs, qs):
            assert got >= svals[int(np.ceil(q * n)) - 1]


# ---------------------------------------------------------------------------
# tracer spans
# ---------------------------------------------------------------------------

class TestTracer:
    def test_nesting_paths_and_depth(self):
        tr = Tracer()
        with tr.span("outer"):
            with tr.span("inner", rows=3):
                pass
        ev = list(tr.events)
        assert [e["name"] for e in ev] == ["inner", "outer"]  # close order
        assert ev[0]["path"] == "outer/inner" and ev[0]["depth"] == 1
        assert ev[0]["rows"] == 3
        assert ev[1]["path"] == "outer" and ev[1]["depth"] == 0

    def test_device_time_covers_registered_outputs(self):
        tr = Tracer()
        with tr.span("jit") as sp:
            y = jax.jit(lambda x: (x @ x).sum())(jnp.ones((64, 64)))
            sp.sync(y)
        ev = tr.events[-1]
        assert ev["total_ms"] >= ev["dispatch_ms"]
        assert sp.total_s >= sp.dispatch_s
        assert float(y) == pytest.approx(64.0 * 64 * 64)

    def test_jsonl_sink(self):
        buf = io.StringIO()
        tr = Tracer(sink=buf)
        with tr.span("a", k="v"):
            pass
        lines = [json.loads(ln) for ln in buf.getvalue().splitlines()]
        assert len(lines) == 1
        assert lines[0]["name"] == "a" and lines[0]["k"] == "v"
        assert {"ts", "dispatch_ms", "total_ms", "depth"} <= set(lines[0])

    def test_span_histogram_lands_in_given_registry(self):
        reg = MetricsRegistry()
        tr = Tracer()
        with tr.span("s", histogram="s_seconds", labels={"g": "x"},
                     registry=reg):
            pass
        h = reg.histogram("s_seconds", g="x")
        assert h is not None and h.count == 1

    def test_disabled_tracer_is_inert(self):
        tr = Tracer(enabled=False)
        span = tr.span("x", histogram="h")
        with span as sp:
            sp.sync(jnp.ones(3))
            sp.set(a=1)
        assert not tr.events
        assert span.total_s == 0.0

    def test_exception_pops_stack_without_emitting(self):
        tr = Tracer()
        with pytest.raises(RuntimeError):
            with tr.span("boom"):
                raise RuntimeError("x")
        assert not tr.events
        with tr.span("after"):
            pass
        assert tr.events[-1]["path"] == "after"   # stack not corrupted


# ---------------------------------------------------------------------------
# service instrumentation
# ---------------------------------------------------------------------------

class TestServiceInstrumentation:
    def test_queue_depth_gauge_tracks_submit_and_flush(self):
        svc, obs = _service()
        svc.create_stream("t", "g")
        svc.ingest("t", _records(40))
        svc.ingest("t", _records(25))
        m = obs.metrics
        assert m.gauge("ingest_pending_rows", group="g") == 65.0
        svc.flush()
        assert m.gauge("ingest_pending_rows", group="g") == 0.0
        assert m.gauge("ingest_pending_rows_peak", group="g") == 65.0
        assert m.counter("ingest_submitted_records_total", group="g") == 65.0

    def test_flush_s_is_device_inclusive_and_histogram_matches(self):
        svc, obs = _service()
        svc.create_stream("t", "g")
        svc.ingest("t", _records(200))
        svc.flush()
        # the PR 1 bug reported near-zero here (it timed the async enqueue);
        # a compile + 200-record sketch dispatch cannot run in < 50us
        assert svc.stats["flush_s"] > 5e-5
        h = obs.metrics.histogram("service_flush_seconds", group="g")
        assert h is not None and h.count == 1
        assert h.total == pytest.approx(svc.stats["flush_s"], rel=0.5)
        hc = obs.metrics.histogram("ingest_flush_seconds",
                                   group="g", kind="sjpc")
        assert hc is not None and hc.count == 1

    def test_window_rotation_metrics(self):
        svc, obs = _service(ServiceConfig(batch_rows=64, window_epochs=2))
        svc.create_stream("t", "g")
        for _ in range(3):
            svc.ingest("t", _records(10))
            svc.advance_epoch()
        m = obs.metrics
        assert m.counter("window_rotations_total", stream="t") == 3.0
        # window_epochs=2: the ring is full from the 2nd rotation on, so
        # rotations 2 and 3 each expire an epoch
        assert m.counter("window_expirations_total", stream="t") == 2.0
        assert m.gauge("window_live_epochs", stream="t") == 2.0
        assert m.gauge("window_version", stream="t") == \
            svc.registry.stream("t").window.version

    def test_estimator_memory_gauge(self):
        svc, obs = _service()
        svc.create_stream("t", "g")
        assert obs.metrics.gauge("estimator_memory_bytes",
                                 stream="t", kind="sjpc") == \
            svc.registry.stream("t").window.memory_bytes()

    def test_disabled_observe_keeps_service_working(self):
        svc = EstimationService(ServiceConfig(batch_rows=64, observe=False))
        svc.create_group("g", CFG)
        svc.create_stream("t", "g")
        svc.ingest("t", _records(100))
        svc.flush()
        # honest flush timing survives obs-off (the block is unconditional)
        assert svc.stats["flush_s"] > 5e-5
        assert svc.obs.metrics.collect() == {}
        assert svc.metrics_report() == ""
        assert svc.snapshot().self_join("t").estimate >= 0.0

    def test_metrics_report_has_derived_gauges(self):
        svc, obs = _service()
        svc.create_stream("t", "g")
        svc.ingest("t", _records(64))
        svc.register_continuous(ContinuousQuery("q", "self_join", ("t",)))
        svc.poll()
        svc.poll()
        text = svc.metrics_report()
        assert 'query_cache_hit_ratio{group="g",kind="sjpc",op="self"}' \
            in text
        assert 'estimator_memory_bytes{kind="sjpc",stream="t"}' in text
        assert "service_poll_seconds_count 2" in text


# ---------------------------------------------------------------------------
# child spans inside a flush and a poll
# ---------------------------------------------------------------------------

# op -> {child path: parent path}: every span a toy flush or poll opens
# inside its top-level span
CHILD_PATHS = {
    "flush": {
        "service.flush/ingest.coalesce": "service.flush",
        "service.flush/ingest.flush_cohort": "service.flush",
        "service.flush/ingest.flush_cohort/ingest.upload":
            "service.flush/ingest.flush_cohort",
        "service.flush/ingest.flush_cohort/ingest.stack":
            "service.flush/ingest.flush_cohort",
        "service.flush/ingest.unstack": "service.flush",
        "service.flush/window.commit": "service.flush",
        "service.flush/window.block": "service.flush",
    },
    "poll": {
        "service.poll/query.self_batch/query.stack":
            "service.poll/query.self_batch",
        "service.poll/query.self_batch/query.bounds":
            "service.poll/query.self_batch",
        "service.poll/query.join_batch/query.stack":
            "service.poll/query.join_batch",
        "service.poll/query.join_batch/query.bounds":
            "service.poll/query.join_batch",
    },
}


def _toy_cycle(svc, rng):
    """One flush and one poll over three streams, two of them joined."""
    for name in ("a", "b", "c"):
        svc.ingest(name, _records(100, rng))
    svc.flush()
    return svc.poll()


def _toy_service(**bundle_kw):
    svc, obs = _service(**bundle_kw)
    for name in ("a", "b", "c"):
        svc.create_stream(name, "g")
    svc.register_continuous(ContinuousQuery("all", "all_thresholds", ("a",)))
    svc.register_continuous(ContinuousQuery("ab", "join", ("a", "b")))
    return svc, obs


def _contains(outer: dict, inner: dict) -> bool:
    slack = 1e-6                       # ts is rounded to the microsecond
    return (outer["ts"] - slack <= inner["ts"]
            and inner["ts"] + inner["total_ms"] * 1e-3
            <= outer["ts"] + outer["total_ms"] * 1e-3 + slack)


class TestChildSpans:
    @pytest.mark.parametrize("op", ["flush", "poll"])
    def test_new_paths_nest_under_their_parents(self, op):
        svc, obs = _toy_service()
        rng = np.random.default_rng(11)
        _toy_cycle(svc, rng)
        n0 = len(obs.tracer.events)
        _toy_cycle(svc, rng)
        events = list(obs.tracer.events)[n0:]
        top = "service." + op
        tops = [e for e in events if e["path"] == top]
        assert len(tops) == 1
        want = CHILD_PATHS[op]
        got = {e["path"] for e in events if _contains(tops[0], e)}
        assert set(want) <= got, sorted(set(want) - got)
        for e in events:
            if e["path"] in want:
                parents = [p for p in events if p["path"] == want[e["path"]]
                           and _contains(p, e)]
                assert len(parents) == 1, e
                assert e["depth"] == parents[0]["depth"] + 1
                assert "streams" in e or "bytes" in e, e
        if op == "flush":
            up = next(e for e in events if e["name"] == "ingest.upload")
            co = next(e for e in events if e["name"] == "ingest.coalesce")
            # 2 rounds of (3 streams, 64 rows): values, mask, round indices
            assert co["bytes"] == up["bytes"] == 2 * 3 * 64 * (6 * 4 + 4) \
                + 2 * 3 * 4
            assert co["streams"] == 3 and co["rounds"] == 2
        if op == "poll":
            batch, stack = ("service.poll/query.self_batch",
                            "service.poll/query.self_batch/query.stack")
            # every stream changed: the cohort's stack is rebuilt
            st, = [e for e in events if e["path"] == stack]
            assert (st["mode"], st["changed"]) == ("full", 3)
            # one stream changed: only its row is restacked
            n1 = len(obs.tracer.events)
            svc.ingest("c", _records(100, rng))
            svc.poll()
            events = list(obs.tracer.events)[n1:]
            b, = [e for e in events if e["path"] == batch]
            st, = [e for e in events if e["path"] == stack]
            assert _contains(b, st) and st["depth"] == b["depth"] + 1
            assert (st["mode"], st["changed"], st["streams"]) == \
                ("incremental", 1, 3)

    @pytest.mark.parametrize("op", ["flush", "poll"])
    def test_children_sum_to_no_more_than_their_parent(self, op):
        svc, obs = _toy_service()
        rng = np.random.default_rng(13)
        _toy_cycle(svc, rng)
        n0 = len(obs.tracer.events)
        _toy_cycle(svc, rng)
        events = list(obs.tracer.events)[n0:]
        want = CHILD_PATHS[op]
        seen = 0
        for parent in set(want.values()):
            for p in (e for e in events if e["path"] == parent):
                kids = [e for e in events if want.get(e["path"]) == parent
                        and _contains(p, e)]
                assert kids, parent
                # each event's total_ms is rounded to 0.1 us
                assert sum(k["total_ms"] for k in kids) \
                    <= p["total_ms"] + 1e-4 * len(kids), (p, kids)
                seen += 1
        assert seen >= 2

    def test_child_with_no_open_span_is_the_null_span(self):
        assert child("orphan") is NULL_SPAN
        tr = Tracer()
        with tr.span("outer"):
            with child("inner", rows=2) as sp:
                assert sp is not NULL_SPAN
        assert child("orphan") is NULL_SPAN    # closed again
        assert [e["path"] for e in tr.events] == ["outer/inner", "outer"]
        assert tr.events[0]["rows"] == 2

    @pytest.mark.parametrize("observe", [True, False])
    def test_child_inside_a_poll_follows_observe(self, monkeypatch, observe):
        """Core code opens ``query.bounds`` with ``child``: a span under
        the batch span when the service observes, the no-op span when it
        does not."""
        from repro.core import sjpc
        seen = []
        bounds = sjpc._batch_bounds

        def spy(*a, **kw):
            seen.append(child("probe"))
            return bounds(*a, **kw)

        monkeypatch.setattr(sjpc, "_batch_bounds", spy)
        svc = EstimationService(ServiceConfig(batch_rows=64,
                                              observe=observe),
                                obs=None if observe else
                                Observability.disabled())
        svc.create_group("g", CFG)
        svc.create_stream("t", "g")
        svc.register_continuous(ContinuousQuery("q", "all_thresholds",
                                                ("t",)))
        svc.ingest("t", _records(64))
        svc.poll()
        assert seen
        assert all((s is NULL_SPAN) != observe for s in seen)

    def test_profiler_annotations_match_span_events(self, tmp_path):
        """With ``annotate=True`` the host plane of the profile holds one
        annotation per span event: same path, order, nesting, and a
        duration within 1 ms."""
        from jax.profiler import ProfileData
        svc, obs = _toy_service(annotate=True)
        rng = np.random.default_rng(12)
        _toy_cycle(svc, rng)                 # compile outside the profile
        n0 = len(obs.tracer.events)
        jax.profiler.start_trace(str(tmp_path))
        try:
            _toy_cycle(svc, rng)
        finally:
            jax.profiler.stop_trace()
        events = sorted(list(obs.tracer.events)[n0:],
                        key=lambda e: (e["ts"], e["depth"]))
        paths = {e["path"] for e in events}
        assert set(CHILD_PATHS["flush"]) | set(CHILD_PATHS["poll"]) <= paths
        (xplane,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
        notes = sorted(
            ((e.start_ns, -e.duration_ns, e.name, e.duration_ns)
             for plane in ProfileData.from_file(str(xplane)).planes
             if plane.name == "/host:CPU"
             for line in plane.lines for e in line.events
             if e.name in paths))
        assert [n[2] for n in notes] == [e["path"] for e in events]
        open_: list = []
        for start, _, name, dur in notes:
            while open_ and open_[-1][1] <= start:
                open_.pop()
            parent = open_[-1][0] if open_ else None
            assert parent == (name.rsplit("/", 1)[0] if "/" in name
                              else None), (name, parent)
            open_.append((name, start + dur))
        for (_, _, name, dur), e in zip(notes, events):
            assert abs(dur * 1e-6 - e["total_ms"]) <= 1.0, (name, e)

    def test_flush_counts_and_spans_only_the_active_streams(self):
        """5 of 12 streams have records: the dispatch holds the 8-slot
        bucket, and 7 streams are counted as skipped."""
        svc, obs = _service()
        for i in range(12):
            svc.create_stream(f"s{i}", "g")
        rng = np.random.default_rng(17)
        svc.ingest("s0", _records(100, rng))            # 2 rounds of 64
        for i in (3, 4, 7, 11):
            svc.ingest(f"s{i}", _records(30, rng))
        n0 = len(obs.tracer.events)
        svc.flush()
        m = obs.metrics
        assert m.counter("ingest_streams_skipped_total", group="g",
                         kind="sjpc") == 12 - 5
        assert m.counter("ingest_dispatch_rows_total", group="g",
                         kind="sjpc") == 8 * 64 * 2
        events = list(obs.tracer.events)[n0:]
        for name in ("ingest.flush_cohort", "ingest.stack"):
            (e,) = [e for e in events if e["name"] == name]
            assert (e["streams"], e["active"]) == (8, 5), e


# ---------------------------------------------------------------------------
# version-keyed query-cache accounting (satellite: cache telemetry)
# ---------------------------------------------------------------------------

def _hits_misses(m, **labels):
    return (m.counter("query_cache_hits_total", **labels),
            m.counter("query_cache_misses_total", **labels))


class TestQueryCacheAccounting:
    def test_steady_state_polls_are_pure_hits(self):
        svc, obs = _service()
        svc.create_stream("t", "g")
        svc.ingest("t", _records(64))
        svc.register_continuous(ContinuousQuery("q", "self_join", ("t",)))
        svc.poll()
        h0, m0 = _hits_misses(obs.metrics, group="g", kind="sjpc", op="self")
        assert m0 >= 1.0                  # first poll computed the batch
        for _ in range(3):
            svc.poll()                    # no-op flushes: version unchanged
        h1, m1 = _hits_misses(obs.metrics, group="g", kind="sjpc", op="self")
        assert m1 == m0                   # zero recomputes
        assert h1 > h0

    def test_ingest_commit_invalidates(self):
        svc, obs = _service()
        svc.create_stream("t", "g")
        svc.ingest("t", _records(64))
        svc.register_continuous(ContinuousQuery("q", "self_join", ("t",)))
        svc.poll()
        _, m0 = _hits_misses(obs.metrics, group="g", kind="sjpc", op="self")
        svc.ingest("t", _records(32))
        svc.poll()                        # version bumped -> recompute
        _, m1 = _hits_misses(obs.metrics, group="g", kind="sjpc", op="self")
        assert m1 == m0 + 1.0

    def test_expiring_rotation_invalidates_non_expiring_does_not(self):
        svc, obs = _service(ServiceConfig(batch_rows=64, window_epochs=3))
        svc.create_stream("t", "g")
        svc.ingest("t", _records(64))
        svc.register_continuous(ContinuousQuery("q", "self_join", ("t",)))
        svc.poll()
        _, m0 = _hits_misses(obs.metrics, group="g", kind="sjpc", op="self")
        svc.advance_epoch()               # live 1 -> 2: nothing expires
        svc.advance_epoch()               # live 2 -> 3: nothing expires
        svc.poll()
        _, m1 = _hits_misses(obs.metrics, group="g", kind="sjpc", op="self")
        assert m1 == m0                   # version untouched, still cached
        svc.advance_epoch()               # ring full: epoch 0's data expires
        svc.poll()
        _, m2 = _hits_misses(obs.metrics, group="g", kind="sjpc", op="self")
        assert m2 == m0 + 1.0

    def test_idle_tenant_cohort_rides_cache(self):
        # PR 5 ride-along: an idle tenant keeps its window version, so its
        # cohort's cache entry survives other-cohort commits -- hits, not
        # misses
        svc, obs = _service()
        svc.create_stream("busy", "g")
        svc.create_stream("idle", "g", estimator="reservoir")
        svc.ingest("busy", _records(64))
        svc.ingest("idle", _records(64))
        svc.register_continuous(ContinuousQuery("qb", "self_join", ("busy",)))
        svc.register_continuous(ContinuousQuery("qi", "self_join", ("idle",)))
        svc.poll()
        _, mi0 = _hits_misses(obs.metrics, group="g", kind="reservoir",
                              op="self")
        _, mb0 = _hits_misses(obs.metrics, group="g", kind="sjpc", op="self")
        svc.ingest("busy", _records(32))  # only the sjpc cohort changes
        svc.poll()
        hi1, mi1 = _hits_misses(obs.metrics, group="g", kind="reservoir",
                                op="self")
        _, mb1 = _hits_misses(obs.metrics, group="g", kind="sjpc", op="self")
        assert mb1 == mb0 + 1.0           # busy cohort recomputed
        assert mi1 == mi0                 # idle cohort: pure cache hit
        assert hi1 >= 1.0

    def test_join_cache_accounting(self):
        svc, obs = _service()
        svc.create_stream("a", "g")
        svc.create_stream("b", "g")
        svc.ingest("a", _records(64))
        svc.ingest("b", _records(64))
        snap = svc.snapshot()
        snap.join("a", "b")
        h0, m0 = _hits_misses(obs.metrics, group="g", kind="sjpc", op="join")
        assert (h0, m0) == (0.0, 1.0)
        snap.join("a", "b")               # same snapshot: cached
        svc.snapshot().join("a", "b")     # new snapshot, same versions
        h1, m1 = _hits_misses(obs.metrics, group="g", kind="sjpc", op="join")
        assert (h1, m1) == (2.0, 1.0)


# ---------------------------------------------------------------------------
# accuracy telemetry
# ---------------------------------------------------------------------------

class TestAccuracyTelemetry:
    def _svc(self, **cfg_kw):
        obs = _bundle()
        svc = EstimationService(
            ServiceConfig(batch_rows=64, audit_rate=1.0, **cfg_kw), obs=obs)
        svc.create_group("g", CFG)
        return svc, obs

    def test_audits_measure_rel_err_and_coverage(self):
        svc, obs = self._svc(window_epochs=4)
        svc.create_stream("a", "g")
        svc.create_stream("b", "g")
        rng = np.random.default_rng(7)
        svc.register_continuous(ContinuousQuery("qs", "self_join", ("a",)))
        svc.register_continuous(ContinuousQuery("qa", "all_thresholds",
                                                ("a",)))
        svc.register_continuous(ContinuousQuery("qj", "join", ("a", "b")))
        svc.ingest("a", _records(120, rng, hi=8))
        svc.ingest("b", _records(80, rng, hi=8))
        svc.poll()
        m = obs.metrics
        # qs: 1 result; qa: d-s+1 = 3 results; qj: 1 result
        assert m.counter("accuracy_audits_total", kind="sjpc") == 5.0
        assert m.counter_total("accuracy_audit_skipped_total") == 0.0
        covered = m.counter("accuracy_ci_covered_total", kind="sjpc")
        assert 0.0 <= covered <= 5.0
        h = m.histogram("accuracy_rel_err", kind="sjpc", s="4")
        assert h is not None and h.count >= 2

    def test_mirror_rotates_with_window(self):
        svc, obs = self._svc(window_epochs=2)
        svc.create_stream("a", "g")
        rng = np.random.default_rng(3)
        svc.register_continuous(ContinuousQuery("q", "self_join", ("a",)))
        for _ in range(4):               # 2 rotations past the window edge
            svc.ingest("a", _records(30, rng, hi=8))
            svc.poll()
            svc.advance_epoch()
        m = obs.metrics
        # every poll audited against exactly the live window: a mirror
        # that failed to expire with the ring would skip as a mismatch
        assert m.counter("accuracy_audit_skipped_total",
                         reason="mirror_mismatch") == 0.0
        assert m.counter("accuracy_audits_total", kind="sjpc") == 4.0

    def test_state_delta_streams_skip_honestly(self):
        svc, obs = self._svc(window_epochs=4)
        svc.create_stream("a", "g")
        # build a foreign delta with the group's own params: a sibling
        # stream's flushed window total is exactly such a state
        svc.create_stream("src", "g")
        svc.ingest("src", _records(16, hi=8))
        svc.flush()
        svc.ingest_state_delta(
            "a", svc.registry.stream("src").window.total)
        svc.register_continuous(ContinuousQuery("q", "self_join", ("a",)))
        svc.poll()
        m = obs.metrics
        assert m.counter("accuracy_audit_skipped_total",
                         reason="state_delta_stream") >= 1.0
        assert m.counter_total("accuracy_audits_total") == 0.0

    def test_oversize_window_skips(self):
        svc, obs = self._svc(window_epochs=4, audit_max_records=32)
        svc.create_stream("a", "g")
        svc.ingest("a", _records(64))
        svc.register_continuous(ContinuousQuery("q", "self_join", ("a",)))
        svc.poll()
        assert obs.metrics.counter("accuracy_audit_skipped_total",
                                   reason="window_too_large") == 1.0

    def test_rate_zero_never_audits(self):
        obs = _bundle()
        svc = EstimationService(ServiceConfig(batch_rows=64), obs=obs)
        svc.create_group("g", CFG)
        svc.create_stream("a", "g")
        svc.ingest("a", _records(32))
        svc.register_continuous(ContinuousQuery("q", "self_join", ("a",)))
        svc.poll()
        assert obs.metrics.counter_total("accuracy_audits_total") == 0.0
        assert svc.obs.auditor is None


# ---------------------------------------------------------------------------
# module-level instrumentation (kernels, estimators)
# ---------------------------------------------------------------------------

class TestGlobalInstrumentation:
    def test_kernel_dispatch_counters(self):
        from repro.core import sketch as sk
        from repro.core.hashing import P31
        from repro.kernels.ops import sketch_moments, sketch_update
        fresh = MetricsRegistry()
        prev = set_default_registry(fresh)
        try:
            rng = np.random.default_rng(0)
            params = sk.make_sketch_params(rng, 2)
            keys = jnp.asarray(rng.integers(0, int(P31), size=32,
                                            dtype=np.uint32))
            c = sketch_update(sk.empty_counters(2, 64), keys, keys, params,
                              None, use_pallas=False)
            sketch_moments(c, use_pallas=False)
            assert fresh.counter("kernel_dispatch_total",
                                 kernel="sketch_update", path="jnp",
                                 impl="jnp_ref") == 1.0
            assert fresh.counter("kernel_dispatch_total",
                                 kernel="sketch_moments", path="jnp",
                                 impl="jnp_ref") == 1.0
        finally:
            set_default_registry(prev)

    def test_bootstrap_replicate_counter(self):
        from repro.estimators import uncertainty as U
        fresh = MetricsRegistry()
        prev = set_default_registry(fresh)
        try:
            rng = np.random.default_rng(1)
            items = jnp.asarray(rng.integers(0, 4, (1, 16, 4), np.uint32))
            valid = jnp.ones((1, 16), jnp.int32)
            keys = jax.random.split(jax.random.PRNGKey(0), 1)
            U.bootstrap_pair_stderr(items, valid, np.array([100.0]),
                                    keys=keys, s=2, replicates=8,
                                    pair_fn=lambda it, va: U.jnp.zeros(
                                        it.shape[:2] + (it.shape[-1] + 1,),
                                        U.jnp.int32))
            assert fresh.counter("bootstrap_replicates_total",
                                 method="bootstrap") == 8.0
        finally:
            set_default_registry(prev)


# ---------------------------------------------------------------------------
# disabled-mode overhead contract (satellite: CI guard)
# ---------------------------------------------------------------------------

class TestOverheadGuard:
    def test_enabled_within_5pct_of_disabled(self):
        """Ingest throughput with metrics+spans enabled must stay within
        5% of the disabled bundle on a seeded workload -- the DESIGN.md
        §15 near-zero-overhead contract.  Measured back-to-back with
        retries: CI machines are noisy, and the contract is about the
        instrumentation cost, not scheduler jitter."""
        recs = _records(256, np.random.default_rng(5))
        cycles = 6

        def throughput(observe: bool) -> float:
            svc = EstimationService(
                ServiceConfig(batch_rows=128, window_epochs=None,
                              observe=observe),
                obs=None if observe else Observability.disabled())
            svc.create_group("g", CFG)
            svc.create_stream("t", "g")
            svc.ingest("t", recs)
            svc.flush()                  # compile at the measured shape
            t0 = time.perf_counter()
            for _ in range(cycles):
                svc.ingest("t", recs)
                svc.flush()
            return cycles * recs.shape[0] / (time.perf_counter() - t0)

        throughput(True)                 # shared jit warmup for both modes
        ratios = []
        for _ in range(4):               # retries absorb CI noise
            off = throughput(False)
            on = throughput(True)
            ratios.append(on / off)
            if ratios[-1] >= 0.95:
                return
        raise AssertionError(
            f"metrics-enabled ingest slower than the 5% overhead budget "
            f"in all attempts: on/off ratios {[f'{r:.3f}' for r in ratios]}")
