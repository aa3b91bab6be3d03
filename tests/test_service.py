"""Estimation service: batched multi-stream ingest == per-stream updates,
sliding-window expiry is bit-exact, windowed queries match offline
estimates, error bars are reported, and the training driver publishes
through the service client.  (DESIGN.md §10 invariants.)"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import sjpc
from repro.core.sjpc import SJPCConfig
from repro.service import (ContinuousQuery, EstimationService, ServiceConfig,
                           MonitorServiceClient, ingest_key)
from repro.service.ingest import multi_round_update


def _records(rng, n, d, card=6):
    return rng.integers(0, card, size=(n, d)).astype(np.uint32)


def _replay_flush(cfg, params, uid, rows, batch_rows):
    """One flush of ``rows`` rebuilt offline: per-level ``sjpc.update``
    over the pipeline's padded, masked rounds and ``ingest_key`` keys."""
    _, ref = sjpc.init(cfg)
    for r in range(-(-rows.shape[0] // batch_rows)):
        chunk = rows[r * batch_rows:(r + 1) * batch_rows]
        padded = np.zeros((batch_rows, cfg.d), np.uint32)
        padded[:chunk.shape[0]] = chunk
        mask = np.zeros((batch_rows,), np.int32)
        mask[:chunk.shape[0]] = 1
        ref = sjpc.update(cfg, params, ref, jnp.asarray(padded),
                          key=ingest_key(cfg, uid, r),
                          row_mask=jnp.asarray(mask))
    return ref


class TestMergeSemantics:
    def test_merge_sums_steps(self):
        """Post-merge updates must fold in a step no shard already used;
        the sum dominates both shards' consumed ranges (maximum does not)."""
        cfg = SJPCConfig(d=3, s=2, ratio=0.5, width=256, depth=2)
        params, sa = sjpc.init(cfg)
        _, sb = sjpc.init(cfg)
        rng = np.random.default_rng(0)
        for _ in range(3):
            sa = sjpc.update(cfg, params, sa, _records(rng, 8, 3))
            sb = sjpc.update(cfg, params, sb, _records(rng, 8, 3))
        merged = sjpc.merge(sa, sb)
        assert int(merged.step) == 6
        assert float(merged.n) == 48.0

    def test_subtract_removes_substream(self):
        cfg = SJPCConfig(d=3, s=2, ratio=1.0, width=256, depth=2)
        params, s0 = sjpc.init(cfg)
        rng = np.random.default_rng(1)
        a, b = _records(rng, 16, 3), _records(rng, 8, 3)
        sa = sjpc.update(cfg, params, s0, a)
        sab = sjpc.update(cfg, params, sa, b)
        back = sjpc.subtract(sab, sjpc.subtract(sab, sa))
        np.testing.assert_array_equal(np.asarray(back.counters),
                                      np.asarray(sa.counters))
        assert float(back.n) == 16.0


class TestMultiStreamUpdate:
    """Acceptance: the batched update produces counters identical to
    per-stream ``sjpc.update`` loops."""

    def test_row_mask_padding_matches_unpadded(self):
        """ratio=1 (no sampling randomness): a padded+masked update equals
        the unpadded update bit-exactly."""
        cfg = SJPCConfig(d=4, s=2, ratio=1.0, width=512, depth=2, seed=9)
        params, s0 = sjpc.init(cfg)
        rng = np.random.default_rng(2)
        vals = _records(rng, 20, 4)
        plain = sjpc.update(cfg, params, s0, jnp.asarray(vals))
        padded = np.zeros((32, 4), np.uint32)
        padded[:20] = vals
        mask = np.zeros((32,), np.int32)
        mask[:20] = 1
        masked = sjpc.update(cfg, params, s0, jnp.asarray(padded),
                             row_mask=jnp.asarray(mask))
        np.testing.assert_array_equal(np.asarray(plain.counters),
                                      np.asarray(masked.counters))
        assert float(masked.n) == 20.0

    def test_batched_equals_per_stream_loop(self):
        """ratio<1: one vmapped dispatch (a single-round
        ``multi_round_update``) == S separate sjpc.update calls given the
        same keys and masks."""
        cfg = SJPCConfig(d=4, s=2, ratio=0.5, width=512, depth=2, seed=5)
        params, s0 = sjpc.init(cfg)
        rng = np.random.default_rng(3)
        S, B = 3, 16
        values = np.stack([_records(rng, B, 4) for _ in range(S)])
        mask = (rng.random((S, B)) < 0.8).astype(np.int32)
        keys = jnp.stack([jax.random.PRNGKey(100 + i) for i in range(S)])

        counters = jnp.stack([s0.counters] * S)
        n = jnp.stack([s0.n] * S)
        steps = jnp.stack([s0.step] * S)
        bc, bn, bs = multi_round_update(cfg, params, counters, n, steps,
                                        jnp.asarray(values)[None],
                                        jnp.asarray(mask)[None], keys[None])
        for i in range(S):
            ref = sjpc.update(cfg, params, s0, jnp.asarray(values[i]),
                              key=keys[i], row_mask=jnp.asarray(mask[i]))
            np.testing.assert_array_equal(np.asarray(bc[i]),
                                          np.asarray(ref.counters))
            assert float(bn[i]) == float(ref.n)

    def test_pipeline_flush_equals_manual_replay(self):
        """Through the full service path: coalescing, padding, key
        derivation -- replayed per-stream with ingest_key -> identical."""
        cfg = SJPCConfig(d=4, s=2, ratio=0.5, width=512, depth=2, seed=17)
        svc = EstimationService(ServiceConfig(batch_rows=32,
                                              window_epochs=None))
        svc.create_group("g", cfg)
        rng = np.random.default_rng(4)
        sizes = {"a": 50, "b": 20, "c": 0}
        data = {}
        for name, sz in sizes.items():
            svc.create_stream(name, "g")
            data[name] = _records(rng, sz, 4)
            if sz:
                svc.ingest(name, data[name])
        svc.flush()
        group = svc.registry.group("g")
        for name in sizes:
            entry = svc.registry.stream(name)
            ref = _replay_flush(cfg, group.params, entry.uid, data[name], 32)
            np.testing.assert_array_equal(
                np.asarray(entry.window.total.counters),
                np.asarray(ref.counters), err_msg=name)
            assert float(entry.window.total.n) == float(sizes[name])


class TestFusedServicePaths:
    """The fused flush == the per-level reference ``sjpc.update`` replayed
    per stream with the pipeline's keys, bit-exactly."""

    def test_fused_flush_equals_oracle_flush(self):
        cfg = SJPCConfig(d=4, s=2, ratio=0.5, width=512, depth=2, seed=41)
        rng = np.random.default_rng(9)
        recs = {"a": _records(rng, 50, 4), "b": _records(rng, 20, 4)}
        svc = EstimationService(ServiceConfig(batch_rows=32,
                                              window_epochs=None))
        svc.create_group("g", cfg)
        for nm, rows in recs.items():
            svc.create_stream(nm, "g")
            svc.ingest(nm, rows)
        svc.flush()
        params = svc.registry.group("g").params
        for nm, rows in recs.items():
            entry = svc.registry.stream(nm)
            want = _replay_flush(cfg, params, entry.uid, rows, 32)
            np.testing.assert_array_equal(
                np.asarray(entry.window.total.counters),
                np.asarray(want.counters), err_msg=nm)
            assert float(entry.window.total.n) == float(want.n)


def _run_epochs(svc, cfg, name, epoch_batches):
    for rows in epoch_batches:
        if rows.shape[0]:
            svc.ingest(name, rows)
        svc.advance_epoch()


def _replay_window(cfg, group, entry, epoch_batches, live_epoch_ids,
                   batch_rows, rounds_per_epoch=None):
    """Offline rebuild of exactly the live epochs with the pipeline's keys.

    The replay coordinate is the stream's OWN consumed-round count: each
    epoch (one flush here) advances it by ceil(rows / batch_rows), no
    matter how many extra rounds a busier cohort-mate forced the shared
    dispatch to run (those are fully masked for this stream and consume
    none of its randomness).  ``rounds_per_epoch``, when given, asserts
    the expected per-epoch round count (fixed-size epochs)."""
    _, st = sjpc.init(cfg)
    rounds_of = [-(-b.shape[0] // batch_rows) for b in epoch_batches]
    for ep in live_epoch_ids:
        rows = epoch_batches[ep]
        start = sum(rounds_of[:ep])
        if rounds_per_epoch is not None:
            assert rounds_of[ep] == rounds_per_epoch
        for r in range(rounds_of[ep]):
            chunk = rows[r * batch_rows:(r + 1) * batch_rows]
            padded = np.zeros((batch_rows, cfg.d), np.uint32)
            padded[:chunk.shape[0]] = chunk
            mask = np.zeros((batch_rows,), np.int32)
            mask[:chunk.shape[0]] = 1
            st = sjpc.update(cfg, group.params, st, jnp.asarray(padded),
                             key=ingest_key(cfg, entry.uid, start + r),
                             row_mask=jnp.asarray(mask))
    return st


class TestWindowExpiry:
    """Satellite: ring-buffer subtraction over k epochs must bit-exactly
    equal a fresh sketch built from only the live epochs."""

    @pytest.mark.parametrize("ratio", [1.0, 0.5])
    def test_expiry_bit_exact_vs_fresh_sketch(self, ratio):
        cfg = SJPCConfig(d=4, s=2, ratio=ratio, width=512, depth=2, seed=23)
        svc = EstimationService(ServiceConfig(batch_rows=32, window_epochs=3))
        svc.create_group("g", cfg)
        entry = svc.create_stream("a", "g")
        group = svc.registry.group("g")
        rng = np.random.default_rng(5)
        epoch_batches = [_records(rng, 40, 4) for _ in range(6)]
        _run_epochs(svc, cfg, "a", epoch_batches)

        # live: epochs 4, 5 (+ empty open epoch); each epoch = 2 rounds of 32
        fresh = _replay_window(cfg, group, entry, epoch_batches, [4, 5],
                               batch_rows=32, rounds_per_epoch=2)
        win = entry.window.window_state()
        np.testing.assert_array_equal(np.asarray(win.counters),
                                      np.asarray(fresh.counters))
        assert float(win.n) == 80.0 == float(fresh.n)

    def test_ring_sum_invariant(self):
        cfg = SJPCConfig(d=4, s=2, ratio=1.0, width=256, depth=2, seed=29)
        svc = EstimationService(ServiceConfig(batch_rows=16, window_epochs=4))
        svc.create_group("g", cfg)
        entry = svc.create_stream("a", "g")
        rng = np.random.default_rng(6)
        for _ in range(9):
            svc.ingest("a", _records(rng, rng.integers(1, 30), 4))
            svc.advance_epoch()
        rs = entry.window.ring_sum()
        np.testing.assert_array_equal(np.asarray(rs.counters),
                                      np.asarray(entry.window.total.counters))
        assert float(rs.n) == float(entry.window.total.n)

    def test_windowed_estimates_nonnegative_with_clamp(self):
        cfg = SJPCConfig(d=4, s=2, ratio=0.5, width=256, depth=2, seed=31)
        svc = EstimationService(ServiceConfig(batch_rows=16, window_epochs=2))
        svc.create_group("g", cfg)
        svc.create_stream("a", "g")
        rng = np.random.default_rng(7)
        for _ in range(8):
            svc.ingest("a", _records(rng, 24, 4))
            svc.advance_epoch()
            res = svc.snapshot().all_thresholds("a", clamp=True)
            for k, r in res.items():
                assert r.estimate >= 0.0, (k, r.estimate)
                assert (r.per_level >= 0.0).all()


class TestServiceQueries:
    """Acceptance: windowed self-join/join estimates match an offline
    ``sjpc.estimate`` over the equivalent window; error bars reported."""

    def _build(self, window_epochs=2):
        cfg = SJPCConfig(d=4, s=2, ratio=0.5, width=1024, depth=3, seed=37)
        svc = EstimationService(ServiceConfig(batch_rows=32,
                                              window_epochs=window_epochs))
        svc.create_group("g", cfg)
        rng = np.random.default_rng(8)
        batches = {"a": [_records(rng, 40, 4) for _ in range(4)],
                   "b": [_records(rng, 30, 4) for _ in range(4)]}
        for name in batches:
            svc.create_stream(name, "g")
        for ep in range(4):
            for name in batches:
                svc.ingest(name, batches[name][ep])
            svc.advance_epoch()
        return cfg, svc, batches

    def test_self_join_matches_offline_estimate(self):
        cfg, svc, batches = self._build()
        group = svc.registry.group("g")
        snap = svc.snapshot()
        for name in ("a", "b"):
            entry = svc.registry.stream(name)
            # 40-row "a" epochs consume 2 rounds each, 30-row "b" epochs
            # just 1 -- b's replay coordinate must NOT be inflated by the
            # cohort rounds a forced (the PR 7 replay-determinism fix)
            offline_state = _replay_window(cfg, group, entry, batches[name],
                                           [3], batch_rows=32)
            offline = sjpc.estimate(cfg, offline_state)
            r = snap.self_join(name)
            assert r.estimate == pytest.approx(offline.g_s, rel=1e-12)
            np.testing.assert_allclose(r.per_level, offline.x, rtol=1e-12)

    def test_join_matches_offline_estimate_join(self):
        cfg, svc, batches = self._build()
        group = svc.registry.group("g")
        ea, eb = svc.registry.stream("a"), svc.registry.stream("b")
        sa = _replay_window(cfg, group, ea, batches["a"], [3], 32, 2)
        sb = _replay_window(cfg, group, eb, batches["b"], [3], 32)
        offline = sjpc.estimate_join(cfg, sa, sb)
        r = svc.snapshot().join("a", "b")
        assert r.estimate == pytest.approx(offline.g_s, rel=1e-12)

    def test_error_bars_reported(self):
        _, svc, _ = self._build()
        r = svc.snapshot().self_join("a")
        assert r.stderr > 0.0 and r.stderr_offline > 0.0
        # Theorem 2 (sampling + sketch) dominates Theorem 1 (sampling only)
        assert r.stderr > r.stderr_offline
        j = svc.snapshot().join("a", "b")
        assert j.stderr > 0.0

    def test_higher_thresholds_available(self):
        cfg, svc, _ = self._build()
        res = svc.snapshot().all_thresholds("a")
        assert sorted(res) == list(range(cfg.s, cfg.d + 1))
        # g_k is monotone non-increasing in k by construction (clamped X >= 0)
        gs = [res[k].estimate for k in sorted(res)]
        assert all(a >= b for a, b in zip(gs, gs[1:]))

    def test_cross_group_join_rejected(self):
        cfg, svc, _ = self._build()
        svc.create_group("other", SJPCConfig(d=4, s=2, width=512, depth=2,
                                             seed=99))
        svc.create_stream("x", "other")
        with pytest.raises(ValueError, match="hash group"):
            svc.snapshot().join("a", "x")

    def test_continuous_queries_poll_from_one_snapshot(self):
        _, svc, _ = self._build()
        svc.register_continuous(ContinuousQuery("sj", "self_join", ("a",)))
        svc.register_continuous(ContinuousQuery("jn", "join", ("a", "b")))
        svc.register_continuous(ContinuousQuery("all", "all_thresholds",
                                                ("b",)))
        out = svc.poll()
        assert set(out) == {"sj", "jn", "all"}
        assert out["sj"].kind == "self_join" and out["jn"].kind == "join"
        assert isinstance(out["all"], dict)
        with pytest.raises(ValueError):
            svc.register_continuous(ContinuousQuery("sj", "self_join", ("a",)))


class TestSnapshotCacheInvalidation:
    """Regression for the stale-F2 hazard: query results are memoized in a
    cache shared across an engine's snapshots, so the keys MUST carry the
    window version -- a snapshot taken after an expiry boundary (or any
    ingest) must never be served an earlier window's cached values."""

    def _build(self):
        cfg = SJPCConfig(d=4, s=2, ratio=1.0, width=256, depth=3, seed=71)
        svc = EstimationService(ServiceConfig(batch_rows=16, window_epochs=2))
        svc.create_group("g", cfg)
        svc.create_stream("a", "g")
        return cfg, svc

    def test_window_version_tracks_mutations(self):
        """version bumps exactly when ``total`` changes: on ingest commits
        and on expiry subtraction -- NOT on no-op flushes or rotations that
        leave the window contents untouched (those must keep caches warm)."""
        _, svc = self._build()
        win = svc.registry.stream("a").window      # window_epochs=2
        v0 = win.version
        svc.ingest("a", _records(np.random.default_rng(0), 8, 4))
        svc.flush()
        assert win.version > v0
        # first rotation: ring not yet full, total unchanged -> no bump
        v1 = win.version
        svc.advance_epoch()
        assert win.version == v1
        # fill the ring; the next rotation expires epoch 0 -> total changes
        svc.ingest("a", _records(np.random.default_rng(1), 8, 4))
        svc.advance_epoch()
        v2 = win.version
        svc.advance_epoch()                        # expiry subtraction
        assert win.version > v2
        # a flush with nothing pending must NOT invalidate caches
        v3 = win.version
        svc.flush()
        assert win.version == v3

    @pytest.mark.parametrize("query", ["self_join", "join"])
    def test_snapshot_across_expiry_boundary_not_stale(self, query):
        """Self-join cache keys embed the stream's window version, join
        keys both streams' versions: neither may serve a stale entry."""
        from repro.service import QueryEngine
        cfg, svc = self._build()
        names = ("a",) if query == "self_join" else ("a", "b")
        if query == "join":
            svc.create_stream("b", "g")

        def ask(snap):
            return getattr(snap, query)(*names)

        rng = np.random.default_rng(5)
        for nm in names:
            svc.ingest(nm, _records(rng, 24, 4))
        svc.advance_epoch()
        before = ask(svc.snapshot())                # fills the shared cache
        # two more epochs: the first epoch's records expire out of the window
        for _ in range(2):
            for nm in names:
                svc.ingest(nm, _records(rng, 24, 4))
            svc.advance_epoch()
        after = ask(svc.snapshot())
        # independent engine with a COLD cache = ground truth
        fresh = ask(QueryEngine(svc.registry).snapshot())
        assert after.estimate == fresh.estimate
        np.testing.assert_array_equal(after.per_level, fresh.per_level)
        # the window really changed, so a stale cache hit would have been
        # observable (the test has teeth)
        assert before.n != after.n or before.estimate != after.estimate

    def test_unchanged_window_is_served_from_cache(self):
        cfg, svc = self._build()
        svc.ingest("a", _records(np.random.default_rng(6), 24, 4))
        svc.advance_epoch()
        s1 = svc.snapshot()
        r1 = s1.self_join("a")
        entries_after_first = len(svc.engine._cache)
        s2 = svc.snapshot()                         # no ingest in between
        r2 = s2.self_join("a")
        assert len(svc.engine._cache) == entries_after_first  # pure lookup
        assert r1.estimate == r2.estimate


class TestDriverServiceClient:
    def test_driver_publishes_windowed_estimates(self, tmp_path):
        from typing import NamedTuple

        from repro.runtime import DriverConfig, TrainDriver
        from repro.sketchstream.monitor import (MonitorState,
                                                SketchMonitorConfig,
                                                init_monitor,
                                                monitor_update_local)

        class S(NamedTuple):
            params: jax.Array
            opt: jax.Array
            monitor: MonitorState
            step: jax.Array

        mcfg = SketchMonitorConfig(d=4, s=3, width=256, depth=2, shards=1)
        mparams, monitor = init_monitor(mcfg)

        @jax.jit
        def step_fn(state, batch):
            c, n = monitor_update_local(mcfg, mparams,
                                        state.monitor.counters[0],
                                        state.monitor.n[0],
                                        batch["tokens"], state.step)
            mon = MonitorState(c[None], n[None], state.step)
            return (S(state.params, state.opt, mon, state.step + 1),
                    {"loss": jnp.zeros(())})

        def make_batch(step):
            rng = np.random.default_rng(1000 + step)
            return {"tokens": jnp.asarray(
                rng.integers(0, 999, size=(8, 32), dtype=np.int32))}

        svc = EstimationService(ServiceConfig(window_epochs=2))
        client = MonitorServiceClient(svc, "train", mcfg)
        init = S(jnp.zeros((4,)), jnp.zeros(()), monitor,
                 jnp.zeros((), jnp.int32))
        cfg = DriverConfig(ckpt_dir=str(tmp_path), ckpt_every=50,
                           log_every=2, sketch_log_every=2)
        driver = TrainDriver(step_fn, init, make_batch, cfg,
                             monitor_cfg=mcfg, service_client=client)
        driver.run(6)
        assert len(driver.sketch_log) == 3          # steps 0, 2, 4
        for entry in driver.sketch_log:
            for k in range(mcfg.s, mcfg.d + 1):
                assert k in entry and f"stderr_{k}" in entry
                assert entry[k] >= 0.0
            assert entry["window_epochs"] == 2
        # window saturated at 2 epochs: later entries cover ~2 publishes'
        # worth of records, not the whole stream
        win_n = svc.snapshot().self_join("train").n[0]
        assert win_n <= 2 * 8 * 2 * 6   # generous cap: < whole stream anyway


class TestVersionStabilityAcrossCohortFlush:
    """The ingest pipeline must not thrash version-keyed query caches:
    a flush that carries no records for a stream -- even when cohort
    mates DO flush and the stream is left out of their dispatch -- leaves
    that stream's window version (and flush replay coordinate)
    untouched."""

    def _build(self, estimator="sjpc"):
        cfg = SJPCConfig(d=4, s=3, ratio=1.0, width=128, depth=2, seed=7)
        svc = EstimationService(ServiceConfig(batch_rows=16,
                                              window_epochs=None))
        svc.create_group("g", cfg)
        svc.create_stream("busy", "g", estimator=estimator)
        svc.create_stream("idle", "g", estimator=estimator)
        return svc

    @pytest.mark.parametrize("estimator", ["sjpc", "reservoir"])
    def test_cohort_mate_flush_preserves_idle_version(self, estimator):
        svc = self._build(estimator)
        rng = np.random.default_rng(3)
        svc.ingest("busy", _records(rng, 40, 4))
        svc.ingest("idle", _records(rng, 40, 4))
        svc.flush()
        idle = svc.registry.stream("idle")
        v0, f0 = idle.window.version, idle.flushes
        r0 = svc.snapshot().self_join("idle")
        cached = len(svc.engine._cache)
        # three flushes with records for the cohort mate only
        for _ in range(3):
            svc.ingest("busy", _records(rng, 40, 4))
            svc.flush()
        assert idle.window.version == v0
        assert idle.flushes == f0
        r1 = svc.snapshot().self_join("idle")
        assert r1.estimate == r0.estimate
        # the idle stream's self-join batches alone after the mates moved,
        # so its cohort entry is recomputed at most once; versions did not
        # churn per flush
        assert len(svc.engine._cache) <= cached + 3

    def test_empty_submission_preserves_version_end_to_end(self):
        """service.ingest of an empty batch followed by flush is a no-op
        for the version even though submit() recorded a chunk."""
        svc = self._build()
        rng = np.random.default_rng(4)
        svc.ingest("busy", _records(rng, 24, 4))
        svc.flush()
        win = svc.registry.stream("busy").window
        v = win.version
        svc.ingest("busy", np.zeros((0, 4), np.uint32))
        svc.flush()
        assert win.version == v

    def test_equal_but_new_pytree_does_not_bump_version(self):
        """absorb_delta's no-op check is leaf-identity based: re-wrapping
        the unchanged leaves in a new state container must keep the
        version (the regression: `is` on the container alone)."""
        svc = self._build()
        svc.ingest("busy", _records(np.random.default_rng(5), 24, 4))
        svc.flush()
        win = svc.registry.stream("busy").window
        v = win.version
        win.absorb_delta(type(win.total)(*win.total))   # new tuple, same leaves
        assert win.version == v


class TestWindowedSampleProvenance:
    def test_total_tag_set_tracks_live_epochs_exactly(self):
        """After W rotations with interleaved ingest, the sample window's
        merged total must carry provenance tags of exactly the live
        non-empty epochs -- no expired epoch survives the fold, and every
        live epoch that kept data is represented."""
        from repro import estimators as E
        cfg = SJPCConfig(d=4, s=3, ratio=1.0, width=128, depth=2, seed=11)
        svc = EstimationService(ServiceConfig(batch_rows=32,
                                              window_epochs=3))
        svc.create_group("g", cfg)
        svc.create_stream(
            "w", "g", estimator="reservoir",
            estimator_cfg=E.ReservoirConfig(d=4, s=3, capacity=48, seed=2))
        rng = np.random.default_rng(9)
        win = svc.registry.stream("w").window
        for epoch in range(7):
            # interleaved ingest: two submissions + flushes per epoch
            svc.ingest("w", _records(rng, 60, 4))
            svc.flush()
            svc.ingest("w", _records(rng, 60, 4))
            svc.advance_epoch()
            live_sids = {int(s.sid) for s in win._slots
                         if s is not None and int(s.n) > 0}
            tags = np.asarray(win.total.tags)
            assert set(tags[tags >= 0].tolist()) == live_sids, epoch
            # the window keeps exactly the last W epochs' provenance
            assert live_sids == set(range(max(0, epoch - 1), epoch + 1))
