"""Estimator-protocol conformance (DESIGN.md §13) over ALL registered kinds.

One parametrized fixture drives every estimator -- SJPC, the streaming
reservoir, and streaming LSH-SS -- through the same contracts:

  * estimate_batch == estimate_ref (<= 1e-6 relative), the batched-path
    vs scalar-oracle identity;
  * merge/subtract algebra: n adds and recovers; merge is commutative in
    the estimates; linear kinds recover state bit-exactly, tagged-sample
    kinds recover provenance exactly;
  * batch permutation invariance: stream order in a stacked cohort cannot
    change any stream's row;
  * degenerate streams n in {0, 1}: finite, g == n at every threshold
    (no pairs exist, so every estimator must report exactly the
    self-pairs).

Plus the reservoir-specific statistical contract: the vectorized
streaming Algorithm R is distributionally equivalent to offline uniform
sampling -- retention is uniform over arrival order, and the estimated
g_s is unbiased against both the exact count and the offline sampler's
mean.  Everything is seeded; failures mean the estimator changed, not
bad luck.
"""
import numpy as np
import jax
import pytest
from hypothesis import given, settings, strategies as st

from repro import estimators as E
from repro.core import baselines, exact
from repro.core.sjpc import SJPCConfig

CFG = SJPCConfig(d=5, s=3, ratio=1.0, width=128, depth=2, seed=31)
KINDS = E.available()
# one shared instance per kind: protocol engines are stateless between
# calls, and sharing keeps each kind's ingest jit cache warm across tests
ESTS = {kind: E.make(kind, CFG) for kind in KINDS}


@pytest.fixture(params=KINDS)
def estimator(request):
    return request.param, ESTS[request.param]


def ingest(est, state, vals, *, key_seed=0):
    """One protocol-path ingest round for a single stream."""
    vals = np.ascontiguousarray(np.asarray(vals, np.uint32))
    B = vals.shape[0]
    states = E.stack_states([state])
    keys = jax.random.fold_in(
        jax.random.PRNGKey(est.ingest_seed), key_seed)[None, None]
    new = est.ingest_rounds(states, vals[None, None],
                            np.ones((1, 1, B), np.int32), keys)
    return E.index_state(new, 0)


def _dups(rng, n=300, d=5):
    vals = rng.integers(0, 40, size=(n, d)).astype(np.uint32)
    for i in range(n // 10):
        vals[n - 1 - i] = vals[i]                 # exact duplicates
    return vals


class TestBatchVsRef:
    def test_estimate_batch_matches_scalar_ref(self, estimator):
        kind, est = estimator
        rng = np.random.default_rng(11)
        st = ingest(est, est.init(sid=0), _dups(rng))
        batch = est.estimate_batch(E.stack_states([st]))
        ref = est.estimate_ref(st)
        for field in ("x", "g", "n"):
            a, b = getattr(batch, field), getattr(ref, field)
            np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6,
                                       err_msg=f"{kind}.{field}")
        np.testing.assert_allclose(batch.stderr, ref.stderr, rtol=1e-6,
                                   atol=1e-6)

    def test_batch_permutation_invariance(self, estimator):
        kind, est = estimator
        rng = np.random.default_rng(12)
        a = ingest(est, est.init(sid=1), _dups(rng), key_seed=1)
        b = ingest(est, est.init(sid=2),
                   rng.integers(0, 9, size=(200, CFG.d)).astype(np.uint32),
                   key_seed=2)
        ab = est.estimate_batch(E.stack_states([a, b]))
        ba = est.estimate_batch(E.stack_states([b, a]))
        np.testing.assert_allclose(ab.g, ba.g[::-1], rtol=1e-9,
                                   err_msg=kind)
        np.testing.assert_allclose(ab.x, ba.x[::-1], rtol=1e-9)


class TestMergeSubtractAlgebra:
    def _two_epochs(self, est):
        rng = np.random.default_rng(13)
        a = ingest(est, est.init(sid=1), _dups(rng), key_seed=1)
        b = ingest(est, est.init(sid=2),
                   rng.integers(0, 9, size=(160, CFG.d)).astype(np.uint32),
                   key_seed=2)
        return a, b

    def test_merge_adds_n_and_is_commutative(self, estimator):
        kind, est = estimator
        a, b = self._two_epochs(est)
        m1, m2 = est.merge(a, b), est.merge(b, a)
        assert float(m1.n) == float(m2.n) == float(a.n) + float(b.n)
        g1 = est.estimate_ref(m1).g
        g2 = est.estimate_ref(m2).g
        np.testing.assert_allclose(g1, g2, rtol=1e-9, err_msg=kind)

    def test_subtract_inverts_merge(self, estimator):
        """Linear kinds recover the counters bit-exactly; tagged-sample
        kinds recover provenance exactly (no surviving slot carries the
        subtracted epoch's tag) and always recover n."""
        kind, est = estimator
        a, b = self._two_epochs(est)
        back = est.subtract(est.merge(a, b), b)
        assert float(back.n) == pytest.approx(float(a.n))
        if est.linear:
            np.testing.assert_array_equal(np.asarray(back.counters),
                                          np.asarray(a.counters))
        else:
            for field in back._fields:
                if field.endswith("tags"):
                    tags = np.asarray(getattr(back, field))
                    assert not np.any(tags == int(b.sid)), (kind, field)

    def test_merge_estimate_consistent_with_union(self, estimator):
        """estimate(merge(a, b)) tracks the union stream: exact for linear
        kinds (sketch linearity), within sampling error for sample kinds
        (the merged sample still estimates the union's n and pair mass)."""
        kind, est = estimator
        a, b = self._two_epochs(est)
        m = est.estimate_ref(est.merge(a, b))
        assert float(m.n[0]) == float(a.n) + float(b.n)
        assert np.all(np.isfinite(m.g)) and np.all(m.g >= 0)
        # g >= n at the lowest threshold (self-pairs are always counted)
        assert m.g[0, 0] >= float(m.n[0]) - 1e-6


class TestDegenerateStreams:
    @pytest.mark.parametrize("n", [0, 1])
    def test_no_pairs_means_g_equals_n(self, estimator, n):
        kind, est = estimator
        st = est.init(sid=0)
        if n:
            st = ingest(est, st, np.ones((1, CFG.d), np.uint32))
        for table in (est.estimate_batch(E.stack_states([st])),
                      est.estimate_ref(st)):
            assert float(table.n[0]) == float(n)
            assert np.all(np.isfinite(table.g))
            np.testing.assert_allclose(table.g[0], float(n), atol=1e-6,
                                       err_msg=f"{kind} n={n}")
            assert np.all(table.stderr >= 0)


class TestServedSideBySide:
    def test_all_kinds_in_one_group_fused_matches_ref(self):
        """The acceptance shape: one hash group serving every estimator
        kind at derived (equal-space) budgets; the fused snapshot path and
        the per-stream reference oracle agree for all of them, and poll()
        returns every stream's standing query from one snapshot."""
        from repro.service import (ContinuousQuery, EstimationService,
                                   ServiceConfig, reference_snapshot)
        svc = EstimationService(ServiceConfig(batch_rows=128,
                                              window_epochs=None))
        svc.create_group("g", CFG)
        rng = np.random.default_rng(21)
        vals = _dups(rng, n=600)
        for kind in KINDS:
            svc.create_stream(f"t/{kind}", "g", estimator=kind)
            svc.ingest(f"t/{kind}", vals)
            svc.register_continuous(
                ContinuousQuery(f"q/{kind}", "self_join", (f"t/{kind}",)))
        res = svc.poll()
        assert set(res) == {f"q/{kind}" for kind in KINDS}
        ref = reference_snapshot(svc.registry)
        for kind in KINDS:
            nm = f"t/{kind}"
            fused = svc.engine.snapshot([nm]).self_join(nm)
            oracle = ref.self_join(nm)
            assert fused.estimate == pytest.approx(oracle.estimate,
                                                   rel=1e-6), kind
            assert fused.n == oracle.n
            mem = svc.registry.stream(nm).estimator.memory_bytes()
            assert 0 < mem <= CFG.counters_bytes  # equal-space by derivation

    def test_join_requires_join_capable_kind(self):
        from repro.service import EstimationService, ServiceConfig
        svc = EstimationService(ServiceConfig(window_epochs=None))
        svc.create_group("g", CFG)
        svc.create_stream("a", "g", estimator="sjpc")
        svc.create_stream("b", "g", estimator="reservoir")
        with pytest.raises(ValueError, match="join-capable"):
            svc.snapshot().join("a", "b")

    def test_sjpc_rejects_an_estimator_cfg(self):
        """SJPC's config is the group's SJPCConfig: a per-stream override
        is refused, not silently dropped."""
        with pytest.raises(ValueError, match="no estimator_cfg"):
            E.make("sjpc", CFG, estimator_cfg={"width": 64})


class TestAlgebraProperties:
    """Hypothesis properties over the protocol algebra, every kind (run
    with real shrinking in the CI property-hypothesis job; the tier-1
    lane drives them through the conftest stub)."""

    @settings(max_examples=6, deadline=None)
    @given(st.integers(min_value=0, max_value=2**31 - 1),
           st.sampled_from([1, 17, 64]))
    def test_merge_n_adds_subtract_recovers_every_kind(self, seed, batch):
        rng = np.random.default_rng(seed)
        va = rng.integers(0, 7, size=(batch, CFG.d)).astype(np.uint32)
        vb = rng.integers(0, 7, size=(batch, CFG.d)).astype(np.uint32)
        for kind, est in ESTS.items():
            a = ingest(est, est.init(sid=1), va, key_seed=seed % 101)
            b = ingest(est, est.init(sid=2), vb, key_seed=seed % 103)
            m = est.merge(a, b)
            assert float(m.n) == 2 * batch, kind
            assert float(est.subtract(m, b).n) == batch, kind
            g = est.estimate_ref(m).g
            assert np.all(np.isfinite(g)) and np.all(g >= 0), kind

    @settings(max_examples=6, deadline=None)
    @given(st.integers(min_value=0, max_value=2**31 - 1))
    def test_batch_matches_ref_on_drawn_streams(self, seed):
        rng = np.random.default_rng(seed)
        vals = rng.integers(0, 5, size=(120, CFG.d)).astype(np.uint32)
        for kind, est in ESTS.items():
            st_ = ingest(est, est.init(sid=0), vals, key_seed=seed % 107)
            batch = est.estimate_batch(E.stack_states([st_]))
            ref = est.estimate_ref(st_)
            np.testing.assert_allclose(batch.g, ref.g, rtol=1e-6, atol=1e-6,
                                       err_msg=kind)

    @settings(max_examples=6, deadline=None)
    @given(st.integers(min_value=0, max_value=2**31 - 1))
    def test_g_non_increasing_in_threshold(self, seed):
        """g(s) counts pairs >= s-similar: with non-negative per-level
        estimates (all kinds construct x >= 0), the suffix-sum table must
        be non-increasing in s."""
        rng = np.random.default_rng(seed)
        vals = _dups(rng, n=200)
        for kind, est in ESTS.items():
            st_ = ingest(est, est.init(sid=0), vals, key_seed=seed % 109)
            g = est.estimate_ref(st_).g[0]
            assert np.all(g[:-1] >= g[1:] - 1e-9), (kind, g)


class TestWindowedSamples:
    def test_windowed_reservoir_tracks_live_epochs_proportionally(self):
        """Sliding-window sample estimators: total = merge-fold of live
        epoch slots.  Two live epochs of duplicate-heavy (all-identical)
        records must BOTH survive the fold roughly proportionally --
        the regression this pins: a content-only merge priority collapsed
        duplicate groups all-or-nothing under top_k."""
        from repro.service import EstimationService, ServiceConfig
        svc = EstimationService(ServiceConfig(batch_rows=64,
                                              window_epochs=3))
        svc.create_group("g", CFG)
        svc.create_stream(
            "w", "g", estimator="reservoir",
            estimator_cfg=E.ReservoirConfig(d=CFG.d, s=CFG.s, capacity=64,
                                            seed=3))
        for epoch_val in (111, 222):
            svc.ingest("w", np.full((500, CFG.d), epoch_val, np.uint32))
            svc.advance_epoch()
        win = svc.registry.stream("w").window
        assert win.n_live() == 1000.0          # both epochs live
        items = np.asarray(win.total.items)
        tags = np.asarray(win.total.tags)
        kept = items[tags >= 0, 0]
        counts = {v: int((kept == v).sum()) for v in (111, 222)}
        assert kept.shape[0] == 64
        # equal-weight epochs: each must keep a substantive share
        assert min(counts.values()) >= 10, counts
        r = svc.snapshot().self_join("w")
        assert np.isfinite(r.estimate) and r.estimate >= 0
        # one more rotation expires epoch 111: n drops to the live window
        svc.ingest("w", np.full((500, CFG.d), 333, np.uint32))
        svc.advance_epoch()
        win_n = svc.registry.stream("w").window.n_live()
        assert win_n == 1000.0                 # epochs {222, 333} + open
        tags = np.asarray(svc.registry.stream("w").window.total.tags)
        items = np.asarray(svc.registry.stream("w").window.total.items)
        assert not np.any(items[tags >= 0, 0] == 111)


class TestReservoirStatistics:
    """The streaming reservoir is distributionally equivalent to offline
    uniform sampling (the satellite's seeded statistical contract)."""

    def test_retention_uniform_over_arrival_order(self):
        """Record i's content encodes its arrival index; over T trials the
        retention counts of early/late arrival quintiles must match the
        uniform expectation R/n within a generous (but seeded) band."""
        cfg = E.ReservoirConfig(d=4, s=2, capacity=16, seed=5)
        est = E.ReservoirEstimator(cfg)
        n, T = 200, 200
        vals = np.repeat(np.arange(n, dtype=np.uint32)[:, None], 4, axis=1)
        counts = np.zeros(n)
        for t in range(T):
            st = ingest(est, est.init(sid=0), vals, key_seed=t)
            kept = np.asarray(st.items)[np.asarray(st.tags) >= 0, 0]
            assert kept.shape[0] == cfg.capacity     # stream >> capacity
            counts[kept] += 1
        assert counts.sum() == T * cfg.capacity
        quintiles = counts.reshape(5, n // 5).sum(axis=1)
        expect = T * cfg.capacity / 5                # 640
        sd = np.sqrt(T * (n // 5) * (cfg.capacity / n)
                     * (1 - cfg.capacity / n))       # ~24.3
        assert np.all(np.abs(quintiles - expect) < 6 * sd), quintiles

    def test_g_unbiased_vs_exact_and_offline_sampler(self):
        """Mean g over trials within CI of the exact count, and
        indistinguishable (by CI overlap) from offline uniform sampling at
        the same sample size."""
        d, n, R, T = 4, 400, 48, 60
        rng = np.random.default_rng(17)
        vals = rng.integers(0, 12, size=(n, d)).astype(np.uint32)
        for i in range(30):
            vals[n - 1 - i] = vals[i]
        s = 3
        g_true = exact.exact_g(vals, s)
        cfg = E.ReservoirConfig(d=d, s=s, capacity=R, seed=9)
        est = E.ReservoirEstimator(cfg)
        stream_g, offline_g = [], []
        for t in range(T):
            st = ingest(est, est.init(sid=0), vals, key_seed=t)
            stream_g.append(float(est.estimate_ref(st).g[0, 0]))
            offline_g.append(baselines.random_sampling_g(
                vals, s, R, np.random.default_rng(5000 + t)))
        stream_g, offline_g = np.array(stream_g), np.array(offline_g)
        se_s = stream_g.std(ddof=1) / np.sqrt(T)
        se_o = offline_g.std(ddof=1) / np.sqrt(T)
        assert abs(stream_g.mean() - g_true) < 4 * se_s, \
            (stream_g.mean(), g_true, se_s)
        assert abs(stream_g.mean() - offline_g.mean()) \
            < 4 * np.hypot(se_s, se_o)


class TestLSHSSStatistics:
    """The streaming LSH-SS audit (the equal_space 60-90%% error
    diagnosis): the stratified pair-reservoir scaling is unbiased -- the
    error was candidate starvation, not a bucket-weight bug.  The online
    pair generator must (a) produce candidates even in a single-round
    ingest (within-round pairing; previously zero candidates -> g
    collapsed to n) and (b) estimate g without bias on uniform data over
    seeded shuffled-arrival trials."""

    CFG_SMALL = E.LSHSSConfig(d=4, s=3, num_hash_cols=1, num_buckets=64,
                              record_capacity=64, pair_capacity=64, seed=7)

    def _ingest_rounds(self, est, vals, batch, key_seed):
        vals = np.ascontiguousarray(np.asarray(vals, np.uint32))
        n, d = vals.shape
        rounds = -(-n // batch)
        pad = rounds * batch - n
        v = np.concatenate([vals, np.zeros((pad, d), np.uint32)])
        mask = np.concatenate([np.ones(n, np.int32),
                               np.zeros(pad, np.int32)])
        base = jax.random.fold_in(
            jax.random.PRNGKey(est.ingest_seed), key_seed)
        keys = np.stack([np.asarray(jax.random.fold_in(base, r))
                         for r in range(rounds)])[:, None]
        new = est.ingest_rounds(
            E.stack_states([est.init(sid=0)]),
            v.reshape(rounds, 1, batch, d), mask.reshape(rounds, 1, batch),
            keys)
        return E.index_state(new, 0)

    def test_single_round_ingest_generates_pairs(self):
        est = E.LSHSSEstimator(self.CFG_SMALL)
        rng = np.random.default_rng(1)
        vals = rng.integers(0, 5, size=(400, 4)).astype(np.uint32)
        st = self._ingest_rounds(est, vals, 400, key_seed=0)
        assert int(st.same_seen) + int(st.cross_seen) > 100
        g = float(est.estimate_ref(st).g[0, 0])
        assert g > float(st.n)          # similar mass is visible, not just n

    def test_g_unbiased_on_uniform_data(self):
        """Seeded multi-trial unbiasedness pin: mean estimate within CI of
        the exact count when arrival order is exchangeable (per-trial
        shuffles).  This is the contract the pre-fix pairing violated on
        arrival-clustered workloads (within-round pairs were never
        candidates)."""
        est = E.LSHSSEstimator(self.CFG_SMALL)
        rng = np.random.default_rng(2)
        n, s, T = 500, 3, 40
        vals = rng.integers(0, 5, size=(n, 4)).astype(np.uint32)
        g_true = exact.exact_g(vals, s)
        ests = []
        for t in range(T):
            order = np.random.default_rng(500 + t).permutation(n)
            st = self._ingest_rounds(est, vals[order], 50, key_seed=t)
            ests.append(float(est.estimate_ref(st).g[0, s - est.s]))
        ests = np.array(ests)
        se = ests.std(ddof=1) / np.sqrt(T)
        assert abs(ests.mean() - g_true) < 4 * se, (ests.mean(), g_true, se)
