"""Fused batched query engine conformance (DESIGN.md §12).

Three layers, each against an independent oracle:

  kernel     fused_query_pallas (interpret mode -- pure CPU) vs the int64
             numpy moment oracle and the jnp fallback, across depths
             {1, 3, 5}, non-square (t != w, multi-tile) widths, and
             empty / single-record sketches;
  estimator  sjpc.estimate_batch / estimate_join_batch vs per-stream
             sjpc.estimate / estimate_join loops (bit-equal here: every
             intermediate is an exact-integer f32);
  service    the batched Snapshot vs ``reference_snapshot``, the
             per-stream numpy reference, within 1e-6.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import sjpc
from repro.core import sketch as sk
from repro.core.sjpc import SJPCConfig
from repro.kernels.fused_query import fused_query_pallas
from repro.kernels.ops import fused_query
from repro.service import (EstimationService, ServiceConfig,
                           reference_snapshot)

# shape/depth grids and builders shared with the registry conformance
# matrix (kernel_cases.py / test_kernel_registry.py)
from kernel_cases import (QUERY_DEPTHS, QUERY_SHAPES,
                          counter_stack as _counter_stack,
                          oracle_moments as _oracle_moments)


class TestKernelConformance:
    @pytest.mark.parametrize("depth", QUERY_DEPTHS)
    @pytest.mark.parametrize("N,L,w,block_w", QUERY_SHAPES)
    def test_moments_match_int64_oracle(self, depth, N, L, w, block_w):
        rng = np.random.default_rng(depth * 1000 + N * 100 + w)
        a = _counter_stack(rng, N, L, depth, w)
        b = _counter_stack(rng, N, L, depth, w)
        out = fused_query_pallas(a, b, block_w=block_w, interpret=True)
        assert out.shape == (N, L, depth)
        np.testing.assert_array_equal(np.asarray(out),
                                      _oracle_moments(a, b).astype(np.float64))

    @pytest.mark.parametrize("depth", QUERY_DEPTHS)
    def test_pallas_bit_identical_to_jnp_fallback(self, depth):
        rng = np.random.default_rng(77 + depth)
        a = _counter_stack(rng, 4, 3, depth, 256)
        pal = fused_query_pallas(a, a, block_w=64, interpret=True)
        ref = fused_query(a, a, use_pallas=False)
        np.testing.assert_array_equal(np.asarray(pal), np.asarray(ref))

    def test_self_case_is_f2(self):
        rng = np.random.default_rng(3)
        a = _counter_stack(rng, 2, 3, 3, 128)
        out = fused_query_pallas(a, a, interpret=True)
        f2 = (np.asarray(a, np.int64) ** 2).sum(axis=-1)
        np.testing.assert_array_equal(np.asarray(out), f2.astype(np.float64))

    def test_empty_sketch_gives_zero_moments(self):
        a = jnp.zeros((2, 3, 3, 128), jnp.int32)
        out = fused_query_pallas(a, a, interpret=True)
        np.testing.assert_array_equal(np.asarray(out), 0.0)


class TestBatchEstimator:
    def _states(self, cfg, batches, seed0=0):
        params, s0 = sjpc.init(cfg)
        rng = np.random.default_rng(11)
        states = []
        for i, nb in enumerate(batches):
            st = s0
            for b in range(nb):
                vals = rng.integers(0, 5, size=(25, cfg.d)).astype(np.uint32)
                st = sjpc.update(cfg, params, st, vals,
                                 key=jax.random.PRNGKey(seed0 + 97 * i + b))
            states.append(st)
        return states

    @pytest.mark.parametrize("depth", QUERY_DEPTHS)
    def test_estimate_batch_matches_per_stream_reference(self, depth):
        cfg = SJPCConfig(d=5, s=3, ratio=0.5, width=256, depth=depth, seed=41)
        states = self._states(cfg, [0, 1, 3, 5])     # includes an EMPTY sketch
        be = sjpc.estimate_batch(
            cfg, jnp.stack([st.counters for st in states]),
            np.array([float(st.n) for st in states], np.float32))
        for i, st in enumerate(states):
            ref = sjpc.estimate(cfg, st)
            np.testing.assert_array_equal(be.y[i], ref.y)
            np.testing.assert_array_equal(be.x[i], ref.x)
            assert be.g[i, 0] == ref.g_s
            # every higher threshold agrees with the reference suffix sums
            for li in range(1, cfg.num_levels):
                assert be.g[i, li] == pytest.approx(
                    float(ref.x[li:].sum()) + ref.n, rel=1e-12, abs=1e-9)

    def test_single_record_sketch(self):
        cfg = SJPCConfig(d=4, s=2, ratio=1.0, width=128, depth=3, seed=42)
        params, s0 = sjpc.init(cfg)
        st = sjpc.update(cfg, params, s0,
                         np.array([[1, 2, 3, 4]], np.uint32),
                         key=jax.random.PRNGKey(0))
        be = sjpc.estimate_batch(cfg, st.counters[None],
                                 np.array([1.0], np.float32))
        ref = sjpc.estimate(cfg, st)
        np.testing.assert_array_equal(be.x[0], ref.x)
        assert be.g[0, 0] == ref.g_s
        assert np.all(np.isfinite(be.stderr)) and np.all(be.stderr >= 0)

    def test_estimate_join_batch_matches_reference(self):
        cfg = SJPCConfig(d=5, s=3, ratio=0.5, width=256, depth=3, seed=43)
        states = self._states(cfg, [2, 3, 1, 4])
        pairs = [(0, 1), (2, 3), (0, 3)]
        bj = sjpc.estimate_join_batch(
            cfg,
            jnp.stack([states[a].counters for a, _ in pairs]),
            jnp.stack([states[b].counters for _, b in pairs]),
            np.array([float(states[a].n) for a, _ in pairs], np.float32),
            np.array([float(states[b].n) for _, b in pairs], np.float32))
        for i, (a, b) in enumerate(pairs):
            ref = sjpc.estimate_join(cfg, states[a], states[b])
            np.testing.assert_array_equal(bj.y[i], ref.y)
            np.testing.assert_array_equal(bj.x[i], ref.x)
            assert bj.g[i, 0] == ref.g_s

    def test_batch_bounds_match_scalar_theorems(self):
        cfg = SJPCConfig(d=5, s=3, ratio=0.5, width=256, depth=3, seed=44)
        states = self._states(cfg, [2, 4])
        be = sjpc.estimate_batch(
            cfg, jnp.stack([st.counters for st in states]),
            np.array([float(st.n) for st in states], np.float32))
        import math
        for i in range(2):
            for li, k in enumerate(range(cfg.s, cfg.d + 1)):
                g = be.g[i, li]
                if g <= 0:
                    assert be.stderr[i, li] == 0.0
                    continue
                off = math.sqrt(sjpc.offline_variance_bound(
                    cfg.d, k, cfg.ratio, g)) * g
                on = math.sqrt(sjpc.online_variance_bound(
                    cfg.d, k, cfg.ratio, cfg.width, be.n[i], g)) * g
                assert be.stderr_offline[i, li] == pytest.approx(off, rel=1e-12)
                assert be.stderr[i, li] == pytest.approx(on, rel=1e-12)


class TestSnapshotConformance:
    """The batched Snapshot (service default) == the per-stream reference
    path, every stream x threshold cell, within 1e-6."""

    def _service(self):
        cfg = SJPCConfig(d=5, s=3, ratio=0.5, width=512, depth=3, seed=51)
        svc = EstimationService(ServiceConfig(batch_rows=64, window_epochs=3))
        svc.create_group("g", cfg)
        rng = np.random.default_rng(13)
        names = [f"t{i}" for i in range(5)]
        for nm in names:
            svc.create_stream(nm, "g")
        for ep in range(4):
            for j, nm in enumerate(names):
                if ep == 0 and j == 4:
                    continue                 # t4 starts empty in epoch 0
                svc.ingest(nm, rng.integers(0, 6, size=(30 + 11 * j, cfg.d))
                           .astype(np.uint32))
            svc.advance_epoch()
        return cfg, svc, names

    def test_batched_snapshot_matches_reference(self):
        cfg, svc, names = self._service()
        snap, ref = svc.snapshot(), reference_snapshot(svc.registry)
        for nm in names:
            for k in range(cfg.s, cfg.d + 1):
                a, b = snap.self_join(nm, k), ref.self_join(nm, k)
                assert a.estimate == pytest.approx(b.estimate, rel=1e-6,
                                                   abs=1e-6)
                assert a.stderr == pytest.approx(b.stderr, rel=1e-6, abs=1e-6)
                assert a.stderr_offline == pytest.approx(b.stderr_offline,
                                                         rel=1e-6, abs=1e-6)
                np.testing.assert_allclose(a.per_level, b.per_level,
                                           rtol=1e-6, atol=1e-6)
                assert a.n == b.n and a.window_epochs == b.window_epochs
        for a_nm, b_nm in [(names[0], names[1]), (names[2], names[4])]:
            ja, jb = snap.join(a_nm, b_nm), ref.join(a_nm, b_nm)
            assert ja.estimate == pytest.approx(jb.estimate, rel=1e-6,
                                                abs=1e-6)
            assert ja.stderr == pytest.approx(jb.stderr, rel=1e-6, abs=1e-6)
            np.testing.assert_allclose(ja.per_level, jb.per_level,
                                       rtol=1e-6, atol=1e-6)

    def test_unclamped_queries_match_too(self):
        cfg, svc, names = self._service()
        snap, ref = svc.snapshot(), reference_snapshot(svc.registry)
        for nm in names[:2]:
            for k in (cfg.s, cfg.d):
                a = snap.self_join(nm, k, clamp=False)
                b = ref.self_join(nm, k, clamp=False)
                assert a.estimate == pytest.approx(b.estimate, rel=1e-6,
                                                   abs=1e-6)

    def test_all_thresholds_single_compiled_batch(self):
        """all_thresholds over every stream shares ONE cached batch entry
        (the one-compiled-call contract)."""
        _, svc, names = self._service()
        snap = svc.snapshot()
        for nm in names:
            snap.all_thresholds(nm)
        self_entries = [k for k in snap._cache if k[0] == "self"]
        assert len(self_entries) == 1

    def test_poll_prefetches_joins_in_one_batch(self):
        from repro.service import ContinuousQuery
        _, svc, names = self._service()
        svc.register_continuous(ContinuousQuery("j01", "join",
                                                (names[0], names[1])))
        svc.register_continuous(ContinuousQuery("j23", "join",
                                                (names[2], names[3])))
        svc.register_continuous(ContinuousQuery("sj", "self_join",
                                                (names[4],)))
        out = svc.poll()
        assert set(out) == {"j01", "j23", "sj"}
        ref = reference_snapshot(svc.registry)
        assert out["j01"].estimate == pytest.approx(
            ref.join(names[0], names[1]).estimate, rel=1e-6, abs=1e-6)


class TestSketchMomentOracle:
    def test_np_estimate_inner_exact_matches_f2_on_self(self):
        rng = np.random.default_rng(9)
        c = rng.integers(-40, 40, size=(3, 4, 256)).astype(np.int32)
        np.testing.assert_array_equal(sk.np_estimate_inner_exact(c, c),
                                      sk.np_estimate_f2_exact(c))
