"""The kernel capability registry (DESIGN.md §17) and its registry-driven
conformance matrix.

The matrix is GENERATED from the registry: one parametrized case per
(op, registered impl) x shape/depth/empty edge grid (tests/kernel_cases.py).
Registering a backend without an oracle is impossible
(``KernelRegistry.register`` refuses it), and a backend that drifts from
its oracle fails here by construction -- nobody has to remember to extend
``test_fused_*.py`` when a tier is added.

Also covered: resolution order per platform, forcing (context manager /
``REPRO_KERNEL_IMPL``), the dispatch-metric ``impl`` label, the
``fused_pairs`` R==0 accounting regression, the ``repro.platform``
bootstrap helpers, and hypothesis properties asserting every registered
impl of every kernel is VALUE-identical (not just close) under input
permutation and leading-dim reshapes on integer inputs.
"""
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, registry as registry_mod
from repro.kernels.registry import (JNP_REF, PALLAS_GPU, PALLAS_INTERPRET,
                                    PALLAS_TPU, KernelRegistry,
                                    RegistryError, kernel_registry,
                                    on_platforms)
from repro.obs.metrics import MetricsRegistry, set_default_registry

from kernel_cases import (KernelCase, entry_call, matrix_cases, oracle_call,
                          pairs_case, counter_stack, sketch_update_case,
                          ingest_inputs, fingerprint_case, flash_case)

REG = kernel_registry()

# completeness at COLLECTION time: an op losing its oracle-carrying impls
# aborts the whole module, not one test deep in the run
REG.check()

ALL_OPS = ("fingerprint", "sketch_update", "sketch_moments", "fused_ingest",
           "fused_query", "fused_pairs", "flash_attention")

MATRIX = [(case, impl.name) for case in matrix_cases()
          for impl in REG.impls(case.op)]


def _assert_matches(case, got, want):
    got, want = np.asarray(got), np.asarray(want)
    if case.tol is None:
        np.testing.assert_array_equal(got, want)
    else:
        np.testing.assert_allclose(got, want, rtol=case.tol, atol=case.tol)


# ---------------------------------------------------------------------------
# the conformance matrix
# ---------------------------------------------------------------------------

class TestConformanceMatrix:
    @pytest.mark.parametrize("case,impl_name", MATRIX,
                             ids=[f"{c.id}-{n}" for c, n in MATRIX])
    def test_impl_matches_its_oracle(self, case, impl_name):
        """Every registered implementation == its attached oracle, called
        through the real ops dispatch layer with ``impl=`` forced."""
        impl = REG.get(case.op, impl_name)
        got = entry_call(case, impl_name)
        want = oracle_call(case, impl.oracle)
        _assert_matches(case, got, want)


# ---------------------------------------------------------------------------
# registry contract
# ---------------------------------------------------------------------------

class TestRegistryContract:
    def test_all_seven_ops_registered(self):
        assert REG.ops() == tuple(sorted(ALL_OPS))

    def test_every_op_has_at_least_two_impls_and_ref_fallback(self):
        for op in REG.ops():
            names = {i.name for i in REG.impls(op)}
            assert len(names) >= 2, (op, names)
            assert JNP_REF in names, (op, names)
            assert PALLAS_INTERPRET in names, (op, names)

    def test_gpu_tier_registered_for_the_four_fused_kernels(self):
        for op in ("fingerprint", "fused_ingest", "fused_query",
                   "fused_pairs"):
            assert PALLAS_GPU in {i.name for i in REG.impls(op)}, op

    def test_registering_without_oracle_is_refused(self):
        """The auto-attachment contract: an impl with no oracle cannot
        exist, so the matrix above can never silently under-cover."""
        private = KernelRegistry()
        with pytest.raises(RegistryError, match="oracle"):
            private.register("op", "x", fn=lambda: None, oracle=None,
                             predicate=on_platforms("cpu"), priority=1)

    def test_duplicate_registration_is_refused(self):
        private = KernelRegistry()
        private.register("op", "x", fn=lambda: None, oracle=lambda: None,
                         predicate=on_platforms("cpu"), priority=1)
        with pytest.raises(RegistryError, match="already registered"):
            private.register("op", "x", fn=lambda: None, oracle=lambda: None,
                             predicate=on_platforms("cpu"), priority=1)

    def test_check_flags_single_impl_ops(self):
        private = KernelRegistry()
        private.register("lonely", JNP_REF, fn=lambda: None,
                         oracle=lambda: None,
                         predicate=on_platforms("cpu"), priority=1)
        with pytest.raises(RegistryError, match="need >= 2"):
            private.check()

    def test_matrix_axis_covers_every_registration(self):
        axis = set(REG.matrix())
        for op in REG.ops():
            for impl in REG.impls(op):
                assert (op, impl.name) in axis


class TestResolution:
    @pytest.fixture(autouse=True)
    def _no_env_force(self, monkeypatch):
        """These tests pin the UN-forced resolution order; neutralize any
        ambient REPRO_KERNEL_IMPL (the CI pallas-interpret lane exports it
        for the whole module)."""
        monkeypatch.delenv(registry_mod.FORCE_ENV, raising=False)

    def test_platform_resolution_order(self):
        """cpu -> jnp_ref; tpu -> pallas_tpu; gpu -> pallas_gpu where
        registered, jnp_ref fallback elsewhere (the acceptance contract:
        the gpu tier falls back cleanly on machines without one)."""
        assert set(REG.resolution("cpu").values()) == {JNP_REF}
        assert set(REG.resolution("tpu").values()) == {PALLAS_TPU}
        gpu_res = REG.resolution("gpu")
        for op in ("fingerprint", "fused_ingest", "fused_query",
                   "fused_pairs"):
            assert gpu_res[op] == PALLAS_GPU
        for op in ("sketch_update", "sketch_moments", "flash_attention"):
            assert gpu_res[op] == JNP_REF

    def test_force_context_redirects_auto_dispatch_only(self):
        with REG.force(PALLAS_INTERPRET):
            assert REG.resolve("fused_pairs").name == PALLAS_INTERPRET
            assert REG.resolve("sketch_update").name == PALLAS_INTERPRET
        assert REG.resolve("fused_pairs", "cpu").name == JNP_REF

    def test_force_per_op_wins_over_wildcard(self):
        with REG.force(PALLAS_INTERPRET):
            with REG.force(PALLAS_GPU, op="fused_pairs"):
                assert REG.resolve("fused_pairs").name == PALLAS_GPU
                assert REG.resolve("fused_query").name == PALLAS_INTERPRET

    def test_env_forcing(self, monkeypatch):
        monkeypatch.setenv(registry_mod.FORCE_ENV,
                           "fused_pairs=pallas_gpu,*=jnp_ref")
        assert REG.resolve("fused_pairs").name == PALLAS_GPU
        assert REG.resolve("fused_query").name == JNP_REF
        monkeypatch.delenv(registry_mod.FORCE_ENV)
        assert REG.resolve("fused_pairs", "cpu").name == JNP_REF

    def test_explicit_impl_wins_over_force(self):
        rng = np.random.default_rng(0)
        items, valid = pairs_case(rng, 1, 12, 3)
        with REG.force(PALLAS_INTERPRET):
            fresh = MetricsRegistry()
            prev = set_default_registry(fresh)
            try:
                ops.fused_pairs(items, valid, use_pallas=False)
                assert fresh.counter("kernel_dispatch_total",
                                     kernel="fused_pairs", path="jnp",
                                     impl=JNP_REF) == 1.0
            finally:
                set_default_registry(prev)

    def test_unknown_names_raise(self):
        with pytest.raises(RegistryError, match="unknown kernel op"):
            REG.resolve("not_an_op")
        with pytest.raises(RegistryError, match="no implementation"):
            REG.get("fused_pairs", "not_a_tier")


# ---------------------------------------------------------------------------
# dispatch accounting (satellite: R==0 + the impl label)
# ---------------------------------------------------------------------------

class TestDispatchAccounting:
    @pytest.fixture(autouse=True)
    def _no_env_force(self, monkeypatch):
        monkeypatch.delenv(registry_mod.FORCE_ENV, raising=False)

    def _fresh(self):
        fresh = MetricsRegistry()
        return fresh, set_default_registry(fresh)

    def test_empty_reservoir_query_is_counted(self):
        """Regression: the fused_pairs R==0 early return used to skip
        ``kernel_dispatch_total`` -- empty-reservoir queries were invisible
        to dispatch telemetry."""
        fresh, prev = self._fresh()
        try:
            out = ops.fused_pairs(np.zeros((2, 0, 4), np.uint32),
                                  np.zeros((2, 0), np.int32))
            assert out.shape == (2, 5) and not np.asarray(out).any()
            assert fresh.counter("kernel_dispatch_total",
                                 kernel="fused_pairs", path="jnp",
                                 impl=JNP_REF) == 1.0
        finally:
            set_default_registry(prev)

    def test_counter_carries_impl_label(self):
        rng = np.random.default_rng(1)
        items, valid = pairs_case(rng, 1, 16, 3)
        fresh, prev = self._fresh()
        try:
            ops.fused_pairs(items, valid)                    # auto: jnp_ref
            ops.fused_pairs(items, valid, use_pallas=True)   # interpreter
            ops.fused_pairs(items, valid, impl=PALLAS_GPU)   # forced tier
            assert fresh.counter("kernel_dispatch_total",
                                 kernel="fused_pairs", path="jnp",
                                 impl=JNP_REF) == 1.0
            assert fresh.counter("kernel_dispatch_total",
                                 kernel="fused_pairs", path="pallas",
                                 impl=PALLAS_INTERPRET) == 1.0
            assert fresh.counter("kernel_dispatch_total",
                                 kernel="fused_pairs", path="pallas",
                                 impl=PALLAS_GPU) == 1.0
        finally:
            set_default_registry(prev)


# ---------------------------------------------------------------------------
# hypothesis: impl-identity under permutation / leading-dim reshape
# ---------------------------------------------------------------------------
# Integer kernels must agree bit-for-bit ACROSS impls and stay bit-stable
# under record permutation (scatter-add commutativity) and leading-dim
# reshapes (batch entries are independent).  flash_attention is the one
# float kernel: each impl must be exactly equivariant to batch permutation
# (independent batch entries), while cross-impl agreement is tolerance-based
# and covered by the matrix above.

def _impls(op):
    return [i.name for i in REG.impls(op)]


class TestImplIdentityProperties:
    @given(st.integers(min_value=0, max_value=2**20),
           st.integers(min_value=2, max_value=30),
           st.integers(min_value=2, max_value=6))
    @settings(max_examples=5, deadline=None)
    def test_fused_pairs_permutation_and_reshape(self, seed, r, d):
        rng = np.random.default_rng(seed)
        items, valid = pairs_case(rng, 2, r, d)
        perm = rng.permutation(r)
        outs = []
        for name in _impls("fused_pairs"):
            base = np.asarray(ops.fused_pairs(items, valid, impl=name))
            permed = np.asarray(ops.fused_pairs(items[:, perm],
                                                valid[:, perm], impl=name))
            np.testing.assert_array_equal(base, permed)
            lead = np.asarray(ops.fused_pairs(
                items.reshape(2, 1, r, d), valid.reshape(2, 1, r),
                impl=name))
            np.testing.assert_array_equal(base, lead.reshape(2, d + 1))
            outs.append(base)
        for other in outs[1:]:
            np.testing.assert_array_equal(outs[0], other)

    @given(st.integers(min_value=0, max_value=2**20),
           st.integers(min_value=1, max_value=4),
           st.integers(min_value=5, max_value=8))
    @settings(max_examples=5, deadline=None)
    def test_fused_query_reshape(self, seed, t, logw):
        rng = np.random.default_rng(seed)
        a = counter_stack(rng, 2, 3, t, 2**logw)
        b = counter_stack(rng, 2, 3, t, 2**logw)
        outs = []
        for name in _impls("fused_query"):
            base = np.asarray(ops.fused_query(a, b, impl=name))
            flat = np.asarray(ops.fused_query(a.reshape(6, 1, t, 2**logw),
                                              b.reshape(6, 1, t, 2**logw),
                                              impl=name))
            np.testing.assert_array_equal(base, flat.reshape(2, 3, t))
            outs.append(base)
        for other in outs[1:]:
            np.testing.assert_array_equal(outs[0], other)

    @given(st.integers(min_value=0, max_value=2**20),
           st.integers(min_value=1, max_value=64))
    @settings(max_examples=5, deadline=None)
    def test_sketch_update_batch_permutation(self, seed, n):
        rng = np.random.default_rng(seed)
        counters, fp1, fp2, bc, sc, weights = sketch_update_case(
            rng, n, 3, 128)
        perm = rng.permutation(n)
        outs = []
        for name in _impls("sketch_update"):
            base = np.asarray(entry_call(
                KernelCase("sketch_update", "p",
                           (counters, fp1, fp2, bc, sc, weights)), name))
            permed = np.asarray(entry_call(
                KernelCase("sketch_update", "p",
                           (counters, fp1[perm], fp2[perm], bc, sc,
                            weights[perm])), name))
            np.testing.assert_array_equal(base, permed)
            outs.append(base)
        for other in outs[1:]:
            np.testing.assert_array_equal(outs[0], other)

    @given(st.integers(min_value=0, max_value=2**20),
           st.integers(min_value=1, max_value=40))
    @settings(max_examples=3, deadline=None)
    def test_fused_ingest_batch_permutation(self, seed, batch):
        from repro.core.sjpc import SJPCConfig
        rng = np.random.default_rng(seed)
        cfg = SJPCConfig(d=4, s=2, width=128, depth=2, seed=9)
        _, _, args = ingest_inputs(rng, cfg, batch)
        counters, values, masks, ids, bases, bc, sc, weights = args
        perm = rng.permutation(batch)
        outs = []
        for name in _impls("fused_ingest"):
            base = np.asarray(ops.fused_ingest(*args, impl=name))
            permed = np.asarray(ops.fused_ingest(
                counters, values[perm], masks, ids, bases, bc, sc,
                weights[perm], impl=name))
            np.testing.assert_array_equal(base, permed)
            outs.append(base)
        for other in outs[1:]:
            np.testing.assert_array_equal(outs[0], other)

    @given(st.integers(min_value=0, max_value=2**20),
           st.integers(min_value=1, max_value=50))
    @settings(max_examples=5, deadline=None)
    def test_fingerprint_row_permutation_equivariant(self, seed, b):
        rng = np.random.default_rng(seed)
        args = fingerprint_case(rng, b, 5, 3)
        values = args[0]
        perm = rng.permutation(b)
        outs = []
        for name in _impls("fingerprint"):
            f1, f2 = ops.fingerprint(*args, impl=name)
            p1, p2 = ops.fingerprint(values[perm], *args[1:], impl=name)
            np.testing.assert_array_equal(np.asarray(f1)[perm],
                                          np.asarray(p1))
            np.testing.assert_array_equal(np.asarray(f2)[perm],
                                          np.asarray(p2))
            outs.append(np.asarray(f1))
        for other in outs[1:]:
            np.testing.assert_array_equal(outs[0], other)

    @given(st.integers(min_value=0, max_value=2**20),
           st.integers(min_value=1, max_value=4))
    @settings(max_examples=5, deadline=None)
    def test_sketch_moments_row_reshape(self, seed, t):
        rng = np.random.default_rng(seed)
        a = counter_stack(rng, 1, 1, t, 256)[0, 0]
        b = counter_stack(rng, 1, 1, t, 256)[0, 0]
        outs = []
        for name in _impls("sketch_moments"):
            base = np.asarray(ops.sketch_moments(a, b, impl=name))
            outs.append(base)
        for other in outs[1:]:
            np.testing.assert_array_equal(outs[0], other)

    @given(st.integers(min_value=0, max_value=2**18))
    @settings(max_examples=3, deadline=None)
    def test_flash_attention_batch_permutation_equivariant(self, seed):
        rng = np.random.default_rng(seed)
        q, k, v = flash_case(rng, 3, 32, 1, 8)
        perm = rng.permutation(3)
        for name in _impls("flash_attention"):
            base = np.asarray(ops.flash_attention(
                q, k, v, block_q=16, block_k=16, impl=name))
            permed = np.asarray(ops.flash_attention(
                q[perm], k[perm], v[perm], block_q=16, block_k=16,
                impl=name))
            np.testing.assert_array_equal(base[perm], permed)


# ---------------------------------------------------------------------------
# repro.platform bootstrap
# ---------------------------------------------------------------------------

class TestPlatformBootstrap:
    def test_bootstrap_auto_reports_active_backend(self):
        from repro import platform as plat
        assert plat.bootstrap("auto") == jax.default_backend()
        assert plat.current() == jax.default_backend()

    def test_service_config_platform_auto(self):
        from repro.service import EstimationService, ServiceConfig
        svc = EstimationService(ServiceConfig())
        assert svc.platform == jax.default_backend()

    def test_subprocess_env_forces_host_devices(self):
        from repro import platform as plat
        env = plat.subprocess_env(4)
        assert "--xla_force_host_platform_device_count=4" in env["XLA_FLAGS"]
        assert "XLA_FLAGS" not in os.environ \
            or env["XLA_FLAGS"] != os.environ.get("XLA_FLAGS") \
            or "device_count=4" in os.environ.get("XLA_FLAGS", "")

    def test_xla_flag_append_is_idempotent(self):
        from repro import platform as plat
        env = {"XLA_FLAGS": "--foo=1"}
        plat.force_host_device_count(2, env)
        plat.force_host_device_count(2, env)
        assert env["XLA_FLAGS"].count("device_count=2") == 1
        assert env["XLA_FLAGS"].startswith("--foo=1")

    def test_gpu_flags_constant_covers_triton_fusion(self):
        from repro import platform as plat
        assert "--xla_gpu_enable_triton_softmax_fusion=true" \
            in plat.GPU_XLA_FLAGS

    @pytest.mark.parametrize("env_dir", [None, "from-env"])
    def test_compile_cache_location(self, monkeypatch, tmp_path, env_dir):
        """The cache goes where JAX_COMPILATION_CACHE_DIR says (and nothing
        is set in code), else to the fixed <checkout>/.jax_cache."""
        from repro import platform as plat
        before = jax.config.jax_compilation_cache_dir
        if env_dir is None:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        else:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                               str(tmp_path / env_dir))
        try:
            path = plat.enable_compile_cache()
            if env_dir is None:
                assert path == str(plat.CHECKOUT / ".jax_cache")
                assert jax.config.jax_compilation_cache_dir == path
                assert (plat.CHECKOUT / "src" / "repro").is_dir()
            else:
                assert path == str(tmp_path / env_dir)
                assert jax.config.jax_compilation_cache_dir == before
        finally:
            jax.config.update("jax_compilation_cache_dir", before)
