"""Compile-only checks against a described (not attached) TPU v5e.

Interpret mode cannot see what the chip's compiler refuses: block shapes
whose last two dimensions break the (8, 128) tiling rule, scalar tables
outside SMEM, working sets over the scoped VMEM limit.  These tests lower
every ``pallas_tpu`` registration for v5e at the shapes ``chip_smoke.py``
runs -- the paper-default lattice (d=6, s=3: L=4 levels, m_max=20), t=3,
w=1024, 512-row rounds, 4096 tenants -- and assert the Mosaic kernel is in
the compiled program.  Nothing runs; no device is needed.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every xdist
worker imports this file.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.fingerprint import fingerprint_pallas
from repro.kernels.flash_attention import flash_attention
from repro.kernels.fused_ingest import fused_ingest_pallas
from repro.kernels.fused_pairs import fused_pairs_pallas
from repro.kernels.fused_query import fused_query_pallas
from repro.kernels.sketch_moments import sketch_moments_pallas
from repro.kernels.sketch_update import sketch_update_pallas

L, T, W, D, M_MAX, ROWS, TENANTS = 4, 3, 1024, 6, 20, 512, 4096
RESERVOIR = 1755            # capacity_for_bytes(PAPER_DEFAULTS)
u32, i32 = jnp.uint32, jnp.int32


@pytest.fixture(scope="module")
def one_chip():
    mp = pytest.MonkeyPatch()
    mp.setenv("TPU_LOG_DIR", "disabled")      # else the compiler logs to /tmp
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                    # noqa: BLE001
        mp.undo()
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these tests
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()
    mp.undo()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile()


def _ingest(*a):
    return fused_ingest_pallas(*a, interpret=False)


# "<op>" or "<op>/<variant>" -> (jit-able fn, argument (shape, dtype)s)
CASES = {
    # one tenant's round, and the service flush: vmapped over 4096 tenants
    "fused_ingest/round": (_ingest, [
        ((L, T, W), i32), ((ROWS, D), u32), ((L, M_MAX, D), u32),
        ((L, M_MAX), u32), ((2,), u32), ((L, T, 2, 4), u32),
        ((L, T, 2, 4), u32), ((ROWS, L, M_MAX), i32)]),
    "fused_ingest/flush": (
        jax.vmap(_ingest, in_axes=(0, 0, None, None, None, None, None, 0)), [
            ((TENANTS, L, T, W), i32), ((TENANTS, ROWS, D), u32),
            ((L, M_MAX, D), u32), ((L, M_MAX), u32), ((2,), u32),
            ((L, T, 2, 4), u32), ((L, T, 2, 4), u32),
            ((TENANTS, ROWS, L, M_MAX), i32)]),
    # a poll: the whole sjpc cohort, and a handful of join pairs
    "fused_query/cohort": (
        lambda a, b: fused_query_pallas(a, b, interpret=False),
        [((TENANTS, L, T, W), i32)] * 2),
    "fused_query/joins": (
        lambda a, b: fused_query_pallas(a, b, interpret=False),
        [((8, L, T, W), i32)] * 2),
    # reservoir histograms, and their bootstrap (64 streams x 32 replicates
    # of 256 items)
    "fused_pairs/reservoir": (
        lambda it, va: fused_pairs_pallas(it, va, interpret=False),
        [((64, RESERVOIR, D), u32), ((64, RESERVOIR), i32)]),
    "fused_pairs/bootstrap": (
        lambda it, va: fused_pairs_pallas(it, va, interpret=False),
        [((64 * 32, 256, D), u32), ((64 * 32, 256), i32)]),
    # the remaining registrations, at the widths their callers use
    "fingerprint": (
        lambda *a: fingerprint_pallas(*a, interpret=False),
        [((ROWS, D), u32), ((M_MAX, D), u32), ((M_MAX,), u32), ((2,), u32)]),
    "sketch_update": (
        lambda *a: sketch_update_pallas(*a, interpret=False),
        [((T, W), i32), ((ROWS * M_MAX,), u32), ((ROWS * M_MAX,), u32),
         ((T, 2, 4), u32), ((T, 2, 4), u32), ((ROWS * M_MAX,), i32)]),
    "sketch_moments": (
        lambda a, b: sketch_moments_pallas(a, b, interpret=False),
        [((T, W), i32)] * 2),
    "flash_attention": (
        lambda q, k, v: flash_attention(q, k, v, interpret=False),
        [((4, 1024, 16, 64), jnp.bfloat16)] * 3),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, case):
    fn, shapes = CASES[case]
    compiled = _compile(fn, one_chip, *shapes)
    assert "tpu_custom_call" in compiled.as_text(), case


def test_every_pallas_tpu_registration_is_covered():
    """A new pallas_tpu registration needs a compile case here."""
    from repro.kernels.registry import PALLAS_TPU, kernel_registry
    ops = {op for op in kernel_registry().ops()
           if PALLAS_TPU in {i.name for i in kernel_registry().impls(op)}}
    covered = {case.split("/")[0] for case in CASES}
    assert ops <= covered, ops - covered


@pytest.mark.parametrize("case,name", [
    ("fused_ingest/round", "sjpc_fused_ingest"),
    ("fused_query/joins", "sjpc_fused_query"),
    ("fused_pairs/reservoir", "sjpc_fused_pairs"),
])
def test_service_kernels_carry_stable_names(one_chip, case, name):
    """The device trace names an op by its HLO instruction: the service's
    kernels pass ``name=``, so the op reads ``<name>.<n>`` in every build
    and not a name derived from the traced function."""
    fn, shapes = CASES[case]
    text = _compile(fn, one_chip, *shapes).as_text()
    assert re.search(rf"%{name}(\.\d+)? = .*tpu_custom_call", text), case
