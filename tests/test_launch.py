"""Launch-layer integration on a 1x1 debug mesh: shardings resolve, the
jitted train step runs end-to-end (model + optimizer + shard_map'd monitor),
decode caches get coherent specs, and the roofline HLO parser works."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec

from repro import configs
from repro.models import model as M
from repro.models.config import compute_dims
from repro.models.layers import split_tree
from repro.launch import shardings as SH
from repro.launch import roofline as RL
from repro.launch.mesh import make_debug_mesh, batch_axes
from repro.launch.train import make_train_step, make_train_state, state_shardings
from repro.launch.serve import cache_shardings
from repro.optim import make_adamw
from repro.optim.schedules import constant
from repro.sketchstream.monitor import SketchMonitorConfig


def test_param_pspecs_cover_every_leaf():
    for name in ["jamba-1.5-large-398b", "dbrx-132b", "seamless-m4t-large-v2",
                 "mamba2-370m"]:
        cfg = configs.reduced(name)
        dims = compute_dims(cfg, tp=1)
        ptree = jax.eval_shape(
            lambda: M.init_params(jax.random.PRNGKey(0), cfg, dims))
        params, axes = split_tree(ptree)
        mesh = make_debug_mesh(1, 1)
        specs = SH.param_pspecs(mesh, axes)
        n_p = len(jax.tree_util.tree_leaves(params))
        n_s = len(jax.tree_util.tree_leaves(
            specs, is_leaf=lambda x: isinstance(x, PartitionSpec)))
        assert n_p == n_s, (name, n_p, n_s)


def test_train_step_runs_on_debug_mesh():
    cfg = configs.reduced("deepseek-moe-16b")     # moe + shared experts
    dims = compute_dims(cfg, tp=1)
    mesh = make_debug_mesh(1, 1)
    mcfg = SketchMonitorConfig(d=4, s=3, width=256, depth=2, shards=1)
    opt = make_adamw(constant(1e-3))
    state, mparams, axes = make_train_state(
        jax.random.PRNGKey(0), cfg, dims, opt, monitor_cfg=mcfg)
    step_fn = make_train_step(cfg, dims, opt, mesh, monitor_cfg=mcfg,
                              monitor_params=mparams, remat="none",
                              ssm_chunk=8, compute_dtype=jnp.float32)
    batch = {
        "tokens": jnp.asarray(np.random.default_rng(0).integers(
            0, cfg.vocab_size, size=(4, 32), dtype=np.int32)),
        "labels": jnp.asarray(np.random.default_rng(1).integers(
            0, cfg.vocab_size, size=(4, 32), dtype=np.int32)),
    }
    with jax.set_mesh(mesh):
        state2, metrics = jax.jit(step_fn)(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert int(state2.step) == 1
    # monitor absorbed the batch
    assert float(state2.monitor.n.sum()) == 4.0
    assert int(jnp.abs(state2.monitor.counters).sum()) > 0


def test_monitor_shard_map_multi_shard():
    """2-shard data mesh: deferred-merge counters live per-shard."""
    if jax.device_count() < 2:
        pytest.skip("needs >= 2 devices")


def test_cache_pspecs_structure():
    cfg = configs.reduced("jamba-1.5-large-398b")
    dims = compute_dims(cfg, tp=1)
    mesh = make_debug_mesh(1, 1)
    cache_ab, shardings = cache_shardings(mesh, cfg, dims, batch=4, max_len=64)
    leaves_a = jax.tree_util.tree_leaves(cache_ab)
    leaves_s = jax.tree_util.tree_leaves(
        shardings, is_leaf=lambda x: hasattr(x, "spec"))
    assert len(leaves_a) == len(leaves_s)


def test_roofline_parser():
    hlo = """
ENTRY %main.1 (p0: f32[1024]) -> f32[1024] {
  %x = bf16[1,512]{1,0} parameter(0)
  %ag = bf16[16,512]{1,0} all-gather(%x), replica_groups={}
  %y = f32[1024]{0} parameter(1)
  %ar = f32[1024]{0} all-reduce(%y), to_apply=%sum
  %rs = f32[64]{0} reduce-scatter(%y), dimensions={0}
  %a2a = (f32[8,32]{1,0}, f32[8,32]{1,0}) all-to-all(%x, %x)
  %cp = u32[128]{0} collective-permute(%x), source_target_pairs={{0,1}}
  ROOT %r = f32[1024]{0} add(%ar, %ar)
}
"""
    out = RL.parse_collectives(hlo)
    assert out["all-gather"]["count"] == 1
    assert out["all-gather"]["bytes"] == 16 * 512 * 2
    assert out["all-reduce"]["wire_bytes"] == 2 * 1024 * 4
    assert out["reduce-scatter"]["bytes"] == 64 * 4
    assert out["all-to-all"]["bytes"] == 2 * 8 * 32 * 4
    assert out["collective-permute"]["bytes"] == 128 * 4
    assert out["total_wire_bytes"] > 0


def test_roofline_parser_loops():
    """Trip-count multiplication: a collective in a while body counts x trip."""
    hlo = """
%cond.1 (p: (s32[])) -> pred[] {
  %iter = s32[] get-tuple-element(%p), index=0
  %limit = s32[] constant(7)
  ROOT %lt = pred[] compare(%iter, %limit), direction=LT
}

%body.1 (p: (s32[])) -> (s32[]) {
  %y2 = f32[256]{0} parameter(1)
  %ar2 = f32[256]{0} all-reduce(%y2), to_apply=%sum
  %d = f32[8,8]{1,0} dot(%m, %m), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  ROOT %t = (s32[]) tuple(%iter)
}

ENTRY %main.2 (p0: s32[]) -> s32[] {
  %m = f32[8,8]{1,0} parameter(2)
  %w = (s32[]) while(%init), condition=%cond.1, body=%body.1
  ROOT %r = s32[] get-tuple-element(%w), index=0
}
"""
    cost = RL.hlo_cost(hlo)
    assert cost["loops"] == {"body.1": 7}
    assert cost["collectives"]["all-reduce"]["count"] == 7
    assert cost["collectives"]["all-reduce"]["wire_bytes"] == 7 * 2 * 256 * 4
    # dot: 2 * 64 out * 8 contraction * 7 trips
    assert cost["flops"] == 7 * 2 * 8 * 8 * 8


def test_roofline_terms():
    r = RL.Roofline.build(flops=197e12, hbm_bytes=819e9 / 2,
                          wire_bytes=50e9 / 4, model_flops=98.5e12)
    assert r.compute_s == pytest.approx(1.0)
    assert r.memory_s == pytest.approx(0.5)
    assert r.collective_s == pytest.approx(0.25)
    assert r.dominant == "compute"
    assert r.useful_ratio == pytest.approx(0.5)


def test_roofline_peaks_by_device_kind():
    """Peaks come from the device kind; an unknown kind raises instead of
    silently borrowing v5e's rates."""
    assert RL.peaks_for("TPU v5 lite").flops == 197e12
    with pytest.raises(ValueError, match="no published peak"):
        RL.peaks_for("TPU v9 imaginary")
    with pytest.raises(ValueError):
        RL.Roofline.build(flops=1.0, hbm_bytes=1.0, wire_bytes=0.0,
                          device_kind="cpu")


def test_cost_analysis_available():
    """cost_analysis + as_text work on this backend (the dry-run relies on
    both)."""
    def f(x, y):
        return jnp.einsum("ij,jk->ik", x, y)
    lowered = jax.jit(f).lower(
        jax.ShapeDtypeStruct((128, 128), jnp.float32),
        jax.ShapeDtypeStruct((128, 128), jnp.float32))
    compiled = lowered.compile()
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    assert cost.get("flops", 0) > 0
    assert "fusion" in compiled.as_text() or "dot" in compiled.as_text()
