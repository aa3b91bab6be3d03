"""Q8Adam under shard_map on the debug mesh + elastic checkpoint restore
with target shardings."""
import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from repro.checkpoint import save_checkpoint, restore_checkpoint
from repro.launch.mesh import make_debug_mesh
from repro.optim.q8sharded import make_q8adam_sharded, state_pspecs
from repro.optim.adamw import make_adamw
from repro.optim.schedules import constant


def _params():
    rng = np.random.default_rng(0)
    return {"w": jnp.asarray(rng.normal(size=(64, 32)).astype(np.float32)),
            "b": jnp.zeros((32,), jnp.float32)}


def _pspecs():
    return {"w": PartitionSpec(None, None), "b": PartitionSpec(None)}


def test_q8_sharded_matches_unsharded_semantics():
    """shard_map Q8 on a 1x1 mesh must track plain AdamW like q8adam does."""
    mesh = make_debug_mesh(1, 1)
    params = _params()
    target = jnp.ones_like(params["w"])

    def grad_fn(p):
        return {"w": 2 * (p["w"] - target), "b": p["b"] * 0}

    opt = make_q8adam_sharded(mesh, constant(0.05), _pspecs(),
                              weight_decay=0.0)
    ref = make_adamw(constant(0.05), weight_decay=0.0)
    with jax.set_mesh(mesh):
        s_q = opt.init(params)
        s_r = ref.init(params)
        p_q, p_r = params, dict(params)
        for _ in range(60):
            p_q, s_q, _ = jax.jit(opt.update)(grad_fn(p_q), s_q, p_q)
            p_r, s_r, _ = jax.jit(ref.update)(grad_fn(p_r), s_r, p_r)
    err_q = float(jnp.abs(p_q["w"] - target).mean())
    err_r = float(jnp.abs(p_r["w"] - target).mean())
    assert err_q < 0.25, err_q
    assert abs(err_q - err_r) < 0.15, (err_q, err_r)


def test_restore_with_target_shardings(tmp_path):
    """Elastic restore: checkpoint written chunked, restored with explicit
    NamedShardings (the restore-onto-a-different-mesh path)."""
    mesh = make_debug_mesh(1, 1)
    tree = _params()
    save_checkpoint(str(tmp_path), 4, tree, chunks=8)
    shardings = {
        "w": NamedSharding(mesh, PartitionSpec("data", None)),
        "b": NamedSharding(mesh, PartitionSpec()),
    }
    restored, man = restore_checkpoint(str(tmp_path), tree, shardings=shardings)
    assert man.step == 4
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.asarray(tree["w"]))
    assert restored["w"].sharding == shardings["w"]
