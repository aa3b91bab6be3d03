"""Q8Adam under shard_map: fully-local int8 moment update (ZeRO-style).

Each device dequantizes / updates / requantizes only ITS shard of every
parameter: zero collectives inside the optimizer (gradients are already
reduced by the backward pass; global-norm clipping happens outside).  The
int8 codes live as (total_shards * nblk_local, 256) arrays with dim0 sharded
across the whole mesh -- 2.03 B/param of optimizer HBM regardless of
topology, which is what fits jamba-398B training on one 256-chip pod.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec


from .adamw import Optimizer, clip_by_global_norm
from .q8adam import quantize, dequantize, quantize_v, dequantize_v, QTensor


class Q8State(NamedTuple):
    step: jax.Array
    m: dict
    v: dict


def _all_axes(mesh) -> tuple:
    return tuple(mesh.axis_names)


def state_pspecs(mesh, param_pspecs):
    """PartitionSpec tree for the Q8 state mirroring a param pspec tree."""
    qspec = QTensor(codes=PartitionSpec(_all_axes(mesh), None),
                    scales=PartitionSpec(_all_axes(mesh), None))
    is_ps = lambda x: isinstance(x, PartitionSpec)
    return Q8State(
        step=PartitionSpec(),
        m=jax.tree_util.tree_map(lambda _: qspec, param_pspecs, is_leaf=is_ps),
        v=jax.tree_util.tree_map(lambda _: qspec, param_pspecs, is_leaf=is_ps))


def make_q8adam_sharded(mesh, lr_fn, param_pspecs, *, b1=0.9, b2=0.95,
                        eps=1e-8, weight_decay=0.1, clip_norm=1.0,
                        seed=23) -> Optimizer:
    axes = _all_axes(mesh)
    sspecs = state_pspecs(mesh, param_pspecs)
    smap = functools.partial(jax.shard_map, mesh=mesh, check_vma=False)

    def local_init(params):
        qm = lambda p: quantize(jnp.zeros(p.shape, jnp.float32))
        qv = lambda p: quantize_v(jnp.zeros(p.shape, jnp.float32))
        return Q8State(step=jnp.zeros((), jnp.int32),
                       m=jax.tree_util.tree_map(qm, params),
                       v=jax.tree_util.tree_map(qv, params))

    def init(params):
        return smap(local_init, in_specs=(param_pspecs,), out_specs=sspecs)(params)

    def local_update(grads, state, params, lr, rkey):
        step = state.step + 1
        bc1 = 1.0 - b1 ** step.astype(jnp.float32)
        bc2 = 1.0 - b2 ** step.astype(jnp.float32)
        leaves, treedef = jax.tree_util.tree_flatten(params)
        gl = treedef.flatten_up_to(grads)
        ml = treedef.flatten_up_to(state.m)
        vl = treedef.flatten_up_to(state.v)
        new_p, new_m, new_v = [], [], []
        for i, (p, g, mq, vq) in enumerate(zip(leaves, gl, ml, vl)):
            g = g.astype(jnp.float32)
            m = b1 * dequantize(mq, p.shape) + (1 - b1) * g
            v = b2 * dequantize_v(vq, p.shape) + (1 - b2) * g * g
            delta = (m / bc1) / (jnp.sqrt(v / bc2) + eps)
            if p.ndim > 1:
                delta = delta + weight_decay * p.astype(jnp.float32)
            new_p.append((p - lr * delta).astype(p.dtype))
            new_m.append(quantize(m, jax.random.fold_in(rkey, 2 * i)))
            new_v.append(quantize_v(v, jax.random.fold_in(rkey, 2 * i + 1)))
        return (treedef.unflatten(new_p),
                Q8State(step, treedef.unflatten(new_m), treedef.unflatten(new_v)))

    def update(grads, state, params):
        grads, gnorm = clip_by_global_norm(grads, clip_norm)
        lr = lr_fn(state.step + 1)
        rkey = jax.random.fold_in(jax.random.PRNGKey(seed), state.step)
        new_params, new_state = smap(
            local_update,
            in_specs=(param_pspecs, sspecs, param_pspecs,
                      PartitionSpec(), PartitionSpec()),
            out_specs=(param_pspecs, sspecs),
        )(grads, state, params, lr, rkey)
        return new_params, new_state, {"grad_norm": gnorm, "lr": lr}

    return Optimizer(init=init, update=update)
