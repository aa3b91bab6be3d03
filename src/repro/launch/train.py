"""Distributed train step: forward/backward + optimizer + SJPC stream monitor.

``make_train_step(cfg, dims, mesh, ...)`` returns (step_fn, state_specs):
step_fn is jit-able with every input/output sharding pinned down, so the
same function serves the real driver (runtime/driver.py) and the dry-run
(launch/dryrun.py lowers it with ShapeDtypeStructs).

The SJPC monitor update runs under shard_map with DEVICE-LOCAL counters
(deferred merge; DESIGN.md §7.1) -- it adds zero collectives to the step.
The runnable driver lives in examples/train_lm_sketch.py (+ runtime/driver
for fault tolerance).
"""
from __future__ import annotations

import functools
from typing import Any, NamedTuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from repro.models import model as M
from repro.models.config import ArchConfig, Dims
from repro.models.layers import split_tree
from repro.optim.adamw import Optimizer, make_adamw
from repro.optim.schedules import warmup_cosine
from repro.sketchstream.monitor import (SketchMonitorConfig, MonitorState,
                                        init_monitor, monitor_update_local)
from . import shardings as SH
from .mesh import batch_axes, data_shards


class TrainState(NamedTuple):
    params: Any
    opt: Any
    monitor: Any           # MonitorState | None
    step: jax.Array


MOE_LB_WEIGHT = 0.01
MOE_Z_WEIGHT = 0.001


def make_train_step(cfg: ArchConfig, dims: Dims, optimizer: Optimizer,
                    mesh=None, *, monitor_cfg: SketchMonitorConfig | None = None,
                    monitor_params=None, remat: str = "full",
                    ssm_chunk: int = 128, attn_chunk: int = 2048,
                    compute_dtype=jnp.bfloat16, seq_parallel: bool = False,
                    probs_dtype=jnp.float32):
    """Returns step_fn(state, batch) -> (state, metrics)."""
    act_spec = (SH.activation_pspec(mesh, seq_parallel=seq_parallel)
                if mesh is not None else None)
    logits_spec = SH.logits_pspec(mesh) if mesh is not None else None
    bd = batch_axes(mesh) if mesh is not None else None

    def loss_fn(params, batch):
        logits, aux = M.forward(params, cfg, dims, batch["tokens"],
                                enc_feats=batch.get("enc_feats"),
                                compute_dtype=compute_dtype, remat=remat,
                                ssm_chunk=ssm_chunk, attn_chunk=attn_chunk,
                                act_spec=act_spec, logits_spec=logits_spec,
                                probs_dtype=probs_dtype)
        loss = M.lm_loss(logits, batch["labels"], cfg.vocab_size,
                         mask=batch.get("mask"))
        total = loss
        if cfg.num_experts:
            total = (total + MOE_LB_WEIGHT * aux["moe_lb_loss"]
                     + MOE_Z_WEIGHT * aux["moe_z_loss"])
        return total, (loss, aux)

    def update_monitor(monitor: MonitorState, tokens, step):
        if monitor_cfg is None:
            return monitor
        if mesh is None or monitor.counters.shape[0] == 1:
            # paper-faithful merged mode: counters replicated, tokens batch-
            # sharded -> GSPMD inserts the per-step all-reduce (this is the
            # baseline the deferred-merge optimization is measured against).
            c, n = monitor_update_local(monitor_cfg, monitor_params,
                                        monitor.counters[0], monitor.n[0],
                                        tokens, step)
            return MonitorState(c[None], n[None], step)

        def local(counters_blk, n_blk, tokens_blk):
            c, n = monitor_update_local(monitor_cfg, monitor_params,
                                        counters_blk[0], n_blk[0],
                                        tokens_blk, step)
            return c[None], n[None]

        c, n = jax.shard_map(
            local, mesh=mesh,
            in_specs=(PartitionSpec(bd, None, None, None),
                      PartitionSpec(bd),
                      PartitionSpec(bd, None)),
            out_specs=(PartitionSpec(bd, None, None, None),
                       PartitionSpec(bd)),
            check_vma=False,
        )(monitor.counters, monitor.n, tokens)
        return MonitorState(c, n, step)

    def step_fn(state: TrainState, batch):
        (total, (loss, aux)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(state.params, batch)
        params, opt, stats = optimizer.update(grads, state.opt, state.params)
        monitor = update_monitor(state.monitor, batch["tokens"], state.step)
        metrics = {"loss": loss, "total_loss": total, **stats}
        if cfg.num_experts:
            metrics.update({k: aux[k] for k in ("moe_lb_loss", "moe_z_loss")})
        return TrainState(params, opt, monitor, state.step + 1), metrics

    return step_fn


def make_train_state(key, cfg: ArchConfig, dims: Dims, optimizer: Optimizer,
                     *, monitor_cfg: SketchMonitorConfig | None = None):
    """Host-side init (small models / tests).  Returns (state, monitor_params,
    logical axes tree for shardings)."""
    ptree = M.init_params(key, cfg, dims)
    params, axes = split_tree(ptree)
    opt = optimizer.init(params)
    monitor = monitor_params = None
    if monitor_cfg is not None:
        monitor_params, monitor = init_monitor(monitor_cfg)
    return (TrainState(params, opt, monitor, jnp.zeros((), jnp.int32)),
            monitor_params, axes)


def state_shardings(mesh, state: TrainState, axes_tree):
    """NamedSharding tree for a TrainState (AdamW-style opt states that
    mirror params; Q8 states carry their own specs via q8sharded)."""
    pshard = SH.param_shardings(mesh, axes_tree)
    rep = NamedSharding(mesh, PartitionSpec())
    bd = batch_axes(mesh)

    # AdamW state: same tree structure as params for m/v; step scalar.
    from repro.optim.adamw import AdamWState
    if isinstance(state.opt, AdamWState):
        opt = AdamWState(step=rep,
                         m=jax.tree_util.tree_map(lambda s: s, pshard),
                         v=jax.tree_util.tree_map(lambda s: s, pshard))
    else:
        opt = jax.tree_util.tree_map(lambda _: rep, state.opt)
    mon = None
    if state.monitor is not None:
        shards = state.monitor.counters.shape[0]
        cspec = PartitionSpec(bd, None, None, None) if shards > 1 else PartitionSpec()
        nspec = PartitionSpec(bd) if shards > 1 else PartitionSpec()
        mon = MonitorState(counters=NamedSharding(mesh, cspec),
                           n=NamedSharding(mesh, nspec), step=rep)
    return TrainState(params=pshard, opt=opt, monitor=mon, step=rep)
