import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")
# ^ MUST run before any jax import: jax locks the device count on first init.
#
# Multi-pod dry-run: lower + compile every (arch x shape) cell on the
# production mesh and extract memory / cost / collective analysis.
#
# This is the proof that the distribution config is coherent without real
# hardware: a sharding mismatch, an OOM-at-compile, or an unsupported
# collective fails the compile.  MUST be the process entry point.
#
# Usage:
#     python -m repro.launch.dryrun --arch qwen2-7b --shape train_4k
#     python -m repro.launch.dryrun --arch all --multi-pod --out out/dryrun
# (no `from __future__ import annotations` here -- the XLA_FLAGS line must
# stay the first statement, and __future__ imports can't follow it)

import argparse
import json
import sys
import time
import traceback

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from repro import configs
from repro.models import model as M
from repro.models.config import compute_dims
from repro.models.layers import split_tree
from repro.optim.adamw import make_adamw
from repro.optim.q8sharded import make_q8adam_sharded, state_pspecs as q8_specs
from repro.optim.schedules import warmup_cosine
from repro.sketchstream.monitor import SketchMonitorConfig, init_monitor
from . import roofline as RL
from . import shardings as SH
from . import serve as SV
from .mesh import make_production_mesh, batch_axes, data_shards
from .train import make_train_step, TrainState, state_shardings

# optimizer HBM decides AdamW vs Q8Adam: fp32 Adam needs 16 B/param.
Q8_THRESHOLD_BYTES = 10e9     # per chip


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _with_shardings(abstract_tree, sharding_tree):
    return jax.tree_util.tree_map(
        lambda a, s: _sds(a.shape, a.dtype, s), abstract_tree, sharding_tree)


def _src_len(seq: int) -> int:
    return max(seq // 4, 16)


def _enc_feats_spec(cfg, batch, seq, mesh):
    if not cfg.is_encdec:
        return None
    return _sds((batch, _src_len(seq), cfg.d_model), jnp.bfloat16,
                NamedSharding(mesh, PartitionSpec(batch_axes(mesh), None, None)))


def pick_optimizer(cfg, mesh, param_pspecs):
    n = cfg.param_count()
    chips = int(np.prod(list(mesh.shape.values())))
    lr = warmup_cosine(3e-4, 2000, 100_000)
    if n * 16 / chips > Q8_THRESHOLD_BYTES:
        return make_q8adam_sharded(mesh, lr, param_pspecs), "q8adam"
    return make_adamw(lr), "adamw"


def lower_train_cell(cfg, mesh, shape: configs.ShapeSpec, *,
                     monitor="deferred", remat: str = "full",
                     attn_chunk: int = 2048, ssm_chunk: int = 128,
                     seq_parallel: bool = False, probs_bf16: bool = False):
    dims = compute_dims(cfg, tp=mesh.shape["model"])
    bd = batch_axes(mesh)
    abstract_params = jax.eval_shape(
        lambda: M.init_params(jax.random.PRNGKey(0), cfg, dims))
    params_ab, axes = split_tree(abstract_params)
    pshard = SH.param_shardings(mesh, axes)
    ppspecs = SH.param_pspecs(mesh, axes)
    params_in = _with_shardings(params_ab, pshard)

    optimizer, opt_name = pick_optimizer(cfg, mesh, ppspecs)
    opt_ab = jax.eval_shape(optimizer.init, params_ab)
    if opt_name == "q8adam":
        opt_specs = SH.to_shardings(mesh, q8_specs(mesh, ppspecs))
        opt_in = _with_shardings(opt_ab, opt_specs)
    else:
        opt_in = _with_shardings(
            opt_ab, type(opt_ab)(
                step=NamedSharding(mesh, PartitionSpec()),
                m=pshard, v=pshard))

    mcfg = mparams = None
    monitor_in = None
    if monitor:
        # monitor="step" = paper-faithful per-step merge (replicated counters,
        # GSPMD all-reduces each step); "deferred" = shard-local counters
        # merged only at query time (the beyond-paper optimization).
        deferred = monitor != "step"
        mcfg = SketchMonitorConfig(shards=data_shards(mesh) if deferred else 1)
        mparams, mon_ab = init_monitor(mcfg)   # tiny concrete arrays are fine
        rep = NamedSharding(mesh, PartitionSpec())
        cspec = (NamedSharding(mesh, PartitionSpec(bd, None, None, None))
                 if deferred else rep)
        nspec = NamedSharding(mesh, PartitionSpec(bd)) if deferred else rep
        monitor_in = type(mon_ab)(
            counters=_sds(mon_ab.counters.shape, mon_ab.counters.dtype, cspec),
            n=_sds(mon_ab.n.shape, mon_ab.n.dtype, nspec),
            step=_sds((), jnp.int32, rep))

    rep = NamedSharding(mesh, PartitionSpec())
    state_in = TrainState(
        params=params_in, opt=opt_in, monitor=monitor_in,
        step=_sds((), jnp.int32, rep))

    bspec = NamedSharding(mesh, PartitionSpec(bd, None))
    batch_in = {
        "tokens": _sds((shape.batch, shape.seq), jnp.int32, bspec),
        "labels": _sds((shape.batch, shape.seq), jnp.int32, bspec),
    }
    ef = _enc_feats_spec(cfg, shape.batch, shape.seq, mesh)
    if ef is not None:
        batch_in["enc_feats"] = ef

    step_fn = make_train_step(cfg, dims, optimizer, mesh,
                              monitor_cfg=mcfg, monitor_params=mparams,
                              remat=remat, attn_chunk=attn_chunk,
                              ssm_chunk=ssm_chunk, seq_parallel=seq_parallel,
                              probs_dtype=(jnp.bfloat16 if probs_bf16
                                           else jnp.float32))
    with jax.set_mesh(mesh):
        lowered = jax.jit(step_fn).lower(state_in, batch_in)
    return lowered, {"optimizer": opt_name, "params": cfg.param_count(),
                     "active_params": cfg.active_param_count()}


def lower_prefill_cell(cfg, mesh, shape: configs.ShapeSpec, *,
                       attn_chunk: int = 2048, ssm_chunk: int = 128):
    dims = compute_dims(cfg, tp=mesh.shape["model"])
    bd = batch_axes(mesh)
    abstract_params = jax.eval_shape(
        lambda: M.init_params(jax.random.PRNGKey(0), cfg, dims))
    params_ab, axes = split_tree(abstract_params)
    params_ab = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, jnp.bfloat16)
        if a.dtype == jnp.float32 else a, params_ab)
    params_in = _with_shardings(params_ab, SH.param_shardings(mesh, axes))
    tokens = _sds((shape.batch, shape.seq), jnp.int32,
                  NamedSharding(mesh, PartitionSpec(bd, None)))
    fn = SV.make_prefill(cfg, dims, mesh, attn_chunk=attn_chunk,
                         ssm_chunk=ssm_chunk)
    ef = _enc_feats_spec(cfg, shape.batch, shape.seq, mesh)
    with jax.set_mesh(mesh):
        if ef is not None:
            lowered = jax.jit(fn).lower(params_in, tokens, ef)
        else:
            lowered = jax.jit(fn).lower(params_in, tokens)
    return lowered, {"params": cfg.param_count()}


def lower_decode_cell(cfg, mesh, shape: configs.ShapeSpec, *,
                      cache_layout: str = "auto"):
    dims = compute_dims(cfg, tp=mesh.shape["model"])
    bd = batch_axes(mesh)
    abstract_params = jax.eval_shape(
        lambda: M.init_params(jax.random.PRNGKey(0), cfg, dims))
    params_ab, axes = split_tree(abstract_params)
    params_ab = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, jnp.bfloat16)
        if a.dtype == jnp.float32 else a, params_ab)
    params_in = _with_shardings(params_ab, SH.param_shardings(mesh, axes))
    src = _src_len(shape.seq) if cfg.is_encdec else 0
    cache_ab, cache_sh = SV.cache_shardings(mesh, cfg, dims, shape.batch,
                                            shape.seq, src_len=src,
                                            layout=cache_layout)
    seq_mode = (SV.seq_sharded_mode(mesh, shape.batch)
                if cache_layout == "auto" else cache_layout == "seq")
    cache_in = _with_shardings(cache_ab, cache_sh)
    b_ax = None if seq_mode else bd
    token = _sds((shape.batch, 1), jnp.int32,
                 NamedSharding(mesh, PartitionSpec(b_ax, None)))
    fn = SV.make_decode_step(cfg, dims, mesh)
    with jax.set_mesh(mesh):
        lowered = jax.jit(fn).lower(params_in, token, cache_in)
    return lowered, {"params": cfg.param_count()}


def run_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
             monitor="deferred", remat: str = "full",
             attn_chunk: int = 2048, ssm_chunk: int = 128,
             cache_layout: str = "auto", seq_parallel: bool = False,
             probs_bf16: bool = False,
             compile_: bool = True) -> dict:
    cfg = configs.get(arch)
    shape = configs.SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = int(np.prod(list(mesh.shape.values())))
    t0 = time.time()
    if shape.kind == "train":
        lowered, meta = lower_train_cell(cfg, mesh, shape, monitor=monitor,
                                         remat=remat, attn_chunk=attn_chunk,
                                         ssm_chunk=ssm_chunk,
                                         seq_parallel=seq_parallel,
                                         probs_bf16=probs_bf16)
    elif shape.kind == "prefill":
        lowered, meta = lower_prefill_cell(cfg, mesh, shape,
                                           attn_chunk=attn_chunk,
                                           ssm_chunk=ssm_chunk)
    else:
        lowered, meta = lower_decode_cell(cfg, mesh, shape,
                                          cache_layout=cache_layout)
    t_lower = time.time() - t0

    report = {
        "arch": arch, "shape": shape_name,
        "mesh": dict(mesh.shape), "chips": chips,
        "kind": shape.kind, "lower_s": round(t_lower, 1),
        "monitor": monitor if shape.kind == "train" else None,
        "remat": remat if shape.kind == "train" else None, **meta,
    }
    if not compile_:
        return report

    t0 = time.time()
    compiled = lowered.compile()
    report["compile_s"] = round(time.time() - t0, 1)

    try:
        mem = compiled.memory_analysis()
        report["memory"] = {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_bytes": getattr(mem, "generated_code_size_in_bytes", None),
        }
    except Exception as e:                                     # CPU backend quirks
        report["memory"] = {"error": str(e)}

    tokens = shape.batch * (shape.seq if shape.kind != "decode" else 1)
    mf = RL.model_flops(cfg, tokens, train=(shape.kind == "train")) / chips
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    parsed = RL.hlo_cost(compiled.as_text())
    rl = RL.Roofline.build(parsed["flops"], parsed["hbm_bytes"],
                           parsed["total_wire_bytes"], model_flops=mf,
                           xla_flops_raw=float(cost.get("flops", 0.0)),
                           legalization_bytes=parsed["legalization_bytes"])
    report["roofline"] = rl.as_dict()
    report["collectives"] = {**parsed["collectives"],
                             "total_wire_bytes": parsed["total_wire_bytes"]}
    report["loops"] = parsed["loops"]
    return report


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--no-monitor", action="store_true")
    ap.add_argument("--monitor-mode", default="deferred",
                    choices=["deferred", "step"])
    ap.add_argument("--remat", default="full")
    ap.add_argument("--attn-chunk", type=int, default=2048)
    ap.add_argument("--cache-layout", default="auto",
                    choices=["auto", "batch", "seq"])
    ap.add_argument("--seq-parallel", action="store_true")
    ap.add_argument("--probs-bf16", action="store_true")
    ap.add_argument("--ssm-chunk", type=int, default=128)
    ap.add_argument("--tag", default=None, help="suffix for output JSON names")
    ap.add_argument("--out", default=None, help="directory for JSON reports")
    args = ap.parse_args(argv)

    archs = configs.ARCH_NAMES if args.arch == "all" else [args.arch]
    ok, failed = 0, []
    for arch in archs:
        shapes = [args.shape] if args.shape != "all" else list(configs.SHAPES)
        shapes = [s for s in shapes if configs.applicable(configs.get(arch), s)]
        if not shapes:
            print(f"[SKIP] {arch}/{args.shape}: inapplicable "
                  "(full attention, no sub-quadratic path; DESIGN.md §5)")
            continue
        for shape in shapes:
            tag = f"{arch}/{shape}/{'2pod' if args.multi_pod else '1pod'}"
            try:
                rep = run_cell(arch, shape, multi_pod=args.multi_pod,
                               monitor=(False if args.no_monitor
                                        else args.monitor_mode),
                               remat=args.remat, attn_chunk=args.attn_chunk,
                               ssm_chunk=args.ssm_chunk,
                               cache_layout=args.cache_layout,
                               seq_parallel=args.seq_parallel,
                               probs_bf16=args.probs_bf16)
                ok += 1
                print(f"[OK] {tag} lower={rep['lower_s']}s "
                      f"compile={rep.get('compile_s')}s "
                      f"dominant={rep.get('roofline', {}).get('dominant')}")
                if args.out:
                    os.makedirs(args.out, exist_ok=True)
                    fn = f"{arch}__{shape}__{'2pod' if args.multi_pod else '1pod'}.json"
                    if args.tag:
                        fn = fn.replace(".json", f"__{args.tag}.json")
                    with open(os.path.join(args.out, fn), "w") as f:
                        json.dump(rep, f, indent=1)
            except Exception:
                failed.append(tag)
                print(f"[FAIL] {tag}")
                traceback.print_exc()
    print(f"\n{ok} cells OK, {len(failed)} failed")
    if failed:
        for t in failed:
            print("  FAIL:", t)
        sys.exit(1)


if __name__ == "__main__":
    main()
