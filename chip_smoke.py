"""On-chip smoke test: the estimation service and a full-width monitored
train step, each through the entry points its users call, on a TPU.

    python chip_smoke.py              # phases A and B on one chip
    python chip_smoke.py --chips 4    # phase C only, on four chips

Phase A  ``EstimationService`` at deployment size: one hash group at the
         paper defaults (d=6, s=3, w=1024, t=3), 4096 SJPC tenants plus 64
         reservoir and 64 LSH-SS tenants at the group's equal space, 4-epoch
         windows, 512-row rounds of ``dblp_like`` records; two flushes, an
         epoch rotation, a third flush, 72 standing queries and three polls.
         Checked against a per-level ``sjpc.update`` replay (bit-equal
         counters), the per-stream numpy query oracle (1e-6), the jnp
         all-pairs oracle (bit-equal histograms) and the exact pair count
         (within 3 reported stderr).
Phase B  ``make_train_step`` for mamba2-370m at its published widths with the
         SJPC monitor inside, AdamW, run for 3 steps by ``TrainDriver``.
         Checked: finite loss, no restart, monitor counters bit-equal to a
         standalone ``monitor_update_local`` replay of the same batches.
Phase C  ``sjpc.ShardedIngest`` with one shard per chip: the merged counters
         bit-equal to the same slices and keys replayed on one chip.

Every phase prints one ``PHASE {...}`` line of numbers.  The last line of
standard output is ``{"ok": true, "device": {...}}``; it is printed only when
every phase and every check passed.  Without a TPU, with REPRO_KERNEL_IMPL
set, or outside a checkout of this repository the script exits non-zero and
prints no result.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

SERVICE_TENANTS = 4096          # sjpc tenants (240 KiB of window each)
SAMPLE_TENANTS = 64             # reservoir and lsh_ss tenants, each
ROWS = 512                      # records per tenant per flush (= one round)
FLUSHES = 3
SELF_QUERIES = 64               # all-thresholds standing queries
JOIN_QUERIES = 8
REPLAY_TENANTS = 64             # sjpc tenants replayed through sjpc.update
EXACT_TENANTS = 8               # tenants checked against core.exact

TRAIN_ARCH = "mamba2-370m"
TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 4, 1024, 3


class SmokeFailure(RuntimeError):
    """A check of the smoke test did not hold."""


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


class CompileClock:
    """Seconds JAX spent in backend compiles (persistent-cache reads
    included), and how many compiles the persistent cache answered."""

    def __init__(self, jax):
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def phase_line(name: str, device, clock, t0: float, c0: float, h0: int,
               **numbers) -> None:
    print("PHASE " + json.dumps({
        "phase": name, "device_kind": device.device_kind,
        "wall_s": time.perf_counter() - t0,
        "compile_s": clock.seconds - c0,
        "compile_cache_hits": clock.cache_hits - h0, **numbers}),
        flush=True)


# ---------------------------------------------------------------------------
# phase A: the estimation service
# ---------------------------------------------------------------------------

def _kernel_impls() -> dict:
    """{kernel: {impl, ...}} from kernel_dispatch_total."""
    from repro.obs import default_registry
    seen: dict = {}
    for key in default_registry().series("kernel_dispatch_total"):
        labels = dict(key)
        seen.setdefault(labels["kernel"], set()).add(labels["impl"])
    return seen


def _flush_runs_kernel(svc, group: str, n_streams: int) -> bool:
    """Compile the flush program of the group's sjpc cohort at the shapes
    the flushes used and look for the Mosaic kernel in it (ingest bypasses
    the kernel registry, so dispatch counters cannot show it)."""
    import jax
    import jax.numpy as jnp
    from repro.service.ingest import ingest_key_grid, multi_round_update
    g = svc.registry.group(group)
    cfg, est = g.cfg, g.estimator("sjpc")
    L, t, w = cfg.num_levels, cfg.depth, cfg.width
    sds = jax.ShapeDtypeStruct
    keys = jax.eval_shape(ingest_key_grid, jnp.uint32(0),
                          sds((n_streams,), jnp.int32),
                          sds((1, n_streams), jnp.int32))
    compiled = multi_round_update.lower(
        cfg, est.params, sds((n_streams, L, t, w), jnp.int32),
        sds((n_streams,), jnp.float32), sds((n_streams,), jnp.int32),
        sds((1, n_streams, ROWS, cfg.d), jnp.uint32),
        sds((1, n_streams, ROWS), jnp.int32), keys).compile()
    return "tpu_custom_call" in compiled.as_text()


def _close(a: float, b: float, rtol: float = 1e-6) -> bool:
    return abs(a - b) <= rtol * max(abs(b), 1.0)


def phase_service(seed: int, *, tenants: int = SERVICE_TENANTS,
                  sample_tenants: int = SAMPLE_TENANTS,
                  self_queries: int = SELF_QUERIES,
                  join_queries: int = JOIN_QUERIES,
                  replay_tenants: int = REPLAY_TENANTS,
                  exact_tenants: int = EXACT_TENANTS) -> dict:
    """Run phase A; returns its numbers (raises SmokeFailure on a failed
    check)."""
    import jax
    import jax.numpy as jnp
    from repro.configs import PAPER_DEFAULTS as cfg
    from repro.core import exact, sjpc
    from repro.data.synthetic import dblp_like
    from repro.kernels import ops, ref
    from repro.service import (ContinuousQuery, EstimationService,
                               ServiceConfig, ingest_key, reference_snapshot)

    t_setup = time.perf_counter()
    svc = EstimationService(ServiceConfig(batch_rows=ROWS, window_epochs=4))
    svc.create_group("g", cfg)
    sjpc_names = [f"sjpc{i:05d}" for i in range(tenants)]
    res_names = [f"res{i:03d}" for i in range(sample_tenants)]
    lsh_names = [f"lsh{i:03d}" for i in range(sample_tenants)]
    for nm in sjpc_names:
        svc.create_stream(nm, "g")
    for nm in res_names:
        svc.create_stream(nm, "g", estimator="reservoir")
    for nm in lsh_names:
        svc.create_stream(nm, "g", estimator="lsh_ss")
    names = sjpc_names + res_names + lsh_names
    data = {nm: dblp_like(ROWS * FLUSHES, d=cfg.d, seed=seed * 1_000_003 + i)
            for i, nm in enumerate(names)}

    rng = np.random.default_rng(seed)
    quarter = self_queries // 4
    targets = (list(rng.choice(sjpc_names, self_queries - 2 * quarter,
                               replace=False))
               + list(rng.choice(res_names, quarter, replace=False))
               + list(rng.choice(lsh_names, quarter, replace=False)))
    pairs = rng.choice(sjpc_names, 2 * join_queries, replace=False)
    queries = ([ContinuousQuery(f"all/{nm}", "all_thresholds", (nm,))
                for nm in targets]
               + [ContinuousQuery(f"join/{a}/{b}", "join", (a, b))
                  for a, b in zip(pairs[::2], pairs[1::2])])
    for q in queries:
        svc.register_continuous(q)
    setup_s = time.perf_counter() - t_setup

    flush_s, poll_s = [], []
    out = None
    for f in range(FLUSHES):
        if f == FLUSHES - 1:
            svc.advance_epoch()
        for nm in names:
            svc.ingest(nm, data[nm][f * ROWS:(f + 1) * ROWS])
        t0 = time.perf_counter()
        svc.flush()
        flush_s.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        out = svc.poll()
        poll_s.append(time.perf_counter() - t0)

    # -- the kernels ran compiled ------------------------------------------
    impls = _kernel_impls()
    for op in ("fused_query", "fused_pairs"):
        check(impls.get(op) == {"pallas_tpu"},
              f"{op} dispatched to {impls.get(op)}, not only pallas_tpu")
    check(all(v == {"pallas_tpu"} for v in impls.values()),
          f"a kernel fell back off pallas_tpu: {impls}")
    check(_flush_runs_kernel(svc, "g", tenants),
          "the flush program holds no tpu_custom_call (fused ingest kernel)")

    # -- window counters == per-level reference replay ---------------------
    params = svc.registry.group("g").params
    update = jax.jit(lambda st, v, m, k: sjpc.update(cfg, params, st, v,
                                                     key=k, row_mask=m))
    ones = jnp.ones((ROWS,), jnp.int32)
    for nm in rng.choice(sjpc_names, replay_tenants, replace=False):
        entry = svc.registry.stream(nm)
        want = sjpc.init(cfg)[1]
        for r in range(FLUSHES):
            want = update(want, data[nm][r * ROWS:(r + 1) * ROWS], ones,
                          ingest_key(cfg, entry.uid, r))
        got = entry.window.window_state()
        check(np.array_equal(np.asarray(got.counters),
                             np.asarray(want.counters)),
              f"{nm}: window counters differ from the sjpc.update replay")
        check(float(got.n) == float(want.n) == ROWS * FLUSHES,
              f"{nm}: window n {float(got.n)} != {ROWS * FLUSHES}")

    # -- reservoir histograms == jnp oracle ---------------------------------
    states = [svc.registry.stream(nm).window.window_state()
              for nm in res_names]
    items = jnp.stack([s.items for s in states])
    valid = jnp.stack([(s.tags >= 0).astype(jnp.int32) for s in states])
    check(np.array_equal(np.asarray(ops.fused_pairs(items, valid)),
                         np.asarray(ref.fused_pairs_ref(items, valid))),
          "reservoir histograms differ from fused_pairs_ref")

    # -- served tables == per-stream numpy oracle (1e-6) ---------------------
    oracle = reference_snapshot(
        svc.registry, sorted({s for q in queries for s in q.streams}))
    worst = 0.0
    for q in queries:
        res = out[q.name]
        served = (res if q.kind == "all_thresholds" else {res.s: res})
        for s, r in served.items():
            check(not r.stale, f"{q.name}: stale result served")
            o = (oracle.self_join(q.streams[0], s) if q.kind != "join"
                 else oracle.join(*q.streams, s))
            for a, b in ((r.estimate, o.estimate), (r.stderr, o.stderr)):
                check(_close(a, b),
                      f"{q.name} s={s}: served {a} vs oracle {b}")
                worst = max(worst, abs(a - b) / max(abs(b), 1.0))

    # -- estimates within 3 stderr of the exact count -----------------------
    sjpc_targets = [nm for nm in targets if nm.startswith("sjpc")]
    for nm in sjpc_targets[:exact_tenants]:
        for s, r in out[f"all/{nm}"].items():
            g = exact.exact_g(data[nm], s)
            check(abs(r.estimate - g) <= 3 * r.stderr,
                  f"{nm} s={s}: estimate {r.estimate} vs exact {g} "
                  f"(stderr {r.stderr})")

    records = len(names) * ROWS * FLUSHES
    return {"sjpc_tenants": tenants, "sample_tenants": 2 * sample_tenants,
            "records": records, "setup_s": setup_s, "flush_s": flush_s,
            "poll_s": poll_s, "queries": len(queries),
            "replayed_tenants": replay_tenants,
            "exact_checked_tenants": min(exact_tenants, len(sjpc_targets)),
            "worst_rel_diff_vs_oracle": worst,
            "kernel_impls": {k: sorted(v) for k, v in impls.items()}}


# ---------------------------------------------------------------------------
# phase B: a monitored train step at published width
# ---------------------------------------------------------------------------

def phase_train(seed: int, *, arch: str = TRAIN_ARCH, batch: int = TRAIN_BATCH,
                seq: int = TRAIN_SEQ, steps: int = TRAIN_STEPS,
                reduced: bool = False) -> dict:
    import jax
    import jax.numpy as jnp
    from repro import configs
    from repro.data.loader import token_batches
    from repro.launch.mesh import make_debug_mesh
    from repro.launch.train import make_train_state, make_train_step
    from repro.models.config import compute_dims
    from repro.optim import make_adamw
    from repro.optim.schedules import constant
    from repro.runtime import DriverConfig, TrainDriver
    from repro.sketchstream.monitor import (SketchMonitorConfig,
                                            monitor_update_local)

    cfg = configs.reduced(arch) if reduced else configs.get(arch)
    dims = compute_dims(cfg, tp=1)
    mesh = make_debug_mesh(1, 1)
    mcfg = SketchMonitorConfig()
    opt = make_adamw(constant(1e-4))
    state, mparams, _ = make_train_state(jax.random.PRNGKey(seed), cfg, dims,
                                         opt, monitor_cfg=mcfg)
    n_params = sum(int(x.size) for x in jax.tree_util.tree_leaves(state.params))
    step = make_train_step(cfg, dims, opt, mesh, monitor_cfg=mcfg,
                           monitor_params=mparams, remat="full")

    # compile first; cut the batch, then the sequence, only if the compiled
    # step does not fit the device
    device = jax.devices()[0]
    limit = (device.memory_stats() or {}).get("bytes_limit")
    cuts = []
    while True:
        tok = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
        with jax.set_mesh(mesh):
            compiled = jax.jit(step).lower(
                state, {"tokens": tok, "labels": tok}).compile()
        ma = compiled.memory_analysis()
        need = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
        if limit is None or need <= limit or (batch == 1 and seq <= 128):
            break
        cuts.append({"batch": batch, "seq": seq, "need_bytes": need})
        if batch > 1:
            batch //= 2
        else:
            seq //= 2
        print(f"train step needs {need} bytes > {limit}: cut to batch "
              f"{batch} x seq {seq}", flush=True)

    gen = token_batches(batch, seq, cfg.vocab_size, seed=seed,
                        dup_fraction=0.2)
    batches: list = []

    def make_batch(i):                       # deterministic in the step
        while len(batches) <= i:
            batches.append(next(gen))
        return {k: jnp.asarray(v) for k, v in batches[i].items()}

    with tempfile.TemporaryDirectory(prefix="chip_smoke_ckpt_") as ckpt:
        driver = TrainDriver(
            compiled, state, make_batch,
            DriverConfig(ckpt_dir=ckpt, ckpt_every=10 * steps, log_every=1,
                         sketch_log_every=10 * steps, max_restarts=0),
            monitor_cfg=mcfg)
        t0 = time.perf_counter()
        driver.run(steps)
        run_s = time.perf_counter() - t0
    check(driver.restarts == 0, f"driver restarted {driver.restarts} times")
    losses = [m["loss"] for m in driver.metrics_log]
    check(len(losses) == steps and all(np.isfinite(losses)),
          f"non-finite or missing losses: {losses}")

    replay = jax.jit(lambda c, n, toks, i: monitor_update_local(
        mcfg, mparams, c, n, toks, i))
    c = jnp.zeros(driver.state.monitor.counters.shape[1:], jnp.int32)
    n = jnp.zeros((), jnp.float32)
    for i in range(steps):
        c, n = replay(c, n, make_batch(i)["tokens"], jnp.int32(i))
    check(np.array_equal(np.asarray(driver.state.monitor.counters[0]),
                         np.asarray(c)),
          "monitor counters differ from the monitor_update_local replay")
    check(float(driver.state.monitor.n[0]) == float(n) == steps * batch,
          f"monitor n {float(driver.state.monitor.n[0])} != {steps * batch}")

    stats = device.memory_stats() or {}
    return {"arch": cfg.name, "params": n_params, "batch": batch, "seq": seq,
            "steps": steps, "cuts": cuts, "compiled_need_bytes": need,
            "run_s": run_s, "step_s": [m["dt"] for m in driver.metrics_log],
            "loss": losses, "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
            "bytes_limit": limit}


# ---------------------------------------------------------------------------
# phase C: sharded ingest over four chips
# ---------------------------------------------------------------------------

def phase_sharded(seed: int, *, shards: int = 4, micro: int = 8192,
                  micro_batches: int = 8) -> dict:
    import functools

    import jax
    import jax.numpy as jnp
    from repro.configs import PAPER_DEFAULTS as cfg
    from repro.core import sjpc
    from repro.data.synthetic import dblp_like

    devices = jax.devices()[:shards]
    check(len(devices) == shards, f"{len(devices)} devices, need {shards}")
    params, _ = sjpc.init(cfg)
    sh = sjpc.ShardedIngest(cfg, params, num_shards=shards, devices=devices)
    check(sh.mapped, "ShardedIngest did not map its shards onto the devices")
    batches = [dblp_like(micro, d=cfg.d, seed=seed * 1_000_003 + m)
               for m in range(micro_batches)]
    t0 = time.perf_counter()
    for b in batches:
        sh.ingest(b)
    merged = sh.merged()
    jax.block_until_ready(merged.counters)
    ingest_s = time.perf_counter() - t0
    placed = {s.device: s.data.shape for s in sh.deltas.counters.addressable_shards}
    check(set(placed) == set(devices)
          and all(shape[0] == 1 for shape in placed.values()),
          f"shard deltas not one per device: {placed}")

    # replay on devices[0], the default device every input lands on
    update = jax.jit(lambda st, v, m, k: sjpc.update(cfg, params, st, v,
                                                     key=k, row_mask=m))
    per = micro // shards
    ones = jnp.ones((per,), jnp.int32)
    accs = [sjpc.init(cfg)[1] for _ in range(shards)]
    for m, b in enumerate(batches):
        for j in range(shards):
            accs[j] = update(accs[j], b[j * per:(j + 1) * per], ones,
                             sh.shard_key(m, j))
    want = functools.reduce(sjpc.merge, accs)
    check(np.array_equal(np.asarray(merged.counters), np.asarray(want.counters)),
          "merged sharded counters differ from the one-chip replay")
    check(float(merged.n) == float(want.n) == micro * micro_batches,
          f"merged n {float(merged.n)} != {micro * micro_batches}")
    return {"shards": shards, "mapped": sh.mapped,
            "records": micro * micro_batches, "ingest_s": ingest_s,
            "devices": [str(d) for d in devices]}


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only phase C (sharded ingest) on 4 chips")
    args = ap.parse_args(argv)

    if os.environ.get("REPRO_KERNEL_IMPL"):
        print("REPRO_KERNEL_IMPL is set: the smoke must run the kernels auto "
              "dispatch picks, not a forced implementation", file=sys.stderr)
        return 2
    if not os.path.isdir(os.path.join(HERE, "src", "repro")):
        print(f"no src/repro next to {__file__}: run from a checkout",
              file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(HERE, "src"))

    import jax
    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"no TPU: JAX's first device is {device.platform!r}",
              file=sys.stderr)
        return 2
    from repro.platform import enable_compile_cache
    cache_dir = enable_compile_cache()        # before the first compile
    if len(jax.devices()) < args.chips:
        print(f"{len(jax.devices())} device(s), --chips {args.chips} needs "
              f"{args.chips}", file=sys.stderr)
        return 2
    from repro.kernels.registry import PALLAS_TPU, kernel_registry
    resolution = kernel_registry().resolution()
    if set(resolution.values()) != {PALLAS_TPU}:
        print(f"auto dispatch does not resolve to {PALLAS_TPU}: {resolution}",
              file=sys.stderr)
        return 2
    print(f"device {device.device_kind} x{len(jax.devices())}, compile cache "
          f"{cache_dir}", flush=True)

    clock = CompileClock(jax)
    phases = ([("C", phase_sharded)] if args.chips == 4
              else [("A", phase_service), ("B", phase_train)])
    for name, fn in phases:
        t0, c0, h0 = time.perf_counter(), clock.seconds, clock.cache_hits
        numbers = fn(args.seed)
        phase_line(name, device, clock, t0, c0, h0, **numbers)
        jax.clear_caches()

    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
