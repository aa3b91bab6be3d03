"""Benchmark runner: `PYTHONPATH=src python -m benchmarks.run [names...]`.

Default (no args) runs the paper benchmarks + the kernel micro-bench and
collates any dry-run roofline JSONs under benchmarks/out/dryrun into the
roofline summary table.  Individual benchmarks: table3 fig4_6 fig8 fig9a
fig9b fig9c fig10 kernels service equal_space distributed roofline.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(__file__)
OUT_DIR = os.path.join(HERE, "out")


def bench_kernels():
    """Pallas kernel (interpret mode) vs jnp reference: correctness + the
    structural numbers the kernel claims (VMEM tile residency)."""
    import jax
    import jax.numpy as jnp
    from repro.core import sketch as sk
    from repro.core.hashing import P31
    from repro.kernels.ops import sketch_update, sketch_moments
    from repro.kernels.registry import kernel_registry

    reg = kernel_registry()
    rng = np.random.default_rng(0)
    # which registry impl auto dispatch resolves to per op on this backend
    # (what the timed use_pallas=None/True/False rows actually ran)
    out = {"resolved_impls": reg.resolution()}
    for n, t, w in [(4096, 3, 1024), (16384, 3, 4096)]:
        params = sk.make_sketch_params(rng, t)
        k1 = jnp.asarray(rng.integers(0, int(P31), size=n, dtype=np.uint32))
        k2 = jnp.asarray(rng.integers(0, int(P31), size=n, dtype=np.uint32))
        weights = jnp.ones((n,), jnp.int32)
        empty = sk.empty_counters(t, w)
        t0 = time.time()
        ref = sketch_update(empty, k1, k2, params, weights, use_pallas=False)
        ref.block_until_ready()
        t_ref = time.time() - t0
        t0 = time.time()
        pal = sketch_update(empty, k1, k2, params, weights, use_pallas=True,
                            interpret=True)
        pal.block_until_ready()
        t_pal = time.time() - t0
        match = bool(jnp.array_equal(ref, pal))
        out[f"n{n}_t{t}_w{w}"] = {"match": match, "ref_s": t_ref,
                                  "pallas_interp_s": t_pal,
                                  "backend": jax.default_backend(),
                                  "impl": reg.resolve("sketch_update").name}
        print(f"sketch_update n={n} t={t} w={w}: match={match} "
              f"(ref {t_ref:.2f}s, pallas-interpret {t_pal:.2f}s)")
        assert match
    return out


def bench_service():
    """Estimation-service numbers: fused ingest, tenant scaling, shard
    scaling, and snapshot query latency (p50/p95).

    Rows:
      ingest_fused_{S}t      fused path (one scan'd dispatch per flush,
                             fused fingerprint->sketch update), 1/4/16
                             tenants
      executor_{K}sh         core ShardedIngest executor at 1/2/4 shards
                             (shard_map over the device mesh when the host
                             exposes enough devices; deferred merge)
      snapshot_*_{N}s        query-side rows (see _query_rows): whole-group
                             snapshot x all thresholds at 1/16/64 streams,
                             fused batched engine, steady-state and
                             cold-cache
    """
    import jax
    from repro.core import sjpc
    from repro.core.sjpc import SJPCConfig
    from repro.service import ContinuousQuery, EstimationService, ServiceConfig

    from repro.kernels.registry import kernel_registry

    cfg = SJPCConfig(d=6, s=4, ratio=0.5, width=1024, depth=3, seed=11)
    rng = np.random.default_rng(0)
    out = {"backend": jax.default_backend(),
           "resolved_impls": kernel_registry().resolution()}
    records_per_tenant = 4096

    def run_pipeline(tenants, *, tag, trace_sink=None):
        svc = EstimationService(ServiceConfig(batch_rows=512, window_epochs=4,
                                              trace_sink=trace_sink))
        svc.create_group("g", cfg)
        names = [f"t{i}" for i in range(tenants)]
        for nm in names:
            svc.create_stream(nm, "g")
        batches = {nm: rng.integers(0, 1000, size=(records_per_tenant, cfg.d),
                                    dtype=np.uint32) for nm in names}

        def _block():
            # flush() enqueues async dispatches; time the compute, not the
            # enqueue (as bench_kernels does)
            jax.block_until_ready([svc.registry.stream(nm).window.total.counters
                                   for nm in names])

        # warmup: compile the (R, S, batch_rows) executable at the SAME
        # round count the measured flushes use (the scan'd dispatch is
        # shape-specialized on R)
        for nm in names:
            svc.ingest(nm, batches[nm])
        svc.flush()
        _block()
        cycles = 3
        t0 = time.time()
        for _ in range(cycles):
            for nm in names:
                svc.ingest(nm, batches[nm])
            svc.flush()
        _block()
        dt = time.time() - t0
        total = records_per_tenant * tenants * cycles
        out[tag] = {
            "tenants": tenants, "records": total,
            "seconds": dt, "records_per_sec": total / dt,
            "rounds": svc.describe()["groups"]["g"]["ingest"]["rounds"],
        }
        print(f"{tag:>18}: {total / dt:>10.0f} records/s "
              f"({total} records, {dt:.2f}s)")
        return svc, names

    trace_path = os.path.join(OUT_DIR, "trace.jsonl")
    if os.path.exists(trace_path):
        os.remove(trace_path)        # the tracer sink appends
    for tenants in (1, 4, 16):
        svc, names = run_pipeline(
            tenants, tag=f"ingest_fused_{tenants}t",
            trace_sink=trace_path if tenants == 4 else None)
        if tenants == 4:
            for nm in names:
                svc.register_continuous(
                    ContinuousQuery(f"q/{nm}", "self_join", (nm,)))
            svc.register_continuous(
                ContinuousQuery("q/join", "join", (names[0], names[1])))
            svc.poll()                       # warmup
            met = svc.obs.metrics
            hits0 = met.counter_total("query_cache_hits_total")
            miss0 = met.counter_total("query_cache_misses_total")
            lats = []
            for _ in range(30):
                t0 = time.time()
                # poll results are host floats (the service blocks on the
                # committed windows and the batch tables), so this wall
                # time is device-inclusive
                svc.poll()
                lats.append(time.time() - t0)
            lats.sort()
            hits = met.counter_total("query_cache_hits_total") - hits0
            misses = met.counter_total("query_cache_misses_total") - miss0
            out["query"] = {
                "continuous_queries": tenants + 1,
                "poll_p50_ms": 1e3 * lats[len(lats) // 2],
                "poll_p95_ms": 1e3 * lats[int(len(lats) * 0.95)],
                "poll_p99_ms": 1e3 * lats[min(int(len(lats) * 0.99),
                                              len(lats) - 1)],
                "per_query_p50_ms": 1e3 * lats[len(lats) // 2] / (tenants + 1),
                # steady-state serving: unchanged windows should be pure
                # version-keyed cache hits
                "cache_hit_rate": hits / max(hits + misses, 1.0),
                "queue_depth_peak": float(
                    met.gauge("ingest_pending_rows_peak", group="g") or 0.0),
            }
            svc.obs.tracer.close()
            out["query"]["trace_events"] = sum(
                1 for _ in open(trace_path)) if os.path.exists(
                    trace_path) else 0
            print(f"poll ({tenants + 1} standing queries): "
                  f"p50 {out['query']['poll_p50_ms']:.1f}ms "
                  f"p95 {out['query']['poll_p95_ms']:.1f}ms "
                  f"p99 {out['query']['poll_p99_ms']:.1f}ms "
                  f"cache-hit {out['query']['cache_hit_rate']:.2f} "
                  f"queue-peak {out['query']['queue_depth_peak']:.0f}")

    # --- core sharded executor: 1/2/4 shards, deferred merge -------------
    # The rows need 4 devices in THIS process.  A child process cannot
    # supply them: this process already holds the backend (on a chip host,
    # the chip itself), so the rows run here or not at all.
    if jax.device_count() >= 4:
        out.update(_executor_rows())
    else:
        print(f"executor rows skipped: {jax.device_count()} device(s), the "
              "sharded executor needs 4 (on the CPU start the run with "
              "XLA_FLAGS=--xla_force_host_platform_device_count=4)")

    out.update(_query_rows())
    return out


def _query_rows():
    """Snapshot query latency: every stream x every threshold of one hash
    group, p50/p95 over repeated snapshots, at 1/16/64 streams.

    Two snapshot modes answer the identical query set:

      snapshot_fused_{N}s       the service default -- fused batched engine
                                with the version-keyed cache shared across
                                snapshots.  Steady-state serving (standing
                                queries polling between flushes, the
                                continuous-query regime): repeated snapshots
                                of an unchanged window are cache lookups.
      snapshot_fused_cold_{N}s  same engine, cache dropped every iteration:
                                isolates the one-compiled-call batch compute
                                (stack + device put + jit'd moments/
                                inversion + host assembly).
    """
    from repro.core.sjpc import SJPCConfig
    from repro.service import EstimationService, ServiceConfig

    cfg = SJPCConfig(d=6, s=4, ratio=0.5, width=2048, depth=3, seed=11)
    svc = EstimationService(ServiceConfig(batch_rows=512, window_epochs=4))
    svc.create_group("q", cfg)
    rng = np.random.default_rng(0)
    names = [f"q{i}" for i in range(64)]
    for nm in names:
        svc.create_stream(nm, "q")
        svc.ingest(nm, rng.integers(0, 1000, size=(2048, cfg.d),
                                    dtype=np.uint32))
    svc.flush()

    def measure(make_snapshot, sub, iters=15):
        for _ in range(2):                       # compile + warm caches
            snap = make_snapshot(sub)
            for nm in sub:
                snap.all_thresholds(nm)
        lats = []
        for _ in range(iters):
            t0 = time.time()
            snap = make_snapshot(sub)
            for nm in sub:
                snap.all_thresholds(nm)
            lats.append(time.time() - t0)
        lats.sort()
        return (1e3 * lats[len(lats) // 2],
                1e3 * lats[int(len(lats) * 0.95)],
                1e3 * lats[min(int(len(lats) * 0.99), len(lats) - 1)])

    def cold_snapshot(sub):
        svc.engine._cache.clear()
        return svc.engine.snapshot(sub)

    out = {}
    thresholds = cfg.num_levels
    for n in (1, 16, 64):
        sub = names[:n]
        rows = {
            f"snapshot_fused_{n}s": lambda s: svc.engine.snapshot(s),
            f"snapshot_fused_cold_{n}s": cold_snapshot,
        }
        for tag, mk in rows.items():
            p50, p95, p99 = measure(mk, sub)
            out[tag] = {"streams": n, "thresholds": thresholds,
                        "cells": n * thresholds, "p50_ms": p50,
                        "p95_ms": p95, "p99_ms": p99}
            print(f"{tag:>24}: p50 {p50:7.2f}ms p95 {p95:7.2f}ms "
                  f"p99 {p99:7.2f}ms "
                  f"({n} streams x {thresholds} thresholds)")
    return out


def _executor_rows():
    """ShardedIngest throughput at 1/2/4 shards (run where >= 4 devices
    exist)."""
    import jax
    from repro.core import sjpc
    from repro.core.sjpc import SJPCConfig

    cfg = SJPCConfig(d=6, s=4, ratio=0.5, width=1024, depth=3, seed=11)
    rng = np.random.default_rng(0)
    params, _ = sjpc.init(cfg)
    micro, n_micro = 2048, 24
    batches = [rng.integers(0, 1000, size=(micro, cfg.d), dtype=np.uint32)
               for _ in range(n_micro)]
    out = {}
    for shards in (1, 2, 4):
        sh = sjpc.ShardedIngest(cfg, params, num_shards=shards)
        sh.ingest(batches[0])                # warmup/compile
        jax.block_until_ready(sh.deltas.counters)
        sh.reset()                           # keep the compiled step fn
        t0 = time.time()
        for b in batches:
            sh.ingest(b)
        merged = sh.merged()
        jax.block_until_ready(merged.counters)
        dt = time.time() - t0
        total = micro * n_micro
        out[f"executor_{shards}sh"] = {
            "shards": shards, "mapped": sh.mapped,
            "records": total, "seconds": dt, "records_per_sec": total / dt,
            "micro_batches": n_micro, "merges": sh.merges,
        }
        print(f"executor {shards} shard(s) "
              f"({'shard_map' if sh.mapped else 'vmap'}): "
              f"{total / dt:>10.0f} records/s ({n_micro} micro-batches, "
              f"1 merge)")
    return out


def bench_planner():
    """Planner suite (DESIGN.md §16): poll latency at 10/100/1000 standing
    queries over 8 hash groups x 8 streams (same derived config, so the
    planner fuses all touched group cohorts into ONE estimate_batch
    launch), with one group's windows churned between polls so a poll is
    never a pure cache walk.

    The CI acceptance guard reads ``p95_ratio_1000q_vs_10q`` from
    results.json and requires <= 3x: serving cost must scale with device
    launches (bounded by fusion + the plan cache), not with query count.
    """
    from repro.core.sjpc import SJPCConfig
    from repro.service import ContinuousQuery, EstimationService, ServiceConfig

    cfg = SJPCConfig(d=6, s=4, ratio=0.5, width=512, depth=2, seed=7)
    rng = np.random.default_rng(0)
    groups, per_group = 8, 8
    churn = rng.integers(0, 1000, size=(256, cfg.d), dtype=np.uint32)
    out = {}
    for n_queries in (10, 100, 1000):
        svc = EstimationService(ServiceConfig(batch_rows=256,
                                              window_epochs=None))
        names = []
        for g in range(groups):
            svc.create_group(f"g{g}", cfg)
            for s in range(per_group):
                nm = f"g{g}/s{s}"
                svc.create_stream(nm, f"g{g}")
                names.append(nm)
        for i in range(n_queries):
            svc.register_continuous(ContinuousQuery(
                f"q{i}", "self_join", (names[i % len(names)],)))
        for nm in names:
            svc.ingest(nm, churn)
        svc.flush()
        # warmup: compile + build the plan, then one churned poll so
        # the steady-state launch shape (just g0's cohort) is compiled
        # before timing starts
        svc.poll()
        svc.ingest(names[0], churn)
        svc.flush()
        svc.poll()
        lats = []
        for _ in range(15):
            # touch g0 (covered by every query count) so each measured
            # poll recomputes that cohort -- steady-state serving with
            # live ingest, not a pure cache walk
            svc.ingest(names[0], churn)
            svc.flush()
            t0 = time.time()
            svc.poll()
            lats.append(time.time() - t0)
        lats.sort()
        tag = f"poll_on_{n_queries}q"
        out[tag] = {
            "queries": n_queries, "planner": True,
            "streams": len(names), "groups": groups,
            "p50_ms": 1e3 * lats[len(lats) // 2],
            "p95_ms": 1e3 * lats[int(len(lats) * 0.95)],
        }
        print(f"{tag:>16}: p50 {out[tag]['p50_ms']:7.2f}ms "
              f"p95 {out[tag]['p95_ms']:7.2f}ms")
    out["p95_ratio_1000q_vs_10q"] = (out["poll_on_1000q"]["p95_ms"]
                                     / out["poll_on_10q"]["p95_ms"])
    print(f"p95(1000q)/p95(10q), planner on: "
          f"{out['p95_ratio_1000q_vs_10q']:.2f}x (guard <= 3.0)")
    return out


def bench_equal_space():
    """The paper's Fig. 8 as a living benchmark (DESIGN.md §13.5): replay
    one seeded planted-cluster stream through ALL served estimator kinds
    at derived (equal-space) budgets, in one hash group, and report

      * per-threshold relative error vs the exact count,
      * ingest throughput (records/s, per-kind cohort dispatch),
      * query latency (whole all-thresholds table, p50 over snapshots).

    The accuracy ordering (SJPC < reservoir at the mid band) is the
    test_paper_accuracy.py service-path contract; this row records the
    margins and the throughput cost of each estimator."""
    import jax
    from repro import estimators as E
    from repro.core import exact
    from repro.core.sjpc import SJPCConfig
    from repro.data.synthetic import planted_cluster_records
    from repro.service import EstimationService, ServiceConfig

    cfg = SJPCConfig(d=6, s=4, ratio=1.0, width=2048, depth=3, seed=17)
    n_records = 16384
    rng = np.random.default_rng(29)
    vals = planted_cluster_records(n_records, cfg.d, rng,
                                   [(4, 256, 3), (5, 192, 2), (6, 96, 1)])
    x_exact = exact.exact_pair_counts(vals)
    g_true = {s: float(x_exact[s:].sum() + n_records)
              for s in range(cfg.s, cfg.d + 1)}

    kinds = E.available()
    from repro.kernels.registry import kernel_registry
    out = {"workload": {"records": n_records, "d": cfg.d,
                        "g_true": {str(s): g for s, g in g_true.items()},
                        "sjpc_bytes": cfg.counters_bytes},
           "resolved_impls": kernel_registry().resolution()}

    # side-by-side accuracy: one service, every kind in one hash group
    svc = EstimationService(ServiceConfig(batch_rows=2048,
                                          window_epochs=None))
    svc.create_group("g", cfg)
    for kind in kinds:
        svc.create_stream(kind, "g", estimator=kind)
        svc.ingest(kind, vals)
    snap = svc.snapshot()
    for kind in kinds:
        row = snap.all_thresholds(kind)
        out[kind] = {
            "memory_bytes": svc.registry.stream(kind).estimator.memory_bytes(),
            "rel_err": {str(s): abs(r.estimate - g_true[s])
                        / max(g_true[s], 1.0)
                        for s, r in row.items()},
            # the served error bars (DESIGN.md §14): relative 1-sigma and
            # whether the 95% interval covers the exact answer
            "stderr_kind": next(iter(row.values())).stderr_kind,
            "stderr_rel": {str(s): r.stderr / max(g_true[s], 1.0)
                           for s, r in row.items()},
            "ci95_covers": {str(s): bool(abs(r.estimate - g_true[s])
                                         <= 1.96 * r.stderr)
                            for s, r in row.items()},
        }

    # per-kind ingest throughput (isolated service -> clean cohort timing)
    for kind in kinds:
        s1 = EstimationService(ServiceConfig(batch_rows=2048,
                                             window_epochs=None))
        s1.create_group("g", cfg)
        s1.create_stream("t", "g", estimator=kind)
        s1.ingest("t", vals)
        s1.flush()                                   # warmup + compile
        jax.block_until_ready(
            jax.tree_util.tree_leaves(s1.registry.stream("t").window.total))
        cycles = 2
        t0 = time.time()
        for _ in range(cycles):
            s1.ingest("t", vals)
            s1.flush()
        jax.block_until_ready(
            jax.tree_util.tree_leaves(s1.registry.stream("t").window.total))
        dt = time.time() - t0
        out[kind]["ingest_records_per_sec"] = n_records * cycles / dt

        # query latency: the full all-thresholds table, p50 over snapshots
        engine = s1.engine
        for _ in range(2):
            engine._cache.clear()
            engine.snapshot(["t"]).all_thresholds("t")
        lats = []
        for _ in range(9):
            engine._cache.clear()                    # cold: compute, not cache
            t0 = time.time()
            engine.snapshot(["t"]).all_thresholds("t")
            lats.append(time.time() - t0)
        lats.sort()
        out[kind]["query_p50_ms"] = 1e3 * lats[len(lats) // 2]
        print(f"{kind:>10}: mem {out[kind]['memory_bytes']:>7}B  "
              f"ingest {out[kind]['ingest_records_per_sec']:>9.0f} rec/s  "
              f"query p50 {out[kind]['query_p50_ms']:6.1f}ms  relerr "
              + " ".join(f"s={s}:{out[kind]['rel_err'][str(s)]:.3f}"
                         for s in range(cfg.s, cfg.d + 1)))
    return out


def bench_distributed():
    """Multi-worker ingest scale-out (DESIGN.md §18.5): the same workload
    through 1/2/4 subprocess-worker clusters; rows carry aggregate ingest
    rec/s, speedup vs the 1-worker baseline, merge p50/p95 latency, and
    replica query-freshness lag.  Worker environments are pinned
    identically (one forced host device, capped threads) so the ratios
    measure tenant sharding, not thread-count drift.  The merge-latency
    trace of the 2-worker smoke run lands next to results.json for
    artifact upload."""
    from repro.distributed import harness
    smoke = harness.run_smoke(os.path.join(OUT_DIR, "distributed_smoke.json"))
    out = harness.run_scaleout((1, 2, 4))
    out["smoke"] = {k: smoke[k] for k in
                    ("linear_exact", "worst_rel_err", "records")}
    return out


def bench_roofline():
    """Collate dry-run JSONs into the roofline summary table."""
    d = os.path.join(OUT_DIR, "dryrun")
    if not os.path.isdir(d) or not os.listdir(d):
        print("no dry-run artifacts under benchmarks/out/dryrun -- run "
              "PYTHONPATH=src python -m repro.launch.dryrun --arch all --out "
              "benchmarks/out/dryrun first")
        return {}
    rows = []
    for fn in sorted(os.listdir(d)):
        if not fn.endswith(".json"):
            continue
        with open(os.path.join(d, fn)) as f:
            rep = json.load(f)
        r = rep.get("roofline", {})
        rows.append({
            "cell": f"{rep['arch']}/{rep['shape']}/{'2pod' if rep['chips'] == 512 else '1pod'}",
            "dominant": r.get("dominant"),
            "compute_ms": round(1e3 * r.get("compute_s", 0), 2),
            "memory_ms": round(1e3 * r.get("memory_s", 0), 2),
            "collective_ms": round(1e3 * r.get("collective_s", 0), 2),
            "useful_ratio": round(r.get("useful_ratio", 0), 3),
        })
    hdr = (f"{'cell':50s} {'dom':10s} {'comp_ms':>9s} {'mem_ms':>9s} "
           f"{'coll_ms':>9s} {'useful':>7s}")
    print(hdr)
    print("-" * len(hdr))
    for r in rows:
        print(f"{r['cell']:50s} {str(r['dominant']):10s} "
              f"{r['compute_ms']:9.2f} {r['memory_ms']:9.2f} "
              f"{r['collective_ms']:9.2f} {r['useful_ratio']:7.3f}")
    return rows


def main(argv):
    os.makedirs(OUT_DIR, exist_ok=True)
    from repro.platform import enable_compile_cache
    enable_compile_cache()
    # REPRO_PLUGINS=examples.plugins adds plugin estimator kinds: suites
    # that enumerate estimators.available() (equal_space) pick them up
    # automatically, so plugin rows land in the collated report
    from repro import estimators
    estimators.load_plugins()
    from benchmarks import paper_benchmarks as PB
    names = argv or (list(PB.ALL)
                     + ["kernels", "service", "planner", "equal_space",
                        "distributed", "roofline"])
    results_path = os.path.join(OUT_DIR, "results.json")
    # merge into prior results so a partial run (e.g. `run service`) never
    # drops the other suites' rows from the collated report
    results = {}
    if os.path.exists(results_path):
        try:
            with open(results_path) as f:
                results = json.load(f)
        except (OSError, json.JSONDecodeError):
            results = {}
    for name in names:
        print(f"\n=== {name} ===")
        t0 = time.time()
        if name == "kernels":
            results[name] = bench_kernels()
        elif name == "service":
            results[name] = bench_service()
        elif name == "planner":
            results[name] = bench_planner()
        elif name == "equal_space":
            results[name] = bench_equal_space()
        elif name == "distributed":
            results[name] = bench_distributed()
        elif name == "roofline":
            results[name] = bench_roofline()
        else:
            results[name] = PB.ALL[name]()
        print(f"[{name}: {time.time() - t0:.1f}s]")
    with open(results_path, "w") as f:
        json.dump(results, f, indent=1, default=str)
    print(f"\nresults -> {results_path}")


if __name__ == "__main__":
    main(sys.argv[1:])
