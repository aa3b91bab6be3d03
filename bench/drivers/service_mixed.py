"""The driver of the mixed-estimator service cells: SJPC tenants beside the
paper's equal-space competitors, with traffic and standing queries aimed
at the competitors.

One ``EstimationService`` is built as the configuration states: one hash
group, then the tenants of each estimator kind in the order of
``tenants`` (uids 0, 1, ... in that order), every kind at the group's
equal space (no ``estimator_cfg``).  Set-up checks the sizes the program
derived against the configuration's ``sizes`` and ``bootstrap``.  The
traffic mix names the kinds it aims at (``kinds``): the generator's
tenants are those kinds' tenants, numbered kind by kind, and the mix's
pick is handed the size of each kind's block (``blocks``).  The loop is
the one of ``service.py``:

    prefill   each queried tenant submits ``prefill_records``, one flush
    cycle     the tenants the pick names each submit their records, then
              the ops of ``cycle.ops`` run in order (``{"op": name,
              "every": k}`` runs one on every k-th cycle only)

Set-up runs ``warmup_cycles`` cycles, which compile every shape the
window uses; the ops named in ``cycle.timed`` are timed on the host clock.

After the window closes the plain reference (``bench/reference/mixed.py``)
replays every submission, commit and epoch advance of the tenants it
checks:

- ``sample_slots_differing``: the kept items, the tags of every slot and
  ``n`` of a seeded sample of the reservoir tenants that submitted in the
  window (``compare.tenants`` of all kinds together), bit for bit;
- ``lsh_state_differing``: the same of the LSH-SS tenants in the sample:
  bucket counts, record sample and buckets, both pair reservoirs, the
  candidates each stratum saw, and ``n``;
- every answer served in the window, estimate and standard error at
  every threshold, against the reference's float64 answers of its own
  windows at that point.
"""
from __future__ import annotations

import copy
import gc
import pathlib
import sys
import time

import numpy as np

from bench import generate
from bench.harness import Run, load_module

_SERVICE = load_module(pathlib.Path(__file__).resolve().parent / "service.py")
COUNTERS = _SERVICE.COUNTERS + ("bootstrap_replicates_total",)


def _check_sizes(svc, conf: dict) -> None:
    """The equal-space sizes the program derived are the stated ones."""
    group = svc.registry.group("g")
    res, lsh = group.estimator("reservoir"), group.estimator("lsh_ss")
    got = {"reservoir": {"capacity": res.cfg.capacity},
           "lsh_ss": {k: getattr(lsh.cfg, k) for k in conf["sizes"]["lsh_ss"]}}
    boot = {"replicates": res.bootstrap, "item_cap": res.bootstrap_cap}
    if (got != conf["sizes"] or boot != conf["bootstrap"]
            or lsh.bootstrap != boot["replicates"]):
        raise RuntimeError(f"derived sizes {got} and bootstrap {boot} "
                           f"(LSH-SS {lsh.bootstrap}) are not the stated "
                           f"{conf['sizes']} and {conf['bootstrap']}")


def _fused_pairs_dispatch(metrics) -> dict:
    """impl -> ``kernel_dispatch_total`` of the ``fused_pairs`` kernel."""
    out = {}
    for key, value in metrics.series("kernel_dispatch_total").items():
        labels = dict(key)
        if labels["kernel"] == "fused_pairs":
            out[labels["impl"]] = value
    return out


def run(cell, *, seed: int, seconds: float, window, started: float,
        reference) -> Run:
    """One run of a mixed-estimator service cell."""
    import jax
    from repro.core.sjpc import SJPCConfig
    from repro.service import (ContinuousQuery, EstimationService,
                               ServiceConfig)

    conf = cell.config
    traffic = copy.deepcopy(cell.traffic)
    cyc = traffic["cycle"]
    if "sjpc" in traffic["kinds"]:
        raise ValueError("the mixed reference answers the sample kinds "
                         "only; SJPC traffic belongs to the service driver")
    sketch = conf["sketch"]
    d = int(sketch["d"])
    ops = [(o, 1) if isinstance(o, str) else (o["op"], int(o["every"]))
           for o in cyc["ops"]]
    ops = [(op, every, load_module(cell.root / "bench" / "ops"
                                   / f"{op}.py").run) for op, every in ops]

    sink = _SERVICE.SpanSink() if window.trace else None
    svc = EstimationService(ServiceConfig(
        **conf["service"], trace_annotate=window.trace, trace_sink=sink))
    svc.create_group("g", SJPCConfig(**sketch))
    names, kinds, blocks = [], {}, {}
    for kind, count in conf["tenants"].items():
        blocks[kind] = []
        for _ in range(int(count)):
            uid = len(names)
            names.append(f"t{uid:05d}")
            if svc.create_stream(names[uid], "g", estimator=kind).uid != uid:
                raise RuntimeError(f"stream {names[uid]} did not get uid "
                                   f"{uid}")
            kinds[uid] = kind
            blocks[kind].append(uid)
    _check_sizes(svc, conf)

    # the generator's tenant p is the aimed tenant uid aimed[p]
    aimed = [u for kind in traffic["kinds"] for u in blocks[kind]]
    cyc["tenants"]["blocks"] = [len(blocks[k]) for k in traffic["kinds"]]
    plan = generate.plan(traffic, tenants=len(aimed), d=d, seed=seed,
                         root=cell.root)
    queried = [aimed[int(p)] for p in plan.self_tenants]
    for i, uid in enumerate(queried):
        svc.register_continuous(ContinuousQuery(f"all{i}", "all_thresholds",
                                                (names[uid],)))

    # every event the windows saw, in order -- the reference replays them:
    # ("submit", uid, first pool row, count), ("commit",), ("advance",)
    history: list = []

    def submit(p, count):
        start, count = plan.take(int(p), int(count))
        svc.ingest(names[aimed[int(p)]], plan.records(start, count))
        history.append(("submit", aimed[int(p)], start, count))

    def effects(out):
        if out.get("commit"):
            history.append(("commit",))
        if out.get("advance"):
            history.append(("advance",))

    if plan.prefill:
        for p in plan.self_tenants:
            submit(p, plan.prefill)
        svc.flush()
        effects({"commit": True})

    timed = set(cyc.get("timed", ()))
    latencies: dict = {op: [] for op in timed}
    served: list = []          # (events so far, answers) of each poll

    def cycle(c: int, measure: bool) -> None:
        tenants, counts = plan.cycle(c)
        with window.mark("bench.submit"):
            for p, m in zip(tenants, counts):
                submit(p, m)
        for op, every, run_op in ops:
            if c % every != every - 1:
                continue
            with window.mark(f"bench.{op}"):
                t0 = time.perf_counter()
                out = run_op(svc)
                dt = time.perf_counter() - t0
            effects(out)
            if measure and op in timed:
                latencies[op].append(dt)
            if measure and "answers" in out:
                served.append((len(history),
                               _SERVICE._answers(out["answers"])))

    warmup = int(traffic["warmup_cycles"])
    for c in range(warmup):
        cycle(c, measure=False)
    c = warmup
    metrics = svc.obs.metrics
    before = {n: metrics.counter_total(n) for n in COUNTERS}
    pairs_before = _fused_pairs_dispatch(metrics)
    first_window_event = len(history)
    with window:
        while window.elapsed() < seconds:
            cycle(c, measure=True)
            c += 1
    window_s = window.seconds
    setup_s = window.wall0 - started
    counters = {n: metrics.counter_total(n) - before[n] for n in COUNTERS}
    pairs = {impl: v - pairs_before.get(impl, 0.0) for impl, v in
             _fused_pairs_dispatch(metrics).items()
             if v != pairs_before.get(impl, 0.0)}
    counters.update({f"fused_pairs_dispatch.{impl}": v
                     for impl, v in pairs.items()})
    print(f"fused_pairs dispatches in the window by implementation: {pairs}",
          file=sys.stderr)
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")

    # -- read back what the comparison needs, then free the service -------
    rng = np.random.default_rng([seed, 1])
    in_window = sorted({e[1] for e in history[first_window_event:]
                        if e[0] == "submit"})
    k = min(int(traffic["compare"]["tenants"]), len(in_window))
    sample = sorted(int(u) for u in rng.choice(in_window, k, replace=False))
    got = {u: {f: np.asarray(v) for f, v in svc.registry.stream(
        names[u]).window.window_state()._asdict().items()} for u in sample}
    polls = len(served)
    failed_polls = sum(1 for _, ans in served if len(ans) != len(queried)
                       or any(a is None for a in ans.values()))
    spans = ([e for e in sink.events if e["ts"] >= window.wall0]
             if sink else [])
    del svc
    gc.collect()

    evidence = {"plan": plan, "history": history, "kinds": kinds,
                "queried": queried, "sample": sample, "got": got,
                "served": served}
    checks = compare(reference, conf, evidence)
    return Run(
        cell=cell, seed=seed, setup_s=setup_s, window_s=window_s,
        attempted=polls, failed=failed_polls,
        work={"cycles": c - warmup, "polls": polls},
        latencies=latencies, counters=counters, spans=spans,
        shapes={"d": d},
        checks=checks, memory_peak_bytes=peak, evidence=evidence)


def compare(reference, conf, evidence, *, control: bool = False):
    """Every number compared, with its limit (``conf['limits']``).

    With ``control`` the reference's control stands in the program's
    place: windows replayed with each round's last record left out (a
    broken "every flushed record counts"), and answers computed in
    float32, the precision below the float64 the configuration states for
    the sample answers.
    """
    ref = reference.Samples(conf)
    W = int(conf["service"]["window_epochs"])
    kinds, sample, served = (evidence["kinds"], evidence["sample"],
                             evidence["served"])
    queried = evidence["queried"]
    limits = conf["limits"]
    polls = dict(served)
    est_gap = err_gap = 0.0
    missing = 0
    cache: dict = {}

    def answer(u, win, dtype):
        key = (u, win.version, dtype)
        if key not in cache:
            cache[key] = ref.answer(kinds[u], win.total, dtype)
        return cache[key]

    def on_poll(i, wins):
        nonlocal est_gap, err_gap, missing
        ans = polls[i]
        for q, u in enumerate(queried):
            a = ans.get(f"all{q}")
            if a is None:
                missing += 1
                continue
            g, err = answer(u, wins[u], np.float64)
            if control:
                g32, err32 = answer(u, wins[u], np.float32)
                a = [(row[0], g32[j], err32[j]) for j, row in enumerate(a)]
            cols = [row[0] - ref.s for row in a]
            est_gap = max(est_gap, _SERVICE._rel_gap([r[1] for r in a],
                                                     g[cols]))
            err_gap = max(err_gap, _SERVICE._rel_gap([r[2] for r in a],
                                                     err[cols]))

    tracked = sorted(set(queried) | set(sample))
    wins = _replay(reference, ref, evidence, tracked, W,
                   on_poll=on_poll if polls else None, polls=polls)
    got = evidence["got"]
    if control:
        dropped = _replay(reference, ref, evidence, sample, W,
                          drop_last_row=True)
        got = {u: dropped[u].total for u in sample}
    differ = {"reservoir": 0, "lsh_ss": 0}
    for u in sample:
        differ[kinds[u]] += _differing(kinds[u], got[u], wins[u].total)
    checks = {}
    for kind, name in (("reservoir", "sample_slots_differing"),
                       ("lsh_ss", "lsh_state_differing")):
        if any(kinds[u] == kind for u in sample):
            checks[name] = {"value": differ[kind], "limit": limits[name]}
    if served:
        checks["answers_missing"] = {"value": missing,
                                     "limit": limits["answers_missing"]}
        checks["estimate_rel_gap"] = {"value": est_gap,
                                      "limit": limits["estimate_rel_gap"]}
        checks["stderr_rel_gap"] = {"value": err_gap,
                                    "limit": limits["stderr_rel_gap"]}
    return checks


def _replay(reference, ref, evidence, tenants, W, *, on_poll=None,
            polls=(), drop_last_row=False) -> dict:
    """Replay the run's history for ``tenants``: at each commit a tenant's
    records since its last commit, in order, are cut into rounds of B rows
    (the tail round padded with masked rows), numbered per tenant, and
    sampled into its open epoch slot; ``on_poll(i, windows)`` runs where
    the run polled.  Returns uid -> :class:`reference.Window`."""
    plan, history, kinds = (evidence["plan"], evidence["history"],
                            evidence["kinds"])
    B = ref.B
    wins = {u: reference.Window(ref, kinds[u], W) for u in tenants}
    rounds_done = dict.fromkeys(tenants, 0)
    pending: dict = {}
    for i, ev in enumerate(history):
        if ev[0] == "submit" and ev[1] in wins:
            pending.setdefault(ev[1], []).append(plan.records(ev[2], ev[3]))
        elif ev[0] == "commit":
            jobs: dict = {}
            for u, parts in pending.items():
                recs = np.concatenate(parts)
                rounds = []
                for lo in range(0, recs.shape[0], B):
                    chunk = recs[lo:lo + B]
                    values = np.zeros((B, recs.shape[1]), np.uint32)
                    values[:chunk.shape[0]] = chunk
                    mask = np.zeros(B, np.int32)
                    mask[:chunk.shape[0]] = 1
                    if drop_last_row:
                        mask[-1] = 0
                    rounds.append((rounds_done[u] + len(rounds), values,
                                   mask))
                rounds_done[u] += len(rounds)
                jobs.setdefault(kinds[u], []).append(
                    (wins[u].open, u, rounds))
            for kind, todo in jobs.items():
                ref.ingest(kind, todo)
            for u in pending:
                wins[u].refold()
            pending = {}
        elif ev[0] == "advance":
            for w in wins.values():
                w.advance()
        if on_poll is not None and i + 1 in polls:
            on_poll(i + 1, wins)
    return wins


def _differing(kind: str, got: dict, want: dict) -> int:
    """Entries of a program state that differ from the reference's: the
    items of every kept slot, every slot's tag, and the counts."""
    def diff(field, keep=None):
        a, b = np.asarray(got[field]), np.asarray(want[field])
        if a.shape != b.shape:
            return max(a.size, b.size)
        if keep is not None:
            a, b = a[keep], b[keep]
        return int((a != b).sum())

    if kind == "reservoir":
        keep = want["tags"] >= 0
        return diff("items", keep) + diff("tags") + diff("n")
    keep = want["rec_tags"] >= 0
    out = (diff("counts") + diff("rec_items", keep)
           + diff("rec_bucket", keep) + diff("rec_tags") + diff("n"))
    for name in ("same", "cross"):
        out += (diff(f"{name}_sim", want[f"{name}_tags"] >= 0)
                + diff(f"{name}_tags") + diff(f"{name}_seen"))
    return out
