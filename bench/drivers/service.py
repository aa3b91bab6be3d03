"""The general driver of the estimation-service cells.

One ``EstimationService`` is built as the configuration states (one hash
group, ``tenants`` streams of its estimator, the ``ServiceConfig`` given),
then driven in closed-loop cycles by the traffic mix's parameters
(``bench/generate.py`` draws everything from ``--seed`` in set-up):

    prefill   each queried tenant submits ``prefill_records``, one flush
    cycle     the tenants the mix's pick names each submit their records,
              then the ops of ``cycle.ops`` run in order

An op runs every cycle, or every k-th with ``{"op": name, "every": k}``.
Each op is ``bench/ops/<name>.py``, whose ``run(svc)`` returns what it did:
``commit`` (every submitted record is now in its window), ``advance`` (the
epochs moved on), ``answers`` (a poll's results).  A new op is a new file.

Set-up runs ``warmup_cycles`` cycles (compiling every shape the window
uses) before the window opens; the window runs whole cycles until
``--seconds`` have passed.  The ops named in ``cycle.timed`` are timed one
by one on the host clock; every one returns host values or blocks on what
it committed, so each time is complete.

After the window closes (peak memory read, the service freed), the plain
reference of the configuration checks what the timed path produced:

- every cell: the window counters and ``n`` of a seeded sample of the
  tenants that submitted in the window (``compare.tenants``), bit for bit;
- cells that poll: every answer served in the window, estimate and
  standard error, against the reference's float64 estimates of the
  reference's own window counters at that point.
"""
from __future__ import annotations

import gc
import json
import time

import numpy as np

from bench import generate
from bench.harness import Run, load_module


class SpanSink:
    """File-like sink for the service's JSON-lines span events."""

    def __init__(self):
        self.events: list = []

    def write(self, line: str) -> None:
        if line.strip():
            self.events.append(json.loads(line))


COUNTERS = ("ingest_submitted_records_total", "ingest_dispatch_rows_total",
            "ingest_dispatches_total", "query_cache_hits_total",
            "query_cache_misses_total")


def run(cell, *, seed: int, seconds: float, window, started: float,
        reference) -> Run:
    """One run of a service cell."""
    import jax
    from repro.core.sjpc import SJPCConfig
    from repro.service import (ContinuousQuery, EstimationService,
                               ServiceConfig)

    conf, traffic = cell.config, cell.traffic
    cyc = traffic["cycle"]
    T = int(conf["tenants"])
    sketch = conf["sketch"]
    B = int(conf["service"]["batch_rows"])
    d = int(sketch["d"])
    trace = window.trace
    # an op is a name, or {"op": name, "every": k} to run it on every k-th
    # cycle only
    ops = [(o, 1) if isinstance(o, str) else (o["op"], int(o["every"]))
           for o in cyc["ops"]]
    ops = [(op, every, load_module(cell.root / "bench" / "ops"
                                   / f"{op}.py").run) for op, every in ops]

    sink = SpanSink() if trace else None
    svc = EstimationService(ServiceConfig(
        **conf["service"], trace_annotate=trace, trace_sink=sink))
    svc.create_group("g", SJPCConfig(**sketch))
    names = [f"t{i:05d}" for i in range(T)]
    for i, nm in enumerate(names):
        if svc.create_stream(nm, "g").uid != i:
            raise RuntimeError(f"stream {nm} did not get uid {i}")

    plan = generate.plan(traffic, tenants=T, d=d, seed=seed, root=cell.root)
    for i, t in enumerate(plan.self_tenants):
        svc.register_continuous(ContinuousQuery(f"all{i}", "all_thresholds",
                                                (names[t],)))
    for i, (a, b) in enumerate(plan.join_pairs):
        svc.register_continuous(ContinuousQuery(f"join{i}", "join",
                                                (names[a], names[b])))

    # every event the windows saw, in order -- the reference replays them:
    # ("submit", tenant, first pool row, count), ("commit",), ("advance",)
    history: list = []
    state = {"pending": 0, "committed": 0, "submitted": 0}

    def submit(t, count):
        start, count = plan.take(int(t), int(count))
        svc.ingest(names[t], plan.records(start, count))
        history.append(("submit", int(t), start, count))
        state["pending"] += count
        return count

    def effects(out):
        if out.get("commit"):
            history.append(("commit",))
            state["committed"] += state["pending"]
            state["pending"] = 0
        if out.get("advance"):
            history.append(("advance",))

    if plan.prefill:
        for t in plan.queried:
            submit(t, plan.prefill)
        svc.flush()
        effects({"commit": True})

    timed = set(cyc.get("timed", ()))
    latencies: dict = {op: [] for op in timed}
    served: list = []          # (events so far, answers) of each poll

    def cycle(c: int, measure: bool) -> None:
        tenants, counts = plan.cycle(c)
        with window.mark("bench.submit"):
            sent = sum(submit(t, m) for t, m in zip(tenants, counts))
        if measure:
            state["submitted"] += sent
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
                served.append((len(history), _answers(out["answers"])))

    warmup = int(traffic["warmup_cycles"])
    for c in range(warmup):
        cycle(c, measure=False)
    c = warmup
    before = {n: svc.obs.metrics.counter_total(n) for n in COUNTERS}
    first_window_event = len(history)
    committed0 = state["committed"]
    with window:
        while window.elapsed() < seconds:
            cycle(c, measure=True)
            c += 1
    window_s = window.seconds
    setup_s = window.wall0 - started
    committed = state["committed"] - committed0
    after = {n: svc.obs.metrics.counter_total(n) for n in COUNTERS}
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")

    # -- read back what the comparison needs, then free the service -------
    rng = np.random.default_rng([seed, 1])
    in_window = sorted({e[1] for e in history[first_window_event:]
                        if e[0] == "submit"})
    k = min(int(traffic["compare"]["tenants"]), len(in_window))
    sample = np.sort(rng.choice(in_window, k, replace=False)) if k else []
    got = {}
    for t in sample:
        st = svc.registry.stream(names[t]).window.window_state()
        got[int(t)] = (np.asarray(st.counters), float(st.n))
    polls = len(served)
    failed_polls = sum(1 for _, ans in served
                       if len(ans) != len(plan.self_tenants)
                       + len(plan.join_pairs)
                       or any(a is None for a in ans.values()))
    spans = ([e for e in sink.events if e["ts"] >= window.wall0]
             if sink else [])
    del svc
    gc.collect()

    evidence = {"plan": plan, "history": history, "sample": sample,
                "got": got, "served": served}
    checks = compare(reference, conf, evidence)
    if "flush" in timed:
        attempted, failed = state["submitted"], state["submitted"] - committed
    else:
        attempted, failed = polls, failed_polls
    L = d - int(sketch["s"]) + 1
    return Run(
        cell=cell, seed=seed, setup_s=setup_s, window_s=window_s,
        attempted=attempted, failed=failed,
        work={"records": committed, "cycles": c - warmup, "polls": polls},
        latencies=latencies,
        counters={k: after[k] - before[k] for k in COUNTERS},
        spans=spans,
        shapes={"tenants": T, "batch_rows": B, "d": d, "levels": L,
                "depth": int(sketch["depth"]), "width": int(sketch["width"])},
        checks=checks, memory_peak_bytes=peak, evidence=evidence)


def _answers(out: dict) -> dict:
    """{query: [(s, estimate, stderr) per threshold]} of one poll; None
    where an answer is stale."""
    ans = {}
    for q, res in out.items():
        rows = (list(res.values()) if isinstance(res, dict) else [res])
        ans[q] = (None if any(r.stale for r in rows)
                  else [(r.s, r.estimate, r.stderr) for r in rows])
    return ans


def compare(reference, conf, evidence, *, control: bool = False):
    """Every number compared, with its limit (``conf['limits']``).

    With ``control`` the reference's control stands in the program's place:
    window counters that leave each round's last record uncounted (a broken
    "every flushed record counts"), and answers computed in bfloat16, the
    precision below the float32 the configuration states for queries.
    """
    ref = reference.SJPC(conf["sketch"], conf["service"]["batch_rows"])
    W = conf["service"].get("window_epochs")
    plan, history = evidence["plan"], evidence["history"]
    sample, got, served = (evidence["sample"], evidence["got"],
                           evidence["served"])
    limits = conf["limits"]
    checks = {}
    if len(sample):
        slots = {int(t): i for i, t in enumerate(sample)}
        rounds, epoch = _rounds(ref, plan, history, slots)
        live = [r for r in rounds if _live(r, epoch, W)]
        counters, n = _replay(ref, live, len(slots))
        if control:
            c2, n2 = _replay(ref, live, len(slots), drop_last_row=True)
            got = {t: (c2[slots[t]], float(n2[slots[t]])) for t in slots}
        differ = sum(int((got[t][0] != counters[i]).sum())
                     for t, i in slots.items())
        n_differ = sum(int(got[t][1] != n[i]) for t, i in slots.items())
        checks["counters_differing"] = {"value": differ,
                                        "limit": limits["counters_differing"]}
        checks["n_differing"] = {"value": n_differ,
                                 "limit": limits["n_differing"]}
    if served:
        est_gap, err_gap, missing = _poll_gaps(ref, plan, history, served, W,
                                               control=control)
        checks["answers_missing"] = {"value": missing,
                                     "limit": limits["answers_missing"]}
        checks["estimate_rel_gap"] = {"value": est_gap,
                                      "limit": limits["estimate_rel_gap"]}
        checks["stderr_rel_gap"] = {"value": err_gap,
                                    "limit": limits["stderr_rel_gap"]}
    return checks


def _rounds(ref, plan, history, slots) -> tuple[list, int]:
    """Replay ``history`` for the tenants in ``slots``: at each commit a
    tenant's records since its last commit, in order, are cut into rounds
    of B rows, numbered per tenant, the tail round padded with masked
    rows.  Returns the rounds in commit order, each (slot, uid, round,
    values (B, d), mask (B,), epoch committed in, event index), and the
    epoch open after the last event."""
    B, seen, pending, epoch = ref.B, {}, {}, 0
    out = []
    for i, ev in enumerate(history):
        if ev[0] == "submit":
            if ev[1] in slots:
                pending.setdefault(ev[1], []).append(
                    plan.records(ev[2], ev[3]))
        elif ev[0] == "commit":
            for t, parts in pending.items():
                recs = np.concatenate(parts)
                for lo in range(0, recs.shape[0], B):
                    chunk = recs[lo:lo + B]
                    vals = np.zeros((B, recs.shape[1]), np.uint32)
                    vals[:chunk.shape[0]] = chunk
                    mask = np.zeros(B, np.int64)
                    mask[:chunk.shape[0]] = 1
                    r = seen.get(t, 0)
                    seen[t] = r + 1
                    out.append((slots[t], t, r, vals, mask, epoch, i))
            pending = {}
        elif ev[0] == "advance":
            epoch += 1
    return out, epoch


def _live(r, epoch: int, W) -> bool:
    """Whether round ``r`` is in the window when ``epoch`` is open: the
    open epoch and the W - 1 before it."""
    return W is None or r[5] > epoch - W


def _replay(ref, rounds, n_slots: int, **kw):
    if not rounds:
        z = np.zeros((n_slots, ref.L, ref.t, ref.w), np.int64)
        return z, np.zeros(n_slots, np.int64)
    sl, uid, rnd, vals, mask = list(zip(*rounds))[:5]
    return ref.replay(np.array(sl), np.array(uid), np.array(rnd),
                      np.stack(vals), np.stack(mask), n_slots, **kw)


def _rel_gap(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1.0)))


def _poll_gaps(ref, plan, history, served, W, *, control: bool = False):
    """Worst relative gap of the served estimates and standard errors
    against the reference, over every answer of every poll in the window
    (``control``: the bfloat16 reference's answers in place of the
    served ones)."""
    queried = [int(t) for t in plan.queried]
    slots = {t: i for i, t in enumerate(queried)}
    rounds, _ = _rounds(ref, plan, history, slots)
    per_round = _per_round_counters(ref, rounds)
    by_event: dict = {}
    for j, r in enumerate(rounds):
        by_event.setdefault(r[6], []).append(j)
    polls = {upto: ans for upto, ans in served}
    counters = np.zeros((len(slots), ref.L, ref.t, ref.w), np.int64)
    n = np.zeros(len(slots), np.int64)
    est_gap = err_gap = 0.0
    missing = epoch = 0
    for i, ev in enumerate(history):
        if ev[0] == "commit":
            for j in by_event.get(i, ()):
                counters[rounds[j][0]] += per_round[j]
                n[rounds[j][0]] += int(rounds[j][4].sum())
        elif ev[0] == "advance":
            epoch += 1
            if W is not None:
                for j, r in enumerate(rounds):
                    if r[6] < i and r[5] == epoch - W:
                        counters[r[0]] -= per_round[j]
                        n[r[0]] -= int(r[4].sum())
        if i + 1 not in polls:
            continue
        ans = polls[i + 1]
        g, err = ref.self_join(counters, n)
        if control:
            ans = _control_answers(ref, plan, slots, counters, n)
        for q, t in enumerate(plan.self_tenants):
            a = ans.get(f"all{q}")
            if a is None:
                missing += 1
                continue
            si = slots[int(t)]
            s_vals = [row[0] - ref.s for row in a]
            est_gap = max(est_gap, _rel_gap([row[1] for row in a],
                                            g[si, s_vals]))
            err_gap = max(err_gap, _rel_gap([row[2] for row in a],
                                            err[si, s_vals]))
        pa = [slots[int(a)] for a, _ in plan.join_pairs]
        pb = [slots[int(b)] for _, b in plan.join_pairs]
        if pa:
            gj, errj = ref.join(counters[pa], counters[pb], n[pa], n[pb])
            for q in range(len(pa)):
                a = ans.get(f"join{q}")
                if a is None:
                    missing += 1
                    continue
                s_vals = [row[0] - ref.s for row in a]
                est_gap = max(est_gap, _rel_gap([row[1] for row in a],
                                                gj[q, s_vals]))
                err_gap = max(err_gap, _rel_gap([row[2] for row in a],
                                                errj[q, s_vals]))
    return est_gap, err_gap, missing


def _control_answers(ref, plan, slots, counters, n) -> dict:
    import ml_dtypes
    bf16 = ml_dtypes.bfloat16
    ks = range(ref.s, ref.d + 1)
    g, err = ref.self_join(counters, n, dtype=bf16)
    ans = {f"all{i}": [(k, g[slots[int(t)], k - ref.s],
                        err[slots[int(t)], k - ref.s]) for k in ks]
           for i, t in enumerate(plan.self_tenants)}
    pa = [slots[int(a)] for a, _ in plan.join_pairs]
    pb = [slots[int(b)] for _, b in plan.join_pairs]
    if pa:
        gj, errj = ref.join(counters[pa], counters[pb], n[pa], n[pb],
                            dtype=bf16)
        for i in range(len(pa)):
            ans[f"join{i}"] = [(ref.s, gj[i, 0], errj[i, 0])]
    return ans


def _per_round_counters(ref, rounds):
    """The counter contribution of each round on its own (K, L, t, w)."""
    K = len(rounds)
    out = np.zeros((K, ref.L, ref.t, ref.w), np.int64)
    step = 64
    for lo in range(0, K, step):
        part = rounds[lo:lo + step]
        c, _ = _replay(ref, [(j,) + tuple(r[1:]) for j, r in enumerate(part)],
                       len(part))
        out[lo:lo + len(part)] = c
    return out
