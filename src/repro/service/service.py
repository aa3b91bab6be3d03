"""`EstimationService`: the multi-tenant streaming estimation front end.

Composes the subsystem (DESIGN.md §10):

  registry.py   named streams grouped by shared hash params (join-ability)
  window.py     per-stream sliding windows; expiry = counter subtraction
  ingest.py     double-buffered, fixed-shape, single-dispatch batched ingest
  query.py      snapshot-based queries with analytical error bars
  planner.py    poll(): fused launches, priorities, tenant admission

Lifecycle:

    svc = EstimationService()
    svc.create_group("g", SJPCConfig(d=6, s=4, width=2048, depth=3))
    svc.create_stream("tenant-a", "g", window_epochs=8)
    svc.ingest("tenant-a", records)        # buffered (numpy in, no device work)
    svc.flush()                            # one jit'd dispatch per group round
    svc.advance_epoch()                    # close the epoch on every window
    r = svc.snapshot().self_join("tenant-a")   # estimate +/- r.stderr

``ingest`` is deliberately device-free so tenant request handling stays
cheap; all device work happens in ``flush`` (and is shared across tenants).
``poll()`` evaluates the registered continuous queries against one shared
snapshot through the query planner -- the batched continuous-query path.
Each stage has one path: the fused ingest scan, the batched query engine,
the planned poll.  Their conformance oracles are plain functions the tests
call (``sjpc.update`` replayed with ``ingest_key``, ``reference_snapshot``).
"""
from __future__ import annotations

import dataclasses
import time

import jax

from repro import estimators
from repro import platform as repro_platform
from repro.core.sjpc import SJPCConfig, SJPCState
from repro.obs import (AccuracyAuditor, Observability, Tracer,
                       default_registry, default_tracer)

from .ingest import IngestPipeline
from .planner import PlannerConfig, QueryPlanner
from .query import ContinuousQuery, QueryEngine, QueryResult, Snapshot
from .registry import HashGroup, StreamEntry, StreamRegistry


_DEFAULT_WINDOW = object()       # "use ServiceConfig.window_epochs" sentinel


@dataclasses.dataclass(frozen=True)
class ServiceConfig:
    platform: str = "auto"           # backend bootstrap (repro.platform):
                                     # "auto" = trust jax's accelerator
                                     # preference; "cpu"/"gpu"/"tpu" pins it
                                     # (effective only before jax init)
    batch_rows: int = 256            # ingest round size per stream
    window_epochs: int | None = 8    # default; per-stream override at create
    auto_flush_rows: int | None = None   # flush() when a group's backlog hits this
    estimator: str = "sjpc"          # default estimator kind for new streams
                                     # (any repro.estimators kind; per-stream
                                     # override at create_stream)
    backing_epochs: int = 0          # default sample-window refill depth K
                                     # (DESIGN.md §14.2; per-stream override
                                     # at create_stream; sample kinds only)
    observe: bool = True             # metrics + spans (DESIGN.md §15); False =
                                     # shared no-op bundle, reference-speed paths
    audit_rate: float = 0.0          # sampled exact-replay accuracy telemetry
                                     # (0 = off; 1 = audit every polled query)
    audit_max_records: int = 65536   # audit skip threshold (exact oracle cost)
    trace_sink: object = None        # JSON-lines span sink: path or file-like
    trace_annotate: bool = False     # bracket spans in jax.profiler annotations
    planner: PlannerConfig = PlannerConfig()   # poll() fusion/budget knobs
                                               # (DESIGN.md §16)


class EstimationService:
    def __init__(self, cfg: ServiceConfig = ServiceConfig(), *,
                 obs: Observability | None = None):
        self.cfg = cfg
        self.platform = repro_platform.bootstrap(cfg.platform)
        if obs is None:
            obs = self._build_obs(cfg)
        if cfg.audit_rate > 0.0 and obs.auditor is None:
            obs = dataclasses.replace(obs, auditor=AccuracyAuditor(
                obs.metrics, rate=cfg.audit_rate,
                max_records=cfg.audit_max_records))
        self.obs = obs
        self.registry = StreamRegistry(obs=self.obs)
        self.engine = QueryEngine(self.registry, obs=self.obs)
        self._pipelines: dict[str, IngestPipeline] = {}
        self._continuous: dict[str, ContinuousQuery] = {}
        self.planner = QueryPlanner(self.registry, cfg.planner, obs=self.obs)
        self.stats = {"ingested_records": 0, "flush_s": 0.0, "epochs": 0,
                      "snapshots": 0, "polls": 0}

    @staticmethod
    def _build_obs(cfg: ServiceConfig) -> Observability:
        """Default bundle: the process-global registry/tracer, a private
        tracer only when the config asks for a sink or profiler
        annotations (so two services never interleave one file)."""
        if not cfg.observe:
            return Observability.disabled()
        metrics = default_registry()
        if cfg.trace_sink is not None or cfg.trace_annotate:
            tracer = Tracer(sink=cfg.trace_sink, annotate=cfg.trace_annotate,
                            registry=metrics)
        else:
            tracer = default_tracer()
        return Observability(metrics=metrics, tracer=tracer)

    # -- provisioning ---------------------------------------------------
    def create_group(self, group_id: str, cfg: SJPCConfig) -> HashGroup:
        group = self.registry.create_group(group_id, cfg)
        self._pipelines[group_id] = IngestPipeline(
            group, batch_rows=self.cfg.batch_rows, obs=self.obs)
        return group

    def create_stream(self, name: str, group_id: str,
                      window_epochs=_DEFAULT_WINDOW, *,
                      estimator: str | None = None,
                      estimator_cfg=None,
                      backing_epochs: int | None = None,
                      uid: int | None = None) -> StreamEntry:
        """Register a stream.  ``estimator`` picks the protocol kind
        ("sjpc" | "reservoir" | "lsh_ss", default from ServiceConfig);
        competitors derive an equal-space config from the group's
        SJPCConfig unless ``estimator_cfg`` overrides it.
        ``backing_epochs`` enables the sample-window refill fold for
        windowed sample estimators (default from ServiceConfig; linear
        kinds reject it -- their expiry is exact already).  ``uid`` pins
        the stream's registry id (distributed workers pin global tenant
        uids so their ingest PRNG grid matches a single-process run --
        see StreamRegistry.register)."""
        if window_epochs is _DEFAULT_WINDOW:
            window_epochs = self.cfg.window_epochs
        kind = estimator or self.cfg.estimator
        if backing_epochs is None:
            backing = self.cfg.backing_epochs
            # the config-level default applies only where it is meaningful
            # (bounded sample windows); explicit arguments stay strict.
            # ``linear`` is a kind-level capability, read from the spec
            # (the group's cached instance resolves legacy registrations)
            if (estimators.spec_of(
                    self.registry.group(group_id).estimator(kind)).linear
                    or window_epochs is None):
                backing = 0
        else:
            backing = backing_epochs
        entry = self.registry.register(
            name, group_id, window_epochs, estimator=kind,
            estimator_cfg=estimator_cfg, backing_epochs=backing, uid=uid)
        if self.obs.metrics.enabled:
            self.obs.metrics.set("estimator_memory_bytes",
                                 float(entry.window.memory_bytes()),
                                 stream=name, kind=kind)
            entry.window._export_gauges()
        return entry

    # -- ingest ---------------------------------------------------------
    def ingest(self, name: str, records) -> int:
        """Buffer records for ``name``; device work is deferred to flush."""
        entry = self.registry.stream(name)
        pipe = self._pipelines[entry.group_id]
        n = pipe.submit(name, records)
        self.stats["ingested_records"] += n
        if self.obs.auditor is not None:
            self.obs.auditor.record(name, records,
                                    entry.window.window_epochs)
        if (self.cfg.auto_flush_rows is not None
                and pipe.pending_rows() >= self.cfg.auto_flush_rows):
            self._flush_group(entry.group_id)
        return n

    def ingest_state_delta(self, name: str, delta: SJPCState) -> None:
        """Absorb an externally-sketched delta (e.g. the training monitor's
        counters since its last publish) into ``name``'s open epoch.  The
        delta must have been sketched with this stream's group params (and
        the stream must run a linear estimator kind -- sample estimators
        cannot absorb foreign states)."""
        entry = self.registry.stream(name)
        est = entry.estimator
        if not estimators.spec_of(est).linear:
            raise ValueError(
                f"stream {name!r} runs non-linear estimator "
                f"{entry.estimator_kind!r}; external state deltas need a "
                "linear (mergeable-by-arithmetic) estimator")
        entry.window.absorb_delta(est.merge(entry.window.ingest_base(), delta))
        if self.obs.auditor is not None:
            self.obs.auditor.mark_unauditable(name)
        self.obs.metrics.inc("ingest_state_deltas_total", stream=name)

    # -- multi-host delta exchange (distributed/, DESIGN.md §18) --------
    def export_deltas(self) -> list:
        """Every stream's unshipped window delta since the last export
        (flushing first so the exports reflect all buffered records):
        ``[(name, kind, epoch, window_version, mode, state), ...]``.
        Streams with nothing new are skipped entirely -- an idle service
        returns ``[]`` and its worker ships the zero-byte heartbeat."""
        self.flush()
        out = []
        for e in self.registry.streams():
            d = e.window.export_delta()
            if d is None:
                continue
            mode, state = d
            out.append((e.name, e.estimator_kind, e.window.epoch,
                        e.window.version, mode, state))
            self.obs.metrics.inc("delta_exports_total", stream=e.name,
                                 mode=mode)
        return out

    def apply_remote_delta(self, name: str, mode: str, state) -> None:
        """Replica-side application of one exported delta.  ``"merge"``
        folds a linear counter delta into the open epoch via the existing
        merge algebra (exactly :meth:`ingest_state_delta`); ``"replace"``
        installs a sample kind's open-slot state and refolds.  Epoch
        alignment (apply-before-advance) is the coordinator's contract."""
        entry = self.registry.stream(name)
        if mode == "merge":
            self.ingest_state_delta(name, state)
            return
        if mode != "replace":
            raise ValueError(f"unknown delta mode {mode!r}")
        if estimators.spec_of(entry.estimator).linear:
            raise ValueError(
                f"stream {name!r} runs linear estimator "
                f"{entry.estimator_kind!r}; replace-mode deltas are the "
                "sample-window protocol (linear kinds merge)")
        entry.window.absorb_delta(state)
        if self.obs.auditor is not None:
            self.obs.auditor.mark_unauditable(name)
        self.obs.metrics.inc("ingest_state_deltas_total", stream=name)

    def _flush_group(self, group_id: str) -> None:
        pipe = self._pipelines[group_id]
        entries = self.registry.streams(group_id)
        with self.obs.span("service.flush", histogram="service_flush_seconds",
                           labels={"group": group_id},
                           group=group_id, streams=len(entries)):
            t0 = time.perf_counter()
            new_states = pipe.flush(entries)
            with self.obs.span("window.commit", streams=len(entries)):
                for e in entries:
                    e.window.absorb_delta(new_states[e.name])
            # jax dispatch is asynchronous: without blocking on the
            # committed windows this timed the *enqueue* and reported
            # near-zero.  flush_s is device-inclusive wall time, obs on
            # or off (the span's histogram inherits the same interval)
            with self.obs.span("window.block", streams=len(entries)):
                jax.block_until_ready([jax.tree_util.tree_leaves(
                    e.window.total) for e in entries])
            self.stats["flush_s"] += time.perf_counter() - t0

    def flush(self) -> None:
        """Drain every group's ingest buffer into the windows."""
        for group_id in list(self._pipelines):
            self._flush_group(group_id)

    # -- windowing ------------------------------------------------------
    def advance_epoch(self, name: str | None = None) -> None:
        """Close the open epoch (flushing first so the epoch boundary is
        exact); expired epochs are subtracted out of their windows."""
        self.flush()
        entries = (self.registry.streams() if name is None
                   else [self.registry.stream(name)])
        for e in entries:
            e.window.advance_epoch()
            if self.obs.auditor is not None:
                self.obs.auditor.advance_epoch(e.name)
        self.stats["epochs"] += 1
        self.obs.metrics.inc("service_epochs_total")

    # -- queries --------------------------------------------------------
    def snapshot(self, names: list[str] | None = None) -> Snapshot:
        self.flush()
        self.stats["snapshots"] += 1
        return self.engine.snapshot(names)

    def register_continuous(self, query: ContinuousQuery) -> None:
        if query.name in self._continuous:
            raise ValueError(f"continuous query {query.name!r} already exists")
        # validate eagerly: unknown streams / non-joinable pairs fail here,
        # not at poll time
        for s in query.streams:
            self.registry.stream(s)
        if query.kind == "join":
            self.registry.require_joinable(*query.streams)
        self._continuous[query.name] = query
        self.planner.invalidate_queries()

    def set_tenant_budget(self, tenant: str, refill: float | None, *,
                          burst: float | None = None) -> None:
        """Set (or clear) one tenant's per-poll standing-query budget; see
        :meth:`QueryPlanner.set_tenant_budget`."""
        self.planner.set_tenant_budget(tenant, refill, burst=burst)

    def poll(self) -> dict[str, QueryResult | dict[int, QueryResult]]:
        """Evaluate every continuous query against ONE shared snapshot.

        The device work is scheduled through the query planner's cached
        fusion plan: matching cohorts across hash groups share one
        ``estimate_batch`` launch, launches run in priority order, and
        over-budget tenants are served their last fresh result with
        ``stale=True`` (DESIGN.md §16).  The individual ``evaluate`` calls
        are then pure cache lookups.
        """
        with self.obs.span("service.poll", histogram="service_poll_seconds",
                           queries=len(self._continuous)):
            out = self.planner.poll(self.snapshot(), self._continuous)
            self.stats["polls"] += 1
        if self.obs.auditor is not None:
            for q in self._continuous.values():
                res = out[q.name]
                if (res.stale if isinstance(res, QueryResult)
                        else any(r.stale for r in res.values())):
                    continue          # already audited when it was fresh
                kind = self.registry.stream(q.streams[0]).estimator_kind
                self.obs.auditor.maybe_audit(res, kind)
        return out

    # -- introspection --------------------------------------------------
    def describe(self) -> dict:
        groups = {}
        for g in self.registry.groups():
            pipe = self._pipelines[g.group_id]
            groups[g.group_id] = {
                "cfg": dataclasses.asdict(g.cfg),
                "streams": {e.name: {"records": e.records,
                                     "estimator": e.estimator_kind,
                                     "window_epochs": e.window.window_epochs,
                                     "live_epochs": e.window.live_epochs,
                                     "memory_bytes": e.window.memory_bytes()}
                            for e in self.registry.streams(g.group_id)},
                "ingest": dict(pipe.stats),
            }
        return {"groups": groups, "continuous": list(self._continuous),
                **self.stats}

    def refresh_gauges(self) -> None:
        """Recompute the derived / point-in-time gauges (memory bytes,
        window geometry, queue depth, per-(group, kind) cache hit
        ratios) so an export reflects *now*, not the last mutation."""
        m = self.obs.metrics
        if not m.enabled:
            return
        for e in self.registry.streams():
            m.set("estimator_memory_bytes", float(e.window.memory_bytes()),
                  stream=e.name, kind=e.estimator_kind)
            e.window._export_gauges()
        for group_id, pipe in self._pipelines.items():
            m.set("ingest_pending_rows", float(pipe.pending_rows()),
                  group=group_id)
        hits = m.series("query_cache_hits_total")
        misses = m.series("query_cache_misses_total")
        for key in sorted(set(hits) | set(misses)):
            h, miss = hits.get(key, 0.0), misses.get(key, 0.0)
            if h + miss > 0:
                m.set("query_cache_hit_ratio", h / (h + miss),
                      **dict(key))

    def metrics_report(self) -> str:
        """The service's metric state in the Prometheus text exposition
        format (derived gauges refreshed first).  ``obs.metrics.collect()``
        is the plain-dict equivalent for programmatic readers."""
        self.refresh_gauges()
        return self.obs.metrics.to_prometheus()
