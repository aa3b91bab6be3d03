"""The Estimator protocol: every similarity-join size estimator -- the
paper's SJPC *and* its competitors -- behind one streaming, service-grade
interface (DESIGN.md §13).

An :class:`Estimator` instance is the per-hash-group engine for one
estimator family: it owns the static configuration (dimensionality d,
sketch threshold s, memory budget, hash/PRNG seeds) and operates on
immutable per-stream **states** (pytrees of jax arrays, so they stack,
ship across devices, and ride the service's batched ingest dispatch
unchanged).  The protocol:

  init(sid)                  fresh per-stream state (sid tags provenance
                             for estimators whose subtract is tag-based)
  ingest_rounds(...)         ALL coalesced rounds of a flush for ALL
                             streams of a cohort in one jit'd dispatch --
                             states stacked on a leading stream axis,
                             records (R, S, B, d), masks (R, S, B),
                             per-(round, stream) PRNG keys (R, S)
  merge / subtract           the window algebra: merge combines disjoint
                             sub-streams, subtract removes a previously
                             merged component (sliding-window expiry).
                             ``linear=True`` estimators do both exactly by
                             counter arithmetic; sampling estimators merge
                             by deterministic weighted union and subtract
                             by provenance tag (exact for the epoch states
                             the window hands them).
  memory_bytes()             the per-stream state footprint -- the paper's
                             equal-space comparison axis (Fig. 8)
  estimate_batch(states)     every (stream, threshold) estimate of a
                             stacked cohort from one dispatch
  estimate_ref(state)        the per-stream host-numpy oracle the batched
                             path is held to (<= 1e-6, tests)

The registry maps estimator kind names ("sjpc", "reservoir", "lsh_ss") to
factories taking the group's ``SJPCConfig`` -- so competitors derive their
space budget FROM the sketch they are compared against, and an equal-space
side-by-side deployment is the default, not a benchmark contrivance.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, NamedTuple

import numpy as np
import jax
import jax.numpy as jnp


class EstimateTable(NamedTuple):
    """Estimates for N same-config streams at EVERY threshold k = s..d.

    Shapes mirror :class:`repro.core.sjpc.SJPCBatchEstimate` (column i
    answers threshold k = s + i).  ``stderr_kind`` names the uncertainty
    method behind the stderr columns -- "analytic" (the paper's Theorem
    1/2 bounds), "bootstrap" / "bootstrap_stratified" (the resampling
    bars of :mod:`repro.estimators.uncertainty`), or "none" (disabled /
    unknown; columns are zero) -- so the service can surface per-kind
    confidence intervals through one contract (DESIGN.md §14).
    """
    x: np.ndarray              # (N, L) per-level k-similar pair estimates
    g: np.ndarray              # (N, L) g_k per threshold
    y: np.ndarray              # (N, L) raw level diagnostics (estimator-specific)
    n: np.ndarray              # (N,) records in each stream's window
    stderr: np.ndarray         # (N, L) absolute 1-sigma bound (0 = unknown)
    stderr_offline: np.ndarray  # (N, L) sampling-only bound (0 = unknown)
    stderr_kind: str = "none"  # uncertainty method behind the bars


class Estimator:
    """Abstract base; subclasses set ``kind`` and the capability flags."""

    kind: str = "abstract"
    linear: bool = False       # exact merge/subtract by state arithmetic
    supports_join: bool = False  # two-stream §6 join estimates

    # subclasses must define: d, s, seed attributes (ints)

    @property
    def num_levels(self) -> int:
        return self.d - self.s + 1

    @property
    def thresholds(self) -> range:
        return range(self.s, self.d + 1)

    @property
    def ingest_seed(self) -> int:
        """Seed of the per-(stream, round) ingest key grid (see
        service.ingest.ingest_key_grid)."""
        return self.seed ^ 0x5E41CE

    # -- protocol ------------------------------------------------------
    def init(self, sid: int = 0):
        raise NotImplementedError

    def ingest_rounds(self, states, values, row_mask, keys):
        """states: pytree stacked on a leading S axis; values (R, S, B, d)
        uint32; row_mask (R, S, B) int32; keys (R, S) PRNG keys.  Returns
        the updated stacked states.  One jit'd dispatch per call."""
        raise NotImplementedError

    def merge(self, a, b):
        raise NotImplementedError

    def subtract(self, a, b):
        raise NotImplementedError

    def memory_bytes(self) -> int:
        raise NotImplementedError

    def estimate_batch(self, states, *,
                       clamp: bool = True) -> EstimateTable:
        """Stacked states (leading N axis) -> the full (N, L) table."""
        raise NotImplementedError

    def estimate_ref(self, state, *, clamp: bool = True) -> EstimateTable:
        """Single-state host-numpy reference (N=1 table); the conformance
        oracle for ``estimate_batch`` and the service's
        ``reference_snapshot``.  Default: the batched path on a singleton
        stack."""
        return self.estimate_batch(stack_states([state]), clamp=clamp)

    # -- generic helpers ----------------------------------------------
    def state_n(self, state) -> float:
        return float(np.asarray(jax.device_get(state.n)))


# ---------------------------------------------------------------------------
# State stacking: pytree states <-> batched (leading-axis) cohorts
# ---------------------------------------------------------------------------

def stack_states(states):
    """Stack same-shape state pytrees along a new leading axis.

    On CPU backends the leaves are stacked host-side (np.stack over the
    zero-copy views, ~5x cheaper than N expand+concat XLA dispatches -- the
    same trade query._stack_states makes); on TPU they stay on device.
    """
    if jax.default_backend() == "tpu":
        return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *states)
    return jax.tree_util.tree_map(
        lambda *xs: np.stack([np.asarray(x) for x in xs]), *states)


def index_state(stacked, i: int):
    """The i-th state of a stacked cohort."""
    return jax.tree_util.tree_map(lambda x: x[i], stacked)


def zeros_like_stack(state, count: int):
    """A (count, ...) stacked pytree of zeros shaped like ``state``."""
    return jax.tree_util.tree_map(
        lambda x: jnp.zeros((count,) + tuple(jnp.shape(x)), x.dtype), state)


def scan_rounds(ingest_one: Callable, states, values, row_mask, keys):
    """Generic (R rounds x S streams) ingest dispatch: ``lax.scan`` over
    the round axis, ``vmap`` over the stream axis -- the execution shape
    service.ingest.multi_round_update gave SJPC, for any estimator whose
    single-stream update is ``ingest_one(state, values, mask, key)``."""
    def body(carry, rnd):
        vals, mask, ks = rnd
        return jax.vmap(ingest_one)(carry, vals, mask, ks), None

    carry, _ = jax.lax.scan(body, states, (values, row_mask, keys))
    return carry


# ---------------------------------------------------------------------------
# Sample-merge helper: deterministic weighted union of two uniform samples
# ---------------------------------------------------------------------------

def priority_merge_keys(items, tags, weight, salt: int):
    """Selection keys for merging uniform samples (A-ES weighted draw).

    Each retained sample item represents ``weight`` = n/m population
    records; a uniform sample of the merged population keeps items with
    probability proportional to represented mass.  A-ES realizes that as
    top-k over keys u^(1/w) -- computed here as log(u)/w (monotone
    equivalent, and f32-stable near 0 where u^(1/w) saturates at 1).
    ``u`` is a hash of (slot index, item, tag, salt), NOT a PRNG draw, so
    the merge is deterministic and symmetric: merge(a, b) selects the
    same multiset as merge(b, a).  The slot index MUST be in the hash:
    keyed on content alone, duplicate items (one epoch's worth of equal
    pair-sim values, a cluster of identical records) would share one key
    and survive or vanish as a block under top_k instead of
    proportionally.  Invalid slots (tag < 0) get -inf keys.
    """
    slot = jnp.arange(items.shape[0], dtype=jnp.uint32)
    h = (jnp.uint32(salt) ^ tags.astype(jnp.uint32)) \
        + slot * jnp.uint32(0x9E3779B9)
    for c in range(items.shape[-1]):
        h = (h * jnp.uint32(0x9E3779B1)) ^ items[..., c].astype(jnp.uint32)
    h = h * jnp.uint32(0x85EBCA77)
    h ^= h >> 15
    u = (h.astype(jnp.float32) + 1.0) / 4294967296.0       # (0, 1]
    key = jnp.log(u) / jnp.maximum(weight, 1e-9)
    return jnp.where(tags >= 0, key, -jnp.inf)


def merge_tagged_samples(items_a, tags_a, n_a, items_b, tags_b, n_b,
                         capacity: int, salt: int):
    """Merge two tagged fixed-capacity uniform samples into one of
    ``capacity`` slots: pool both, keep the top-``capacity`` priority keys
    (weighted by represented population, see :func:`priority_merge_keys`).
    Returns (items, tags) with empty slots tagged -1.  ``capacity`` may
    exceed the pooled slot count (the window's backing-epoch refill folds
    into an *expanded* total); the shortfall is padded with empty slots.
    """
    m_a = jnp.sum((tags_a >= 0).astype(jnp.float32))
    m_b = jnp.sum((tags_b >= 0).astype(jnp.float32))
    w_a = jnp.asarray(n_a, jnp.float32) / jnp.maximum(m_a, 1.0)
    w_b = jnp.asarray(n_b, jnp.float32) / jnp.maximum(m_b, 1.0)
    items = jnp.concatenate([items_a, items_b], axis=0)
    tags = jnp.concatenate([tags_a, tags_b], axis=0)
    keys = jnp.concatenate([
        priority_merge_keys(items_a, tags_a, w_a, salt),
        priority_merge_keys(items_b, tags_b, w_b, salt)], axis=0)
    k = min(capacity, items.shape[0])
    _, top = jax.lax.top_k(keys, k)
    sel_valid = jnp.take(tags, top) >= 0
    out_items = jnp.take(items, top, axis=0)
    out_tags = jnp.where(sel_valid, jnp.take(tags, top), -1)
    if k < capacity:
        pad = capacity - k
        out_items = jnp.concatenate(
            [out_items, jnp.zeros((pad,) + out_items.shape[1:],
                                  out_items.dtype)], axis=0)
        out_tags = jnp.concatenate(
            [out_tags, jnp.full((pad,), -1, out_tags.dtype)], axis=0)
    return out_items, out_tags


# ---------------------------------------------------------------------------
# Spec registry: ONE declarative record per estimator kind (DESIGN.md §19)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class EstimatorSpec:
    """Everything any layer needs to know about an estimator kind.

    One registration feeds every consumer: the factory (``make``), the
    state NamedTuple class (the distributed wire codec), the window
    strategy (``linear``: delta-ring vs slot-fold, and with it the wire
    delta mode), join gating (``join_capable``), the uncertainty story
    (``stderr_kind``), the planner's fusion-signature contribution
    (``fusion``), and the accuracy auditor's exact-replay oracle
    (``exact_oracle``).

    Capability fields default to ``None`` = "resolve from the instance
    attribute" (``est.linear`` / ``est.supports_join`` / a served table's
    ``stderr_kind``), so legacy ``register(kind, factory)`` calls keep
    working unchanged; :func:`spec_of` performs that resolution.

      factory(sjpc_cfg, *, params=None, estimator_cfg=None)
      fusion(est) -> hashable        planner fusion-signature config part
      exact_oracle(query_kind, records) -> (s -> float)  exact g replay
    """
    kind: str
    factory: Callable | None = None
    state_cls: type | None = None
    linear: bool | None = None
    join_capable: bool | None = None
    stderr_kind: str | None = None
    fusion: Callable | None = None
    exact_oracle: Callable | None = None
    registrant: str = "?"

    @property
    def wire_mode(self) -> str:
        """The distributed delta mode this kind ships (DESIGN.md §18.2):
        linear kinds send per-epoch counter increments (``"merge"``),
        sample kinds replace their open slot (``"replace"``)."""
        return "merge" if self.linear else "replace"


_REGISTRY: dict[str, EstimatorSpec] = {}


def _callable_id(fn):
    """Identity of a callable that survives module re-import: the same
    source definition re-executed (importlib.reload of a plugin module)
    produces a new function object but the same (module, qualname)."""
    if fn is None:
        return None
    return (getattr(fn, "__module__", None),
            getattr(fn, "__qualname__", repr(fn)))


def _cls_id(cls):
    if cls is None:
        return None
    return (getattr(cls, "__module__", None),
            getattr(cls, "__qualname__", cls.__name__),
            tuple(getattr(cls, "_fields", ())))


def _spec_signature(sp: EstimatorSpec):
    """The comparison key for idempotent re-registration: identical specs
    (same definitions, even across a module reload) are a no-op; anything
    else is a conflict."""
    return (sp.kind, _callable_id(sp.factory), _cls_id(sp.state_cls),
            sp.linear, sp.join_capable, sp.stderr_kind,
            _callable_id(sp.fusion), _callable_id(sp.exact_oracle))


def register_spec(spec: EstimatorSpec) -> EstimatorSpec:
    """Register (or idempotently re-register) a kind's spec.

    Identical re-registration -- same kind, same factory/state-class
    definitions, same capability fields -- is a no-op, so plugin modules
    survive being imported twice (or reloaded).  A *conflicting*
    re-registration raises, naming both registrants.  A spec may also
    *complete* a partial prior registration: a state-class-only spec (the
    wire codec's channel) merges with a later factory registration for
    the same kind, and vice versa.
    """
    prev = _REGISTRY.get(spec.kind)
    if prev is None:
        _REGISTRY[spec.kind] = spec
        return spec
    if _spec_signature(prev) == _spec_signature(spec):
        # Idempotent -- but ADOPT the newcomer: after importlib.reload the
        # re-executed module's class/function objects are the live ones
        # (the module dict is updated in place, so factories registered
        # earlier already resolve names against the NEW definitions).
        # Keeping the stale objects would make decode-by-kind hand back a
        # class that `is not` the one fresh states carry.
        _REGISTRY[spec.kind] = spec
        return spec
    merged = _merge_specs(prev, spec)
    if merged is None:
        raise ValueError(
            f"estimator kind {spec.kind!r} already registered by "
            f"{prev.registrant} with a conflicting spec; refused "
            f"re-registration from {spec.registrant}")
    _REGISTRY[spec.kind] = merged
    return merged


def _merge_specs(prev: EstimatorSpec, new: EstimatorSpec):
    """Fill ``None`` fields of ``prev`` from ``new`` (and vice versa);
    ``None`` if any concrete field disagrees (a genuine conflict)."""
    updates = {}
    for f in ("factory", "state_cls", "linear", "join_capable",
              "stderr_kind", "fusion", "exact_oracle"):
        a, b = getattr(prev, f), getattr(new, f)
        if a is None and b is not None:
            updates[f] = b
        elif a is not None and b is not None:
            ident = _cls_id if f == "state_cls" else (
                _callable_id if callable(a) else (lambda x: x))
            if ident(a) != ident(b):
                return None
    return dataclasses.replace(prev, **updates) if updates else prev


def register(kind: str, factory: Callable, *, state_cls: type | None = None,
             linear: bool | None = None, join_capable: bool | None = None,
             stderr_kind: str | None = None, fusion: Callable | None = None,
             exact_oracle: Callable | None = None) -> EstimatorSpec:
    """Register an estimator kind (declaratively, once, for every layer).

    ``factory(sjpc_cfg, *, params=None, estimator_cfg=None)`` ->
    Estimator.  ``estimator_cfg`` overrides the kind's derived config.  The
    keyword fields populate the kind's :class:`EstimatorSpec`; omitted
    ones resolve from the instance (see :func:`spec_of`), so the legacy
    two-argument form keeps working.  Identical re-registration is a
    no-op; a conflicting one raises, naming both registrants.
    """
    return register_spec(EstimatorSpec(
        kind=kind, factory=factory, state_cls=state_cls, linear=linear,
        join_capable=join_capable, stderr_kind=stderr_kind, fusion=fusion,
        exact_oracle=exact_oracle,
        registrant=getattr(factory, "__module__", "?")))


def register_state_type(kind: str, cls: type) -> None:
    """Register the state NamedTuple class for ``kind`` (the wire codec's
    decode channel).  Merges into the kind's spec: idempotent for the
    same class, conflict (naming both registrants) otherwise."""
    prev = _REGISTRY.get(kind)
    if prev is not None and prev.state_cls is not None \
            and _cls_id(prev.state_cls) != _cls_id(cls):
        raise ValueError(
            f"state type for kind {kind!r} already registered as "
            f"{prev.state_cls.__name__} (by {prev.registrant}), not "
            f"{cls.__name__} (from {getattr(cls, '__module__', '?')})")
    register_spec(EstimatorSpec(
        kind=kind, state_cls=cls,
        registrant=getattr(cls, "__module__", "?")))


def spec(kind: str) -> EstimatorSpec:
    """The registered spec for ``kind`` (KeyError if unknown)."""
    if kind not in _REGISTRY:
        raise KeyError(
            f"unknown estimator kind {kind!r}; available: {available()}")
    return _REGISTRY[kind]


def spec_of(est: Estimator) -> EstimatorSpec:
    """The RESOLVED spec for an estimator instance: registered fields win;
    ``None`` capability fields fall back to the instance attributes.  For
    instances of unregistered kinds (ad-hoc subclasses in tests) this
    synthesizes a spec entirely from the instance."""
    kind = getattr(est, "kind", "abstract")
    sp = _REGISTRY.get(kind)
    if sp is None:
        sp = EstimatorSpec(kind=kind, registrant=type(est).__module__)
    updates = {}
    if sp.linear is None:
        updates["linear"] = bool(getattr(est, "linear", False))
    if sp.join_capable is None:
        updates["join_capable"] = bool(getattr(est, "supports_join", False))
    return dataclasses.replace(sp, **updates) if updates else sp


def state_type(kind: str) -> type:
    """The registered state NamedTuple class for ``kind`` (the wire
    codec's container type; KeyError if none registered)."""
    sp = _REGISTRY.get(kind)
    if sp is None or sp.state_cls is None:
        raise KeyError(
            f"no state type registered for estimator kind {kind!r}; "
            f"register_state_type() it (plugins: import the plugin module "
            f"on the decoding side too)")
    return sp.state_cls


def available() -> list[str]:
    """Kinds that can be instantiated (state-type-only registrations --
    a decode-side wire channel without a factory -- are excluded)."""
    return sorted(k for k, sp in _REGISTRY.items() if sp.factory is not None)


def make(kind: str, sjpc_cfg, *, params=None,
         estimator_cfg=None) -> Estimator:
    """Instantiate an estimator for a hash group.

    ``sjpc_cfg`` is the group's :class:`~repro.core.sjpc.SJPCConfig`; it
    defines (d, s, seed) for every kind and the byte budget competitors
    match (equal space by construction).  ``params`` carries the group's
    shared hash randomness (SJPC only).  ``estimator_cfg`` overrides the
    derived per-kind config.  The service registry caches one instance
    per (group, kind) so a group's streams share one engine and its jit
    caches.
    """
    sp = _REGISTRY.get(kind)
    if sp is None or sp.factory is None:
        raise KeyError(
            f"unknown estimator kind {kind!r}; available: {available()}")
    return sp.factory(sjpc_cfg, params=params, estimator_cfg=estimator_cfg)


def load_plugins(modules=None) -> list[str]:
    """Import plugin modules for their registration side effect.

    ``modules`` is an iterable of module names; default is the
    ``REPRO_PLUGINS`` environment variable (comma-separated), so services
    and benchmarks pick up plugin kinds without code changes.  Importing
    an already-imported module is a no-op (and re-registration of an
    identical spec is too), so this is safe to call repeatedly.
    """
    import importlib
    import os
    if modules is None:
        raw = os.environ.get("REPRO_PLUGINS", "")
        modules = [m for m in (p.strip() for p in raw.split(",")) if m]
    loaded = []
    for name in modules:
        importlib.import_module(name)
        loaded.append(name)
    return loaded


# ---------------------------------------------------------------------------
# Shared exact-replay oracle for pairwise-similarity kinds
# ---------------------------------------------------------------------------

def pairwise_exact_oracle(query_kind: str, records):
    """The exact g replay shared by every kind that estimates the paper's
    pairwise-similarity counts (DESIGN.md §15.4): given the mirrored
    record batches of a query's streams, return ``g(s)`` -- the exact
    number of candidate pairs at threshold ``s``.

    ``records`` is a tuple of per-stream ``(n, d)`` uint32 arrays: one
    entry for self-join queries, two for §6 joins.  Kinds whose estimand
    is NOT this g (a distinct counter, say) register their own oracle --
    or ``None``, which the auditor surfaces as a reason-labeled skip.
    """
    from repro.core import exact
    if query_kind == "join":
        a, b = records
        counts = np.asarray(exact.brute_force_join_counts(a, b))
        return lambda s: float(counts[s:].sum())
    recs = records[0]
    x = np.asarray(exact.exact_pair_counts(recs))
    n = recs.shape[0]
    return lambda s: float(x[s:].sum() + n)
