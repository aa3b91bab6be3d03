"""SJPC -- Similarity Self-Join Pair Count (the paper's Algorithm 1).

One-pass, sublinear-space estimation of g_s = #{record pairs at least
s-similar} for a stream of d-column records:

  Step 1  per record, per level k in [s, d]: sample ~r*C(d,k) column
          combinations, fingerprint each projected sub-value, insert into
          the level's Fast-AGMS sketch.
  Step 2  Y_k = sketch F2 estimate of the level-k sub-value stream.
  Step 3  invert the lattice system (Eq. 4):
              X_k = (Y_k - r*C(d,k)*n) / r^2  -  sum_{j>k} C(j,k) X_j
          and return sum_k X_k (+ n for self-pairs -> g_s).

State is a pytree of int32 counters (levels, t, w) -- linear, so
data-parallel shards merge by addition (``jax.lax.psum``) and merging can be
deferred arbitrarily.  ``update`` is pure jnp (jit/shard_map-safe); the
Pallas-accelerated path swaps in kernels.ops.sketch_update_fused.

The similarity *join* estimator (paper §6, Eq. 7) works on two streams
sketched with the *same* hash parameters; Y_k is then the sketch inner
product and the inversion drops the self-pair term.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import NamedTuple, Sequence

import numpy as np
import jax
import jax.numpy as jnp

from repro.obs.trace import child

from . import projections as proj
from . import sketch as sk
from .fingerprint import make_fingerprint_bases, subvalue_fingerprints
from .hashing import cw_hash_pair, hash_bucket, hash_sign


@dataclasses.dataclass(frozen=True)
class SJPCConfig:
    """Static configuration (hashable; safe to close over in jit)."""
    d: int                  # record dimensionality (number of columns)
    s: int                  # similarity threshold (count of equal columns)
    ratio: float = 0.5      # projection sampling ratio r
    width: int = 1024       # sketch width w (counters per row, pow2)
    depth: int = 3          # sketch depth t (median of t estimates)
    seed: int = 0x5A5A

    def __post_init__(self):
        assert 1 <= self.s <= self.d, "need 1 <= s <= d"
        assert 0 < self.ratio <= 1.0
        assert self.width & (self.width - 1) == 0

    @property
    def num_levels(self) -> int:
        return self.d - self.s + 1

    def level_k(self, idx: int) -> int:
        return self.s + idx

    @property
    def counters_bytes(self) -> int:
        return self.num_levels * self.depth * self.width * 4


class SJPCParams(NamedTuple):
    """Hash/fingerprint randomness (arrays; checkpointed with the state)."""
    bucket_coeffs: jax.Array   # (levels, t, 2, 4) uint32
    sign_coeffs: jax.Array     # (levels, t, 2, 4) uint32
    fp_bases: jax.Array        # (2,) uint32


class SJPCState(NamedTuple):
    """Linear sketch state.  counters: (levels, t, w) int32; n: records seen."""
    counters: jax.Array
    n: jax.Array               # float32 scalar (exact for n < 2^24; int path below)
    step: jax.Array            # int32 PRNG folding counter


def init(cfg: SJPCConfig) -> tuple[SJPCParams, SJPCState]:
    rng = np.random.default_rng(cfg.seed)
    params = sk.make_sketch_params(rng, cfg.depth, stack=(cfg.num_levels,))
    fp_bases = make_fingerprint_bases(rng)
    state = SJPCState(
        counters=sk.empty_counters(cfg.depth, cfg.width, stack=(cfg.num_levels,)),
        n=jnp.zeros((), jnp.float32),
        step=jnp.zeros((), jnp.int32),
    )
    return SJPCParams(params.bucket_coeffs, params.sign_coeffs, jnp.asarray(fp_bases)), state


def _level_tables(cfg: SJPCConfig):
    return proj.lattice(cfg.d, cfg.s)


def update(cfg: SJPCConfig, params: SJPCParams, state: SJPCState, values,
           key: jax.Array | None = None, *, update_fn=None,
           row_mask: jax.Array | None = None) -> SJPCState:
    """Absorb a batch of records.  values: (B, d) uint32/int32.

    ``update_fn(counters, fp1, fp2, level_params, weights) -> counters`` lets
    callers swap the reference jnp update for the Pallas kernel; default is
    the reference.

    ``row_mask`` ((B,) int32/bool, optional) marks valid rows; rows with mask
    0 contribute nothing to the counters or to ``n``.  This is what lets the
    service ingest pipeline pad per-tenant batches to a shared static shape
    and still produce counters identical to an unpadded per-stream update.
    """
    values = jnp.asarray(values).astype(jnp.uint32)
    B = values.shape[0]
    if key is None:
        key = jax.random.fold_in(jax.random.PRNGKey(cfg.seed ^ 0xC0FFEE), state.step)
    update_fn = update_fn or sk.sketch_update
    if row_mask is not None:
        row_mask = jnp.asarray(row_mask).astype(jnp.int32).reshape(B)

    counters = state.counters
    new_counters = []
    for idx, level in enumerate(_level_tables(cfg)):
        lkey = jax.random.fold_in(key, idx)
        weights = proj.sample_combo_weights(lkey, B, level.num, cfg.ratio)
        if row_mask is not None:
            weights = weights * row_mask[:, None]
        fp1, fp2 = subvalue_fingerprints(
            values, jnp.asarray(level.masks), jnp.asarray(level.ids), params.fp_bases)
        level_params = sk.SketchParams(params.bucket_coeffs[idx], params.sign_coeffs[idx])
        new_counters.append(update_fn(counters[idx], fp1, fp2, level_params, weights))
    n_new = jnp.float32(B) if row_mask is None else row_mask.sum().astype(jnp.float32)
    # step counts rounds that CARRIED data: a fully-masked (padding-only)
    # round is a content no-op and consumes no randomness, so it must not
    # advance the replay/bootstrap coordinate either -- a stream riding
    # along fully masked in a busy cohort stays bit-identical to a solo
    # replay of its own record rounds (ingest.py's determinism contract)
    step_inc = (jnp.int32(1) if row_mask is None
                else (n_new > 0).astype(jnp.int32))
    return SJPCState(
        counters=jnp.stack(new_counters),
        n=state.n + n_new,
        step=state.step + step_inc,
    )


def _sample_level_weights(cfg: SJPCConfig, key: jax.Array, batch: int,
                          row_mask: jax.Array | None):
    """Per-level (B, C(d,k)) sampling weights, exactly as ``update`` draws
    them (same fold-in order, same uniforms) -- the fused paths reuse this so
    they stay bit-identical to the reference path under a shared key."""
    weights = []
    for idx, level in enumerate(_level_tables(cfg)):
        lkey = jax.random.fold_in(key, idx)
        w = proj.sample_combo_weights(lkey, batch, level.num, cfg.ratio)
        if row_mask is not None:
            w = w * row_mask[:, None]
        weights.append(w)
    return weights


def update_fused(cfg: SJPCConfig, params: SJPCParams, state: SJPCState, values,
                 key: jax.Array | None = None, *,
                 row_mask: jax.Array | None = None,
                 use_pallas: bool | None = None,
                 interpret: bool | None = None) -> SJPCState:
    """``update``, but as the fused ingest hot path.

    Same contract and **bit-identical counters** as :func:`update` given the
    same ``key`` (asserted in tests/test_fused_ingest.py); the difference is
    execution shape.  On TPU backends (or ``use_pallas=True``) the whole
    record batch runs through the fused Pallas kernel -- fingerprints
    produced in VMEM feed the one-hot MXU contraction directly, one launch
    for every lattice level.  Elsewhere it runs the fused pure-jnp
    formulation: ONE masked-Horner fingerprint pass over the concatenated
    combination table and ONE scatter into the flattened (L, t, w) counter
    block (per-combination hash coefficients gathered by level), which
    replaces the per-level chain of 2L+L dispatching ops of the reference
    path with 3 large ones.
    """
    values = jnp.asarray(values).astype(jnp.uint32)
    B = values.shape[0]
    if key is None:
        key = jax.random.fold_in(jax.random.PRNGKey(cfg.seed ^ 0xC0FFEE), state.step)
    if row_mask is not None:
        row_mask = jnp.asarray(row_mask).astype(jnp.int32).reshape(B)
    level_weights = _sample_level_weights(cfg, key, B, row_mask)
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"

    if use_pallas:
        from repro.kernels.fused_ingest import fused_ingest_pallas
        pad = proj.padded_lattice(cfg.d, cfg.s)
        wpad = jnp.stack(
            [jnp.pad(w, ((0, 0), (0, pad.m_max - w.shape[1])))
             for w in level_weights], axis=1)                    # (B, L, m_max)
        if interpret is None:
            interpret = jax.default_backend() != "tpu"
        counters = fused_ingest_pallas(
            state.counters, values, jnp.asarray(pad.masks),
            jnp.asarray(pad.ids), params.fp_bases,
            params.bucket_coeffs, params.sign_coeffs, wpad,
            interpret=interpret)
    else:
        cat = proj.concat_lattice(cfg.d, cfg.s)
        t, w = cfg.depth, cfg.width
        fp1, fp2 = subvalue_fingerprints(
            values, jnp.asarray(cat.masks), jnp.asarray(cat.ids),
            params.fp_bases)                                     # (B, m_total)
        wcat = jnp.concatenate(level_weights, axis=1)            # (B, m_total)
        level_of = jnp.asarray(cat.level_of)                     # (m_total,)
        # per-combination coefficients, depth-major for broadcasting:
        # (t, 1, m_total, 2, 4) against fp (B, m_total) -> hashes (t, B, m_total)
        bcoef = jnp.moveaxis(params.bucket_coeffs[level_of], 1, 0)[:, None]
        scoef = jnp.moveaxis(params.sign_coeffs[level_of], 1, 0)[:, None]
        bucket = hash_bucket(cw_hash_pair(fp1, fp2, bcoef), w)
        sign = hash_sign(cw_hash_pair(fp1, fp2, scoef)) * wcat[None]
        plane = level_of[None, None, :] * t + jnp.arange(t, dtype=jnp.int32)[:, None, None]
        counters = (state.counters.reshape(-1)
                    .at[plane * w + bucket].add(sign)
                    .reshape(state.counters.shape))

    n_new = jnp.float32(B) if row_mask is None else row_mask.sum().astype(jnp.float32)
    # data-carrying rounds only (see `update`): padding-only rounds must not
    # advance the replay coordinate
    step_inc = (jnp.int32(1) if row_mask is None
                else (n_new > 0).astype(jnp.int32))
    return SJPCState(counters=counters, n=state.n + n_new,
                     step=state.step + step_inc)


def merge(a: SJPCState, b: SJPCState) -> SJPCState:
    """Linearity: sketches of disjoint sub-streams add.

    ``step`` feeds ``jax.random.fold_in`` to derive per-batch sampling keys,
    so the merged step must be a value no shard has already folded in.
    ``maximum`` is wrong there: two shards merged at equal step k
    would hand the merged sketch step k -- the exact fold-in key a shard that
    keeps ingesting would use for its own next batch, correlating the
    supposedly independent projection samples (and, under tree merges,
    replaying keys the shards already consumed).  The *sum* of the step
    counters dominates every step either side has folded in, so post-merge
    updates draw fresh keys.  Shards that keep ingesting concurrently after
    a merge (forked lineages) should pass explicit ``key``s to ``update``
    instead of relying on the step counter.
    """
    return SJPCState(a.counters + b.counters, a.n + b.n, a.step + b.step)


def subtract(a: SJPCState, b: SJPCState) -> SJPCState:
    """Linearity, the other direction: remove the sub-stream ``b`` sketched
    into ``a`` (sliding-window expiry; ``b`` must be a sub-stream of ``a``).

    ``step`` keeps ``a.step``: expiry removes old *data*, not PRNG history --
    the keys ``b`` consumed were consumed, and reusing them would correlate
    a re-ingest of the expired epoch with live data.
    """
    return SJPCState(a.counters - b.counters, a.n - b.n, a.step)


def all_reduce(state: SJPCState, axis_names) -> SJPCState:
    """Merge device-local sketches across mesh axes (inside shard_map/pjit)."""
    return SJPCState(
        counters=jax.lax.psum(state.counters, axis_names),
        n=jax.lax.psum(state.n, axis_names),
        step=state.step,
    )


_SHARD_SALT = 0x5A4D


class ShardedIngest:
    """Device-sharded ingest executor with deferred merges.

    Exploits sketch linearity for data parallelism: each record micro-batch
    is split across ``num_shards`` shards, every shard folds its slice into a
    shard-local *delta* sketch, and no cross-shard communication happens on
    the ingest path at all.  ``merged()`` pays the single cross-device
    reduction (``lax.psum`` semantics, executed as one sum over the shard
    axis) for however many micro-batches were absorbed since construction --
    N micro-batches cost one reduction, not N.

    When the runtime exposes at least ``num_shards`` devices the per-shard
    update runs inside :func:`jax.shard_map` over a 1-D 'shards'
    mesh with the delta states and record slices sharded on the leading
    axis; with fewer devices the identical computation runs as a ``vmap``
    over the shard axis (bit-identical counters -- the update is integer
    arithmetic, so tests exercise either path interchangeably).

    Per-shard sampling keys are ``fold_in(batch_key, shard)``; replaying the
    same slices with the same keys through plain :func:`update` rebuilds any
    shard bit-exactly (the conformance contract, see tests).
    """

    def __init__(self, cfg: SJPCConfig, params: SJPCParams,
                 state: SJPCState | None = None, *, num_shards: int | None = None,
                 use_fused: bool = True, use_pallas: bool | None = None,
                 interpret: bool | None = None, devices=None):
        devices = list(devices if devices is not None else jax.local_devices())
        self.num_shards = int(num_shards or len(devices))
        assert self.num_shards >= 1
        self.cfg, self.params = cfg, params
        self.base = state if state is not None else init(cfg)[1]
        self.use_fused = use_fused
        self.use_pallas = use_pallas
        self.interpret = interpret
        self.micro_batches = 0
        self.merges = 0

        self._mesh = None
        if self.num_shards > 1 and len(devices) >= self.num_shards:
            from jax.sharding import Mesh
            self._mesh = Mesh(np.asarray(devices[:self.num_shards]), ("shards",))
        self.deltas = self._zero_deltas()
        self._step_fn = self._build_step_fn()

    @property
    def mapped(self) -> bool:
        """True when shard updates run under shard_map on a device mesh
        (False: single-device vmap with identical numbers)."""
        return self._mesh is not None

    def _zero_deltas(self) -> SJPCState:
        zeros = SJPCState(
            counters=jnp.zeros((self.num_shards,) + tuple(self.base.counters.shape),
                               jnp.int32),
            n=jnp.zeros((self.num_shards,), jnp.float32),
            step=jnp.zeros((self.num_shards,), jnp.int32))
        if self._mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            shard = NamedSharding(self._mesh, P("shards"))
            zeros = jax.tree_util.tree_map(
                lambda x: jax.device_put(x, shard), zeros)
        return zeros

    def reset(self, base: SJPCState | None = None) -> None:
        """Drop accumulated deltas (and optionally rebase), keeping the
        compiled step function -- unlike constructing a fresh executor."""
        if base is not None:
            self.base = base
        self.deltas = self._zero_deltas()
        self.micro_batches = 0

    # ------------------------------------------------------------------
    def _build_step_fn(self):
        cfg, params = self.cfg, self.params
        update_one = functools.partial(
            update_fused if self.use_fused else update, cfg, params)
        kwargs = ({"use_pallas": self.use_pallas, "interpret": self.interpret}
                  if self.use_fused else {})

        def shard_step(delta, values, row_mask, key):
            return update_one(delta, values, key=key, row_mask=row_mask, **kwargs)

        if self._mesh is None:
            def step(deltas, values, row_mask, keys):
                return jax.vmap(shard_step)(deltas, values, row_mask, keys)
            return jax.jit(step)

        from jax.sharding import PartitionSpec as P

        def local(deltas, values, row_mask, keys):
            # local views carry a leading shard axis of size 1
            st = shard_step(
                SJPCState(deltas.counters[0], deltas.n[0], deltas.step[0]),
                values[0], row_mask[0], keys[0])
            return SJPCState(st.counters[None], st.n[None], st.step[None])

        step = jax.shard_map(local, mesh=self._mesh,
                             in_specs=(P("shards"), P("shards"), P("shards"),
                                       P("shards")),
                             out_specs=P("shards"), check_vma=False)
        return jax.jit(step)

    # ------------------------------------------------------------------
    def ingest(self, values, key: jax.Array | None = None,
               row_mask=None) -> None:
        """Absorb one micro-batch: split across shards, update shard-local
        deltas, defer the merge.  values (B, d); rows pad to a shard
        multiple with mask 0."""
        values = np.ascontiguousarray(np.asarray(values, dtype=np.uint32))
        B = values.shape[0]
        if key is None:
            key = jax.random.fold_in(
                jax.random.PRNGKey(self.cfg.seed ^ _SHARD_SALT),
                self.micro_batches)
        mask = (np.ones((B,), np.int32) if row_mask is None
                else np.asarray(row_mask, np.int32).reshape(B))
        pad = (-B) % self.num_shards
        if pad:
            values = np.pad(values, ((0, pad), (0, 0)))
            mask = np.pad(mask, (0, pad))
        per = values.shape[0] // self.num_shards
        keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(
            jnp.arange(self.num_shards))
        self.deltas = self._step_fn(
            self.deltas,
            jnp.asarray(values.reshape(self.num_shards, per, self.cfg.d)),
            jnp.asarray(mask.reshape(self.num_shards, per)), keys)
        self.micro_batches += 1

    def merged(self) -> SJPCState:
        """The single deferred cross-shard reduction: base + sum of deltas.

        ``step`` follows :func:`merge` semantics (sum over shards) so
        post-merge updates can never replay a shard's consumed fold-in keys.
        """
        self.merges += 1
        return SJPCState(
            counters=self.base.counters + self.deltas.counters.sum(axis=0),
            n=self.base.n + self.deltas.n.sum(),
            step=self.base.step + self.deltas.step.sum(),
        )

    def shard_key(self, micro_batch: int, shard: int) -> jax.Array:
        """The sampling key shard ``shard`` folded in for micro-batch
        ``micro_batch`` (the offline-replay coordinate)."""
        base = jax.random.fold_in(
            jax.random.PRNGKey(self.cfg.seed ^ _SHARD_SALT), micro_batch)
        return jax.random.fold_in(base, shard)


# ---------------------------------------------------------------------------
# Step 2+3: estimation (host-side numpy; cheap, exact in float64)
# ---------------------------------------------------------------------------

def level_f2(state: SJPCState) -> np.ndarray:
    """Y_k for k = s..d, int64-exact median-of-rows F2."""
    counters = np.asarray(jax.device_get(state.counters))
    return sk.np_estimate_f2_exact(counters).astype(np.float64)


def f2_to_pair_count(d: int, s: int, n: float, r: float, y: Sequence[float],
                     *, clamp: bool = True) -> np.ndarray:
    """Procedure f2toPairCnt of Algorithm 1 (Eq. 4 inversion).

    ``y[i]`` is the level-(s+i) self-join size estimate.  Returns X[s..d]
    (estimated #pairs exactly k-similar, ordered-pair convention).

    NOTE (paper erratum): Algorithm 1 line 34 subtracts ``r^2 C(j,k) X[j]``
    from the *r^2-scaled* accumulator (division by r^2 happens only at line
    38), which applies the r^2 correction twice and biases estimates upward
    for r < 1.  Multiplying Eq. 4 through by r^2 shows the scaled recursion
    must subtract ``C(j,k) X_scaled[j]`` -- that is what Lemma 4 proves and
    what we implement (the two coincide at r = 1; verified unbiased in
    tests/test_sjpc_estimator.py).
    """
    X = np.zeros(d + 1, dtype=np.float64)     # r^2-scaled accumulators
    for k in range(d, s - 1, -1):
        acc = float(y[k - s]) - math.comb(d, k) * r * n
        for j in range(k + 1, d + 1):
            acc -= math.comb(j, k) * X[j]
        if clamp:
            acc = max(acc, 0.0)
        X[k] = acc
    X = X / (r * r)
    return X[s:]


class SJPCEstimate(NamedTuple):
    x: np.ndarray          # X[s..d]: per-level k-similar pair estimates
    pairs: float           # sum_k X_k (similar pairs, ordered, excl. self)
    g_s: float             # pairs + n (the paper's g_s, Eq. 2)
    y: np.ndarray          # raw level F2 estimates (diagnostics)
    n: float


def estimate(cfg: SJPCConfig, state: SJPCState, *, clamp: bool = True) -> SJPCEstimate:
    y = level_f2(state)
    n = float(jax.device_get(state.n))
    x = f2_to_pair_count(cfg.d, cfg.s, n, cfg.ratio, y, clamp=clamp)
    pairs = float(x.sum())
    return SJPCEstimate(x=x, pairs=pairs, g_s=pairs + n, y=y, n=n)


# ---------------------------------------------------------------------------
# Similarity join (two streams; paper §6)
# ---------------------------------------------------------------------------

def join_level_inner(state_a: SJPCState, state_b: SJPCState) -> np.ndarray:
    ca = np.asarray(jax.device_get(state_a.counters))
    cb = np.asarray(jax.device_get(state_b.counters))
    return sk.np_estimate_inner_exact(ca, cb).astype(np.float64)


def inner_to_join_count(d: int, s: int, r: float, y: Sequence[float],
                        *, clamp: bool = True) -> np.ndarray:
    """Eq. 7: X_k = Y_k / r^2 - sum_{j>k} C(j,k) X_j (no self-pair term)."""
    X = np.zeros(d + 1, dtype=np.float64)
    for k in range(d, s - 1, -1):
        acc = float(y[k - s]) / (r * r)
        for j in range(k + 1, d + 1):
            acc -= math.comb(j, k) * X[j]
        if clamp:
            acc = max(acc, 0.0)
        X[k] = acc
    return X[s:]


def estimate_join(cfg: SJPCConfig, state_a: SJPCState, state_b: SJPCState,
                  *, clamp: bool = True) -> SJPCEstimate:
    """Similarity join size of two streams sketched with identical params."""
    y = join_level_inner(state_a, state_b)
    x = inner_to_join_count(cfg.d, cfg.s, cfg.ratio, y, clamp=clamp)
    pairs = float(x.sum())
    return SJPCEstimate(x=x, pairs=pairs, g_s=pairs, y=y,
                        n=float(jax.device_get(state_a.n)))


# ---------------------------------------------------------------------------
# Batched estimation: every (stream, threshold) cell from ONE compiled call
# ---------------------------------------------------------------------------

class SJPCBatchEstimate(NamedTuple):
    """Estimates for N same-config sketches at EVERY threshold k = s..d.

    Column i answers threshold k = s + i; ``g[:, i]`` is the suffix sum
    ``x[:, i:].sum(axis=1)`` (+ n for self-joins), so one batch holds the
    full all-thresholds table of every stream.
    """
    x: np.ndarray              # (N, L) per-level k-similar pair estimates
    g: np.ndarray              # (N, L) g_k per threshold (join: join size)
    y: np.ndarray              # (N, L) raw level F2 / inner estimates
    n: np.ndarray              # (N,) records; joins: (N, 2) per side
    stderr: np.ndarray         # (N, L) absolute 1-sigma bound (Theorem 2)
    stderr_offline: np.ndarray  # (N, L) sampling-only bound (Theorem 1)


@functools.partial(jax.jit, static_argnames=("cfg", "clamp", "join",
                                             "use_pallas", "interpret"))
def _estimate_batch_core(cfg: SJPCConfig, counters_a, counters_b, n, *,
                         clamp: bool, join: bool, use_pallas, interpret):
    """The fused query dispatch: stacked (N, L, t, w) counters -> per-stream
    (y, x, g) arrays, one compiled call.

    The per-level Python loops of the reference path (``level_f2`` +
    ``f2_to_pair_count`` / ``inner_to_join_count``) become: one fused moment
    launch over every (stream, level, depth-row), a median over the depth
    axis, and the Eq. 4 / Eq. 7 recursion unrolled over the L static levels
    (vectorized over streams).  f32 is exact while intermediates stay
    exact-integer (< 2^24) -- true for the tested magnitudes; conformance vs
    the float64 numpy oracle is asserted to 1e-6 beyond that
    (tests/test_fused_query.py).
    """
    from repro.kernels.ops import fused_query
    d, s, r = cfg.d, cfg.s, cfg.ratio
    moments = fused_query(counters_a, counters_b, use_pallas=use_pallas,
                          interpret=interpret)             # (N, L, t)
    y = jnp.median(moments, axis=-1)                       # (N, L)

    # Eq. 4 (self; r^2-scaled accumulators, one division at the end) or
    # Eq. 7 (join) -- identical recursion orders to the numpy reference.
    X: dict[int, jax.Array] = {}
    for k in range(d, s - 1, -1):
        if join:
            acc = y[:, k - s] / jnp.float32(r * r)
        else:
            acc = y[:, k - s] - jnp.float32(math.comb(d, k) * r) * n
        for j in range(k + 1, d + 1):
            acc = acc - jnp.float32(math.comb(j, k)) * X[j]
        if clamp:
            acc = jnp.maximum(acc, 0.0)
        X[k] = acc
    x = jnp.stack([X[k] for k in range(s, d + 1)], axis=1)  # (N, L)
    if not join:
        x = x / jnp.float32(r * r)
    g = jnp.cumsum(x[:, ::-1], axis=1)[:, ::-1]             # suffix sums
    if not join:
        g = g + n[:, None]
    return y, x, g


def _batch_bounds(cfg: SJPCConfig, n: np.ndarray,
                  g: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Vectorized Theorem 1/2 plug-in bounds, float64, same op order as the
    scalar ``offline_variance_bound`` / ``online_variance_bound`` so the
    batched stderr matches the per-stream reference bit for bit.
    n (N,), g (N, L) -> (online, offline) absolute 1-sigma bounds (N, L)."""
    d, r, w = cfg.d, cfg.ratio, cfg.width
    lead = np.array([math.comb(d, k) ** 2 / r * math.comb(2 * (d - k), d - k)
                     for k in range(cfg.s, d + 1)], dtype=np.float64)
    g = np.asarray(g, np.float64)
    n = np.asarray(n, np.float64).reshape(-1, 1)
    with np.errstate(divide="ignore", invalid="ignore"):
        off = np.sqrt(lead[None, :] / g) * g
        on = np.sqrt(lead[None, :] * ((1 + 2 / w) / g
                                      + (2 / w) * (1 + n / (r * g)) ** 2)) * g
    pos = g > 0
    return np.where(pos, on, 0.0), np.where(pos, off, 0.0)


def _stack_counters(counters) -> jax.Array:
    counters = jnp.asarray(counters)
    assert counters.ndim == 4, \
        f"expected stacked (N, levels, t, w) counters; got {counters.shape}"
    return counters


def estimate_batch(cfg: SJPCConfig, counters, n, *, clamp: bool = True,
                   use_pallas: bool | None = None,
                   interpret: bool | None = None) -> SJPCBatchEstimate:
    """Self-join estimates for N stacked sketches, all thresholds at once.

    counters: (N, levels, t, w) int32 (stacked ``SJPCState.counters`` of
    streams sharing one config/params draw); n: (N,) records per stream.
    """
    counters = _stack_counters(counters)
    n = jnp.asarray(n, jnp.float32).reshape(counters.shape[0])
    y, x, g = _estimate_batch_core(cfg, counters, counters, n, clamp=clamp,
                                   join=False, use_pallas=use_pallas,
                                   interpret=interpret)
    y, x, g, n = (np.asarray(jax.device_get(a), np.float64)
                  for a in (y, x, g, n))
    with child("query.bounds", streams=counters.shape[0]):
        on, off = _batch_bounds(cfg, n, g)
    return SJPCBatchEstimate(x=x, g=g, y=y, n=n, stderr=on, stderr_offline=off)


def estimate_join_batch(cfg: SJPCConfig, counters_a, counters_b, n_a, n_b, *,
                        clamp: bool = True, use_pallas: bool | None = None,
                        interpret: bool | None = None) -> SJPCBatchEstimate:
    """Join sizes for N stacked sketch PAIRS (identical hash params per
    pair), all thresholds at once.  Error bars follow the reference proxy
    (DESIGN.md §10.4): the self-join bound at n = max(n_a, n_b) with
    max(estimate, 1) plugged in."""
    counters_a = _stack_counters(counters_a)
    counters_b = _stack_counters(counters_b)
    N = counters_a.shape[0]
    n_a = jnp.asarray(n_a, jnp.float32).reshape(N)
    n_b = jnp.asarray(n_b, jnp.float32).reshape(N)
    y, x, g = _estimate_batch_core(cfg, counters_a, counters_b, n_a,
                                   clamp=clamp, join=True,
                                   use_pallas=use_pallas, interpret=interpret)
    y, x, g, n_a, n_b = (np.asarray(jax.device_get(a), np.float64)
                         for a in (y, x, g, n_a, n_b))
    with child("query.bounds", streams=N):
        on, off = _batch_bounds(cfg, np.maximum(n_a, n_b),
                                np.maximum(g, 1.0))
    return SJPCBatchEstimate(x=x, g=g, y=y, n=np.stack([n_a, n_b], axis=1),
                             stderr=on, stderr_offline=off)


# ---------------------------------------------------------------------------
# Analytical bounds (Theorems 1-3) -- used in tests and EXPERIMENTS.md
# ---------------------------------------------------------------------------

def offline_variance_bound(d: int, s: int, r: float, g_s: float) -> float:
    """Theorem 1: var(G_s / g_s) <= C(d,s)^2 (1/r) C(2(d-s), d-s) / g_s."""
    return math.comb(d, s) ** 2 / r * math.comb(2 * (d - s), d - s) / g_s


def online_variance_bound(d: int, s: int, r: float, w: int, n: float, g_s: float) -> float:
    """Theorem 2 (depth-1 sketch)."""
    lead = math.comb(d, s) ** 2 / r * math.comb(2 * (d - s), d - s)
    return lead * ((1 + 2 / w) / g_s + (2 / w) * (1 + n / (r * g_s)) ** 2)
