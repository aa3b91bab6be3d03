"""Streaming uniform record sampling behind the Estimator protocol.

The paper's one-pass competitor (§2.1, Fig. 8): keep R records chosen
uniformly without replacement from the stream (Vitter's Algorithm R), and
estimate x[k] as the sample's all-pairs similarity histogram scaled by
n(n-1)/(m(m-1)).  PRs 0-3 carried this only as an offline batch function
(``baselines.random_sampling_pair_counts``); this module is the *served*
version: state is a fixed-shape pytree, ingest is one jit'd vectorized
dispatch per flush, and the query hot path -- previously O(R^2 d) host
numpy -- is the fused all-pairs kernel (kernels/fused_pairs.py).

Vectorized Algorithm R: record with global arrival index g (0-based) is
accepted with probability min(1, R/(g+1)) into a uniform random slot;
within a batch all accept/slot draws are independent given the starting
count, so the whole batch resolves in one pass -- per slot, the *latest*
accepted candidate wins (a scatter-max over arrival order), which is
exactly sequential processing.  Distributional equivalence to offline
uniform sampling is pinned statistically in tests/test_estimators.py.

Epoch algebra: inserted items are tagged with the state's ``sid``
(provenance).  ``merge`` is the deterministic weighted union of
base.merge_tagged_samples (``backing > 0`` folds into an expanded total
-- the window's backing-epoch refill, DESIGN.md §14.2); ``subtract(a,
b)`` drops a's items tagged with b's sid -- exact for the per-epoch
states the sliding window hands it (dropping one component of a uniform
sample of a union leaves a uniform sample of the rest), at the honest
streaming cost that expired slots cannot be refilled from data the
sample never kept.

Error bars: the bootstrap-with-Serfling stderr of
estimators/uncertainty.py (stderr_kind "bootstrap"), with every
replicate histogram riding the fused kernel's N axis in one launch.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import exact
from repro.core.sjpc import SJPCConfig
from repro.obs.trace import child

from . import uncertainty
from .base import (EstimateTable, Estimator, merge_tagged_samples,
                   pairwise_exact_oracle, register, scan_rounds)

_MERGE_SALT = 0x7E5E4B01


@dataclasses.dataclass(frozen=True)
class ReservoirConfig:
    d: int                   # record dimensionality
    s: int                   # lowest queryable threshold
    capacity: int            # reservoir slots R
    seed: int = 0x5A5A

    def __post_init__(self):
        assert 1 <= self.s <= self.d, "need 1 <= s <= d"
        assert self.capacity >= 1, "reservoir needs at least one slot"


class ReservoirState(NamedTuple):
    items: jax.Array         # (R, d) uint32 stored records
    tags: jax.Array          # (R,) int32 provenance sid; -1 = empty slot
    n: jax.Array             # int32 records seen.  Exact integer on
    #   purpose: Algorithm R's acceptance probability R/(g+1) needs the
    #   true arrival index (a float32 n freezes at 2^24 and would skew
    #   retention toward recent records); int32 is exact to 2^31.
    sid: jax.Array           # int32 provenance tag for new insertions
    step: jax.Array          # int32 PRNG folding counter


def reservoir_accept(key, n0, mask, capacity: int):
    """One batch of vectorized Algorithm R bookkeeping.

    mask (B,) int32 marks candidate rows; ``n0`` (int32 scalar) is the
    stream count before the batch.  Returns (win (R,) bool, src (R,)
    int32 batch row feeding each winning slot, n_new): per slot the
    latest accepted candidate wins, which is bit-equivalent to processing
    the batch sequentially.  Shared by the record reservoir here and the
    stratified pair reservoirs of estimators.lsh_ss.
    """
    B = mask.shape[0]
    maskb = mask != 0
    pos = jnp.cumsum(mask.astype(jnp.int32)) - 1       # index among candidates
    gidx = n0 + pos                                    # global arrival index
    ku, ks = jax.random.split(key)
    # acceptance w.p. capacity/(gidx+1), decided on INTEGERS: draw a
    # uniform arrival rank r in [0, gidx] and accept iff r < capacity.
    # The float form u * (gidx+1) < capacity loses exactness once gidx
    # crosses 2^24 (f32 rounds adjacent arrival indices together, skewing
    # retention on long streams -- the drift the int32 ``n`` comment
    # guards against); the integer draw is exact to the int32 range.
    rank = jax.random.randint(ku, (B,), 0, jnp.maximum(gidx + 1, 1))
    rand_slot = jax.random.randint(ks, (B,), 0, capacity)
    accept = maskb & ((gidx < capacity) | (rank < capacity))
    slot = jnp.where(gidx < capacity, jnp.clip(gidx, 0, capacity - 1),
                     rand_slot)
    order = jnp.where(accept, pos, -1)
    best = jnp.full((capacity,), -1, jnp.int32).at[slot].max(order)
    # map winning candidate index -> batch row (candidate indices are
    # unique among masked rows; masked-out rows scatter into the spare
    # B-th slot that is never read)
    row_of = jnp.zeros((B + 1,), jnp.int32) \
        .at[jnp.where(maskb, pos, B)].set(jnp.arange(B, dtype=jnp.int32))
    win = best >= 0
    src = jnp.take(row_of, jnp.clip(best, 0, B))
    return win, src, n0 + jnp.sum(mask.astype(jnp.int32))


class ReservoirEstimator(Estimator):
    kind = "reservoir"
    linear = False
    supports_join = False

    def __init__(self, cfg: ReservoirConfig, *,
                 bootstrap_replicates: int = uncertainty.DEFAULT_REPLICATES,
                 bootstrap_item_cap: int = uncertainty.DEFAULT_ITEM_CAP):
        self.cfg = cfg
        # bootstrap error bars (0 replicates disables -> stderr_kind
        # "none"); a capacity-1 reservoir can never hold a pair, so its
        # bars would be identically zero -- disable rather than mislabel
        if bootstrap_replicates == 1:
            raise ValueError("bootstrap_replicates must be 0 (disabled) "
                             "or >= 2 (a std needs two replicates)")
        self.bootstrap = (int(bootstrap_replicates) if cfg.capacity >= 2
                          else 0)
        self.bootstrap_cap = int(bootstrap_item_cap)
        self._rounds_fn = jax.jit(
            functools.partial(scan_rounds, self._ingest_one))

    @property
    def d(self) -> int:
        return self.cfg.d

    @property
    def s(self) -> int:
        return self.cfg.s

    @property
    def seed(self) -> int:
        return self.cfg.seed

    def memory_bytes(self) -> int:
        # items + tags; n/sid/step are O(1) scalars
        return self.cfg.capacity * (self.cfg.d + 1) * 4

    # -- protocol ------------------------------------------------------
    def init(self, sid: int = 0) -> ReservoirState:
        R, d = self.cfg.capacity, self.cfg.d
        return ReservoirState(
            items=jnp.zeros((R, d), jnp.uint32),
            tags=jnp.full((R,), -1, jnp.int32),
            n=jnp.zeros((), jnp.int32),
            sid=jnp.asarray(sid, jnp.int32),
            step=jnp.zeros((), jnp.int32))

    def _ingest_one(self, state: ReservoirState, values, mask,
                    key) -> ReservoirState:
        values = values.astype(jnp.uint32)
        win, src, n_new = reservoir_accept(
            key, state.n, mask.astype(jnp.int32), self.cfg.capacity)
        taken = jnp.take(values, src, axis=0)
        # step (the bootstrap_key coordinate) advances only on rounds that
        # carried data: a fully-masked padding round is a content no-op and
        # must leave the state -- bars included -- bit-identical to a solo
        # replay without it (ingest.py's determinism contract)
        carried = (jnp.sum(mask.astype(jnp.int32)) > 0).astype(jnp.int32)
        return ReservoirState(
            items=jnp.where(win[:, None], taken, state.items),
            tags=jnp.where(win, state.sid, state.tags),
            n=n_new,
            sid=state.sid,
            step=state.step + carried)

    def ingest_rounds(self, states, values, row_mask, keys):
        return self._rounds_fn(states, jnp.asarray(values),
                               jnp.asarray(row_mask), keys)

    def refill_capacity(self, backing: int) -> int:
        """Fold capacity with ``backing`` half-capacity backing epochs
        (the window refill of DESIGN.md §14.2): cap + backing * cap//2."""
        return self.cfg.capacity + backing * (self.cfg.capacity // 2)

    def merge(self, a: ReservoirState, b: ReservoirState, *,
              backing: int = 0) -> ReservoirState:
        """Deterministic weighted union.  ``backing > 0`` merges into an
        *expanded* sample of ``refill_capacity(backing)`` slots -- the
        window's backing-epoch refill fold; the inputs may be any mix of
        base-capacity epoch states and already-expanded totals."""
        items, tags = merge_tagged_samples(
            a.items, a.tags, a.n, b.items, b.tags, b.n,
            self.refill_capacity(backing), _MERGE_SALT ^ self.cfg.seed)
        return ReservoirState(items=items, tags=tags, n=a.n + b.n,
                              sid=jnp.maximum(a.sid, b.sid),
                              step=a.step + b.step)

    def subtract(self, a: ReservoirState, b: ReservoirState) -> ReservoirState:
        keep = a.tags != b.sid
        return ReservoirState(
            items=a.items,
            tags=jnp.where(keep, a.tags, -1),
            n=jnp.maximum(a.n - b.n, 0),
            sid=a.sid, step=a.step)

    # -- estimation ----------------------------------------------------
    def _table(self, hist: np.ndarray, n: np.ndarray, m: np.ndarray,
               stderr: np.ndarray | None = None) -> EstimateTable:
        """hist (N, d+1) float64 sample pair counts -> the (N, L) table.
        Scale n(n-1)/(m(m-1)); m < 2 yields the zero histogram (the
        empty-stream guard of baselines.random_sampling_pair_counts)."""
        x_full = hist * uncertainty.pair_scale(n, m)[:, None]  # (N, d+1)
        x = x_full[:, self.s:]
        g = np.cumsum(x[:, ::-1], axis=1)[:, ::-1] + n[:, None]
        if stderr is None:
            stderr = np.zeros_like(x)
        # the reservoir is a pure sampling estimator: the online and the
        # sampling-only (offline) bars coincide
        return EstimateTable(x=x, g=g, y=hist[:, self.s:], n=n,
                             stderr=stderr, stderr_offline=stderr,
                             stderr_kind=("bootstrap" if self.bootstrap
                                          else "none"))

    def _bootstrap_stderr(self, items, valid, n, step, *, use_pallas=None,
                          pair_fn=None) -> np.ndarray | None:
        """(N, L) bootstrap-with-Serfling stderr of the g table, or None
        when disabled (bootstrap_replicates=0)."""
        if not self.bootstrap:
            return None
        keys = uncertainty.bootstrap_key(self.cfg.seed, n, step)
        return uncertainty.bootstrap_pair_stderr(
            items, valid, np.asarray(jax.device_get(n), np.float64),
            keys=keys, s=self.s, replicates=self.bootstrap,
            item_cap=self.bootstrap_cap, use_pallas=use_pallas,
            pair_fn=pair_fn)

    def estimate_batch(self, states, *,
                       clamp: bool = True) -> EstimateTable:
        del clamp                                  # counts are >= 0 already
        from repro.kernels.ops import fused_pairs
        # device arrays flow straight into the kernel (no host round-trip
        # re-uploading the sample per query); only the small outputs --
        # histogram, valid counts, n -- are fetched
        valid = (jnp.asarray(states.tags) >= 0).astype(jnp.int32)
        N, R = valid.shape
        with child("query.pairs", streams=N, slots=R):
            hist = np.asarray(jax.device_get(fused_pairs(
                states.items, valid))).astype(np.float64)
        n = np.asarray(jax.device_get(states.n), np.float64)
        m = np.asarray(jax.device_get(valid.sum(axis=1)), np.float64)
        stderr = self._bootstrap_stderr(states.items, valid, states.n,
                                        states.step)
        return self._table(hist, n, m, stderr)

    def estimate_ref(self, state: ReservoirState, *,
                     clamp: bool = True) -> EstimateTable:
        """O(m^2 d) numpy oracle: brute-force histogram of the valid
        sample (core.exact), then the identical scaling.  The bootstrap
        stderr re-draws the same replicate indices (same per-state keys)
        but bins them through the numpy oracle."""
        del clamp
        tags = np.asarray(jax.device_get(state.tags))
        valid = (tags >= 0).astype(np.int32)
        items = np.asarray(jax.device_get(state.items))
        hist = (exact.brute_force_pair_counts(items[tags >= 0])
                if items[tags >= 0].shape[0] else np.zeros(self.d + 1))
        n = np.array([self.state_n(state)], np.float64)

        def pair_fn(it, va):
            it, va = np.asarray(it), np.asarray(va)
            lead = it.shape[:-2]
            flat_it = it.reshape((-1,) + it.shape[-2:])
            flat_va = va.reshape((-1, va.shape[-1]))
            out = np.stack([exact.brute_force_pair_counts(r[v != 0])
                            if (v != 0).sum() else np.zeros(self.d + 1)
                            for r, v in zip(flat_it, flat_va)])
            return out.reshape(lead + (self.d + 1,))

        stderr = self._bootstrap_stderr(
            items[None], valid[None], jnp.asarray(state.n)[None],
            jnp.asarray(state.step)[None], use_pallas=False,
            pair_fn=pair_fn)
        return self._table(hist[None], n,
                           np.array([float(valid.sum())], np.float64),
                           stderr)


def capacity_for_bytes(sjpc_cfg: SJPCConfig) -> int:
    """The Fig. 8 equal-space rule, served: the records (plus provenance
    tag) storable in the byte budget of the group's SJPC counters."""
    return max(1, sjpc_cfg.counters_bytes // ((sjpc_cfg.d + 1) * 4))


def _factory(sjpc_cfg: SJPCConfig, *, params=None, estimator_cfg=None):
    del params                               # no shared hash randomness
    if estimator_cfg is None:
        estimator_cfg = ReservoirConfig(
            d=sjpc_cfg.d, s=sjpc_cfg.s, capacity=capacity_for_bytes(sjpc_cfg),
            seed=sjpc_cfg.seed)
    return ReservoirEstimator(estimator_cfg)


register("reservoir", _factory, state_cls=ReservoirState, linear=False,
         join_capable=False, stderr_kind="bootstrap",
         exact_oracle=pairwise_exact_oracle)
