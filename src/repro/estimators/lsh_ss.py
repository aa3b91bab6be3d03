"""Streaming LSH-SS behind the Estimator protocol.

The paper's stratified competitor (§2.3, Lee et al. [17], arXiv:1104.3212)
is multi-pass offline: build LSH buckets over the values of a random
column subset, then sample same-bucket ("high") and cross-bucket ("low")
pairs and scale each stratum's similar fraction.  The one-pass variant
served here maintains every ingredient online:

  * a **bucket-count sketch**: one hashed counter per LSH bucket (the
    values of the ``num_hash_cols`` chosen columns, avalanche-hashed into
    ``num_buckets`` slots).  sum c_b(c_b - 1) estimates the same-stratum
    ordered-pair count; hash collisions merge buckets, biasing the split
    conservatively toward the same stratum (documented, bounded by the
    load factor).  Linear, so merge/subtract are exact counter arithmetic.
  * a **record reservoir** (Algorithm R, with each record's bucket id):
    the online pair generator.  Every arriving record g is paired with one
    uniform *earlier* record: a uniform rank u in [0, g) resolves to the
    in-batch record when it falls inside the current round, else to a
    uniform stored reservoir slot (the reservoir is itself a uniform
    sample of the past).  The pair is a same- or cross-stratum candidate
    by bucket equality.  Pairing only against the stored reservoir -- the
    pre-fix behavior -- silently dropped every within-round pair, biasing
    the stratum fractions low whenever similar records arrive together.
  * two **stratified pair reservoirs**: per stratum, Algorithm R over its
    candidate pairs, storing only the pair's match count (int) -- the
    similar fraction of each stratum at query time is a mask-and-count.

Estimates: g_s = p1 * same_pairs + p2 * cross_pairs + n, exactly the
offline formula (core/baselines.py:lsh_ss_g) with every term read from
the online state.  No analytical error bound exists (the paper proves
none for LSH-SS); the served stderr is the *stratified bootstrap* of
estimators/uncertainty.py (resample each stratum's pair reservoir, scale
by the near-exact linear stratum totals; stderr_kind
"bootstrap_stratified").

Sample-state algebra follows estimators.reservoir: provenance-tagged
slots, deterministic weighted union on merge, tag-drop on subtract; the
bucket counts merge/subtract linearly.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.sjpc import SJPCConfig
from repro.obs.trace import child

from . import uncertainty
from .base import (EstimateTable, Estimator, merge_tagged_samples,
                   pairwise_exact_oracle, register, scan_rounds)
from .reservoir import reservoir_accept

_MERGE_SALT = 0x15A55B01


@dataclasses.dataclass(frozen=True)
class LSHSSConfig:
    d: int                     # record dimensionality
    s: int                     # lowest queryable threshold
    num_hash_cols: int = 1     # LSH column-subset size c, 1 <= c <= d
    num_buckets: int = 1024    # hashed bucket counters (power of two)
    record_capacity: int = 256   # record reservoir slots
    pair_capacity: int = 256     # pair reservoir slots per stratum
    seed: int = 0x5A5A

    def __post_init__(self):
        if not 1 <= self.s <= self.d:
            raise ValueError(f"need 1 <= s={self.s} <= d={self.d}")
        if not 1 <= self.num_hash_cols <= self.d:
            raise ValueError(
                f"num_hash_cols={self.num_hash_cols} outside [1, d={self.d}]"
                " (the paper's LSH-SS hashes a random column subset)")
        if self.num_buckets & (self.num_buckets - 1):
            raise ValueError("num_buckets must be a power of two")
        assert self.record_capacity >= 1 and self.pair_capacity >= 1


class LSHSSState(NamedTuple):
    counts: jax.Array        # (Bh,) int32 records per hashed bucket
    rec_items: jax.Array     # (R, d) uint32 record reservoir
    rec_bucket: jax.Array    # (R,) int32 bucket id of each stored record
    rec_tags: jax.Array      # (R,) int32 provenance; -1 = empty
    same_sim: jax.Array      # (M,) int32 match counts, same-bucket stratum
    same_tags: jax.Array     # (M,) int32
    same_seen: jax.Array     # int32 same-stratum candidates seen
    cross_sim: jax.Array     # (M,) int32 match counts, cross-bucket stratum
    cross_tags: jax.Array    # (M,) int32
    cross_seen: jax.Array    # int32
    n: jax.Array             # int32 records seen (exact: Algorithm R needs
    #   true arrival indices -- see estimators.reservoir.ReservoirState.n)
    sid: jax.Array           # int32 provenance tag for insertions
    step: jax.Array          # int32


class LSHSSEstimator(Estimator):
    kind = "lsh_ss"
    linear = False
    supports_join = False

    def __init__(self, cfg: LSHSSConfig, *,
                 bootstrap_replicates: int = uncertainty.DEFAULT_REPLICATES):
        self.cfg = cfg
        rng = np.random.default_rng(cfg.seed ^ 0x15AC01)
        self.cols = np.sort(rng.choice(cfg.d, size=cfg.num_hash_cols,
                                       replace=False))
        # stratified bootstrap error bars (0 disables -> stderr_kind "none")
        if bootstrap_replicates == 1:
            raise ValueError("bootstrap_replicates must be 0 (disabled) "
                             "or >= 2 (a std needs two replicates)")
        self.bootstrap = int(bootstrap_replicates)
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
        c = self.cfg
        return (c.num_buckets * 4 + c.record_capacity * (c.d + 2) * 4
                + 2 * c.pair_capacity * 8)

    # ------------------------------------------------------------------
    def _bucket(self, values) -> jax.Array:
        """Avalanche hash of the chosen columns' values -> bucket id."""
        h = jnp.full(values.shape[:-1], 0x811C9DC5, jnp.uint32) \
            ^ jnp.uint32(self.cfg.seed)
        for c in self.cols:
            h = (h * jnp.uint32(0x01000193)) \
                ^ (values[..., int(c)].astype(jnp.uint32)
                   + jnp.uint32(0x9E3779B1))
        h ^= h >> 15
        h = h * jnp.uint32(0x85EBCA77)
        h ^= h >> 13
        return (h & jnp.uint32(self.cfg.num_buckets - 1)).astype(jnp.int32)

    def init(self, sid: int = 0) -> LSHSSState:
        c = self.cfg
        return LSHSSState(
            counts=jnp.zeros((c.num_buckets,), jnp.int32),
            rec_items=jnp.zeros((c.record_capacity, c.d), jnp.uint32),
            rec_bucket=jnp.zeros((c.record_capacity,), jnp.int32),
            rec_tags=jnp.full((c.record_capacity,), -1, jnp.int32),
            same_sim=jnp.zeros((c.pair_capacity,), jnp.int32),
            same_tags=jnp.full((c.pair_capacity,), -1, jnp.int32),
            same_seen=jnp.zeros((), jnp.int32),
            cross_sim=jnp.zeros((c.pair_capacity,), jnp.int32),
            cross_tags=jnp.full((c.pair_capacity,), -1, jnp.int32),
            cross_seen=jnp.zeros((), jnp.int32),
            n=jnp.zeros((), jnp.int32),
            sid=jnp.asarray(sid, jnp.int32),
            step=jnp.zeros((), jnp.int32))

    def _ingest_one(self, state: LSHSSState, values, mask,
                    key) -> LSHSSState:
        cfg = self.cfg
        values = values.astype(jnp.uint32)
        mask = mask.astype(jnp.int32)
        maskb = mask != 0
        bucket = self._bucket(values)                       # (B,)
        counts = state.counts.at[jnp.where(maskb, bucket, 0)] \
            .add(jnp.where(maskb, 1, 0))

        kp, kq, ks, kc, kr = jax.random.split(key, 5)
        # pair one candidate per arriving record with a uniform EARLIER
        # record: arrival g draws a uniform rank u in [0, g); ranks inside
        # the current round resolve to the in-batch record directly, ranks
        # before it to a uniform reservoir slot (the reservoir is a uniform
        # sample of the past, so the partner stays ~uniform).  The old
        # reservoir-only draw skipped every within-round pair, which
        # silently biased the stratum fractions low on workloads whose
        # similar records arrive close together (planted clusters, bursts)
        # -- the dominant term of the equal_space LSH-SS error.
        B = mask.shape[0]
        pos = jnp.cumsum(mask) - 1                          # candidate index
        gidx = state.n + pos                                # global arrival
        u = jax.random.randint(kp, mask.shape, 0, jnp.maximum(gidx, 1))
        within = maskb & (u >= state.n)
        # pre-round ranks: while the reservoir is warming up (n < R) its
        # slots are filled sequentially, so rank u lives at slot u exactly
        # -- a fresh uniform slot draw there would drop candidates landing
        # on still-empty slots, thinning pre-round pairs relative to
        # within-round ones.  Once full, every uniform slot is valid.
        slot_draw = jax.random.randint(kq, mask.shape, 0,
                                       cfg.record_capacity)
        warmup = state.n < cfg.record_capacity
        slot = jnp.where(warmup,
                         jnp.clip(u, 0, cfg.record_capacity - 1), slot_draw)
        row_of = jnp.zeros((B + 1,), jnp.int32) \
            .at[jnp.where(maskb, pos, B)].set(jnp.arange(B, dtype=jnp.int32))
        in_row = jnp.take(row_of, jnp.clip(u - state.n, 0, B))
        p_items = jnp.where(within[:, None],
                            jnp.take(values, in_row, axis=0),
                            jnp.take(state.rec_items, slot, axis=0))
        p_bucket = jnp.where(within, jnp.take(bucket, in_row),
                             jnp.take(state.rec_bucket, slot))
        p_ok = (gidx > 0) & jnp.where(
            within, True, jnp.take(state.rec_tags, slot) >= 0)
        p_sim = jnp.sum((values == p_items).astype(jnp.int32), axis=1)
        p_same = p_bucket == bucket

        def pair_reservoir(k, cand, sims, tags, seen, sim_vals):
            win, src, seen_new = reservoir_accept(
                k, seen, cand.astype(jnp.int32), cfg.pair_capacity)
            return (jnp.where(win, jnp.take(sim_vals, src), sims),
                    jnp.where(win, state.sid, tags),
                    seen_new)

        same_sim, same_tags, same_seen = pair_reservoir(
            ks, maskb & p_ok & p_same, state.same_sim, state.same_tags,
            state.same_seen, p_sim)
        cross_sim, cross_tags, cross_seen = pair_reservoir(
            kc, maskb & p_ok & ~p_same, state.cross_sim, state.cross_tags,
            state.cross_seen, p_sim)

        win, src, n_new = reservoir_accept(
            kr, state.n, mask, cfg.record_capacity)
        taken = jnp.take(values, src, axis=0)
        return LSHSSState(
            counts=counts,
            rec_items=jnp.where(win[:, None], taken, state.rec_items),
            rec_bucket=jnp.where(win, jnp.take(bucket, src),
                                 state.rec_bucket),
            rec_tags=jnp.where(win, state.sid, state.rec_tags),
            same_sim=same_sim, same_tags=same_tags, same_seen=same_seen,
            cross_sim=cross_sim, cross_tags=cross_tags,
            cross_seen=cross_seen,
            n=n_new, sid=state.sid,
            # data-carrying rounds only (see reservoir._ingest_one): padding
            # rounds must not advance the bootstrap/replay coordinate
            step=state.step + (jnp.sum(mask) > 0).astype(jnp.int32))

    def ingest_rounds(self, states, values, row_mask, keys):
        return self._rounds_fn(states, jnp.asarray(values),
                               jnp.asarray(row_mask), keys)

    # -- algebra -------------------------------------------------------
    def _merge_sample(self, items_a, tags_a, n_a, items_b, tags_b, n_b,
                      capacity):
        return merge_tagged_samples(items_a, tags_a, n_a, items_b, tags_b,
                                    n_b, capacity,
                                    _MERGE_SALT ^ self.cfg.seed)

    def refill_capacity(self, backing: int) -> tuple[int, int]:
        """(record, pair) fold capacities with ``backing`` half-capacity
        backing epochs (window refill, DESIGN.md §14.2)."""
        c = self.cfg
        return (c.record_capacity + backing * (c.record_capacity // 2),
                c.pair_capacity + backing * (c.pair_capacity // 2))

    def merge(self, a: LSHSSState, b: LSHSSState, *,
              backing: int = 0) -> LSHSSState:
        cfg = self.cfg
        rec_cap, pair_cap = self.refill_capacity(backing)
        # record reservoir: carry the bucket id as an extra merged column
        rec_a = jnp.concatenate(
            [a.rec_items, a.rec_bucket.astype(jnp.uint32)[:, None]], axis=1)
        rec_b = jnp.concatenate(
            [b.rec_items, b.rec_bucket.astype(jnp.uint32)[:, None]], axis=1)
        rec, rec_tags = self._merge_sample(rec_a, a.rec_tags, a.n,
                                           rec_b, b.rec_tags, b.n,
                                           rec_cap)
        same, same_tags = self._merge_sample(
            a.same_sim.astype(jnp.uint32)[:, None], a.same_tags, a.same_seen,
            b.same_sim.astype(jnp.uint32)[:, None], b.same_tags, b.same_seen,
            pair_cap)
        cross, cross_tags = self._merge_sample(
            a.cross_sim.astype(jnp.uint32)[:, None], a.cross_tags,
            a.cross_seen,
            b.cross_sim.astype(jnp.uint32)[:, None], b.cross_tags,
            b.cross_seen, pair_cap)
        return LSHSSState(
            counts=a.counts + b.counts,
            rec_items=rec[:, :cfg.d],
            rec_bucket=rec[:, cfg.d].astype(jnp.int32),
            rec_tags=rec_tags,
            same_sim=same[:, 0].astype(jnp.int32), same_tags=same_tags,
            same_seen=a.same_seen + b.same_seen,
            cross_sim=cross[:, 0].astype(jnp.int32), cross_tags=cross_tags,
            cross_seen=a.cross_seen + b.cross_seen,
            n=a.n + b.n, sid=jnp.maximum(a.sid, b.sid),
            step=a.step + b.step)

    def subtract(self, a: LSHSSState, b: LSHSSState) -> LSHSSState:
        drop = b.sid
        return LSHSSState(
            counts=a.counts - b.counts,
            rec_items=a.rec_items, rec_bucket=a.rec_bucket,
            rec_tags=jnp.where(a.rec_tags == drop, -1, a.rec_tags),
            same_sim=a.same_sim,
            same_tags=jnp.where(a.same_tags == drop, -1, a.same_tags),
            same_seen=jnp.maximum(a.same_seen - b.same_seen, 0),
            cross_sim=a.cross_sim,
            cross_tags=jnp.where(a.cross_tags == drop, -1, a.cross_tags),
            cross_seen=jnp.maximum(a.cross_seen - b.cross_seen, 0),
            n=jnp.maximum(a.n - b.n, 0), sid=a.sid, step=a.step)

    # -- estimation ----------------------------------------------------
    def _stderr(self, same_sim, same_tags, same_seen, cross_sim, cross_tags,
                cross_seen, same_pairs, cross_pairs, n, step):
        """(N, L) stratified-bootstrap stderr, or zeros when disabled."""
        if not self.bootstrap:
            return np.zeros((np.asarray(n).shape[0], self.num_levels))
        return uncertainty.stratified_bootstrap_stderr(
            same_sim, same_tags >= 0, same_seen,
            cross_sim, cross_tags >= 0, cross_seen,
            same_pairs, cross_pairs, d=self.d, s=self.s,
            seed=self.cfg.seed, n=n, step=step,
            replicates=self.bootstrap)

    def _strata(self, counts, same_sim, same_tags, cross_sim, cross_tags,
                n):
        """Vectorized numpy: stratum totals from the bucket counts, per-
        stratum level fractions from the pair reservoirs, Eq. of §2.3.
        Returns the (N, L) x and g tables, the same-stratum hit counts,
        and the two stratum pair totals."""
        counts = counts.astype(np.float64)
        same_pairs = (counts * (counts - 1)).sum(axis=-1)       # ordered
        total = n * (n - 1)
        cross_pairs = np.maximum(total - same_pairs, 0.0)
        levels = np.arange(self.d + 1)

        def level_fracs(sim, tags):
            ok = tags >= 0
            m = ok.sum(axis=-1).astype(np.float64)
            hits = ((sim[..., None] == levels) & ok[..., None]) \
                .sum(axis=-2).astype(np.float64)                # (N, d+1)
            with np.errstate(divide="ignore", invalid="ignore"):
                return np.where(m[:, None] > 0, hits / m[:, None], 0.0), hits

        f1, y1 = level_fracs(same_sim, same_tags)
        f2, _ = level_fracs(cross_sim, cross_tags)
        x_full = f1 * same_pairs[:, None] + f2 * cross_pairs[:, None]
        x = x_full[:, self.s:]
        g = np.cumsum(x[:, ::-1], axis=1)[:, ::-1] + n[:, None]
        return x, g, y1, same_pairs, cross_pairs

    def estimate_batch(self, states, *,
                       clamp: bool = True) -> EstimateTable:
        """The stratum scaling of :meth:`_strata`; error bars from the
        stratified bootstrap of DESIGN.md §14 (the bucket totals are
        linear and near-exact; the pair-reservoir fractions carry the
        sampling randomness)."""
        del clamp                                  # pure host-numpy math
        fields = ("counts", "same_sim", "same_tags", "same_seen",
                  "cross_sim", "cross_tags", "cross_seen", "n", "step")
        with child("query.strata", streams=len(states.n)):
            st = dict(zip(fields, map(np.asarray, jax.device_get(
                [getattr(states, f) for f in fields]))))
            n = st["n"].astype(np.float64)
            x, g, y1, same_pairs, cross_pairs = self._strata(
                st["counts"], st["same_sim"], st["same_tags"],
                st["cross_sim"], st["cross_tags"], n)
        stderr = self._stderr(st["same_sim"], st["same_tags"],
                              st["same_seen"], st["cross_sim"],
                              st["cross_tags"], st["cross_seen"], same_pairs,
                              cross_pairs, n, st["step"])
        return EstimateTable(x=x, g=g, y=y1[:, self.s:], n=n,
                             stderr=stderr, stderr_offline=stderr,
                             stderr_kind=("bootstrap_stratified"
                                          if self.bootstrap else "none"))

    def estimate_ref(self, state: LSHSSState, *,
                     clamp: bool = True) -> EstimateTable:
        """Scalar python-loop oracle for the batched numpy path (the
        stderr column reuses the shared stratified bootstrap, whose
        per-stream PRNG makes batch == ref by construction)."""
        del clamp
        get = lambda a: np.asarray(jax.device_get(a))
        counts = get(state.counts).astype(np.int64)
        n = float(get(state.n))
        same_pairs = float((counts * (counts - 1)).sum())
        cross_pairs = max(n * (n - 1) - same_pairs, 0.0)
        x = np.zeros(self.d + 1)
        y = np.zeros(self.d + 1)
        for sim, tags, pairs, record_y in (
                (get(state.same_sim), get(state.same_tags), same_pairs, True),
                (get(state.cross_sim), get(state.cross_tags), cross_pairs,
                 False)):
            ok = tags >= 0
            m = int(ok.sum())
            for k in range(self.d + 1):
                hits = int(((sim == k) & ok).sum())
                if record_y:
                    y[k] = hits
                if m > 0:
                    x[k] += hits / m * pairs
        xs = x[self.s:]
        g = np.array([xs[i:].sum() + n for i in range(self.num_levels)])
        stderr = self._stderr(
            get(state.same_sim)[None], get(state.same_tags)[None],
            get(state.same_seen)[None], get(state.cross_sim)[None],
            get(state.cross_tags)[None], get(state.cross_seen)[None],
            np.array([same_pairs]), np.array([cross_pairs]),
            np.array([n]), get(state.step)[None])
        return EstimateTable(x=xs[None], g=g[None], y=y[self.s:][None],
                             n=np.array([n]), stderr=stderr,
                             stderr_offline=stderr,
                             stderr_kind=("bootstrap_stratified"
                                          if self.bootstrap else "none"))


def derive_config(sjpc_cfg: SJPCConfig, *, num_hash_cols: int = 1) -> LSHSSConfig:
    """Split the group's SJPC byte budget across the three structures:
    ~half to the record reservoir, ~quarter to the pair reservoirs,
    the rest to bucket counters (capped at 1024 buckets)."""
    budget = sjpc_cfg.counters_bytes
    d = sjpc_cfg.d
    num_buckets = 1024
    while num_buckets * 4 > max(budget // 4, 64):
        num_buckets //= 2
    record_capacity = max(1, (budget // 2) // ((d + 2) * 4))
    pair_capacity = max(1, (budget // 4) // (2 * 8))
    return LSHSSConfig(d=d, s=sjpc_cfg.s, num_hash_cols=num_hash_cols,
                       num_buckets=max(num_buckets, 16),
                       record_capacity=record_capacity,
                       pair_capacity=pair_capacity, seed=sjpc_cfg.seed)


def _factory(sjpc_cfg: SJPCConfig, *, params=None, estimator_cfg=None):
    del params                # no shared hash randomness
    if estimator_cfg is None:
        estimator_cfg = derive_config(sjpc_cfg)
    return LSHSSEstimator(estimator_cfg)


register("lsh_ss", _factory, state_cls=LSHSSState, linear=False,
         join_capable=False, stderr_kind="bootstrap_stratified",
         exact_oracle=pairwise_exact_oracle)
