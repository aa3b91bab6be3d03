"""A KMV/theta bottom-K distinct-value sketch as a PLUGIN estimator kind.

This module is the docs/PLUGINS.md cookbook example: a complete estimator
kind ("theta_kmv") registered from outside ``src/repro`` through the one
declarative :class:`repro.estimators.EstimatorSpec` surface.  Nothing in
the service, wire, planner, or observability layers names it -- they all
read the spec.

The sketch is the classic KMV ("k minimum values") / theta tuple sketch:
hash every record to a uniform 32-bit key and keep the K smallest distinct
(key, provenance-tag) entries, each with the multiplicity of records that
produced it.  With ``theta`` = (K-th smallest retained key + 1) / 2^32,
every distinct value survives independently with probability ``theta``,
so retained counts scale by ``1/theta``:

* distinct values  D-hat = (retained_distinct - 1) / theta  (full sketch)
* duplicate pairs  P-hat = sum_v c_v * (c_v - 1) / theta    (ordered)

A duplicate pair agrees on ALL d attributes, so it is k-similar at every
threshold: the estimator reports ``x`` = 0 except at level d (the
duplicate pairs) and the constant column ``g_k = n + P-hat`` -- a lawful,
weakly non-increasing g table, just a deliberately coarse one.  That is
the point of the example: the conformance matrix, the wire format, and
the service accept it because it honors the *protocol*, not because it
matches the paper's estimator.

Window semantics are the sample-window algebra of reservoir.py: states
are NOT linear (a bottom-K union is not counter addition), merge is the
exact identity bottomK(A union B) = bottomK(bottomK(A) union bottomK(B)),
and subtract drops entries by provenance tag (exact for the epoch states
the window hands it).  No exact-replay oracle is registered -- the
accuracy auditor skips this kind with ``reason="no_exact_oracle"``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.estimators import (EstimateTable, Estimator, register,
                              scan_rounds, stack_states)

_EMPTY_KEY = jnp.uint32(0xFFFFFFFF)   # slot sentinel; validity is tag >= 0
_ENTRY_BYTES = 12                      # key u32 + count i32 + tag i32


@dataclasses.dataclass(frozen=True)
class ThetaConfig:
    """Static plugin configuration, derived from the group's SJPCConfig
    by the factory (equal-space: capacity = counters_bytes // 12)."""
    d: int
    s: int
    capacity: int
    seed: int


class ThetaState(NamedTuple):
    """One stream's sketch: K slots of (key, count, tag) entries.

    ``tag`` is the provenance sid (-1 = empty slot) -- the same
    tag-algebra reservoir.py uses, so the window's epoch expiry
    (subtract-by-tag) is exact.  ``keys`` of empty slots hold the
    0xFFFFFFFF sentinel so a plain sort pushes them to the tail.
    """
    keys: jnp.ndarray     # (K,) uint32
    counts: jnp.ndarray   # (K,) int32 records retained behind each key
    tags: jnp.ndarray     # (K,) int32 provenance sid, -1 = empty
    n: jnp.ndarray        # ()  int32 records represented
    sid: jnp.ndarray      # ()  int32 this state's provenance tag


def _hash_rows(values: jnp.ndarray, seed: int) -> jnp.ndarray:
    """(B, d) uint32 records -> (B,) uniform 32-bit keys (fold-multiply
    mix per attribute + a murmur3-style finalizer)."""
    h = jnp.full(values.shape[0], jnp.uint32(seed ^ 0x0D15C0DE))
    for c in range(values.shape[-1]):
        h = (h ^ values[..., c].astype(jnp.uint32)) * jnp.uint32(0x9E3779B1)
    h ^= h >> 16
    h = h * jnp.uint32(0x85EBCA6B)
    h ^= h >> 13
    h = h * jnp.uint32(0xC2B2AE35)
    return h ^ (h >> 16)


def _combine(keys, counts, tags, capacity: int):
    """bottomK of a pooled entry list: lexsort by (key, tag) with empties
    last, coalesce equal (key, tag) runs, keep the first ``capacity``.

    The two-pass stable argsort is a lexicographic sort (secondary key
    first); empty slots sort via a +inf tag surrogate so a *valid* entry
    whose key happens to equal the sentinel still lands ahead of them.
    """
    valid = tags >= 0
    tag_key = jnp.where(valid, tags, jnp.int32(0x7FFFFFFF))
    order = jnp.argsort(tag_key, stable=True)
    keys, counts, tag_key = keys[order], counts[order], tag_key[order]
    order = jnp.argsort(keys, stable=True)
    keys, counts, tag_key = keys[order], counts[order], tag_key[order]

    m = keys.shape[0]
    first = jnp.concatenate([
        jnp.ones((1,), bool),
        (keys[1:] != keys[:-1]) | (tag_key[1:] != tag_key[:-1])])
    gid = jnp.cumsum(first.astype(jnp.int32)) - 1
    out_counts = jax.ops.segment_sum(counts, gid, num_segments=m)
    out_keys = jnp.full((m,), _EMPTY_KEY).at[gid].set(keys)
    out_tags = jnp.full((m,), -1, jnp.int32).at[gid].set(
        jnp.where(tag_key == jnp.int32(0x7FFFFFFF), -1, tag_key))
    out_counts = jnp.where(out_tags >= 0, out_counts, 0)
    return out_keys[:capacity], out_counts[:capacity], out_tags[:capacity]


class ThetaEstimator(Estimator):
    kind = "theta_kmv"
    linear = False
    supports_join = False

    def __init__(self, cfg: ThetaConfig):
        self.cfg = cfg
        self._rounds_fn = jax.jit(
            functools.partial(scan_rounds, self._ingest_one))

    # -- static config -------------------------------------------------
    @property
    def d(self) -> int:
        return self.cfg.d

    @property
    def s(self) -> int:
        return self.cfg.s

    @property
    def seed(self) -> int:
        return self.cfg.seed

    # -- state algebra -------------------------------------------------
    def init(self, sid: int = 0) -> ThetaState:
        K = self.cfg.capacity
        return ThetaState(
            keys=jnp.full((K,), _EMPTY_KEY),
            counts=jnp.zeros((K,), jnp.int32),
            tags=jnp.full((K,), -1, jnp.int32),
            n=jnp.zeros((), jnp.int32),
            sid=jnp.asarray(sid, jnp.int32))

    def _ingest_one(self, state: ThetaState, values, mask, key) -> ThetaState:
        del key                                   # hash-based, PRNG-free
        live = mask > 0
        row_keys = jnp.where(live, _hash_rows(values, self.cfg.seed),
                             _EMPTY_KEY)
        row_tags = jnp.where(live, state.sid, jnp.int32(-1))
        keys, counts, tags = _combine(
            jnp.concatenate([state.keys, row_keys]),
            jnp.concatenate([state.counts, live.astype(jnp.int32)]),
            jnp.concatenate([state.tags, row_tags]),
            self.cfg.capacity)
        return ThetaState(keys=keys, counts=counts, tags=tags,
                          n=state.n + jnp.sum(mask).astype(jnp.int32),
                          sid=state.sid)

    def ingest_rounds(self, states, values, row_mask, keys):
        return self._rounds_fn(states, jnp.asarray(values),
                               jnp.asarray(row_mask), keys)

    def merge(self, a: ThetaState, b: ThetaState, *,
              backing: int = 0) -> ThetaState:
        """Exact union: bottomK over the pooled entries.  ``backing`` is
        accepted for window-refill call compatibility; a KMV sketch keeps
        its K smallest keys regardless, so there is nothing to expand."""
        del backing
        keys, counts, tags = _combine(
            jnp.concatenate([a.keys, b.keys]),
            jnp.concatenate([a.counts, b.counts]),
            jnp.concatenate([a.tags, b.tags]),
            self.cfg.capacity)
        return ThetaState(keys=keys, counts=counts, tags=tags,
                          n=a.n + b.n, sid=jnp.maximum(a.sid, b.sid))

    def subtract(self, a: ThetaState, b: ThetaState) -> ThetaState:
        drop = a.tags == b.sid
        keys, counts, tags = _combine(
            jnp.where(drop, _EMPTY_KEY, a.keys),
            jnp.where(drop, 0, a.counts),
            jnp.where(drop, -1, a.tags),
            self.cfg.capacity)
        return ThetaState(keys=keys, counts=counts, tags=tags,
                          n=jnp.maximum(a.n - b.n, 0), sid=a.sid)

    def memory_bytes(self) -> int:
        return self.cfg.capacity * _ENTRY_BYTES

    # -- estimation ----------------------------------------------------
    def _row(self, keys: np.ndarray, counts: np.ndarray, tags: np.ndarray,
             n: float) -> tuple[float, float]:
        """One sketch -> (distinct-hat, ordered-duplicate-pairs-hat)."""
        valid = tags >= 0
        m = int(valid.sum())
        if m == 0 or n <= 0:
            return 0.0, 0.0
        ks = keys[valid].astype(np.uint64)
        cs = counts[valid].astype(np.float64)
        uniq, inv = np.unique(ks, return_inverse=True)
        per_key = np.zeros(uniq.shape[0])
        np.add.at(per_key, inv, cs)
        if m < self.cfg.capacity:
            theta, distinct = 1.0, float(uniq.size)       # exact regime
        else:
            theta = (float(ks.max()) + 1.0) / 4294967296.0
            distinct = max(float(uniq.size) - 1.0, 1.0) / theta
        dup = float((per_key * (per_key - 1.0)).sum()) / theta
        return distinct, dup

    def estimate_batch(self, states, *,
                       clamp: bool = True) -> EstimateTable:
        keys = np.asarray(jax.device_get(states.keys))
        counts = np.asarray(jax.device_get(states.counts))
        tags = np.asarray(jax.device_get(states.tags))
        n = np.asarray(jax.device_get(states.n)).astype(np.float64)
        N, L = n.shape[0], self.num_levels
        x = np.zeros((N, L))
        y = np.zeros((N, L))
        for i in range(N):
            distinct, dup = self._row(keys[i], counts[i], tags[i], n[i])
            x[i, L - 1] = dup                     # duplicates match at d
            y[i, :] = distinct                    # diagnostic: D-hat
        if clamp:
            x = np.maximum(x, 0.0)
        g = np.cumsum(x[:, ::-1], axis=1)[:, ::-1] + n[:, None]
        zeros = np.zeros_like(x)
        return EstimateTable(x=x, g=g, y=y, n=n, stderr=zeros,
                             stderr_offline=zeros, stderr_kind="none")

    def estimate_ref(self, state, *, clamp: bool = True) -> EstimateTable:
        return self.estimate_batch(stack_states([state]), clamp=clamp)


def _factory(cfg, *, params=None, estimator_cfg=None):
    """Equal-space factory: the sketch budget comes from the group's
    SJPCConfig (DESIGN.md §13), 12 bytes per retained entry."""
    del params
    capacity = max(int(cfg.counters_bytes) // _ENTRY_BYTES, 8)
    theta_cfg = estimator_cfg or ThetaConfig(
        d=cfg.d, s=cfg.s, capacity=capacity, seed=cfg.seed ^ 0x7E7A)
    return ThetaEstimator(theta_cfg)


register("theta_kmv", _factory, state_cls=ThetaState,
         linear=False, join_capable=False, stderr_kind="none")
