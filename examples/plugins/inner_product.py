"""A Pagh-Sivertsen-style inner-product filter estimator as a PLUGIN kind.

The second DESIGN.md §19 plugin: a *linear*, *join-capable* estimator
kind ("ipf") registered entirely from outside ``src/repro`` -- it rides
the delta-ring window, the MODE_MERGE wire path, the fused join planner,
and the accuracy auditor purely through its :class:`EstimatorSpec`.

The sketch follows the inner-product filtering idea of Pagh et al. /
Pagh-Sivertsen (PAPERS.md): for every threshold level k it maintains one
CountSketch row of width W, partitioned into C(d, k) disjoint regions --
one per size-k attribute subset.  A record hashes each of its C(d, k)
subset projections into that subset's own region with a +/-1 sign.  Two
records colliding *on the same subset's value* add coherently; everything
else cancels in expectation.  The second moment of row k therefore has

    E[y_k] = n * C(d, k) + sum_{j >= k} C(j, k) * x_j

(each record self-collides on all C(d, k) of its subsets; a pair agreeing
on exactly j attributes agrees on C(j, k) size-k subsets) -- which is
EXACTLY the paper's Eq. 4 moment system at sampling ratio r = 1.  The
estimator therefore reuses the public inversions ``sjpc.f2_to_pair_count``
(self-join) and ``sjpc.inner_to_join_count`` (Eq. 7 two-stream join)
verbatim: a genuinely different sketch served through the same algebra.

Because the regions are disjoint, a single record never collides with
itself across subsets: at n <= 1 the moments are exact and the estimate
degenerates to the truth, as the conformance matrix demands.  States are
plain counter arrays, so merge/subtract are leaf-wise +/- (``linear=True``:
delta-ring windows, arithmetic wire deltas, bit-exact expiry).
"""
from __future__ import annotations

import dataclasses
import functools
import itertools
from typing import NamedTuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import sjpc
from repro.estimators import (EstimateTable, Estimator,
                              pairwise_exact_oracle, register, scan_rounds,
                              stack_states)


@dataclasses.dataclass(frozen=True)
class IPFConfig:
    """Static sketch shape: one (num_levels, row_width) counter plane.
    Frozen + hashable on purpose: the instance's config doubles as the
    planner's fusion-signature key (see ``_fusion_key``)."""
    d: int
    s: int
    row_width: int
    seed: int

    @property
    def num_levels(self) -> int:
        return self.d - self.s + 1


class IPFState(NamedTuple):
    """One stream's sketch: the counter plane plus the record count.
    The counter leaf is named ``counters`` like SJPC's -- linear states
    are pure arithmetic, and keeping the conventional name lets generic
    linear-algebra checks (tests, harness oracles) apply unchanged."""
    counters: jnp.ndarray   # (L, W) int32
    n: jnp.ndarray          # ()  int32


def _fmix(h: jnp.ndarray) -> jnp.ndarray:
    h ^= h >> 16
    h = h * jnp.uint32(0x85EBCA6B)
    h ^= h >> 13
    h = h * jnp.uint32(0xC2B2AE35)
    return h ^ (h >> 16)


class IPFEstimator(Estimator):
    kind = "ipf"
    linear = True
    supports_join = True

    def __init__(self, cfg: IPFConfig):
        self.cfg = cfg
        W = cfg.row_width
        # host-side per-level constants: subset index arrays, region
        # strides, per-subset hash salts (all closed over by the jit)
        self._subsets, self._strides, self._salts = [], [], []
        for k in self.thresholds:
            subs = np.array(list(itertools.combinations(range(cfg.d), k)),
                            dtype=np.int32).reshape(-1, k)
            stride = W // subs.shape[0]
            if stride < 1:
                raise ValueError(
                    f"ipf row_width {W} cannot partition into "
                    f"C({cfg.d},{k}) = {subs.shape[0]} subset regions")
            base_salt = (cfg.seed * 2654435761 ^ (k << 16)) & 0xFFFFFFFF
            salts = (np.uint32(base_salt)
                     ^ (np.arange(subs.shape[0]).astype(np.uint64)
                        * 0x85EBCA6B & 0xFFFFFFFF).astype(np.uint32))
            self._subsets.append(subs)
            self._strides.append(stride)
            self._salts.append(salts)
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
    def init(self, sid: int = 0) -> IPFState:
        del sid                                    # linear: no provenance
        return IPFState(
            counters=jnp.zeros((self.num_levels, self.cfg.row_width),
                               jnp.int32),
            n=jnp.zeros((), jnp.int32))

    def _ingest_one(self, state: IPFState, values, mask, key) -> IPFState:
        del key                                    # hash-based, PRNG-free
        counters = state.counters
        madd = mask.astype(jnp.int32)              # (B,)
        for li, (subs, stride, salts) in enumerate(
                zip(self._subsets, self._strides, self._salts)):
            sub = values[:, subs].astype(jnp.uint32)     # (B, C, k)
            h = jnp.broadcast_to(jnp.asarray(salts)[None, :], sub.shape[:2])
            for t in range(sub.shape[-1]):
                h = (h ^ sub[..., t]) * jnp.uint32(0x9E3779B1)
            h = _fmix(h)
            sign = (1 - 2 * (h >> 31).astype(jnp.int32))       # (B, C)
            base = jnp.arange(subs.shape[0], dtype=jnp.int32) * stride
            bucket = base[None, :] + (h % jnp.uint32(stride)).astype(jnp.int32)
            contrib = sign * madd[:, None]
            counters = counters.at[li, bucket.reshape(-1)].add(
                contrib.reshape(-1))
        return IPFState(counters=counters,
                        n=state.n + jnp.sum(madd))

    def ingest_rounds(self, states, values, row_mask, keys):
        return self._rounds_fn(states, jnp.asarray(values),
                               jnp.asarray(row_mask), keys)

    def merge(self, a: IPFState, b: IPFState) -> IPFState:
        return IPFState(counters=a.counters + b.counters, n=a.n + b.n)

    def subtract(self, a: IPFState, b: IPFState) -> IPFState:
        # exact counter arithmetic, deliberately unclamped: the window's
        # delta-ring expiry relies on subtract being merge's true inverse
        return IPFState(counters=a.counters - b.counters, n=a.n - b.n)

    def memory_bytes(self) -> int:
        return self.num_levels * self.cfg.row_width * 4

    # -- estimation ----------------------------------------------------
    def _host(self, states):
        counters = np.asarray(jax.device_get(states.counters),
                              dtype=np.float64)            # (N, L, W)
        n = np.asarray(jax.device_get(states.n), dtype=np.float64)
        return counters, n

    def estimate_batch(self, states, *,
                       clamp: bool = True) -> EstimateTable:
        counters, n = self._host(states)
        y = (counters ** 2).sum(axis=2)            # (N, L) second moments
        N, L = y.shape
        x = np.zeros((N, L))
        for i in range(N):
            x[i] = sjpc.f2_to_pair_count(self.d, self.s, n[i], 1.0, y[i],
                                         clamp=clamp)
        g = np.cumsum(x[:, ::-1], axis=1)[:, ::-1] + n[:, None]
        zeros = np.zeros_like(x)
        return EstimateTable(x=x, g=g, y=y, n=n, stderr=zeros,
                             stderr_offline=zeros, stderr_kind="none")

    def estimate_ref(self, state, *, clamp: bool = True) -> EstimateTable:
        return self.estimate_batch(stack_states([state]), clamp=clamp)

    def estimate_join_batch(self, states_a, states_b, *,
                            clamp: bool = True) -> EstimateTable:
        ca, n_a = self._host(states_a)
        cb, n_b = self._host(states_b)
        y = (ca * cb).sum(axis=2)                  # (N, L) inner products
        N, L = y.shape
        x = np.zeros((N, L))
        for i in range(N):
            x[i] = sjpc.inner_to_join_count(self.d, self.s, 1.0, y[i],
                                            clamp=clamp)
        g = np.cumsum(x[:, ::-1], axis=1)[:, ::-1]  # join g: pairs only
        zeros = np.zeros_like(x)
        return EstimateTable(x=x, g=g, y=y, n=np.stack([n_a, n_b], axis=1),
                             stderr=zeros, stderr_offline=zeros,
                             stderr_kind="none")

    def estimate_join_ref(self, state_a, state_b, *,
                          clamp: bool = True) -> EstimateTable:
        return self.estimate_join_batch(stack_states([state_a]),
                                        stack_states([state_b]),
                                        clamp=clamp)


def _fusion_key(est: IPFEstimator):
    """Planner fusion signature: same frozen config -> same jit shape ->
    fusable cohort (the spec's ``fusion`` hook; DESIGN.md §19)."""
    return est.cfg


def _factory(cfg, *, params=None, estimator_cfg=None):
    """Equal-space factory: spread the group's counter budget
    (L * depth * width int32 cells) across L partitioned rows of
    W = depth * width cells -- memory_bytes == cfg.counters_bytes."""
    del params
    ipf_cfg = estimator_cfg or IPFConfig(
        d=cfg.d, s=cfg.s, row_width=cfg.width * cfg.depth,
        seed=cfg.seed ^ 0x1BF0)
    return IPFEstimator(ipf_cfg)


register("ipf", _factory, state_cls=IPFState,
         linear=True, join_capable=True, stderr_kind="none",
         fusion=_fusion_key, exact_oracle=pairwise_exact_oracle)
