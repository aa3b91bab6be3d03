"""SJPC behind the Estimator protocol: a thin adapter over core/sjpc.py.

Nothing numerical lives here -- every path delegates to the PR 1-3 code
(``sjpc.update_fused`` via the service's ``multi_round_update`` scan,
``sjpc.estimate_batch``, the Theorem 1/2 bounds), so the fused
ingest/query conformance suites keep pinning the exact same functions.
The adapter's job is shape only: expose those functions with the protocol
signatures the generalized service layers (registry/window/ingest/query)
dispatch over, alongside the reservoir and LSH-SS competitors.
"""
from __future__ import annotations

import numpy as np

from repro.core import sjpc
from repro.core.sjpc import SJPCConfig, SJPCParams, SJPCState

from .base import (EstimateTable, Estimator, pairwise_exact_oracle, register,
                   stack_states)


class SJPCEstimator(Estimator):
    """The paper's estimator (Algorithm 1) as the protocol's reference
    implementation: linear (merge/subtract are exact counter arithmetic),
    joinable (§6 inner products), with analytical error bounds."""

    kind = "sjpc"
    linear = True
    supports_join = True

    def __init__(self, cfg: SJPCConfig, params: SJPCParams | None = None):
        self.cfg = cfg
        self.params = params if params is not None else sjpc.init(cfg)[0]

    # -- static properties --------------------------------------------
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
        return self.cfg.counters_bytes

    # -- protocol ------------------------------------------------------
    def init(self, sid: int = 0) -> SJPCState:
        del sid                      # linear subtract needs no provenance
        return sjpc.init(self.cfg)[1]

    def ingest_rounds(self, states, values, row_mask, keys):
        # the PR 2 fused scan'd dispatch, verbatim (lazy import: service
        # imports estimators at registry time, so the module edge must
        # point service -> estimators at import and back only at runtime)
        from repro.service.ingest import multi_round_update
        counters, n, steps = multi_round_update(
            self.cfg, self.params, states.counters, states.n, states.step,
            values, row_mask, keys)
        return SJPCState(counters=counters, n=n, step=steps)

    def merge(self, a: SJPCState, b: SJPCState) -> SJPCState:
        return sjpc.merge(a, b)

    def subtract(self, a: SJPCState, b: SJPCState) -> SJPCState:
        return sjpc.subtract(a, b)

    def estimate_batch(self, states, *,
                       clamp: bool = True) -> EstimateTable:
        be = sjpc.estimate_batch(self.cfg, states.counters, states.n,
                                 clamp=clamp)
        return EstimateTable(*be, stderr_kind="analytic")

    def estimate_ref(self, state: SJPCState, *,
                     clamp: bool = True) -> EstimateTable:
        """The PR 1 per-stream oracle: int64-exact F2, float64 inversion,
        scalar Theorem 1/2 bounds -- identical op order to the path the
        reference query engine served before the protocol refactor."""
        cfg = self.cfg
        y = sjpc.level_f2(state)
        n = self.state_n(state)
        x = sjpc.f2_to_pair_count(cfg.d, cfg.s, n, cfg.ratio, y, clamp=clamp)
        L = cfg.num_levels
        g = np.array([x[i:].sum() + n for i in range(L)], np.float64)
        on = np.zeros(L)
        off = np.zeros(L)
        for i, s in enumerate(self.thresholds):
            if g[i] > 0:
                off[i] = np.sqrt(sjpc.offline_variance_bound(
                    cfg.d, s, cfg.ratio, g[i])) * g[i]
                on[i] = np.sqrt(sjpc.online_variance_bound(
                    cfg.d, s, cfg.ratio, cfg.width, n, g[i])) * g[i]
        return EstimateTable(x=x[None], g=g[None], y=np.asarray(y)[None],
                             n=np.array([n]), stderr=on[None],
                             stderr_offline=off[None],
                             stderr_kind="analytic")

    # -- join (SJPC-only capability) ----------------------------------
    def estimate_join_batch(self, states_a, states_b, *,
                            clamp: bool = True) -> EstimateTable:
        be = sjpc.estimate_join_batch(
            self.cfg, states_a.counters, states_b.counters,
            states_a.n, states_b.n, clamp=clamp)
        return EstimateTable(*be, stderr_kind="analytic")

    def estimate_join_ref(self, state_a, state_b, *,
                          clamp: bool = True) -> EstimateTable:
        """Per-pair oracle: int64-exact inner products + float64 inversion,
        with the reference proxy error bars (self-join bound at
        n = max(n_a, n_b), g = max(estimate, 1); DESIGN.md §10.4)."""
        cfg = self.cfg
        y = sjpc.join_level_inner(state_a, state_b)
        x = sjpc.inner_to_join_count(cfg.d, cfg.s, cfg.ratio, y, clamp=clamp)
        L = cfg.num_levels
        g = np.array([x[i:].sum() for i in range(L)], np.float64)
        n_a, n_b = self.state_n(state_a), self.state_n(state_b)
        n = max(n_a, n_b)
        on = np.zeros(L)
        off = np.zeros(L)
        for i, s in enumerate(self.thresholds):
            gp = max(g[i], 1.0)
            off[i] = np.sqrt(sjpc.offline_variance_bound(
                cfg.d, s, cfg.ratio, gp)) * gp
            on[i] = np.sqrt(sjpc.online_variance_bound(
                cfg.d, s, cfg.ratio, cfg.width, n, gp)) * gp
        return EstimateTable(x=x[None], g=g[None], y=np.asarray(y)[None],
                             n=np.array([[n_a, n_b]]), stderr=on[None],
                             stderr_offline=off[None],
                             stderr_kind="analytic")


def _factory(sjpc_cfg, *, params=None, estimator_cfg=None):
    # SJPC has no separate config: it IS the group's SJPCConfig
    if estimator_cfg is not None:
        raise ValueError("sjpc takes no estimator_cfg; its config is the "
                         "hash group's SJPCConfig")
    return SJPCEstimator(sjpc_cfg, params)


register("sjpc", _factory, state_cls=SJPCState, linear=True,
         join_capable=True, stderr_kind="analytic",
         exact_oracle=pairwise_exact_oracle)


__all__ = ["SJPCEstimator", "stack_states"]
