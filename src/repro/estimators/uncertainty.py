"""Calibrated error bars for sample-kind estimators (DESIGN.md §14).

The paper's accuracy story (Thms. 1/2, Figs. 4/8) is about *bounded*
error, yet until this module the service hard-zeroed ``stderr`` for every
sample-kind estimator -- a correctness bug in the served confidence, not a
missing feature.  The remedy is the standard one for sampling estimators
with no closed-form bound (Efron bootstrap, plus Serfling's
without-replacement correction):

  * **Bootstrap over the retained sample** (reservoir): resample the valid
    sample B times with replacement, recompute the scaled pair-count table
    per replicate, and report the replicate standard deviation.  All B
    histograms ride the existing fused all-pairs kernel's N dimension in
    ONE launch (``kernels.ops.fused_pairs`` accepts stacked leading dims),
    so the error bar costs one extra kernel call, not B.

  * **m-out-of-m cap**: at service-scale reservoirs (R ~ thousands) a full
    resample would multiply the O(R^2 d) pair reduction by B.  Replicates
    are capped at ``item_cap`` items and the replicate std is rescaled by
    sqrt(b / m) -- the m-out-of-n bootstrap correction for a degree-2
    U-statistic whose leading variance term is O(1/m).

  * **Serfling finite-population correction**: the reservoir samples
    *without replacement* from the n-record window, so the iid bootstrap
    overstates the variance by the factor Serfling's inequality removes;
    every stderr is scaled by sqrt(max(1 - (m-1)/n, 0)).

  * **Stratified bootstrap** (LSH-SS): the estimate is
    f1·same_pairs + f2·cross_pairs + n with the stratum totals read from
    *linear* (near-exact) bucket counters and the fractions from two
    fixed-capacity pair reservoirs.  Each stratum's reservoir is resampled
    independently; the per-stratum replicate deviations are scaled by that
    stratum's pair mass and Serfling factor (population = candidates seen),
    then combined -- a stratified bootstrap of exactly the random part of
    the estimator.

Every path is deterministic given the estimator seed and the state's
(n, step) coordinates: snapshots of an unchanged window report identical
error bars, so the query engine's version-keyed cache stays coherent.

``EstimateTable.stderr_kind`` names the method ("analytic" for SJPC's
Theorem 1/2 bounds, "bootstrap" / "bootstrap_stratified" here, "none"
when disabled) so ``service.query`` can surface per-kind confidence
intervals through one uniform contract.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from repro.obs.metrics import default_registry
from repro.obs.trace import child

DEFAULT_REPLICATES = 32     # bootstrap resamples B
DEFAULT_ITEM_CAP = 256      # m-out-of-m cap b per replicate

_BOOT_SALT = 0xB0075  # PRNG domain separator vs ingest / merge salts


def serfling_factor(n, m):
    """Serfling's without-replacement variance factor, as a std multiplier.

    For a size-m uniform sample drawn without replacement from an
    n-record population, Serfling's inequality tightens the iid
    (with-replacement) bound by (1 - (m-1)/n); the matching stderr
    correction is its square root.  Degenerate windows (n <= 1 or an
    exhausted population) clamp to [0, 1].
    """
    n = np.asarray(n, np.float64)
    m = np.asarray(m, np.float64)
    with np.errstate(divide="ignore", invalid="ignore"):
        f = np.where(n > 0, 1.0 - (m - 1.0) / np.maximum(n, 1.0), 1.0)
    return np.sqrt(np.clip(f, 0.0, 1.0))


def bootstrap_key(seed: int, n, step):
    """Per-stream PRNG keys for bootstrap resampling: deterministic in the
    estimator seed and the state's (n, step) coordinates, so an unchanged
    window always reports the same error bar.  n (N,), step (N,) ->
    (N,) keys."""
    base = jax.random.PRNGKey(np.uint32(seed) ^ np.uint32(_BOOT_SALT))

    def one(n_i, step_i):
        return jax.random.fold_in(jax.random.fold_in(base, n_i), step_i)

    return jax.vmap(one)(jnp.asarray(n, jnp.int32),
                         jnp.asarray(step, jnp.int32))


def resample_valid_slots(keys, valid, replicates: int, item_cap: int):
    """Bootstrap slot indices over the valid entries of fixed-shape samples.

    valid (N, R) bool/int -> (idx (N, B, b) int32, rep_valid (N, B, b)
    int32, b_sizes (N,) int32) with b = min(item_cap, R): ``idx`` draws
    uniformly *with replacement* from each stream's valid slots (columns
    past ``b_i = min(m_i, item_cap)`` are masked out by ``rep_valid``, as
    are whole streams with m < 2 -- no pairs, no bootstrap).  Everything
    stays a device computation: the caller can gather items and feed the
    (N*B, b, d) stack straight through the fused all-pairs kernel.
    """
    valid = jnp.asarray(valid) != 0
    N, R = valid.shape
    b = min(item_cap, R)
    m = jnp.sum(valid.astype(jnp.int32), axis=1)              # (N,)
    # valid slot ids first, in slot order: argsort of ~valid is stable
    order = jnp.argsort(~valid, axis=1).astype(jnp.int32)      # (N, R)

    def draw(key, m_i):
        return jax.random.randint(key, (replicates, b), 0,
                                  jnp.maximum(m_i, 1))

    r = jax.vmap(draw)(keys, m)                                # (N, B, b)
    idx = jnp.take_along_axis(order[:, None, :], r, axis=2)
    b_sizes = jnp.minimum(m, b)
    col = jnp.arange(b, dtype=jnp.int32)
    rep_valid = jnp.broadcast_to(
        (col[None, None, :] < b_sizes[:, None, None])
        & (m[:, None, None] >= 2), (N, replicates, b)).astype(jnp.int32)
    return idx, rep_valid, b_sizes


def pair_scale(n, m):
    """n(n-1) / (m(m-1)) with the m < 2 guard -> the zero table."""
    n = np.asarray(n, np.float64)
    m = np.asarray(m, np.float64)
    with np.errstate(divide="ignore", invalid="ignore"):
        return np.where(m >= 2, n * (n - 1.0)
                        / np.maximum(m * (m - 1.0), 1.0), 0.0)


def suffix_stderr_from_reps(x_reps: np.ndarray) -> np.ndarray:
    """Replicate per-level tables (N, B, L) -> stderr of the suffix-sum
    g table (N, L): std (ddof=1) of the per-replicate suffix sums.  (The
    additive +n of g is deterministic and drops out of the deviation.)"""
    g_reps = np.cumsum(x_reps[:, :, ::-1], axis=2)[:, :, ::-1]
    return g_reps.std(axis=1, ddof=1)


def bootstrap_pair_stderr(items, valid, n, *, keys, s: int,
                          replicates: int = DEFAULT_REPLICATES,
                          item_cap: int = DEFAULT_ITEM_CAP,
                          use_pallas=None, interpret=None,
                          pair_fn=None) -> np.ndarray:
    """Bootstrap stderr of a scaled all-pairs suffix table (reservoir).

    items (N, R, d) stored samples, valid (N, R), n (N,) float window
    counts; returns (N, L) absolute stderr for g_k, k = s..d, already
    rescaled by the m-out-of-m cap (sqrt(b/m)) and the Serfling factor.
    ``pair_fn(items, valid)`` computes stacked pair histograms (defaults
    to the fused kernel; tests inject the numpy oracle).
    """
    if pair_fn is None:
        from repro.kernels.ops import fused_pairs

        def pair_fn(it, va):
            return fused_pairs(it, va, use_pallas=use_pallas,
                               interpret=interpret)

    items = jnp.asarray(items)
    N, R, d = items.shape
    L = d - s + 1
    if replicates < 2 or R < 2:
        return np.zeros((N, L))
    with child("query.bootstrap", streams=N, replicates=replicates,
               method="bootstrap", slots=min(item_cap, R)):
        m = np.asarray(jax.device_get(
            jnp.sum(jnp.asarray(valid) != 0, axis=1)), np.float64)
        reg = default_registry()
        if reg.enabled:
            reg.inc("bootstrap_replicates_total", N * replicates,
                    method="bootstrap")
        idx, rep_valid, b_sizes = resample_valid_slots(
            keys, valid, replicates, item_cap)
        # gather replicate items on device; ONE fused launch over the
        # stacked (N, B) leading dims computes every replicate histogram
        rep_items = jnp.take_along_axis(items[:, None, :, :],
                                        idx[:, :, :, None], axis=2)
        hists = np.asarray(jax.device_get(pair_fn(rep_items, rep_valid)),
                           np.float64)                    # (N, B, d+1)

        n = np.asarray(n, np.float64)
        b_sizes = np.asarray(jax.device_get(b_sizes), np.float64)
        scale_b = pair_scale(n, b_sizes)                      # (N,)
        x_reps = hists[:, :, s:] * scale_b[:, None, None]     # (N, B, L)
        stderr = suffix_stderr_from_reps(x_reps)
        # m-out-of-m cap rescale (U-stat leading variance is O(1/m)) and
        # the Serfling without-replacement correction
        with np.errstate(divide="ignore", invalid="ignore"):
            cap_scale = np.where(m >= 2, np.sqrt(
                np.minimum(b_sizes, m) / np.maximum(m, 1.0)), 0.0)
        return stderr * (cap_scale * serfling_factor(n, m))[:, None]


def _resample_fracs(sim, valid, levels, rng, replicates: int):
    """Bayesian-bootstrap level-fraction replicates of ONE stream's
    stratum reservoir: sim (M,) int match counts, valid (M,) ->
    ((B, d+1) replicate fractions, m).

    Replicates draw f* ~ Dirichlet(hits + 1/2) -- the Rubin bootstrap
    under the Jeffreys prior -- rather than the empirical multinomial.
    The smoothing matters: rare levels (one cross-stratum hit scales to
    ~n^2/M pairs) are zero in a third of reservoirs, and the empirical
    bootstrap then reports *zero* spread for mass it simply failed to
    see, collapsing the error bar exactly where it is needed most.  The
    Jeffreys pseudo-count keeps a half-hit of spread at every level, at
    the cost of a slightly conservative bar on well-observed ones.
    m == 0 gives all-zero fractions (the stratum contributes nothing).
    """
    vals = np.asarray(sim)[np.asarray(valid) != 0]
    m = vals.shape[0]
    if m == 0:
        return np.zeros((replicates, levels.shape[0])), 0.0
    hits = (vals[:, None] == levels).sum(axis=0)
    return rng.dirichlet(hits + 0.5, size=replicates), float(m)


def stratified_bootstrap_stderr(same_sim, same_valid, same_seen,
                                cross_sim, cross_valid, cross_seen,
                                same_pairs, cross_pairs, *, d: int, s: int,
                                seed: int, n, step,
                                replicates: int = DEFAULT_REPLICATES
                                ) -> np.ndarray:
    """Stratified bootstrap stderr for the LSH-SS g table (N, L).

    Each stratum's pair reservoir is resampled independently; its centered
    replicate fraction deviations are scaled by the stratum's (linear,
    near-exact) pair mass and its Serfling factor (population = candidates
    seen), then combined per replicate -- bootstrapping exactly the random
    part of x = f1*same_pairs + f2*cross_pairs.
    """
    same_pairs = np.asarray(same_pairs, np.float64)
    cross_pairs = np.asarray(cross_pairs, np.float64)
    if replicates < 2:
        raise ValueError("stratified bootstrap needs >= 2 replicates")
    levels = np.arange(d + 1)
    N = same_pairs.shape[0]
    with child("query.bootstrap", streams=N, replicates=replicates,
               method="bootstrap_stratified"):
        reg = default_registry()
        if reg.enabled:
            reg.inc("bootstrap_replicates_total", N * replicates,
                    method="bootstrap_stratified")
        n_i = np.asarray(n, np.int64).reshape(N)
        step_i = np.asarray(step, np.int64).reshape(N)
        seen_s = np.asarray(same_seen, np.float64).reshape(N)
        seen_c = np.asarray(cross_seen, np.float64).reshape(N)
        x_dev = np.zeros((N, replicates, d + 1))
        for i in range(N):
            # per-stream rng keyed on (seed, n, step): a stream's error bar
            # is independent of its position in a stacked cohort
            # (batch == ref)
            rng = np.random.default_rng(np.random.SeedSequence(
                [int(np.uint32(seed) ^ np.uint32(_BOOT_SALT)),
                 int(n_i[i]) & 0xFFFFFFFF, int(step_i[i]) & 0xFFFFFFFF]))
            for sim, valid, seen, pairs in (
                    (np.asarray(same_sim)[i], np.asarray(same_valid)[i],
                     seen_s[i], same_pairs[i]),
                    (np.asarray(cross_sim)[i], np.asarray(cross_valid)[i],
                     seen_c[i], cross_pairs[i])):
                f, m = _resample_fracs(sim, valid, levels, rng, replicates)
                dev = f - f.mean(axis=0, keepdims=True)        # (B, d+1)
                x_dev[i] += dev * (pairs * serfling_factor(seen, m))
        return suffix_stderr_from_reps(x_dev[:, :, s:])
