"""Pallas TPU kernel: fused all-pairs similarity histogram of a reservoir.

The reservoir-sampling estimator's query hot path: given the stored sample
of a stream -- items (R, d) plus a validity mask -- count, for every level
k in [0, d], the ordered pairs (i != j, both valid) whose records agree on
exactly k columns.  The scaled suffix sums of that histogram are the
estimator's x[k] / g_s table (core/baselines.py eq.; DESIGN.md §13.3).

Done naively on host numpy this is O(R^2 d) Python-driven work per query;
here it is ONE kernel launch over stacked samples:

  grid (N, i_tiles, j_tiles):
    stream axis     -- parallel; each stream owns an (R, d) sample slab
    i/j tile axes   -- sequential; the (d+1,) histogram accumulator stays
                       resident in VMEM while every (block_r, block_r) pair
                       tile of the R x R match matrix reduces into it

  per cell:  the Hamming-match tile  M[a, b] = #{c : A[a, c] == B[b, c]}
             builds column-by-column on the VPU (d is static and small);
             pair validity (both slots live, a != b on the diagonal tile)
             masks it, and each of the d+1 histogram bins is an indicator
             reduction of the tile, so the R^2-sized match matrix never
             leaves the chip.

Counts are exact: every reduction is int32.  The pure-jnp fallback
(kernels/ref.py:fused_pairs_ref) is bit-identical; both are tested against
the O(n^2) numpy oracle (core/exact.py:brute_force_pair_counts) across depths/widths/empty inputs
in tests/test_fused_pairs.py.

The N grid axis is the batching surface for more than streams: the
bootstrap error bars (estimators/uncertainty.py, DESIGN.md §14) flatten
their (streams, replicates) stack into it through ``kernels.ops
.fused_pairs`` (which accepts arbitrary leading dims), so B resampled
histograms per stream cost one launch, not B.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_R = 128


def _kernel(items_i_ref, items_j_ref, valid_i_ref, valid_j_ref, out_ref,
            *, d: int, block_r: int):
    gi, gj = pl.program_id(1), pl.program_id(2)

    @pl.when(jnp.logical_and(gi == 0, gj == 0))
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    a = items_i_ref[0]                                   # (BR, d) uint32
    bt = items_j_ref[0]                                  # (d, BR) uint32
    # Hamming-match tile, column by column (d is static and tiny): the i
    # side is read as columns, the j side (passed transposed) as rows
    match = jnp.zeros((block_r, block_r), jnp.int32)
    for c in range(d):
        match += (a[:, c:c + 1] == bt[c:c + 1, :]).astype(jnp.int32)

    # pair validity: both slots live, and not the self-pair on the diagonal
    row = jax.lax.broadcasted_iota(jnp.int32, (block_r, block_r), 0) \
        + gi * block_r
    col = jax.lax.broadcasted_iota(jnp.int32, (block_r, block_r), 1) \
        + gj * block_r
    ok = (valid_i_ref[0] != 0) & (valid_j_ref[0] != 0) & (row != col)
    match = jnp.where(ok, match, -1)                     # -1 = masked out

    # bin into the (1, d+1) histogram row: per level, a sublane then a lane
    # reduction of the indicator tile (exact int32 counts)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, d + 1), 1)
    hist = jnp.zeros((1, d + 1), jnp.int32)
    for k in range(d + 1):
        per_col = jnp.sum((match == k).astype(jnp.int32), axis=0,
                          keepdims=True)                 # (1, BR)
        hist += jnp.where(lane == k,
                          jnp.sum(per_col, axis=1, keepdims=True), 0)
    out_ref[0] += hist


@functools.partial(jax.jit, static_argnames=("block_r", "interpret"))
def fused_pairs_pallas(items, valid, *, block_r: int = DEFAULT_BLOCK_R,
                       interpret: bool = True):
    """(N, R, d) samples x (N, R) validity -> (N, d+1) int32 histograms.

    out[i, k] = #ordered pairs (a != b, both valid) of stream i's sample
    agreeing on exactly k columns.  ``interpret=True`` runs the kernel in
    the Pallas interpreter (any backend); on a TPU pass interpret=False.
    """
    N, R, d = items.shape
    assert valid.shape == (N, R), (valid.shape, (N, R))
    items = items.astype(jnp.uint32)
    valid = valid.astype(jnp.int32)
    block_r = min(block_r, max(R, 8))
    pad_r = (-R) % block_r
    if pad_r:                     # padded slots carry valid=0: contribute 0
        items = jnp.pad(items, ((0, 0), (0, pad_r), (0, 0)))
        valid = jnp.pad(valid, ((0, 0), (0, pad_r)))
    r_pad = R + pad_r

    # every operand and the output keep the stream axis out of their last
    # two block dims: the j side and its validity travel as rows
    # (transposed / (N, 1, R)), the i side's validity as a column
    tiles = r_pad // block_r
    kernel = functools.partial(_kernel, d=d, block_r=block_r)
    out = pl.pallas_call(
        kernel,
        grid=(N, tiles, tiles),
        in_specs=[
            pl.BlockSpec((1, block_r, d), lambda n, gi, gj: (n, gi, 0)),
            pl.BlockSpec((1, d, block_r), lambda n, gi, gj: (n, 0, gj)),
            pl.BlockSpec((1, block_r, 1), lambda n, gi, gj: (n, gi, 0)),
            pl.BlockSpec((1, 1, block_r), lambda n, gi, gj: (n, 0, gj)),
        ],
        out_specs=pl.BlockSpec((1, 1, d + 1), lambda n, gi, gj: (n, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((N, 1, d + 1), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
        name="sjpc_fused_pairs",
    )(items, jnp.swapaxes(items, 1, 2), valid[:, :, None], valid[:, None, :])
    return out[:, 0]
