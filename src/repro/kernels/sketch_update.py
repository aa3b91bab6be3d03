"""Pallas TPU kernel: Fast-AGMS sketch update as one-hot MXU matmuls.

The paper's hot loop is ``C[i, h2(e)] += h1(e)`` per stream element -- a
random-access scatter, which TPUs execute miserably.  TPU-native adaptation:
for a block of keys, build the (block, w_tile) one-hot bucket matrix and
contract it against the sign vector on the MXU:

    delta[i, :] = signs_i^T (1 x BN)  @  onehot_i (BN x BW)

Products are ±1 and the contraction length is the block size, so float32
accumulation is exact (|sum| <= BN << 2^24).  Counters stay resident in VMEM
across the sequential key-block grid dimension; the width dimension is
blocked as a parallel grid dimension (hashes are recomputed per width tile
-- 12 uint32 multiplies per key, negligible).

Grid: (num_key_blocks [sequential accumulate], num_width_blocks [parallel]).
The kernel emits counters_in + delta so callers treat it as a pure update.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.hashing import cw_hash_pair, hash_sign

DEFAULT_BLOCK_N = 1024
DEFAULT_BLOCK_W = 1024


def _kernel(fp1_ref, fp2_ref, weight_ref, counters_ref, bcoef_ref, scoef_ref,
            out_ref, *, depth: int, block_w: int):
    gn = pl.program_id(0)
    gw = pl.program_id(1)

    @pl.when(gn == 0)
    def _init():
        out_ref[...] = counters_ref[...]

    fp1 = fp1_ref[...]                      # (BN,) uint32
    fp2 = fp2_ref[...]
    weight = weight_ref[...].astype(jnp.float32)          # (BN,)
    w_lo = (gw * block_w).astype(jnp.int32)

    col = jax.lax.broadcasted_iota(jnp.int32, (fp1.shape[0], block_w), 1)
    for i in range(depth):                  # depth is small + static
        hb = cw_hash_pair(fp1, fp2, bcoef_ref[i])          # (BN,) uint32
        # global bucket id; the width tile covers [w_lo, w_lo + BW)
        bucket = (hb & jnp.uint32(out_ref.shape[1] * pl.num_programs(1) - 1)).astype(jnp.int32)
        onehot = (bucket[:, None] - w_lo == col).astype(jnp.float32)   # (BN, BW)
        sign = hash_sign(cw_hash_pair(fp1, fp2, scoef_ref[i])).astype(jnp.float32)
        contrib = jnp.dot((sign * weight)[None, :], onehot,
                          preferred_element_type=jnp.float32)          # (1, BW)
        out_ref[i, :] += contrib[0].astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("block_n", "block_w", "interpret"))
def sketch_update_pallas(counters, fp1, fp2, bucket_coeffs, sign_coeffs, weights,
                         *, block_n: int = DEFAULT_BLOCK_N,
                         block_w: int = DEFAULT_BLOCK_W,
                         interpret: bool = True):
    """counters (t, w) int32 + flat keys (N,) -> updated (t, w) counters.

    ``interpret=True`` runs the kernel in the Pallas interpreter (any
    backend); on a TPU pass interpret=False.  N is padded to a block
    multiple with weight-0 elements; w must be a power of two (sketch
    invariant).
    """
    t, w = counters.shape
    fp1 = fp1.reshape(-1)
    fp2 = fp2.reshape(-1)
    weights = weights.reshape(-1).astype(jnp.int32)
    n = fp1.shape[0]

    block_n = min(block_n, max(n, 128))
    block_w = min(block_w, w)
    # non-divisor width tiles would leave tail columns unwritten and break
    # the `& (w_total - 1)` bucket mask -- fail loudly instead
    assert w & (w - 1) == 0, "sketch width must be a power of two"
    assert block_w & (block_w - 1) == 0, \
        f"block_w={block_w} must be a power of two (so it divides w={w})"
    pad = (-n) % block_n
    if pad:
        fp1 = jnp.pad(fp1, (0, pad))
        fp2 = jnp.pad(fp2, (0, pad))
        weights = jnp.pad(weights, (0, pad))
    n_pad = n + pad

    grid = (n_pad // block_n, w // block_w)
    kernel = functools.partial(_kernel, depth=t, block_w=block_w)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n,), lambda gn, gw: (gn,)),
            pl.BlockSpec((block_n,), lambda gn, gw: (gn,)),
            pl.BlockSpec((block_n,), lambda gn, gw: (gn,)),
            pl.BlockSpec((t, block_w), lambda gn, gw: (0, gw)),
            pl.BlockSpec((t, 2, 4), lambda gn, gw: (0, 0, 0)),
            pl.BlockSpec((t, 2, 4), lambda gn, gw: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((t, block_w), lambda gn, gw: (0, gw)),
        out_shape=jax.ShapeDtypeStruct((t, w), jnp.int32),
        interpret=interpret,
    )(fp1, fp2, weights, counters, bucket_coeffs, sign_coeffs)
