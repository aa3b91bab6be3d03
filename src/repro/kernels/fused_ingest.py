"""Pallas TPU kernel: fused fingerprint -> multi-level Fast-AGMS ingest.

This is Step 1 of Algorithm 1 as ONE kernel launch.  The unfused path
(kernels/fingerprint.py + kernels/sketch_update.py) round-trips the (B, M)
fingerprint matrix through HBM between two dispatches and launches once per
lattice level; here every level's projection fingerprints are produced in
VMEM and immediately contracted into that level's counters, so the record
slab is read once and nothing intermediate ever leaves the chip:

  grid (L, w_tiles, b_blocks):
    level axis      -- parallel; each level has its own combo table, hash
                       coefficients, and (t, w) counter plane
    width axis      -- parallel; counters are tiled (t, block_w)
    batch axis      -- innermost + sequential: the (t, block_w) counter tile
                       stays resident in VMEM while every record block's
                       contribution accumulates into it (the deferred-flush
                       analogue of the cross-device merge deferral)

  per cell:  masked-Horner fingerprints (block_b, m_max) for this level's
             combos, then per depth row and per combo slot the bucket
             column is compared against the tile's lane iota and the
             signed weights are summed down the record axis (exact int32).

TPU layout: every operand blocked along the level axis keeps that axis out
of its last two block dimensions (masks are passed as (L, d, m_max), ids as
(L, 1, m_max), weights as (L, B, m_max)); the fingerprint bases and the
hash coefficients are scalar tables in SMEM.

Levels are padded to a rectangular (L, m_max) combo table; padded slots
carry weight 0 everywhere (enforced by the caller via
``projections.PaddedLattice.valid``), so they contribute nothing -- the
kernel output is bit-identical to the per-level reference chain
(asserted across remainders/depths/tiles in tests/test_fused_ingest.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.hashing import addmod_p31, hash_sign, mulmod_p31
from .fingerprint import horner_fingerprints

DEFAULT_BLOCK_B = 256
DEFAULT_BLOCK_W = 1024


def _cw_hash_pair_smem(x, y, coef_ref, base: int):
    """``hashing.cw_hash_pair`` with its (2, 4) coefficients read as
    scalars from a flat SMEM table starting at ``base``."""
    out = None
    for half, v in enumerate((x, y)):
        c = [coef_ref[base + 4 * half + i].astype(jnp.uint32)
             for i in range(4)]
        h = jnp.broadcast_to(c[3], v.shape)
        for i in (2, 1, 0):
            h = addmod_p31(mulmod_p31(h, v), c[i])
        out = h if out is None else addmod_p31(out, h)
    return out


def _kernel(bases_ref, bcoef_ref, scoef_ref, values_ref, masks_ref, ids_ref,
            wt_ref, counters_ref, out_ref, *, depth: int, block_w: int,
            width: int):
    lvl, gw, gb = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(gb == 0)
    def _init():
        out_ref[...] = counters_ref[...]

    # --- fingerprints for this (record block, level) pair, in VMEM --------
    fp1, fp2 = horner_fingerprints(values_ref[...], masks_ref[0], ids_ref[0],
                                   bases_ref)                # (BB, M) each

    # --- straight into the sketch: per depth row, per combo slot ----------
    weight = wt_ref[0]                                       # (BB, M) int32
    lane = (jax.lax.broadcasted_iota(jnp.int32, (fp1.shape[0], block_w), 1)
            + gw * block_w)
    for i in range(depth):                                   # depth is static
        coef = (lvl * depth + i) * 8
        hb = _cw_hash_pair_smem(fp1, fp2, bcoef_ref, coef)
        bucket = (hb & jnp.uint32(width - 1)).astype(jnp.int32)
        signed = (hash_sign(_cw_hash_pair_smem(fp1, fp2, scoef_ref, coef))
                  * weight)
        row = jnp.zeros((1, block_w), jnp.int32)
        for m in range(fp1.shape[1]):                        # m_max is static
            hit = bucket[:, m:m + 1] == lane                 # (BB, BW)
            row += jnp.sum(jnp.where(hit, signed[:, m:m + 1], 0), axis=0,
                           keepdims=True)
        out_ref[0, i:i + 1, :] += row


def _as_i32(x):
    """Canonical field elements (< 2^31) as int32, for SMEM scalar tables."""
    return jnp.asarray(x).astype(jnp.int32).reshape(-1)


@functools.partial(jax.jit,
                   static_argnames=("block_b", "block_w", "interpret"))
def fused_ingest_pallas(counters, values, masks, ids, bases,
                        bucket_coeffs, sign_coeffs, weights,
                        *, block_b: int = DEFAULT_BLOCK_B,
                        block_w: int = DEFAULT_BLOCK_W,
                        interpret: bool = True):
    """One launch: records -> fingerprints -> every level's sketch.

    counters (L, t, w) int32; values (B, d) uint32; masks (L, m_max, d) /
    ids (L, m_max) padded combo tables; bases (2,); bucket/sign_coeffs
    (L, t, 2, 4); weights (B, L, m_max) int32 with 0 in padded slots (and in
    masked-out rows).  Returns updated (L, t, w) counters.

    ``interpret=True`` runs the kernel in the Pallas interpreter (any
    backend); on a TPU pass interpret=False.
    """
    L, t, w = counters.shape
    B, d = values.shape
    m_max = ids.shape[1]
    values = values.astype(jnp.uint32)
    weights = jnp.moveaxis(weights.astype(jnp.int32), 1, 0)   # (L, B, m_max)
    masks_t = jnp.swapaxes(masks.astype(jnp.uint32), 1, 2)     # (L, d, m_max)
    ids = ids.astype(jnp.uint32).reshape(L, 1, m_max)

    block_b = min(block_b, max(-(-B // 8) * 8, 8))
    block_w = min(block_w, w)
    # the bucket mask `& (w - 1)` and the untiled-tail hazard both require
    # power-of-two tiles that divide the (power-of-two) width
    assert w & (w - 1) == 0, "sketch width must be a power of two"
    assert block_w & (block_w - 1) == 0, \
        f"block_w={block_w} must be a power of two (so it divides w={w})"
    pad_b = (-B) % block_b
    if pad_b:
        values = jnp.pad(values, ((0, pad_b), (0, 0)))
        weights = jnp.pad(weights, ((0, 0), (0, pad_b), (0, 0)))
    b_pad = B + pad_b

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    grid = (L, w // block_w, b_pad // block_b)
    kernel = functools.partial(_kernel, depth=t, block_w=block_w, width=w)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            smem, smem, smem,
            pl.BlockSpec((block_b, d), lambda l, gw, gb: (gb, 0)),
            pl.BlockSpec((1, d, m_max), lambda l, gw, gb: (l, 0, 0)),
            pl.BlockSpec((1, 1, m_max), lambda l, gw, gb: (l, 0, 0)),
            pl.BlockSpec((1, block_b, m_max), lambda l, gw, gb: (l, gb, 0)),
            pl.BlockSpec((1, t, block_w), lambda l, gw, gb: (l, 0, gw)),
        ],
        out_specs=pl.BlockSpec((1, t, block_w), lambda l, gw, gb: (l, 0, gw)),
        out_shape=jax.ShapeDtypeStruct((L, t, w), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="sjpc_fused_ingest",
    )(_as_i32(bases), _as_i32(bucket_coeffs), _as_i32(sign_coeffs),
      values, masks_t, ids, weights, counters)
