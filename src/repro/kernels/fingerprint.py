"""Pallas TPU kernel: batched sub-value fingerprinting (masked Horner).

Computes the (B, M) matrix of polynomial fingerprints of every record
projected under every level-k column combination -- the projection-
generation step of Algorithm 1, fully dense (no gathers; excluded columns
are `where`-skipped using the static combination-mask table).

Tiling: grid (B_tiles, M_tiles); each kernel instance holds a
(block_b, d) slab of records and a (d, block_m) slab of (transposed)
combination masks in VMEM and emits a (block_b, block_m) fingerprint tile;
the two fingerprint bases are scalars in SMEM.  d is a static python
loop (d <= ~12 for SJPC's practical regime, paper §9).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.hashing import addmod_p31, mulmod_p31, reduce_p31

DEFAULT_BLOCK_B = 256
DEFAULT_BLOCK_M = 256


def horner_fingerprints(values, masks_t, ids, bases_ref):
    """Both masked-Horner fingerprints of a record block, inside a kernel.

    values (BB, d) uint32 records; masks_t (d, M) combination masks
    (transposed, so column ``col`` is a lane row); ids (1, M) combination
    ids; bases_ref: the two fingerprint bases as an int32 SMEM table.
    Returns (fp1, fp2), each (BB, M) uint32.
    """
    values = reduce_p31(values)
    seed = addmod_p31(reduce_p31(ids), jnp.uint32(1))        # (1, M)
    shape = (values.shape[0], seed.shape[1])
    fps = []
    for which in (0, 1):
        base = bases_ref[which].astype(jnp.uint32)
        fp = jnp.broadcast_to(seed, shape)
        for col in range(masks_t.shape[0]):                  # d is static
            v = addmod_p31(values[:, col:col + 1], jnp.uint32(1))
            nxt = addmod_p31(mulmod_p31(fp, base), v)
            fp = jnp.where(masks_t[col:col + 1, :] != 0, nxt, fp)
        fps.append(fp)
    return tuple(fps)


def _kernel(bases_ref, values_ref, masks_ref, ids_ref, out1_ref, out2_ref):
    out1_ref[...], out2_ref[...] = horner_fingerprints(
        values_ref[...], masks_ref[...], ids_ref[...], bases_ref)


@functools.partial(jax.jit, static_argnames=("block_b", "block_m", "interpret"))
def fingerprint_pallas(values, combo_masks, combo_ids, bases,
                       *, block_b: int = DEFAULT_BLOCK_B,
                       block_m: int = DEFAULT_BLOCK_M,
                       interpret: bool = True):
    """values (B, d) x combos (M, d) -> (fp1, fp2) each (B, M) uint32."""
    values = values.astype(jnp.uint32)
    combo_masks = combo_masks.astype(jnp.uint32)
    combo_ids = combo_ids.astype(jnp.uint32)
    B, d = values.shape
    M = combo_ids.shape[0]

    bb = min(block_b, max(-(-B // 8) * 8, 8))
    bm = min(block_m, max(-(-M // 128) * 128, 128))
    pad_b = (-B) % bb
    pad_m = (-M) % bm
    if pad_b:
        values = jnp.pad(values, ((0, pad_b), (0, 0)))
    if pad_m:
        combo_masks = jnp.pad(combo_masks, ((0, pad_m), (0, 0)))
        combo_ids = jnp.pad(combo_ids, (0, pad_m))

    grid = (values.shape[0] // bb, combo_ids.shape[0] // bm)
    out_shape = (values.shape[0], combo_ids.shape[0])
    fp1, fp2 = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((bb, d), lambda gb, gm: (gb, 0)),
            pl.BlockSpec((d, bm), lambda gb, gm: (0, gm)),
            pl.BlockSpec((1, bm), lambda gb, gm: (0, gm)),
        ],
        out_specs=[
            pl.BlockSpec((bb, bm), lambda gb, gm: (gb, gm)),
            pl.BlockSpec((bb, bm), lambda gb, gm: (gb, gm)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(out_shape, jnp.uint32),
            jax.ShapeDtypeStruct(out_shape, jnp.uint32),
        ],
        interpret=interpret,
    )(jnp.asarray(bases).astype(jnp.int32), values, combo_masks.T,
      combo_ids[None, :])
    return fp1[:B, :M], fp2[:B, :M]
