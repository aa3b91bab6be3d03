"""Pallas TPU kernel: fused batched query moments for stacked sketches.

Step 2 of Algorithm 1 (and its §6 join analogue) for MANY sketches at once:
given counter stacks of shape (N, L, t, w) -- N streams, L lattice levels,
depth t, width w -- compute every (stream, level, depth-row) moment

  out[i, l, k] = sum_j A[i, l, k, j] * B[i, l, k, j]

in ONE launch.  F2 (self-join) is the A = B case; the similarity-join
estimator uses two different stacks sketched with identical hash params.
The median over the depth axis and the lattice inversion are O(N*L*t)
scalars and stay in the surrounding jit (`sjpc._estimate_batch_core`).

  grid (N / BLOCK_N, w_tiles):
    stream axis     -- parallel; each step owns BLOCK_N streams' (L, t, w)
                      counter blocks (all levels at once)
    width axis      -- innermost + sequential: the (BLOCK_N, L, t)
                      accumulator stays resident in VMEM while every
                      (t, block_w) counter tile reduces into it
                      (counters-squared reduction never leaves the chip)

f32 products/sums are exact while every partial sum stays below 2^24 --
the paper's O(log n)-bit counter analysis puts SJPC magnitudes well inside
that for the widths used here; the int64-exact numpy oracle
(`core.sketch.np_estimate_f2_exact` / `np_estimate_inner_exact`) remains
the reference for anything larger.  The pure-jnp fallback
(`kernels.ref.fused_query_ref`) is bit-identical on such exact-integer
inputs (asserted in tests/test_fused_query.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_W = 2048
BLOCK_N = 8                 # streams per grid step


def _kernel(a_ref, b_ref, out_ref):
    gw = pl.program_id(1)

    @pl.when(gw == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    a = a_ref[...].astype(jnp.float32)           # (BN, L, t, block_w)
    b = b_ref[...].astype(jnp.float32)
    out_ref[...] += jnp.sum(a * b, axis=-1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("block_w", "interpret"))
def fused_query_pallas(counters_a, counters_b, *,
                       block_w: int = DEFAULT_BLOCK_W,
                       interpret: bool = True):
    """(N, L, t, w) x (N, L, t, w) -> (N, L, t) float32 row moments.

    ``interpret=True`` runs the kernel in the Pallas interpreter (any
    backend); on a TPU pass interpret=False.
    """
    assert counters_a.shape == counters_b.shape, \
        (counters_a.shape, counters_b.shape)
    N, L, t, w = counters_a.shape
    bw = min(block_w, w)
    # widths are powers of two (sketch invariant), so any pow2 tile divides
    assert w % bw == 0, f"block_w={bw} must divide width w={w}"
    bn = min(BLOCK_N, N)
    pad_n = (-N) % bn
    if pad_n:                     # zero planes: moments 0, sliced off below
        counters_a = jnp.pad(counters_a, ((0, pad_n), (0, 0), (0, 0), (0, 0)))
        counters_b = jnp.pad(counters_b, ((0, pad_n), (0, 0), (0, 0), (0, 0)))
    # the output keeps a trailing unit lane axis so its (t, 1) block tail
    # equals the array tail, as the TPU block rule requires
    spec = pl.BlockSpec((bn, L, t, bw), lambda i, gw: (i, 0, 0, gw))
    out = pl.pallas_call(
        _kernel,
        grid=((N + pad_n) // bn, w // bw),
        in_specs=[spec, spec],
        out_specs=pl.BlockSpec((bn, L, t, 1), lambda i, gw: (i, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((N + pad_n, L, t, 1), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="sjpc_fused_query",
    )(counters_a, counters_b)
    return out[:N, ..., 0]
