"""Pallas TPU flash attention: O(S·block) HBM traffic for the train/prefill
hotspot.

EXPERIMENTS.md §Perf Iteration 4 showed the dominant memory term of every
attention train/prefill cell is the O(S²) score/probability matrices
materializing at XLA fusion boundaries — and that no jnp-level change
removes them (the dot operand must exist).  This kernel is the fix the
analysis calls for: the (bq, bk) score tile lives ONLY in VMEM scratch;
HBM sees just Q, K, V and O.  Memory-term napkin for deepseek-coder
train_4k attention: 35 TB -> ~0.3 TB per step per device (the residual
QKV/O streaming).

Layout: grid (BH, nq, nk) with the kv axis innermost (sequential); online
softmax state (m, l, acc) lives in VMEM scratch across the kv sweep, and
the output block is written once on the last kv step.  Causal tiles fully
above the diagonal are skipped with pl.when.  GQA: the index_map for K/V
divides the head index, so KV heads are never repeat-expanded in HBM.

``interpret=True`` validates on the CPU; compiled path is the
TPU target.  Oracle: models.attention.full_attention.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, causal: bool, block_q: int, block_k: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_lo = iq * block_q
    k_lo = ik * block_k

    def visible():      # any (q, k) pair in this tile with q >= k?
        return q_lo + block_q - 1 >= k_lo

    @pl.when((not causal) or visible())
    def _tile():
        q = q_ref[0].astype(jnp.float32)                  # (bq, hd)
        k = k_ref[0].astype(jnp.float32)                  # (bk, hd)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_k), 0)
            kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_k), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        m_prev = m_ref[...]                               # (bq, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(m_new > 0.5 * NEG_INF, p, 0.0)
        alpha = jnp.where(m_prev > 0.5 * NEG_INF,
                          jnp.exp(m_prev - m_new), 0.0)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = (acc_ref[...] * alpha
                        + jax.lax.dot_general(
                            p, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))
        m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "q_heads_per_kv", "interpret"))
def flash_attention_pallas(q, k, v, *, causal: bool = True,
                           block_q: int = 512, block_k: int = 512,
                           q_heads_per_kv: int = 1, interpret: bool = True):
    """q (BH, Sq, hd) flattened over batch x q-heads; k, v (BKV, Skv, hd)
    flattened over batch x kv-heads, with BH = BKV * q_heads_per_kv
    (GQA: q head h reads kv head h // q_heads_per_kv -- no HBM expansion).

    Returns (BH, Sq, hd) in q.dtype.
    """
    bh, sq, hd = q.shape
    bkv, skv, _ = k.shape
    assert bh == bkv * q_heads_per_kv, (bh, bkv, q_heads_per_kv)
    block_q = min(block_q, sq)
    block_k = min(block_k, skv)
    assert sq % block_q == 0 and skv % block_k == 0, (sq, block_q, skv, block_k)
    grid = (bh, sq // block_q, skv // block_k)
    scale = 1.0 / np.sqrt(hd)

    kernel = functools.partial(_kernel, scale=scale, causal=causal,
                               block_q=block_q, block_k=block_k)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b, iq, ik: (b, iq, 0)),
            pl.BlockSpec((1, block_k, hd),
                         lambda b, iq, ik, g=q_heads_per_kv: (b // g, ik, 0)),
            pl.BlockSpec((1, block_k, hd),
                         lambda b, iq, ik, g=q_heads_per_kv: (b // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda b, iq, ik: (b, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),     # running max m
            pltpu.VMEM((block_q, 1), jnp.float32),     # normalizer l
            pltpu.VMEM((block_q, hd), jnp.float32),    # weighted accumulator
        ],
        interpret=interpret,
    )(q, k, v)


def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 512,
                    block_k: int = 512, interpret: bool = True):
    """Model-layout wrapper: q (B, Sq, H, hd), k/v (B, Skv, KV, hd) ->
    (B, Sq, H, hd).  Flattens batch x heads, maps GQA via index arithmetic.
    """
    b, sq, h, hd = q.shape
    _, skv, kvh, _ = k.shape
    g = h // kvh
    # (B, S, H, hd) -> (B*H, S, hd) with h-major so h // g maps to kv head
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, sq, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(b * kvh, skv, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(b * kvh, skv, hd)
    of = flash_attention_pallas(qf, kf, vf, causal=causal, block_q=block_q,
                                block_k=block_k, q_heads_per_kv=g,
                                interpret=interpret)
    return of.reshape(b, h, sq, hd).transpose(0, 2, 1, 3)
