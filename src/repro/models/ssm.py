"""Mamba2 / SSD (state-space duality) mixer: chunked scan + recurrent decode.

The SSD recurrence per head (state N, head dim P):

    h_t = exp(a_t) * h_{t-1} + dt_t * (B_t outer x_t)        a_t = -exp(A_log)*dt_t
    y_t = C_t . h_t + D * x_t

Train/prefill uses the chunked form: a ``lax.scan`` over length-L chunks
carries the (B, H, N, P) inter-chunk state; within a chunk the quadratic
"attention-like" form computes intra-chunk contributions with the decay mask
exp(cum[i] - cum[j]).  Memory is O(B * L * H * (L + N + P)) per step
independent of sequence length -- this is what makes ``long_500k`` run.

Decode is the O(1) recurrent step (plus a (k-1)-deep causal-conv state).

TP: heads shard over the `model` axis (every per-head tensor carries the
"ssm_heads" logical axis); B/C group projections are small and replicated.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .config import Dims
from .layers import P, dense_init, zeros_init, ones_init

DEFAULT_CHUNK = 128


def init_mamba(key, dims: Dims) -> dict:
    cfg = dims.cfg
    d, g, n, kconv = cfg.d_model, cfg.ssm_groups, cfg.ssm_state, cfg.ssm_conv
    h, p = dims.ssm_heads, cfg.ssm_head_dim
    ks = jax.random.split(key, 8)
    # A init in [1, 16] (mamba2 default): A_log = log(uniform)
    a_init = jnp.log(jnp.linspace(1.0, 16.0, h, dtype=jnp.float32))
    # dt bias ~ softplus^-1(uniform in [1e-3, 1e-1])
    dt0 = jnp.exp(jnp.linspace(np.log(1e-3), np.log(1e-1), h, dtype=jnp.float32))
    dt_bias = dt0 + jnp.log(-jnp.expm1(-dt0))
    return {
        "wz": dense_init(ks[0], (d, h, p), ("embed", "ssm_heads", "hd")),
        "wx": dense_init(ks[1], (d, h, p), ("embed", "ssm_heads", "hd")),
        "wB": dense_init(ks[2], (d, g, n), ("embed", "ssm_group", "state")),
        "wC": dense_init(ks[3], (d, g, n), ("embed", "ssm_group", "state")),
        "wdt": dense_init(ks[4], (d, h), ("embed", "ssm_heads")),
        "conv_x": dense_init(ks[5], (h, p, kconv), ("ssm_heads", "hd", "conv"),
                             scale=1.0 / np.sqrt(kconv)),
        "conv_bc": dense_init(ks[6], (2 * g * n, kconv), ("conv_ch", "conv"),
                              scale=1.0 / np.sqrt(kconv)),
        "A_log": P(a_init, ("ssm_heads",)),
        "dt_bias": P(dt_bias, ("ssm_heads",)),
        "D": ones_init((h,), ("ssm_heads",)),
        "norm": ones_init((h, p), ("ssm_heads", "hd")),
        "wo": dense_init(ks[7], (h, p, d), ("ssm_heads", "hd", "embed_out"),
                         scale=1.0 / np.sqrt(h * p)),
    }


def _causal_conv(seq, weight, *, state=None):
    """Depthwise causal conv along time.  seq (B, S, C), weight (C, K).

    state: optional (B, K-1, C) left context (decode/prefill chaining);
    zeros when None.  Returns (out (B, S, C), new_state (B, K-1, C)).
    """
    b, s, c = seq.shape
    k = weight.shape[-1]
    if state is None:
        state = jnp.zeros((b, k - 1, c), seq.dtype)
    full = jnp.concatenate([state, seq], axis=1)              # (B, S+K-1, C)
    out = jnp.zeros((b, s, c), jnp.float32)
    for i in range(k):                                        # K is 4: unrolled
        out = out + full[:, i:i + s, :].astype(jnp.float32) * weight[:, i].astype(jnp.float32)
    new_state = full[:, -(k - 1):, :] if k > 1 else jnp.zeros((b, 0, c), seq.dtype)
    return out.astype(seq.dtype), new_state


def _project(params, u, dims: Dims):
    """u (B, S, d) -> z, x, Bm, Cm, dt (pre-conv, pre-activation)."""
    z = jnp.einsum("bsd,dhp->bshp", u, params["wz"])
    x = jnp.einsum("bsd,dhp->bshp", u, params["wx"])
    bm = jnp.einsum("bsd,dgn->bsgn", u, params["wB"])
    cm = jnp.einsum("bsd,dgn->bsgn", u, params["wC"])
    dt = jnp.einsum("bsd,dh->bsh", u, params["wdt"])
    return z, x, bm, cm, dt


def _conv_split(params, x, bm, cm, conv_state=None):
    """Apply the causal convs; returns activated x, B, C and new conv states."""
    b, s, h, p = x.shape
    g, n = bm.shape[2], bm.shape[3]
    xs = x.reshape(b, s, h * p)
    cw = params["conv_x"].reshape(h * p, -1)
    bc = jnp.concatenate([bm.reshape(b, s, g * n), cm.reshape(b, s, g * n)], axis=-1)
    st_x = None if conv_state is None else conv_state["x"]
    st_bc = None if conv_state is None else conv_state["bc"]
    xs, new_x = _causal_conv(xs, cw, state=st_x)
    bc, new_bc = _causal_conv(bc, params["conv_bc"], state=st_bc)
    xs = jax.nn.silu(xs).reshape(b, s, h, p)
    bc = jax.nn.silu(bc)
    bm = bc[..., :g * n].reshape(b, s, g, n)
    cm = bc[..., g * n:].reshape(b, s, g, n)
    return xs, bm, cm, {"x": new_x, "bc": new_bc}


def ssd_chunked(x, a, dt, bm, cm, *, chunk: int = DEFAULT_CHUNK, h0=None):
    """Chunked SSD.  x (B,S,H,P), a/dt (B,S,H), bm/cm (B,S,G,N).

    Returns (y (B,S,H,P) fp32, h_final (B,H,N,P) fp32).
    """
    b, s, h, p = x.shape
    g, n = bm.shape[2], bm.shape[3]
    hg = h // g
    l = min(chunk, s)
    assert s % l == 0, (s, l)
    nc = s // l

    xdt = (x.astype(jnp.float32) * dt[..., None])              # (B,S,H,P)
    # chunked views, scanned over axis 0
    xc = jnp.moveaxis(xdt.reshape(b, nc, l, h, p), 1, 0)
    ac = jnp.moveaxis(a.reshape(b, nc, l, h), 1, 0)
    bc_ = jnp.moveaxis(bm.astype(jnp.float32).reshape(b, nc, l, g, n), 1, 0)
    cc_ = jnp.moveaxis(cm.astype(jnp.float32).reshape(b, nc, l, g, n), 1, 0)

    if h0 is None:
        h0 = jnp.zeros((b, h, n, p), jnp.float32)

    def step2(hstate, inp):
        xk, ak, bk, ck = inp                # (B,L,H,P) (B,L,H) (B,L,G,N) x2
        cum = jnp.cumsum(ak, axis=1)        # inclusive (B,L,H)
        # ---- intra-chunk (quadratic in L) ----
        cb = jnp.einsum("bign,bjgn->bijg", ck, bk)             # (B,L,L,G)
        ii = jax.lax.broadcasted_iota(jnp.int32, (l, l), 0)
        jj = jax.lax.broadcasted_iota(jnp.int32, (l, l), 1)
        # mask the exponent, not the exponential: above the diagonal
        # cum_i - cum_j > 0 and exp overflows, and the masked inf turns
        # the gradient into 0 * inf = nan
        w = jnp.exp(jnp.where((ii >= jj)[None, :, :, None],
                              cum[:, :, None, :] - cum[:, None, :, :],
                              -jnp.inf))                        # (B,i,j,H)
        if g > 1:
            scores = jnp.repeat(cb, hg, axis=3)                # (B,i,j,H)
        else:
            scores = jnp.broadcast_to(cb, (b, l, l, h))
        scores = scores * w
        y = jnp.einsum("bijh,bjhp->bihp", scores, xk)
        # inter-chunk: y_i += exp(cum_i) * C_i . h_in
        ckh = _group_to_heads(ck, h)                           # (B,L,H,N)
        y = y + jnp.exp(cum)[..., None] * jnp.einsum("bihn,bhnp->bihp", ckh, hstate)
        # state update
        last = cum[:, -1:, :]                                  # (B,1,H)
        wstate = jnp.exp(last - cum)                           # (B,L,H)
        bkh = _group_to_heads(bk, h)                           # (B,L,H,N)
        s_new = jnp.einsum("bjh,bjhn,bjhp->bhnp", wstate, bkh, xk)
        hstate = jnp.exp(last[:, 0, :])[:, :, None, None] * hstate + s_new
        return hstate, y

    h_final, ys = jax.lax.scan(step2, h0, (xc, ac, bc_, cc_))
    y = jnp.moveaxis(ys, 0, 1).reshape(b, s, h, p)
    return y, h_final


def _group_to_heads(t, h):
    """(B, L, G, N) -> (B, L, H, N) by repeating each group H/G times."""
    b, l, g, n = t.shape
    if g == h:
        return t
    return jnp.broadcast_to(t[:, :, :, None, :], (b, l, g, h // g, n)).reshape(b, l, h, n)


def mamba_block(params, u, dims: Dims, *, chunk: int = DEFAULT_CHUNK,
                conv_state=None, ssm_state=None):
    """Full-sequence mixer.  u (B, S, d) -> (out (B,S,d), new states)."""
    cfg = dims.cfg
    z, x, bm, cm, dt = _project(params, u, dims)
    x, bm, cm, new_conv = _conv_split(params, x, bm, cm, conv_state)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])   # (B,S,H)
    a = -jnp.exp(params["A_log"]) * dt                                 # (B,S,H)
    y, h_final = ssd_chunked(x, a, dt, bm, cm, chunk=chunk, h0=ssm_state)
    y = y + params["D"][:, None] * x.astype(jnp.float32)
    y = _gated_norm(params["norm"], y, z, cfg.rms_eps)
    out = jnp.einsum("bshp,hpd->bsd", y.astype(u.dtype), params["wo"])
    return out, {"conv": new_conv, "ssm": h_final}


def _gated_norm(scale, y, z, eps):
    """RMSNorm(y * silu(z)) * scale -- mamba2's gated output norm (per head)."""
    y = y * jax.nn.silu(z.astype(jnp.float32))
    var = jnp.mean(y * y, axis=-1, keepdims=True)
    return y * jax.lax.rsqrt(var + eps) * scale


def mamba_decode_step(params, u, dims: Dims, conv_state, ssm_state):
    """One-token recurrent step.  u (B, 1, d).

    conv_state: {"x": (B,K-1,H*P), "bc": (B,K-1,2GN)}; ssm_state (B,H,N,P).
    """
    cfg = dims.cfg
    z, x, bm, cm, dt = _project(params, u, dims)
    x, bm, cm, new_conv = _conv_split(params, x, bm, cm, conv_state)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])   # (B,1,H)
    a = -jnp.exp(params["A_log"]) * dt
    h = dims.ssm_heads
    bkh = _group_to_heads(bm.astype(jnp.float32), h)[:, 0]             # (B,H,N)
    ckh = _group_to_heads(cm.astype(jnp.float32), h)[:, 0]
    xdt = x.astype(jnp.float32)[:, 0] * dt[:, 0][..., None]            # (B,H,P)
    ssm_state = (jnp.exp(a[:, 0])[..., None, None] * ssm_state
                 + bkh[..., None] * xdt[:, :, None, :])                # (B,H,N,P)
    y = jnp.einsum("bhn,bhnp->bhp", ckh, ssm_state)[:, None]           # (B,1,H,P)
    y = y + params["D"][:, None] * x.astype(jnp.float32)
    y = _gated_norm(params["norm"], y, z, cfg.rms_eps)
    out = jnp.einsum("bshp,hpd->bsd", y.astype(u.dtype), params["wo"])
    return out, {"conv": new_conv, "ssm": ssm_state}


def init_mamba_state(dims: Dims, batch: int, dtype=jnp.bfloat16):
    """Zero decode state for one mamba layer."""
    cfg = dims.cfg
    h, p = dims.ssm_heads, cfg.ssm_head_dim
    g, n, k = cfg.ssm_groups, cfg.ssm_state, cfg.ssm_conv
    return {
        "conv": {"x": jnp.zeros((batch, k - 1, h * p), dtype),
                 "bc": jnp.zeros((batch, k - 1, 2 * g * n), dtype)},
        "ssm": jnp.zeros((batch, h, n, p), jnp.float32),
    }
