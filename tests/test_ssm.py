"""SSD (Mamba2) mixer: chunked scan vs naive recurrence oracle, chunk-size
invariance, decode-step equivalence, state passing."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.models.ssm import ssd_chunked


def _naive_ssd(x, a, dt, bm, cm):
    """Reference recurrence: h_t = exp(a_t) h_{t-1} + dt_t B_t (x_t)."""
    b, s, h, p = x.shape
    g, n = bm.shape[2], bm.shape[3]
    hg = h // g
    hstate = np.zeros((b, h, n, p), np.float64)
    ys = np.zeros((b, s, h, p), np.float64)
    x = np.asarray(x, np.float64)
    a = np.asarray(a, np.float64)
    dt = np.asarray(dt, np.float64)
    bm = np.asarray(bm, np.float64)
    cm = np.asarray(cm, np.float64)
    for t in range(s):
        for hh in range(h):
            gg = hh // hg
            decay = np.exp(a[:, t, hh])[:, None, None]
            outer = (bm[:, t, gg, :, None] *
                     (dt[:, t, hh, None] * x[:, t, hh, :])[:, None, :])
            hstate[:, hh] = decay * hstate[:, hh] + outer
            ys[:, t, hh] = np.einsum("bn,bnp->bp", cm[:, t, gg], hstate[:, hh])
    return ys, hstate


def _rand(seed, b=2, s=16, h=4, p=8, g=2, n=6):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(b, s, h, p)).astype(np.float32))
    dt = jnp.asarray(rng.uniform(0.01, 0.5, size=(b, s, h)).astype(np.float32))
    a = jnp.asarray(-rng.uniform(0.1, 2.0, size=(b, s, h)).astype(np.float32)) * dt
    bm = jnp.asarray(rng.normal(size=(b, s, g, n)).astype(np.float32))
    cm = jnp.asarray(rng.normal(size=(b, s, g, n)).astype(np.float32))
    return x, a, dt, bm, cm


class TestSSD:
    @given(st.integers(0, 1000))
    @settings(max_examples=10, deadline=None)
    def test_matches_naive_recurrence(self, seed):
        x, a, dt, bm, cm = _rand(seed)
        y, hf = ssd_chunked(x, a, dt, bm, cm, chunk=4)
        y_ref, h_ref = _naive_ssd(x, a, dt, bm, cm)
        np.testing.assert_allclose(np.asarray(y), y_ref, rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(hf), h_ref, rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("chunk", [2, 4, 8, 16])
    def test_chunk_size_invariance(self, chunk):
        x, a, dt, bm, cm = _rand(7)
        y_full, h_full = ssd_chunked(x, a, dt, bm, cm, chunk=16)
        y_c, h_c = ssd_chunked(x, a, dt, bm, cm, chunk=chunk)
        np.testing.assert_allclose(np.asarray(y_c), np.asarray(y_full),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(h_c), np.asarray(h_full),
                                   rtol=1e-4, atol=1e-4)

    def test_initial_state_continuation(self):
        """SSD over [first half] then [second half with carried state] ==
        SSD over the full sequence (prefill-chaining invariant)."""
        x, a, dt, bm, cm = _rand(9, s=16)
        y_full, h_full = ssd_chunked(x, a, dt, bm, cm, chunk=4)
        y1, h1 = ssd_chunked(x[:, :8], a[:, :8], dt[:, :8], bm[:, :8],
                             cm[:, :8], chunk=4)
        y2, h2 = ssd_chunked(x[:, 8:], a[:, 8:], dt[:, 8:], bm[:, 8:],
                             cm[:, 8:], chunk=4, h0=h1)
        np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)),
                                   np.asarray(y_full), rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(h2), np.asarray(h_full),
                                   rtol=1e-4, atol=1e-4)

    def test_decay_bounds_state(self):
        """Strongly negative a -> state forgets; y depends only on recent x."""
        x, a, dt, bm, cm = _rand(11, s=12)
        a_strong = jnp.full_like(a, -50.0)
        y, _ = ssd_chunked(x, a_strong, dt, bm, cm, chunk=4)
        # contribution of x_0 to y_6 is exp(sum a_1..6) ~ e^-300 ~ 0
        x2 = x.at[:, 0].set(x[:, 0] * 100)
        y2, _ = ssd_chunked(x2, a_strong, dt, bm, cm, chunk=4)
        np.testing.assert_allclose(np.asarray(y[:, 6:]), np.asarray(y2[:, 6:]),
                                   rtol=1e-5, atol=1e-5)


def test_chunked_gradient_finite_under_strong_decay():
    """Within a chunk the decay cum_i - cum_j grows past exp's f32 range
    above the diagonal; those entries are masked, and must stay masked in
    the backward pass too (no 0 * inf = nan gradients)."""
    x, _, dt, bm, cm = _rand(3, s=64)
    a = jnp.full(dt.shape, -4.0, jnp.float32)     # cum spans ~250 per chunk

    def loss(x, a):
        y, h = ssd_chunked(x, a, dt, bm, cm, chunk=64)
        return jnp.sum(y) + jnp.sum(h)

    gx, ga = jax.grad(loss, argnums=(0, 1))(x, a)
    assert np.isfinite(np.asarray(gx)).all()
    assert np.isfinite(np.asarray(ga)).all()
