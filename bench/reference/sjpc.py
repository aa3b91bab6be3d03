"""Plain reference of the SJPC estimation service (arXiv:1806.03313, §3-§6).

Written from the method and the configuration's stated constants alone; it
imports nothing of the program.  NumPy uint64/int64 arithmetic does the
hashing and the sketch exactly; JAX's own PRNG (``jax.random``, the
library, not the program) draws the projection samples from the stated key
schedule.

What the configuration states, and this file follows:

- the hash family: four Carter-Wegman degree-3 polynomials over
  GF(2^31 - 1) per (level, depth row), bucket and sign, applied to a pair
  of Rabin fingerprints; coefficients and the two fingerprint bases drawn
  in that order by ``numpy.random.default_rng(sketch.seed)``;
- the fingerprint of a level-k sub-value: Horner over (column bitmask + 1,
  v_c1 + 1, ..., v_ck + 1) in the field, one per base;
- the projection sample of a record at level k: a uniform subset of its
  C(d, k) column combinations of size floor(r C(d, k)), plus one with
  probability frac(r C(d, k)), taken as the top ranks of uniforms drawn
  from the key ``fold_in(fold_in(fold_in(PRNGKey(seed ^ 0x5E41CE), uid),
  round), level)`` (split into the rank key and the rounding key);
- a stream's window counters: the sum over its record rounds of
  sign x sample weight in each (level, row, bucket); ``n`` its records;
- a query: median over depth rows of F2 (self) or of the inner product
  (join), the Eq. 4 / Eq. 7 inversion with estimates clamped at 0, g_k the
  suffix sums (+ n for a self-join), and the Theorem 2 plug-in standard
  error (joins: at n = max(n_a, n_b) and g = max(g, 1)).
"""
from __future__ import annotations

import itertools
import math

import numpy as np

P = 2 ** 31 - 1
INGEST_SALT = 0x5E41CE
_KEY_CHUNK = 128            # rounds whose samples are drawn in one call


class SJPC:
    def __init__(self, sketch: dict, batch_rows: int):
        self.d, self.s = int(sketch["d"]), int(sketch["s"])
        self.r = float(sketch["ratio"])
        self.w, self.t = int(sketch["width"]), int(sketch["depth"])
        self.seed = int(sketch["seed"])
        self.B = int(batch_rows)
        self.L = self.d - self.s + 1
        self.levels = []
        for k in range(self.s, self.d + 1):
            combos = list(itertools.combinations(range(self.d), k))
            masks = np.zeros((len(combos), self.d), bool)
            for i, cols in enumerate(combos):
                masks[i, list(cols)] = True
            ids = np.array([sum(1 << c for c in cols) for cols in combos],
                           np.uint64)
            self.levels.append((k, masks, ids))
        rng = np.random.default_rng(self.seed)
        shape = (self.L, self.t, 2, 4)
        self.bucket_coef = rng.integers(0, P, size=shape,
                                        dtype=np.uint32).astype(np.uint64)
        self.sign_coef = rng.integers(0, P, size=shape,
                                      dtype=np.uint32).astype(np.uint64)
        bases = rng.integers(0, P, size=(2,), dtype=np.uint32)
        self.bases = (bases % np.uint32(P - 2) + np.uint32(2)).astype(np.uint64)
        self._draw = None

    # -- the projection sample -------------------------------------------
    def _sample_sizes(self, m: int) -> tuple[int, float]:
        target = m * self.r
        lo = int(math.floor(target + 1e-9))
        frac = target - lo
        return min(lo, m), (0.0 if frac < 1e-9 else frac)

    def _uniforms(self, uids: np.ndarray, rounds: np.ndarray):
        """Per level: rank uniforms (K, B, M) and rounding uniforms (K, B)."""
        import jax
        import jax.numpy as jnp
        if self._draw is None:
            sizes = [masks.shape[0] for _, masks, _ in self.levels]
            base_seed, B = self.seed ^ INGEST_SALT, self.B

            def one(uid, rnd):
                key = jax.random.fold_in(
                    jax.random.fold_in(jax.random.PRNGKey(base_seed), uid), rnd)
                out = []
                for idx, m in enumerate(sizes):
                    k_sel, k_round = jax.random.split(
                        jax.random.fold_in(key, idx))
                    out.append((jax.random.uniform(k_sel, (B, m)),
                                jax.random.uniform(k_round, (B, 1))[:, 0]))
                return out

            self._draw = jax.jit(jax.vmap(one))
        K = len(uids)
        pad = -K % _KEY_CHUNK
        u = np.concatenate([uids, np.zeros(pad, uids.dtype)]).astype(np.int32)
        r = np.concatenate([rounds, np.zeros(pad, rounds.dtype)]).astype(np.int32)
        parts = []
        for lo in range(0, len(u), _KEY_CHUNK):
            got = self._draw(jnp.asarray(u[lo:lo + _KEY_CHUNK]),
                             jnp.asarray(r[lo:lo + _KEY_CHUNK]))
            parts.append([(np.asarray(a), np.asarray(b)) for a, b in got])
        return [(np.concatenate([p[i][0] for p in parts])[:K],
                 np.concatenate([p[i][1] for p in parts])[:K])
                for i in range(self.L)]

    def sample_weights(self, uids, rounds) -> list[np.ndarray]:
        """Per level, (K, B, C(d, k)) {0, 1} weights of the rounds
        (uids[i], rounds[i])."""
        out = []
        for (k, masks, _), (scores, u_round) in zip(
                self.levels, self._uniforms(np.asarray(uids),
                                            np.asarray(rounds))):
            m = masks.shape[0]
            lo, frac = self._sample_sizes(m)
            if lo >= m and frac == 0.0:
                out.append(np.ones(scores.shape, np.int64))
                continue
            order = np.argsort(-scores, axis=-1, kind="stable")
            ranks = np.empty_like(order)
            np.put_along_axis(ranks, order,
                              np.broadcast_to(np.arange(m), order.shape), -1)
            size = lo + (u_round < frac).astype(np.int64) if frac > 0 else lo
            out.append((ranks < np.asarray(size)[..., None]).astype(np.int64))
        return out

    # -- fingerprints and hashes ------------------------------------------
    def _fingerprints(self, values, masks, ids):
        """values (..., d) -> two (..., M) fingerprints."""
        v = (values.astype(np.uint64) % P + 1) % P
        out = []
        for base in self.bases:
            fp = np.broadcast_to((ids % P + 1) % P,
                                 values.shape[:-1] + ids.shape).copy()
            for col in range(self.d):
                nxt = (fp * base + v[..., col:col + 1]) % P
                fp = np.where(masks[:, col], nxt, fp)
            out.append(fp)
        return out

    @staticmethod
    def _poly(x, c):
        h = np.broadcast_to(c[3], x.shape).copy()
        for i in (2, 1, 0):
            h = (h * x + c[i]) % P
        return h

    def _pair_hash(self, fp1, fp2, coef):
        return (self._poly(fp1, coef[0]) + self._poly(fp2, coef[1])) % P

    # -- window counters ---------------------------------------------------
    def replay(self, slots, uids, rounds, values, masks, n_slots: int, *,
               drop_last_row: bool = False):
        """Window counters of ``n_slots`` streams from their record rounds.

        Round i (records ``values[i]`` (B, d), row mask ``masks[i]`` (B,))
        belongs to stream slot ``slots[i]``, whose id is ``uids[i]``, and is
        that stream's ``rounds[i]``-th round.  Returns counters
        (n_slots, L, t, w) int64 and n (n_slots,).  ``drop_last_row`` is the
        control: it leaves each round's last record uncounted.
        """
        slots = np.asarray(slots)
        masks = np.asarray(masks).astype(np.int64)
        if drop_last_row:
            masks = masks.copy()
            masks[:, -1] = 0
        counters = np.zeros(n_slots * self.L * self.t * self.w, np.int64)
        for lo in range(0, len(slots), _KEY_CHUNK):
            sl = slice(lo, lo + _KEY_CHUNK)
            weights = self.sample_weights(np.asarray(uids)[sl],
                                          np.asarray(rounds)[sl])
            vals = np.asarray(values[sl])
            for li, ((_, cmask, ids), wts) in enumerate(zip(self.levels,
                                                            weights)):
                fp1, fp2 = self._fingerprints(vals, cmask, ids)  # (K, B, M)
                wts = wts * masks[sl][:, :, None]
                for row in range(self.t):
                    hb = self._pair_hash(fp1, fp2, self.bucket_coef[li, row])
                    hs = self._pair_hash(fp1, fp2, self.sign_coef[li, row])
                    bucket = (hb & np.uint64(self.w - 1)).astype(np.int64)
                    sign = 1 - 2 * (hs & np.uint64(1)).astype(np.int64)
                    plane = (slots[sl] * self.L + li) * self.t + row
                    idx = plane[:, None, None] * self.w + bucket
                    counters += np.bincount(idx.ravel(),
                                            weights=(sign * wts).ravel(),
                                            minlength=counters.size
                                            ).astype(np.int64)
        n = np.bincount(slots, weights=masks.sum(axis=1),
                        minlength=n_slots).astype(np.int64)
        return counters.reshape(n_slots, self.L, self.t, self.w), n

    # -- queries -----------------------------------------------------------
    def _lead(self) -> np.ndarray:
        d, r = self.d, self.r
        return np.array([math.comb(d, k) ** 2 / r * math.comb(2 * (d - k), d - k)
                         for k in range(self.s, d + 1)], np.float64)

    def _stderr(self, n, g):
        w, r = self.w, self.r
        lead = self._lead()[None, :]
        n = np.asarray(n, np.float64).reshape(-1, 1)
        g = np.asarray(g, np.float64)
        with np.errstate(divide="ignore", invalid="ignore"):
            on = np.sqrt(lead * ((1 + 2 / w) / g
                                 + (2 / w) * (1 + n / (r * g)) ** 2)) * g
        return np.where(g > 0, on, 0.0)

    def _invert(self, y, n, *, join: bool, dtype):
        d, s, r = self.d, self.s, self.r
        X = {}
        for k in range(d, s - 1, -1):
            if join:
                acc = y[:, k - s] / dtype(r * r)
            else:
                acc = y[:, k - s] - dtype(math.comb(d, k) * r) * n
            for j in range(k + 1, d + 1):
                acc = acc - dtype(math.comb(j, k)) * X[j]
            X[k] = np.maximum(acc, dtype(0))
        x = np.stack([X[k] for k in range(s, d + 1)], axis=1)
        if not join:
            x = x / dtype(r * r)
        g = np.cumsum(x[:, ::-1], axis=1, dtype=dtype)[:, ::-1]
        return g + n[:, None] if not join else g

    def self_join(self, counters, n, *, dtype=np.float64):
        """(g, stderr), each (N, L): every threshold s..d of N streams.
        ``dtype`` other than float64 is the control's lower precision."""
        c = np.asarray(counters)
        if dtype is np.float64:
            f2 = (c.astype(np.int64) ** 2).sum(-1).astype(np.float64)
        else:
            cd = c.astype(dtype)
            f2 = (cd * cd).sum(-1, dtype=dtype)
        y = np.median(f2, axis=-1).astype(dtype)
        g = self._invert(y, np.asarray(n).astype(dtype), join=False,
                         dtype=dtype)
        return g.astype(np.float64), self._stderr(n, g.astype(np.float64))

    def join(self, ca, cb, na, nb, *, dtype=np.float64):
        """(g, stderr), each (N, L): join sizes of N stream pairs."""
        a, b = np.asarray(ca), np.asarray(cb)
        if dtype is np.float64:
            ip = (a.astype(np.int64) * b.astype(np.int64)).sum(-1)
            ip = ip.astype(np.float64)
        else:
            ip = (a.astype(dtype) * b.astype(dtype)).sum(-1, dtype=dtype)
        y = np.median(ip, axis=-1).astype(dtype)
        g = self._invert(y, None, join=True, dtype=dtype).astype(np.float64)
        n = np.maximum(np.asarray(na, np.float64), np.asarray(nb, np.float64))
        return g, self._stderr(n, np.maximum(g, 1.0))
