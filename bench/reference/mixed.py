"""Plain reference of the sample tenants of the estimation service: the
paper's equal-space competitors (arXiv:1806.03313 §2.1 and §2.3), random
sampling by Vitter's Algorithm R and LSH-SS (Lee, Ng and Shim,
arXiv:1104.3212), windowed and answered with bootstrap error bars.

Written from the methods and the configuration's stated sizes and
constants alone; it imports nothing of the program.  NumPy does the
sampling bookkeeping, the hashing and the answers (float64); JAX's own
PRNG (``jax.random``, the library) draws the random numbers from the
stated key schedule, and JAX's float32 ``log`` and division compute the
merge priorities, so that they round as the chip does.

What the configuration states, and this file follows:

- keys: a tenant's i-th round that carried its records is keyed
  ``fold_in(fold_in(PRNGKey(seed ^ 0x5E41CE), uid), i)``;
- Algorithm R over one round of B rows: the masked rows are the
  candidates, in order, candidate j at arrival index g = n + j.  The key
  splits into (rank key, slot key); rank ~ randint(0, max(g + 1, 1)) and
  slot ~ randint(0, R) per row.  A candidate is kept iff g < R or
  rank < R, in slot g while g < R, else in its drawn slot; per slot the
  latest kept candidate wins.  Kept slots take the state's ``sid`` as tag;
- LSH-SS over one round: the key splits five ways (partner rank, partner
  slot, same-stratum key, cross-stratum key, record key).  Bucket
  counters count each record's bucket (an FNV-style hash of the chosen
  column, then an avalanche).  Each record is paired with one uniform
  earlier record: rank u ~ randint(0, max(g, 1)); u past the round's start
  names an earlier record of the round, else a record slot (slot u while
  the record sample is filling, else the drawn slot).  The pair is a
  candidate when the record is not the first and its partner exists; it
  joins the same stratum when both buckets agree, else the cross one.  Each
  stratum keeps its candidates' match counts by Algorithm R (its key,
  arrival index = candidates seen), and the record sample keeps records
  and their buckets by Algorithm R (record key);
- windows: each epoch samples into a slot of its own (sid = epoch); the
  served state is the merge-fold of the ring's slots in ring order,
  refreshed by every commit that changed the open slot and by every
  expiry; an advance that expires nothing leaves it as it was;
- merge: pool two samples and keep the ``capacity`` largest priorities
  log(u) / w, u = (h + 1) / 2^32 from a multiplicative hash h of (salt,
  tag, slot index, item), w = records represented per kept item; ties keep
  the lower pool index; empty slots never win over kept ones;
- answers: the reservoir's ordered-pair similarity histogram of its kept
  records, scaled by n(n-1) / (m(m-1)), with the bootstrap error bar of
  32 replicates of min(256, R) draws with replacement, keyed
  ``fold_in(fold_in(PRNGKey(seed ^ 0xB0075), n), step)``, rescaled by
  sqrt(b/m) and Serfling's sqrt(1 - (m-1)/n); LSH-SS's stratified sum
  f_same * same_pairs + f_cross * cross_pairs + n, with the stratified
  bootstrap: per tenant one NumPy generator seeded
  ``SeedSequence([seed ^ 0xB0075, n, step])`` draws Dirichlet(hits + 1/2)
  fractions per stratum (same, then cross; none for an empty stratum).
"""
from __future__ import annotations

import numpy as np

INGEST_SALT = 0x5E41CE
BOOT_SALT = 0xB0075
MERGE_SALT = {"reservoir": 0x7E5E4B01, "lsh_ss": 0x15A55B01}
LSH_COLS_SALT = 0x15AC01        # seeds the draw of LSH-SS's hashed columns
_CHUNK = 64                     # rounds drawn per device call
U32 = np.uint32


def pair_hist(items: np.ndarray, d: int) -> np.ndarray:
    """Ordered pairs (a != b) of the rows of ``items`` (m, d), counted by
    the number of columns they agree on: (d + 1,) int64."""
    m = items.shape[0]
    eq = np.zeros((m, m), np.int8)
    for c in range(d):
        eq += items[:, c, None] == items[None, :, c]
    np.fill_diagonal(eq, -1)
    return np.bincount(eq.ravel() + 1, minlength=d + 2)[1:]


def pair_scale(n, m, dtype=np.float64):
    n, m = dtype(n), dtype(m)
    return n * (n - 1) / (m * (m - 1)) if m >= 2 else dtype(0)


def serfling(n, m, dtype=np.float64):
    n, m = dtype(n), dtype(m)
    f = 1 - (m - 1) / max(n, dtype(1)) if n > 0 else dtype(1)
    return np.sqrt(np.clip(f, 0, 1)).astype(dtype)


def suffix_std(x_reps) -> np.ndarray:
    """(B, L) replicate level estimates -> std (ddof 1) of their suffix
    sums, (L,)."""
    return np.cumsum(x_reps[:, ::-1], axis=1)[:, ::-1].std(axis=0, ddof=1)


def _kept(n0: int, mask, rank, slot_draw, capacity: int):
    """Algorithm R over one round: (slots written, batch row of each)."""
    g = n0 + np.cumsum(mask) - 1
    keep = (mask != 0) & ((g < capacity) | (rank < capacity))
    slot = np.where(g < capacity, np.clip(g, 0, capacity - 1), slot_draw)
    rows = np.flatnonzero(keep)[::-1]       # latest first: it wins its slot
    slots, first = np.unique(slot[rows], return_index=True)
    return slots, rows[first]


class Samples:
    """Reservoir and LSH-SS states, rounds, merges and answers."""

    def __init__(self, conf: dict):
        sk, sizes = conf["sketch"], conf["sizes"]
        self.d, self.s = int(sk["d"]), int(sk["s"])
        self.seed = int(sk["seed"])
        self.B = int(conf["service"]["batch_rows"])
        self.R = int(sizes["reservoir"]["capacity"])
        lsh = sizes["lsh_ss"]
        self.buckets = int(lsh["num_buckets"])
        self.rec_cap = int(lsh["record_capacity"])
        self.pair_cap = int(lsh["pair_capacity"])
        self.cols = np.sort(np.random.default_rng(
            self.seed ^ LSH_COLS_SALT).choice(
                self.d, size=int(lsh["num_hash_cols"]), replace=False))
        self.replicates = int(conf["bootstrap"]["replicates"])
        self.item_cap = int(conf["bootstrap"]["item_cap"])
        self._jit = None

    # -- draws from the stated keys (JAX's PRNG) ---------------------------
    def _fns(self):
        if self._jit is None:
            import jax
            import jax.numpy as jnp

            def key_of(uid, rnd):
                base = jax.random.PRNGKey(self.seed ^ INGEST_SALT)
                return jax.random.fold_in(jax.random.fold_in(base, uid), rnd)

            def algo_r(key, n0, mask, capacity):
                g = n0 + jnp.cumsum(mask) - 1
                k_rank, k_slot = jax.random.split(key)
                return (jax.random.randint(k_rank, mask.shape, 0,
                                           jnp.maximum(g + 1, 1)),
                        jax.random.randint(k_slot, mask.shape, 0, capacity))

            def reservoir(uid, rnd, n0, mask):
                return algo_r(key_of(uid, rnd), n0, mask, self.R)

            def lsh(uid, rnd, n0, mask):
                kp, kq, ks, kc, kr = jax.random.split(key_of(uid, rnd), 5)
                g = n0 + jnp.cumsum(mask) - 1
                return (jax.random.randint(kp, mask.shape, 0,
                                           jnp.maximum(g, 1)),
                        jax.random.randint(kq, mask.shape, 0, self.rec_cap),
                        *algo_r(kr, n0, mask, self.rec_cap),
                        jnp.stack([ks, kc]))

            def strata(keys, seen, cand):
                return jax.vmap(lambda k, n0, c: algo_r(
                    k, n0, c, self.pair_cap))(keys, seen, cand)

            def boot(n, step, m):
                base = jax.random.PRNGKey(U32(self.seed) ^ U32(BOOT_SALT))
                key = jax.random.fold_in(jax.random.fold_in(base, n), step)
                return jax.random.randint(
                    key, (self.replicates, min(self.item_cap, self.R)), 0,
                    jnp.maximum(m, 1))

            self._jit = {"reservoir": jax.jit(jax.vmap(reservoir)),
                         "lsh_ss": jax.jit(jax.vmap(lsh)),
                         "strata": jax.jit(strata), "boot": jax.jit(boot)}
        return self._jit

    def _round_draws(self, kind, uids, rnds, n0, masks):
        """Per-round draws of the state-free part, ``_CHUNK`` rounds a
        call: a list of tuples of per-round arrays."""
        K = len(uids)
        pad = -K % _CHUNK
        args = [np.concatenate([np.asarray(a, np.int32),
                                np.zeros((pad,) + np.shape(a)[1:], np.int32)])
                for a in (uids, rnds, n0, masks)]
        out = []
        for lo in range(0, K + pad, _CHUNK):
            got = self._fns()[kind](*(a[lo:lo + _CHUNK] for a in args))
            out.extend(zip(*(np.asarray(x) for x in got)))
        return out[:K]

    # -- states ----------------------------------------------------------
    def init(self, kind: str, sid: int) -> dict:
        d = self.d
        if kind == "reservoir":
            return {"items": np.zeros((self.R, d), U32),
                    "tags": np.full(self.R, -1, np.int64),
                    "n": 0, "sid": sid, "step": 0}
        M = self.pair_cap
        return {"counts": np.zeros(self.buckets, np.int64),
                "rec_items": np.zeros((self.rec_cap, d), U32),
                "rec_bucket": np.zeros(self.rec_cap, np.int64),
                "rec_tags": np.full(self.rec_cap, -1, np.int64),
                "same_sim": np.zeros(M, np.int64),
                "same_tags": np.full(M, -1, np.int64), "same_seen": 0,
                "cross_sim": np.zeros(M, np.int64),
                "cross_tags": np.full(M, -1, np.int64), "cross_seen": 0,
                "n": 0, "sid": sid, "step": 0}

    def bucket(self, values: np.ndarray) -> np.ndarray:
        h = np.full(values.shape[0], 0x811C9DC5, U32) ^ U32(self.seed)
        for c in self.cols:
            h = (h * U32(0x01000193)) ^ (values[:, c].astype(U32)
                                         + U32(0x9E3779B1))
        h ^= h >> U32(15)
        h = h * U32(0x85EBCA77)
        h ^= h >> U32(13)
        return (h & U32(self.buckets - 1)).astype(np.int64)

    def ingest(self, kind: str, jobs: list) -> None:
        """Apply rounds to states in place.  ``jobs``: (state, uid,
        [(round index, values (B, d), mask (B,)), ...]) per tenant; each
        tenant's rounds in order."""
        flat, n0 = [], []                   # n0: records before each round
        for st, uid, rounds in jobs:
            n = st["n"]
            for r, values, mask in rounds:
                flat.append((st, uid, r, values, mask))
                n0.append(n)
                n += int(mask.sum())
        if not flat:
            return
        draws = self._round_draws(kind, [f[1] for f in flat],
                                  [f[2] for f in flat], n0,
                                  [f[4] for f in flat])
        for (st, _, _, values, mask), dr in zip(flat, draws):
            if kind == "reservoir":
                slots, rows = _kept(st["n"], mask, *dr, self.R)
                st["items"][slots] = values[rows]
                st["tags"][slots] = st["sid"]
            else:
                self._lsh_round(st, values, mask, *dr)
            st["n"] += int(mask.sum())
            st["step"] += int(mask.sum() > 0)

    def _lsh_round(self, st, values, mask, u, slot_draw, rank_r, slot_r,
                   stratum_keys) -> None:
        n0, live = st["n"], mask != 0
        bucket = self.bucket(values)
        np.add.at(st["counts"], bucket[live], 1)
        g = n0 + np.cumsum(mask) - 1
        within = live & (u >= n0)
        rows = np.flatnonzero(live)
        mate = rows[np.where(within, u - n0, 0)] if rows.size else rows
        slot = (np.clip(u, 0, self.rec_cap - 1) if n0 < self.rec_cap
                else slot_draw)
        p_items = np.where(within[:, None], values[mate],
                           st["rec_items"][slot])
        p_bucket = np.where(within, bucket[mate], st["rec_bucket"][slot])
        ok = live & (g > 0) & (within | (st["rec_tags"][slot] >= 0))
        sim = (values == p_items).sum(axis=1)
        same = p_bucket == bucket
        cand = np.stack([ok & same, ok & ~same]).astype(np.int32)
        rank, sdraw = (np.asarray(a) for a in self._fns()["strata"](
            stratum_keys, np.array([st["same_seen"], st["cross_seen"]],
                                   np.int32), cand))
        for i, name in enumerate(("same", "cross")):
            slots, src = _kept(st[name + "_seen"], cand[i], rank[i],
                               sdraw[i], self.pair_cap)
            st[name + "_sim"][slots] = sim[src]
            st[name + "_tags"][slots] = st["sid"]
            st[name + "_seen"] += int(cand[i].sum())
        slots, src = _kept(n0, mask, rank_r, slot_r, self.rec_cap)
        st["rec_items"][slots] = values[src]
        st["rec_bucket"][slots] = bucket[src]
        st["rec_tags"][slots] = st["sid"]

    # -- the window's merge ----------------------------------------------
    @staticmethod
    def _priority(items, tags, n, salt):
        import jax.numpy as jnp
        slot = np.arange(tags.shape[0], dtype=U32)
        h = (U32(salt) ^ tags.astype(U32)) + slot * U32(0x9E3779B9)
        for c in range(items.shape[1]):
            h = (h * U32(0x9E3779B1)) ^ items[:, c].astype(U32)
        h = h * U32(0x85EBCA77)
        h ^= h >> U32(15)
        w = (jnp.asarray(np.int32(n), jnp.float32)
             / jnp.maximum(jnp.float32((tags >= 0).sum()), 1.0))
        u = (jnp.asarray(h).astype(jnp.float32) + 1.0) / 4294967296.0
        key = np.asarray(jnp.log(u) / jnp.maximum(w, 1e-9))
        return np.where(tags >= 0, key, -np.inf)

    def _union(self, kind, a_items, a_tags, a_n, b_items, b_tags, b_n, cap):
        salt = MERGE_SALT[kind] ^ self.seed
        keys = np.concatenate([self._priority(a_items, a_tags, a_n, salt),
                               self._priority(b_items, b_tags, b_n, salt)])
        top = np.argsort(-keys, kind="stable")[:cap]
        items = np.concatenate([a_items, b_items])[top]
        tags = np.concatenate([a_tags, b_tags])[top]
        return items, np.where(tags >= 0, tags, -1)

    def merge(self, kind: str, a: dict, b: dict) -> dict:
        out = {"n": a["n"] + b["n"], "sid": max(a["sid"], b["sid"]),
               "step": a["step"] + b["step"]}
        if kind == "reservoir":
            out["items"], out["tags"] = self._union(
                kind, a["items"], a["tags"], a["n"], b["items"], b["tags"],
                b["n"], self.R)
            return out
        d = self.d
        rec = [np.concatenate([x["rec_items"],
                               x["rec_bucket"].astype(U32)[:, None]], 1)
               for x in (a, b)]
        items, out["rec_tags"] = self._union(
            kind, rec[0], a["rec_tags"], a["n"], rec[1], b["rec_tags"],
            b["n"], self.rec_cap)
        out["rec_items"] = items[:, :d]
        out["rec_bucket"] = items[:, d].astype(np.int64)
        for name in ("same", "cross"):
            sims, out[name + "_tags"] = self._union(
                kind, a[name + "_sim"].astype(U32)[:, None],
                a[name + "_tags"], a[name + "_seen"],
                b[name + "_sim"].astype(U32)[:, None], b[name + "_tags"],
                b[name + "_seen"], self.pair_cap)
            out[name + "_sim"] = sims[:, 0].astype(np.int64)
            out[name + "_seen"] = a[name + "_seen"] + b[name + "_seen"]
        out["counts"] = a["counts"] + b["counts"]
        return out

    # -- answers -----------------------------------------------------------
    def answer(self, kind: str, st: dict, dtype=np.float64):
        """(g, stderr), each (L,) for thresholds s..d.  ``dtype`` other
        than float64 is the control's lower precision."""
        if kind == "reservoir":
            return self._reservoir_answer(st, dtype)
        return self._lsh_answer(st, dtype)

    def _suffix(self, x, n):
        return np.cumsum(x[::-1])[::-1] + n

    def _reservoir_answer(self, st, dtype):
        d, s = self.d, self.s
        valid = st["tags"] >= 0
        m, n = int(valid.sum()), dtype(st["n"])
        x = pair_hist(st["items"][valid], d)[s:].astype(dtype) \
            * pair_scale(n, m, dtype)
        if self.replicates < 2 or self.R < 2:
            return self._suffix(x, n), np.zeros(d - s + 1, dtype)
        draw = np.asarray(self._fns()["boot"](
            np.int32(st["n"]), np.int32(st["step"]), np.int32(m)))
        b = min(m, draw.shape[1])
        order = np.flatnonzero(valid)
        x_reps = np.zeros((self.replicates, d - s + 1), dtype)
        if m >= 2:
            for i, r in enumerate(draw[:, :b]):
                x_reps[i] = pair_hist(st["items"][order[r]], d)[s:]
            x_reps *= pair_scale(n, b, dtype)
        cap = np.sqrt(dtype(b) / dtype(m)) if m >= 2 else dtype(0)
        err = suffix_std(x_reps) * (cap * serfling(n, m, dtype))
        return self._suffix(x, n), err.astype(dtype)

    def _lsh_answer(self, st, dtype):
        d, s, levels = self.d, self.s, self.d + 1
        counts = st["counts"].astype(dtype)
        n = dtype(st["n"])
        pairs = {"same": (counts * (counts - 1)).sum()}
        pairs["cross"] = max(n * (n - 1) - pairs["same"], dtype(0))
        rng = np.random.default_rng(np.random.SeedSequence(
            [int(U32(self.seed) ^ U32(BOOT_SALT)), st["n"] & 0xFFFFFFFF,
             st["step"] & 0xFFFFFFFF]))
        x = np.zeros(levels, dtype)
        x_dev = np.zeros((self.replicates, levels), dtype)
        for name in ("same", "cross"):
            kept = st[name + "_sim"][st[name + "_tags"] >= 0]
            m = kept.shape[0]
            if not m:
                continue
            hits = np.bincount(kept, minlength=levels)
            x += hits.astype(dtype) / dtype(m) * pairs[name]
            f = rng.dirichlet(hits + 0.5, size=self.replicates).astype(dtype)
            x_dev += (f - f.mean(axis=0)) * (
                pairs[name] * serfling(st[name + "_seen"], m, dtype))
        return self._suffix(x[s:], n), suffix_std(x_dev[:, s:]).astype(dtype)


class Window:
    """One tenant's sliding window of ``epochs`` per-epoch sample slots."""

    def __init__(self, ref: Samples, kind: str, epochs: int):
        self.ref, self.kind, self.W = ref, kind, epochs
        self.slots = [ref.init(kind, 0)] + [None] * (epochs - 1)
        self.pos, self.live, self.epoch = 0, 1, 0
        self.total = self.slots[0]
        self.version = 0

    @property
    def open(self) -> dict:
        return self.slots[self.pos]

    def refold(self) -> None:
        live = [x for x in self.slots if x is not None]
        total = live[0]
        for x in live[1:]:
            total = self.ref.merge(self.kind, total, x)
        self.total = total
        self.version += 1

    def advance(self) -> None:
        self.epoch += 1
        self.pos = (self.pos + 1) % self.W
        expiring = self.live >= self.W
        if not expiring:
            self.live += 1
        self.slots[self.pos] = self.ref.init(self.kind, self.epoch)
        if expiring:
            self.refold()
