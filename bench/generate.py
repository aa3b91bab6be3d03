"""The general traffic generator: records and per-cycle tenant activity for
the service cells, drawn from ``--seed`` and a traffic mix's parameters.

Everything is drawn in set-up, before the window, so generation never sits
on the timed path.  Every seed gets the same sizes (tenants per cycle,
records per submission, queries); only which tenants and which values
change.

Records come from one pool of fresh DBLP-shaped blocks.  Each tenant reads
the pool from its own cursor, which starts at a seeded stripe of its own
and moves on by what it submits, so no tenant is sent the same record twice
until it has read the whole pool.  Which tenants submit, and how many
records each, is a *pick*: ``bench/picks/<name>.py``, found by the name the
mix gives, so a new activity pattern is a new file, never an edit here.
"""
from __future__ import annotations

import dataclasses
import pathlib

import numpy as np

PICKS = pathlib.Path(__file__).resolve().parent / "picks"


def dblp_like(rng: np.random.Generator, count: int, n: int, d: int, *,
              card_fractions, dup_fraction: float,
              dup_columns: int) -> np.ndarray:
    """``count`` independent blocks of ``n`` DBLP-shaped records, (count,
    n, d) uint32.

    Column c takes values uniform in [0, max(2, n * card_fractions[c])):
    title- and author-like columns of high cardinality, then year- and
    venue-like ones of very low.  The last ``dup_fraction`` of each block
    are near-copies of earlier records of the block that agree on
    ``dup_columns`` columns; the others are drawn anew.
    """
    fr = list(card_fractions)[:d] + [0.01] * max(0, d - len(card_fractions))
    cards = np.array([max(2, int(n * f)) for f in fr], np.int64)
    recs = (rng.random((count, n, d)) * cards).astype(np.uint32)
    n_dup = int(n * dup_fraction)
    if n_dup:
        src = rng.integers(0, n - n_dup, size=(count, n_dup))
        recs[:, n - n_dup:] = np.take_along_axis(recs, src[:, :, None], 1)
        # d - dup_columns distinct columns per copy, drawn anew
        order = np.argsort(rng.random((count, n_dup, d)), axis=-1)
        redraw = order[..., :d - dup_columns]
        fresh = (rng.random(redraw.shape) * cards[redraw]).astype(np.uint32)
        np.put_along_axis(recs[:, n - n_dup:], redraw, fresh, axis=-1)
    return recs


def load_pick(name: str, root: pathlib.Path | None = None):
    from bench.harness import load_module
    return load_module((root / "bench" / "picks" if root else PICKS)
                       / f"{name}.py")


@dataclasses.dataclass
class Plan:
    """One run's traffic, fixed before the window opens."""
    self_tenants: np.ndarray        # (Q_self,) tenants with a standing query
    join_pairs: np.ndarray          # (Q_join, 2)
    prefill: int                    # records each queried tenant sends first
    pool: np.ndarray                # (P + tail, d): the record pool, its
    #                                 first ``tail`` rows repeated at the end
    size: int                       # P, the pool's own length
    picks: list                     # cycle -> (tenants, records of each)
    cursor: dict                    # tenant -> next pool row it reads

    def take(self, tenant: int, count: int) -> tuple[int, int]:
        """The next ``count`` records of ``tenant``: (first pool row, count);
        ``pool[start:start + count]`` holds them."""
        start = self.cursor[tenant]
        self.cursor[tenant] = (start + count) % self.size
        return start, count

    def records(self, start: int, count: int) -> np.ndarray:
        return self.pool[start:start + count]

    def cycle(self, c: int) -> tuple[np.ndarray, np.ndarray]:
        return self.picks[c % len(self.picks)]

    @property
    def queried(self) -> np.ndarray:
        return np.concatenate([self.self_tenants, self.join_pairs.reshape(-1)])


def plan(traffic: dict, *, tenants: int, d: int, seed: int,
         root: pathlib.Path | None = None) -> Plan:
    rng = np.random.default_rng(seed)
    rec = traffic["records"]
    q = traffic.get("queries", {})
    n_self, n_join = int(q.get("all_thresholds", 0)), int(q.get("join", 0))
    chosen = rng.choice(tenants, n_self + 2 * n_join, replace=False)
    self_tenants = chosen[:n_self]
    join_pairs = chosen[n_self:].reshape(n_join, 2)
    queried = np.concatenate([self_tenants, join_pairs.reshape(-1)])

    cyc = traffic["cycle"]
    pick = load_pick(cyc["tenants"]["pick"], root)
    picks = pick.picks(rng, cyc, cycles=int(traffic["plan_cycles"]),
                       tenants=tenants, self_tenants=self_tenants,
                       join_pairs=join_pairs)
    picks = [(np.asarray(t, np.int64), np.asarray(n, np.int64))
             for t, n in picks]

    pool = traffic["pool"]
    block = int(pool["block"])
    blocks = -(-int(pool["records"]) // block)
    size = blocks * block
    recs = dblp_like(rng, blocks, block, d,
                     card_fractions=rec["card_fractions"],
                     dup_fraction=rec["dup_fraction"],
                     dup_columns=rec["dup_columns"]).reshape(size, d)
    prefill = int(traffic.get("prefill_records", 0))
    tail = min(size, max([prefill] + [int(n.max(initial=0))
                                      for _, n in picks]))
    # every tenant that ever submits gets its own stripe of the pool
    senders = np.unique(np.concatenate(
        [queried] + [t for t, _ in picks]).astype(np.int64))
    rank = rng.permutation(len(senders))
    cursor = {int(t): int(r) * size // len(senders)
              for t, r in zip(senders, rank)}
    return Plan(self_tenants=self_tenants, join_pairs=join_pairs,
                prefill=prefill, pool=np.concatenate([recs, recs[:tail]]),
                size=size, picks=picks, cursor=cursor)
