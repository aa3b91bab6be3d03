"""Batched ingest: double-buffered submission, fixed-shape coalescing, and
ONE jit'd device dispatch per estimator cohort per flush.

Why batch across tenants: each tenant's trickle of records is far too small
to saturate a device, and per-tenant dispatches pay per-call overhead S
times.  The pipeline stacks every stream of a hash group along a leading
axis -- states (S, ...) pytrees, records (R, S, B, d), row masks
(R, S, B), per-(round, stream) PRNG keys (R, S) -- and consumes ALL R
coalesced rounds of a flush in one ``Estimator.ingest_rounds`` dispatch
per **estimator cohort** (streams of one kind; DESIGN.md §13.4).  A group
whose streams all run SJPC -- the default -- is exactly one ``lax.scan``
inside one jit (:func:`multi_round_update`), vmapping the single-stream
update over the stream axis.  The inner update is ``sjpc.update_fused``
(fingerprint -> multi-level sketch in one kernel launch on TPU, the
fused-scatter formulation elsewhere), bit-identical to the per-level
reference ``sjpc.update`` for the same keys (tests/test_fused_ingest.py,
tests/test_service.py replay streams through it).

Shapes are static: records are coalesced into rounds of exactly
``batch_rows`` rows per stream, the tail round padded with zero rows that
carry row_mask 0 (contributing nothing to counters or n -- see
``sjpc.update``).  Only the streams with pending records dispatch: the A
active streams of a cohort of S are compacted, in uid order, into the
smallest power-of-two bucket S_d >= A (capped at S), the S_d - A pad slots
fully masked and their outputs dropped.  jit compiles once per
(R, S_d, batch_rows) -- at most ceil(log2 S) + 1 stream buckets -- and
reuses the executable across flushes of the same shape.  The update is a
vmap over streams, so a stream's result does not depend on which others
share its dispatch.

Double buffering: ``submit`` appends to the *front* buffer while ``flush``
drains the *back* buffer; the buffers swap at flush start.  In-process this
models (and under an async caller provides) ingest that never blocks on a
device dispatch in flight.

Determinism: the sampling key for stream u's i-th consumed round is
``ingest_key(cfg, uid, i)`` -- a pure function, so any window can be
re-built offline bit-exactly by replaying the same record rounds with the
same keys (tests/test_service.py does exactly this).
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import sjpc
from repro.core.sjpc import SJPCConfig, SJPCState
from repro.estimators import index_state, stack_states
from repro.obs import Observability

from .registry import HashGroup, StreamEntry

_INGEST_SALT = 0x5E41CE


def ingest_key(cfg: SJPCConfig, uid: int, round_idx: int) -> jax.Array:
    """The PRNG key stream u folds into its round_idx-th ingest round."""
    base = jax.random.PRNGKey(cfg.seed ^ _INGEST_SALT)
    return jax.random.fold_in(jax.random.fold_in(base, uid), round_idx)


@jax.jit
def ingest_key_grid(seed, uids, round_idx) -> jax.Array:
    """Vectorized :func:`ingest_key`: uids (S,), round_idx (R, S) ->
    keys (R, S).  Bit-identical to the scalar function (fold_in is
    elementwise deterministic under vmap); one dispatch instead of R*S."""
    base = jax.random.PRNGKey(seed)

    def one(uid, ridx):
        return jax.random.fold_in(jax.random.fold_in(base, uid), ridx)

    return jax.vmap(jax.vmap(one))(
        jnp.broadcast_to(uids[None, :], round_idx.shape), round_idx)


def _one_stream(cfg, params, c, n_s, step_s, vals, mask, key):
    st = sjpc.update_fused(cfg, params, SJPCState(c, n_s, step_s), vals,
                           key=key, row_mask=mask)
    return st.counters, st.n, st.step


@functools.partial(jax.jit, static_argnames=("cfg",))
def multi_round_update(cfg, params, counters, n, steps, values, row_mask,
                       keys):
    """ALL rounds of a flush in one dispatch: ``lax.scan`` over the round
    axis of values (R, S, B, d) / row_mask (R, S, B) / keys (R, S), each
    round one vmap of the single-stream update over the stream axis.

    counters (S, L, t, w) int32; n (S,) f32; steps (S,) int32.  Returns
    the updated (counters, n, steps).
    """
    one = jax.vmap(functools.partial(_one_stream, cfg, params))

    def body(carry, rnd):
        vals, mask, ks = rnd
        return one(*carry, vals, mask, ks), None

    carry, _ = jax.lax.scan(body, (counters, n, steps),
                            (values, row_mask, keys))
    return carry


class IngestPipeline:
    """Per-group ingest front end.  Not thread-safe by itself; the service
    serializes submit/flush (the double buffer is about device overlap and
    fixed-shape coalescing, not about lock-free concurrency)."""

    def __init__(self, group: HashGroup, *, batch_rows: int = 256,
                 obs: Observability | None = None):
        assert batch_rows >= 1
        self.group = group
        self.batch_rows = batch_rows
        self.obs = obs if obs is not None else Observability.disabled()
        self._front: dict[str, list[np.ndarray]] = {}
        self._front_rows = 0                 # queue depth, kept incrementally
        self._back: dict[str, list[np.ndarray]] = {}
        self.stats = {"submitted_records": 0, "flushes": 0, "rounds": 0,
                      "dispatches": 0, "padded_rows": 0, "dispatch_rows": 0}

    # ------------------------------------------------------------------
    def submit(self, name: str, records) -> int:
        """Queue records ((n, d) integer array) for ``name``; returns n."""
        records = np.ascontiguousarray(np.asarray(records, dtype=np.uint32))
        if records.ndim != 2 or records.shape[1] != self.group.cfg.d:
            raise ValueError(
                f"records must be (n, d={self.group.cfg.d}); got {records.shape}")
        self._front.setdefault(name, []).append(records)
        self._front_rows += records.shape[0]
        self.stats["submitted_records"] += records.shape[0]
        m = self.obs.metrics
        if m.enabled:
            gid = self.group.group_id
            m.inc("ingest_submitted_records_total", records.shape[0],
                  group=gid)
            m.set("ingest_pending_rows", self._front_rows, group=gid)
            m.set_max("ingest_pending_rows_peak", self._front_rows, group=gid)
        return records.shape[0]

    def pending_rows(self) -> int:
        return self._front_rows

    # ------------------------------------------------------------------
    def flush(self, entries: list[StreamEntry]) -> dict:
        """Drain the queued records of ``entries`` (all streams of this
        group, in uid order) and return each stream's new ingest state
        (cumulative window for linear estimators, open-epoch state for
        windowed sample estimators -- whatever ``window.ingest_base``
        hands out).

        Streams dispatch in **estimator cohorts**: the streams of one
        estimator kind that have pending records share one batched
        ``ingest_rounds`` call over a power-of-two bucket of stream slots
        (module docstring); streams with no records are left out of the
        dispatch and keep their state, window version and replay
        coordinate.  An all-SJPC group is exactly one
        :func:`multi_round_update` dispatch.  ``entry.flushes`` counts the
        rounds that carried the stream's OWN rows, and is the replay
        coordinate for :func:`ingest_key` -- cohort rounds that existed only
        for a busier cohort-mate are fully masked here, consume none of this
        stream's randomness, and do not advance it.
        """
        self._front, self._back = self._back, self._front
        self._front_rows = 0
        pending = {name: (np.concatenate(chunks) if chunks else
                          np.zeros((0, self.group.cfg.d), np.uint32))
                   for name, chunks in self._back.items()}
        self._back = {}
        if self.obs.metrics.enabled:
            self.obs.metrics.set("ingest_pending_rows", 0,
                                 group=self.group.group_id)

        entries = sorted(entries, key=lambda e: e.uid)
        out = {e.name: e.window.ingest_base() for e in entries}
        # cohorts key on the estimator INSTANCE: streams of one kind but
        # with an explicit estimator_cfg override are distinct cohorts
        # (different state shapes / seeds must not share a dispatch)
        cohorts: dict[int, list[StreamEntry]] = {}
        for e in entries:
            cohorts.setdefault(id(e.estimator), []).append(e)
        self.stats["flushes"] += 1
        for cohort in cohorts.values():
            self._flush_cohort(cohort, pending, out)
        return out

    def _flush_cohort(self, entries: list[StreamEntry], pending: dict,
                      out: dict) -> None:
        B, cfg = self.batch_rows, self.group.cfg
        est = entries[0].estimator
        active = [(e, pending[e.name]) for e in entries
                  if e.name in pending and pending[e.name].shape[0]]
        if not active:
            return
        rounds = max(-(-rows.shape[0] // B) for _, rows in active)
        # only the A streams with records dispatch, padded to a power-of-two
        # bucket of S_d slots: jit compiles once per (rounds, S_d), at most
        # ceil(log2 S) + 1 stream buckets, and A == S dispatches the cohort
        S, A = len(entries), len(active)
        S_d = min(1 << (A - 1).bit_length(), S)
        n_records = sum(rows.shape[0] for _, rows in active)

        with self.obs.span("ingest.coalesce", streams=S_d, active=A,
                           rounds=rounds) as sp:
            values = np.zeros((rounds, S_d, B, cfg.d), np.uint32)
            mask = np.zeros((rounds, S_d, B), np.int32)
            round_idx = np.zeros((rounds, S_d), np.int32)
            sp.set(bytes=values.nbytes + mask.nbytes + round_idx.nbytes)
            for i, (e, rows) in enumerate(active):
                own = -(-rows.shape[0] // B)
                for r in range(own):
                    chunk = rows[r * B:(r + 1) * B]
                    values[r, i, :chunk.shape[0]] = chunk
                    mask[r, i, :chunk.shape[0]] = 1
                # a stream's replay coordinate advances only by the rounds
                # that carried ITS rows -- trailing rounds that exist only
                # for a busier cohort-mate are fully masked for this
                # stream, consume no randomness, and must not shift its key
                # stream, or the window content would depend on
                # co-tenants' backlog sizes and the offline replay contract
                # (module docstring) would break
                round_idx[:, i] = e.flushes + np.arange(rounds)
                e.flushes += own
                e.records += int(rows.shape[0])
        self.stats["padded_rows"] += S_d * B * rounds - n_records

        gid, kind = self.group.group_id, entries[0].estimator_kind
        with self.obs.span("ingest.flush_cohort",
                           histogram="ingest_flush_seconds",
                           labels={"group": gid, "kind": kind},
                           group=gid, kind=kind, streams=S_d, active=A,
                           rounds=rounds) as sp:
            # stack before the upload: the stack's per-stream temporaries
            # are freed before the record block lands on the device.  The
            # S_d - A pad slots reuse the first active stream's state
            # object (no new arrays); their rows are all masked and their
            # outputs are dropped
            slots = [e for e, _ in active]
            slots += [slots[0]] * (S_d - A)
            with self.obs.span("ingest.stack", streams=S_d, active=A):
                states = stack_states([out[e.name] for e in slots])
            with self.obs.span("ingest.upload",
                               bytes=values.nbytes + mask.nbytes
                               + round_idx.nbytes):
                keys = ingest_key_grid(
                    jnp.uint32(est.ingest_seed),
                    jnp.asarray([e.uid for e in slots], jnp.int32),
                    jnp.asarray(round_idx))
                values, mask = jnp.asarray(values), jnp.asarray(mask)
            states = est.ingest_rounds(states, values, mask, keys)
            # device-time semantics: the span blocks on the dispatched
            # states before its clock stops (trace events show dispatch
            # vs compute separately)
            sp.sync(*jax.tree_util.tree_leaves(states))
        self.stats["rounds"] += rounds
        self.stats["dispatches"] += 1
        self.stats["dispatch_rows"] += S_d * B * rounds
        m = self.obs.metrics
        if m.enabled:
            m.inc("ingest_dispatches_total", group=gid, kind=kind)
            m.inc("ingest_rounds_total", rounds, group=gid, kind=kind)
            m.inc("ingest_dispatch_rows_total", S_d * B * rounds,
                  group=gid, kind=kind)
            m.inc("ingest_streams_skipped_total", S - A, group=gid,
                  kind=kind)
        # idle streams keep their ingest base: their window content and
        # version are unchanged (committing a step-only bump would thrash
        # version-keyed query caches)
        with self.obs.span("ingest.unstack", streams=A):
            for i, (e, _) in enumerate(active):
                out[e.name] = index_state(states, i)
