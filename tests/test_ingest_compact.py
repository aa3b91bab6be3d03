"""Active-stream compaction in the ingest pipeline: a flush dispatches only
the streams with pending records, in a power-of-two bucket of slots, and
commits exactly what a full-width dispatch with every idle stream fully
masked would have committed."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.sjpc import SJPCConfig
from repro.estimators import index_state, stack_states
from repro.service import EstimationService, ServiceConfig
from repro.service.ingest import ingest_key_grid, multi_round_update

B = 16


def _records(rng, n, d):
    return rng.integers(0, 6, size=(n, d)).astype(np.uint32)


def _service(streams, estimator="sjpc", seed=7):
    # ratio < 1: sampling reads each round's key
    cfg = SJPCConfig(d=4, s=3, ratio=0.5, width=128, depth=2, seed=seed)
    svc = EstimationService(ServiceConfig(batch_rows=B, window_epochs=None))
    svc.create_group("g", cfg)
    names = [f"t{i:02d}" for i in range(streams)]
    for nm in names:
        svc.create_stream(nm, "g", estimator=estimator)
    return svc, names


def _full_width(entries, pending):
    """The dispatch every stream of the cohort rode before compaction:
    all S streams, idle ones fully masked, keys for every stream."""
    est = entries[0].estimator
    rounds = max(-(-rows.shape[0] // B) for rows in pending.values())
    S = len(entries)
    d = pending[next(iter(pending))].shape[1]
    values = np.zeros((rounds, S, B, d), np.uint32)
    mask = np.zeros((rounds, S, B), np.int32)
    round_idx = np.zeros((rounds, S), np.int32)
    for i, e in enumerate(entries):
        rows = pending.get(e.name, np.zeros((0, d), np.uint32))
        for r in range(rounds):
            chunk = rows[r * B:(r + 1) * B]
            values[r, i, :chunk.shape[0]] = chunk
            mask[r, i, :chunk.shape[0]] = 1
        round_idx[:, i] = e.flushes + np.arange(rounds)
    states = stack_states([e.window.ingest_base() for e in entries])
    keys = ingest_key_grid(jnp.uint32(est.ingest_seed),
                           jnp.asarray([e.uid for e in entries], jnp.int32),
                           jnp.asarray(round_idx))
    return est.ingest_rounds(states, jnp.asarray(values), jnp.asarray(mask),
                             keys)


@pytest.mark.parametrize("estimator", ["sjpc", "reservoir"])
def test_compact_dispatch_commits_what_full_width_commits(estimator):
    svc, names = _service(64, estimator)
    rng = np.random.default_rng(21)
    # a first flush over every other stream, so replay coordinates differ
    # and those reservoirs (102 slots) are full: later rounds read keys
    for nm in names[::2]:
        svc.ingest(nm, _records(rng, 120, 4))
    svc.flush()
    # 5 of 64 active, uneven counts; t02 spans 3 rounds
    counts = {"t02": 40, "t10": 5, "t21": 16, "t41": 17, "t62": 1}
    pending = {nm: _records(rng, c, 4) for nm, c in counts.items()}
    entries = sorted(svc.registry.streams("g"), key=lambda e: e.uid)
    want = _full_width(entries, pending)
    before = {e.name: (e.window.version, e.flushes, e.window.total)
              for e in entries}
    for nm, rows in pending.items():
        svc.ingest(nm, rows)
    svc.flush()
    for i, e in enumerate(entries):
        v0, f0, total0 = before[e.name]
        if e.name in counts:
            ref = jax.tree_util.tree_leaves(index_state(want, i))
            got = jax.tree_util.tree_leaves(e.window.total)
            assert len(got) == len(ref)
            for a, b in zip(got, ref):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
            assert e.window.version == v0 + 1
            assert e.flushes == f0 + -(-counts[e.name] // B)
        else:
            assert e.window.version == v0
            assert e.flushes == f0
            assert e.window.total is total0
    if estimator == "sjpc":
        st = svc.registry.stream("t02").window.total
        ref = index_state(want, 2)
        for leaf in ("counters", "n", "step"):
            np.testing.assert_array_equal(np.asarray(getattr(st, leaf)),
                                          np.asarray(getattr(ref, leaf)))


def test_active_streams_share_a_power_of_two_bucket():
    # a configuration no other test compiles, so the jit cache counts are
    # this test's own
    svc, names = _service(20, seed=1515)
    rng = np.random.default_rng(5)
    pipe = svc._pipelines["g"]
    shapes = []

    def flush(active):
        for nm in names[:active]:
            svc.ingest(nm, _records(rng, B, 4))        # one round each
        rows0 = pipe.stats["dispatch_rows"]
        n0 = multi_round_update._cache_size()
        svc.flush()
        shapes.append((pipe.stats["dispatch_rows"] - rows0) // B)
        return multi_round_update._cache_size() - n0

    assert flush(5) == 1
    assert flush(6) == 0 and flush(8) == 0      # one executable for 5..8
    assert flush(9) == 1
    assert flush(20) == 1                       # A == S: the whole cohort
    assert shapes == [8, 8, 8, 16, 20]
    for e in svc.registry.streams("g"):
        assert e.records == B * sum(e.uid < a for a in (5, 6, 8, 9, 20))
