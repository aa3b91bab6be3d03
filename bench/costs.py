"""Least work a call has to do, from the shapes of its operands alone.

A roofline share is (least bytes / peak bandwidth) over the measured device
time of the call.  The counts below read only the operand shapes of the
call that is timed, never how a kernel tiles or loops, so a later kernel
that does the same job is measured against the same work.
"""
from __future__ import annotations

WORD = 4          # every operand here is 32-bit (uint32 / int32 / float32)


def ingest_flush_bytes(*, rounds: int, streams: int, batch_rows: int, d: int,
                       levels: int, depth: int, width: int) -> int:
    """HBM bytes one ``multi_round_update`` call cannot avoid.

    Operands: records (R, S, B, d) and row mask (R, S, B), read once;
    counters (S, L, t, w), read once and written once; per-stream ``n`` and
    ``step`` (S,), read and written; the (R, S) key grid (2 words a key),
    read.  The integer hashing work is no bound: the v5e publishes no int32
    VPU peak, so the share is taken against bandwidth alone.
    """
    R, S, B = rounds, streams, batch_rows
    records = R * S * B * d
    mask = R * S * B
    counters = 2 * S * levels * depth * width
    scalars = 2 * 2 * S
    keys = 2 * R * S
    return WORD * (records + mask + counters + scalars + keys)


def query_bytes(*, streams: int, levels: int, depth: int, width: int,
                join: bool) -> int:
    """HBM bytes one ``_estimate_batch_core`` call cannot avoid: the stacked
    (N, L, t, w) counters, one side for a self-join table (both operands
    are the same array) and both sides for N join pairs; the (N,) record
    counts; and the (N, L) outputs y, x and g, written once."""
    N = streams
    sides = 2 if join else 1
    counters = sides * N * levels * depth * width
    return WORD * (counters + N + 3 * N * levels)
