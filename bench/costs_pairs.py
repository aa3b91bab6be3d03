"""Least work of one ``fused_pairs`` launch, from the shapes of its
operands alone: N stacked samples of R slots of d columns, their (N, R)
validity, and the (N, d + 1) histogram of ordered valid pairs by the
number of columns they agree on.  Like ``costs.py`` it reads only the
operand shapes of the timed call, never how the kernel tiles or pads.
"""
from __future__ import annotations

WORD = 4          # items uint32, validity and histogram int32


def pairs_bytes(*, streams: int, slots: int, d: int) -> int:
    """HBM bytes a launch cannot avoid: items N·R·d and validity N·R read
    once, the histogram N·(d+1) written once."""
    N, R = streams, slots
    return WORD * (N * R * d + N * R + N * (d + 1))


def pairs_comparisons(*, streams: int, slots: int, d: int) -> int:
    """Column comparisons of every ordered pair of distinct slots:
    N·R·(R-1)·d, for a roofline on operations once a peak for them is
    published."""
    return streams * slots * (slots - 1) * d
