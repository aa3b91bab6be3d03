"""The least-work count behind ``pairs.roofline_pct``, checked against
values worked out by hand from the operand shapes of the mixed cell's two
launches (d = 6; a reservoir of 1,755 slots; bootstrap replicates of 256
draws)."""
from bench import costs_pairs


def test_pairs_bytes_of_the_histogram_launch():
    # items 64 x 1,755 x 6 = 673,920 words, validity 112,320, histogram
    # 64 x 7 = 448
    assert costs_pairs.pairs_bytes(streams=64, slots=1755, d=6) == \
        4 * (673_920 + 112_320 + 448) == 3_146_752


def test_pairs_bytes_of_the_bootstrap_launch():
    # 64 streams x 32 replicates stacked: 2,048 samples of 256 slots
    assert costs_pairs.pairs_bytes(streams=2048, slots=256, d=6) == \
        4 * (3_145_728 + 524_288 + 14_336)


def test_pairs_comparisons_count_ordered_pairs_of_distinct_slots():
    assert costs_pairs.pairs_comparisons(streams=1, slots=3, d=6) == 36
    # the bootstrap launch: 2,048 x 256 x 255 x 6
    assert costs_pairs.pairs_comparisons(streams=2048, slots=256, d=6) == \
        802_160_640
