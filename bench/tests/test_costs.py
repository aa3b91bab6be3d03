"""The least-work counts behind the roofline shares, checked against values
worked out by hand from the operand shapes at the paper's defaults
(d=6, s=3 so L=4 levels, t=3, w=1024; 512-row rounds)."""
from bench import costs

PAPER = dict(d=6, levels=4, depth=3, width=1024)


def test_ingest_flush_bytes_at_the_ingest_cell():
    # 2 rounds x 4096 streams x 512 rows: records 25,165,824 words, mask
    # 4,194,304; counters in and out 2 x 4096 x 12,288 = 100,663,296;
    # n and step in and out 16,384; keys 2 x 2 x 4096 = 16,384
    got = costs.ingest_flush_bytes(rounds=2, streams=4096, batch_rows=512,
                                   **PAPER)
    assert got == 4 * 130_056_192 == 520_224_768


def test_ingest_flush_bytes_ignore_how_the_kernel_tiles():
    one = costs.ingest_flush_bytes(rounds=1, streams=1, batch_rows=512,
                                   **PAPER)
    # 512 x 6 records + 512 mask + 2 x 12,288 counters + 4 scalars + 2 key
    assert one == 4 * (3072 + 512 + 24_576 + 4 + 2)


def test_query_bytes_self_table_and_join_pairs():
    p = dict(levels=4, depth=3, width=1024)
    # 4096 x 12,288 counters + 4096 n + 3 x 4096 x 4 outputs
    assert costs.query_bytes(streams=4096, join=False, **p) == \
        4 * (50_331_648 + 4096 + 49_152)
    # one pair reads both sides: 2 x 12,288 + 1 + 12
    assert costs.query_bytes(streams=1, join=True, **p) == 4 * 24_589
