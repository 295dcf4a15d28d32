"""What the post-load compaction of one shard has to move, from row
counts and widths alone — never from the padded capacity of a launch and
never from how the program implements it.

The merge is bound by bytes, not by arithmetic (a compare and at most one
64-bit add per row): its least time on a chip is bytes / peak bytes/s.
"""

from __future__ import annotations

SEQ_BYTES, TYPE_BYTES = 8, 1  # a row's sequence number and its op type


def row_bytes(key_bytes: int, value_bytes: int) -> int:
    return key_bytes + SEQ_BYTES + TYPE_BYTES + value_bytes


def bloom_bytes(rows: int, bits_per_key: int) -> int:
    return (rows * bits_per_key + 7) // 8


def merge_bytes(rows_in: int, rows_out: int, key_bytes: int,
                value_bytes: int, bits_per_key: int) -> int:
    """Bytes one shard's merge needs: every input row read once, every
    output row written once, the output's bloom filter written once."""
    width = row_bytes(key_bytes, value_bytes)
    return (rows_in * width + rows_out * width
            + bloom_bytes(rows_out, bits_per_key))


def unit_rows(config: dict) -> tuple:
    """(rows into, rows out of) the compaction of one unit of ``config``:
    the generator's own counts (``workload.unit_row_counts``)."""
    from chipbench import workload

    return workload.unit_row_counts(int(config["rows_per_slot"]),
                                    bool(config["live_counters"]))


def unit_bytes(config: dict) -> int:
    rows_in, rows_out = unit_rows(config)
    return merge_bytes(rows_in, rows_out, int(config["key_bytes"]),
                       int(config["value_bytes"]),
                       int(config["options"]["bits_per_key"]))
