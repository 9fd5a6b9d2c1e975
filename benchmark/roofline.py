"""The table of peaks and the bytes a request's data needs.

``bytes_needed`` counts the data, not what the program stages: every
row a call must read costs shards x min(131,072 B dense, 4 B x the row's
bits in a shard). The count does not change when an implementation
stages less or more, so a share of the roofline stays a share; a share
over 100 % is a fault in this count. A GroupBy's group masks are
computed, not read: its bytes are its dimensions' rows, its filter and
its ``sum`` field, and its kernel, bound by arithmetic, reads low here
by nature.
"""

from __future__ import annotations

from benchmark.datagen import field_of, kind_of
from benchmark.reference import BITMAP_OPS, SHARD_WIDTH

# Google Cloud documentation, "TPU v5e": 16 GB of HBM at 819 GB/s a chip
PEAKS = {
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}

DENSE_ROW_BYTES = SHARD_WIDTH // 8
COUNT_BYTES = 8  # one kept count per row per shard


def peak(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}")
    return PEAKS[device_kind]


def _rows_bytes(classes, shards: int) -> float:
    return sum(n * shards * min(DENSE_ROW_BYTES, 4.0 * bits) for n, bits in classes)


def _all_rows(config: dict, field: str) -> float:
    f = field_of(config, field)
    return _rows_bytes(kind_of(f).row_bits(f, config["shards"]), config["shards"])


def _one_row(config: dict, field: str, row: int) -> float:
    f = field_of(config, field)
    classes = kind_of(f).row_bits(f, config["shards"])
    for n, bits in classes:  # classes are in row order
        if row < n:
            return _rows_bytes([(1, bits)], config["shards"])
        row -= n
    raise ValueError(f"row past the end of field {field}")


def _bitmap_bytes(config: dict, e) -> float:
    if e is None:
        return 0.0
    if e[0] == "Row":
        return _one_row(config, e[1], e[2])
    if e[0] == "Range":
        return _all_rows(config, e[1])
    if e[0] in BITMAP_OPS:
        return sum(_bitmap_bytes(config, c) for c in e[1:])
    raise ValueError(f"unknown bitmap expression {e[0]!r}")


def bytes_needed(config: dict, call) -> float:
    tag = call[0]
    if tag == "Count":
        return _bitmap_bytes(config, call[1])
    if tag == "Sum":
        return _all_rows(config, call[1]) + _bitmap_bytes(config, call[2])
    if tag == "TopN":
        if call[2] is None:
            # unfiltered: the kept count of every row, not the rows
            f = field_of(config, call[1])
            shards = config["shards"]
            classes = kind_of(f).row_bits(f, shards)
            # a row with under one bit a shard sits in that share of the shards
            return COUNT_BYTES * sum(n * shards * min(1.0, bits) for n, bits in classes)
        return _all_rows(config, call[1]) + _bitmap_bytes(config, call[2])
    if tag == "GroupBy":
        dims = sum(
            sum(_one_row(config, d[1], r) for r in d[2]) if len(d) > 2 else _all_rows(config, d[1])
            for d in call[1]
        )
        agg = call[3].get("sum")
        return dims + _bitmap_bytes(config, call[2]) + (_all_rows(config, agg) if agg else 0.0)
    return _bitmap_bytes(config, call)
