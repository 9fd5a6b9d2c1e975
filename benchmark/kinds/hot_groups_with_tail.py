"""A ranked set field: hot rows in correlated groups over a singleton
tail (``chip_smoke.py``'s ``_shard_data`` + ``build_shard``, PR 21).

Parameters: ``hot_rows`` (a multiple of ``group``), ``group``,
``hot_bits`` (bits per hot row per shard), ``tail_rows`` (singleton rows
per shard, numbered after the hot rows).

Row j of a group keeps a share 1 - j/(group+4) of the group's base
columns and fills up with fresh random ones, so a group's rows overlap
its base row by clearly separated amounts in every shard: a TopN
filtered by a group's base row has no tie at the cut.
"""

from __future__ import annotations

import numpy as np

from benchmark.reference import SHARD_WIDTH, WORDS64, PackedRows, unpack

VIEW = "standard"


def generate(cfg: dict, rng: np.random.Generator, shard: int) -> dict:
    hot_rows, group, hot_bits = cfg["hot_rows"], cfg["group"], cfg["hot_bits"]
    density = hot_bits / SHARD_WIDTH
    hot = np.empty((hot_rows, WORDS64), dtype="<u8")
    for g in range(hot_rows // group):
        base = rng.random(SHARD_WIDTH) < density
        keep_rank = rng.random(SHARD_WIDTH)
        for j in range(group):
            mask = base & (keep_rank < 1.0 - j / (group + 4))
            fresh = hot_bits - int(mask.sum())
            if fresh > 0:
                mask[rng.integers(0, SHARD_WIDTH, size=fresh)] = True
            hot[g * group + j] = np.packbits(mask, bitorder="little").view("<u8")
    return {"hot": hot}


def fragments(cfg: dict, data: dict, shard: int):
    """[(view, sorted position chunks, write the ranked cache file)]."""
    width = np.uint64(SHARD_WIDTH)
    hot = data["hot"]

    def chunks():
        for r in range(cfg["hot_rows"]):
            cols = np.flatnonzero(unpack(hot[r])).astype(np.uint64)
            yield np.uint64(r) * width + cols
        rows = np.arange(cfg["tail_rows"], dtype=np.uint64) + np.uint64(
            cfg["hot_rows"] + shard * cfg["tail_rows"]
        )
        cols = (rows * np.uint64(2654435761)) % width
        yield rows * width + cols

    return [(VIEW, chunks(), True)]


def meta(cfg: dict):
    return None  # a set field with the default ranked cache


def reference(cfg: dict, stacked: dict) -> PackedRows:
    return PackedRows(stacked["hot"], tail_max=1 if cfg["tail_rows"] else 0)


def row_bits(cfg: dict, shards: int) -> list:
    """For the bytes the data needs: bits per row per shard, by class of
    row: [(number of rows, bits per row per shard)]."""
    return [(cfg["hot_rows"], cfg["hot_bits"]), (cfg["tail_rows"] * shards, 1.0 / shards)]
