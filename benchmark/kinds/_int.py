"""What the int kinds share: bit-sliced planes from values."""

from __future__ import annotations

import numpy as np

from benchmark.reference import SHARD_WIDTH, IntValues


def depth(cfg: dict) -> int:
    """Smallest i with max - min < 2^i (upstream BitDepth)."""
    return max(1, int(cfg["max"] - cfg["min"]).bit_length())


def present(cfg: dict, rng: np.random.Generator):
    share = cfg.get("present", 1.0)
    return None if share >= 1.0 else rng.random(SHARD_WIDTH) < share


def fragments(cfg: dict, data: dict, shard: int):
    vals, exists = data["vals"], data.get("exists")
    width = np.uint64(SHARD_WIDTH)
    base = (vals - cfg["min"]).astype(np.uint32)
    d = depth(cfg)

    def chunks():
        for i in range(d):
            bit = ((base >> i) & 1).astype(bool)
            cols = np.flatnonzero(bit if exists is None else bit & exists)
            yield np.uint64(i) * width + cols.astype(np.uint64)
        cols = np.arange(SHARD_WIDTH) if exists is None else np.flatnonzero(exists)
        yield np.uint64(d) * width + cols.astype(np.uint64)

    return [("bsig_" + cfg["name"], chunks(), False)]


def meta(cfg: dict):
    return {"type": "int", "min": cfg["min"], "max": cfg["max"]}


def reference(cfg: dict, stacked: dict) -> IntValues:
    return IntValues(stacked["vals"], stacked.get("exists"))


def row_bits(cfg: dict, shards: int):
    share = cfg.get("present", 1.0) * SHARD_WIDTH
    # value planes hold about half the present columns; the existence plane all
    return [(depth(cfg), share / 2), (1, share)]
