"""A set field in which every column holds exactly one row, drawn from
``shares`` (one share per row id 0..rows-1; they sum to 1)."""

from __future__ import annotations

import numpy as np

from benchmark.reference import SHARD_WIDTH, Codes

VIEW = "standard"


def _shares(cfg: dict) -> np.ndarray:
    p = np.asarray(cfg["shares"], dtype=np.float64)
    if p.size != cfg["rows"] or cfg["rows"] > 256 or abs(p.sum() - 1.0) > 1e-6:
        raise ValueError(f"field {cfg['name']}: {p.size} shares summing to {p.sum()}")
    return p / p.sum()


def generate(cfg: dict, rng: np.random.Generator, shard: int) -> dict:
    edges = np.cumsum(_shares(cfg))
    edges[-1] = 1.0
    codes = np.searchsorted(edges, rng.random(SHARD_WIDTH), side="right")
    return {"codes": codes.astype(np.uint8)}


def fragments(cfg: dict, data: dict, shard: int):
    codes = data["codes"]
    order = np.argsort(codes, kind="stable").astype(np.uint64)
    positions = codes[order].astype(np.uint64) * np.uint64(SHARD_WIDTH) + order
    return [(VIEW, iter([positions]), True)]


def meta(cfg: dict):
    return None


def reference(cfg: dict, stacked: dict) -> Codes:
    return Codes(stacked["codes"], cfg["rows"])


def row_bits(cfg: dict, shards: int):
    return [(1, float(s) * SHARD_WIDTH) for s in _shares(cfg)]
