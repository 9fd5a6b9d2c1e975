"""An int field, uniform over ``min``..``max``, held by a share
``present`` of the columns."""

from __future__ import annotations

import numpy as np

from benchmark.kinds._int import fragments, meta, present, reference, row_bits  # noqa: F401
from benchmark.reference import SHARD_WIDTH


def generate(cfg: dict, rng: np.random.Generator, shard: int) -> dict:
    vals = rng.integers(cfg["min"], cfg["max"] + 1, size=SHARD_WIDTH, dtype=np.int32)
    out = {"vals": vals}
    exists = present(cfg, rng)
    if exists is not None:
        out["exists"] = exists
    return out
