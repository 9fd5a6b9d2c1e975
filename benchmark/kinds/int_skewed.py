"""An int field with a right-skewed (log-normal) distribution:
``median`` and ``sigma`` of the log-normal, clipped to ``min``..``max``,
held by a share ``present`` of the columns."""

from __future__ import annotations

import numpy as np

from benchmark.kinds._int import fragments, meta, present, reference, row_bits  # noqa: F401
from benchmark.reference import SHARD_WIDTH


def generate(cfg: dict, rng: np.random.Generator, shard: int) -> dict:
    raw = rng.lognormal(np.log(cfg["median"]), cfg["sigma"], size=SHARD_WIDTH)
    out = {"vals": np.clip(raw, cfg["min"], cfg["max"]).astype(np.int32)}
    exists = present(cfg, rng)
    if exists is not None:
        out["exists"] = exists
    return out
