"""Star Schema Benchmark ``lineorder`` joined with ``part`` and
``supplier`` and denormalised: the attributes flight 2 (Q2.1-Q2.3) reads
of the same 2^20 line items a shard that ``ssb_lineorder`` draws, and
SSB's own ``lo_revenue`` of each of them.

The harness hands each field its own generator,
``default_rng([seed, shard, i])``; the kind reads seed and shard back
from it. Day, quantity, discount and unit price come from
``ssb_lineorder._line_items`` (so a configuration's date and measure
fields describe the same items), the part's brand and the supplier's
region from this kind's own stream ``[seed, shard, TAG]``. ``star``
gives a shard's raw columns under the paper's names, brand and region as
its strings, for a test that computes flight 2 as the paper writes it.

Columns (``column`` in a field's configuration), each key uniform:

- ``p_brand1``, 1,000 rows: ``MFGR#mcbb`` is row ``category x 40 + bb -
  1``, where ``category = (m - 1) x 5 + (c - 1)``;
- ``p_category``, 25 rows: ``MFGR#mc`` is row ``category``, the brand's
  row // 40;
- ``s_region``, 5 rows: dbgen's order, ``REGIONS``;
- ``lo_revenue``, an int field: ``lo_extendedprice x (100 -
  lo_discount) // 100``, 0..10,494,950, 24 bits.

A set field's ``shares`` are written out in the configuration (the
traffic generator ranks rows by them) and have to be the uniform ones.
"""

from __future__ import annotations

import functools

import numpy as np

from benchmark.kinds import _int, categorical, ssb_lineorder
from benchmark.kinds.ssb_lineorder import ShardwiseCodes, ShardwiseInts
from benchmark.reference import SHARD_WIDTH

TAG = 0x55B20  # the dimension keys' own stream, beside the line items' 0x55B10

BRANDS_PER_CATEGORY = 40
REGIONS = ("AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST")

SET_COLUMNS = {"p_brand1": 1000, "p_category": 25, "s_region": len(REGIONS)}
INT_COLUMNS = {
    "lo_revenue": (0, 50 * ssb_lineorder.UNIT_PRICE_CENTS[1]),  # discount 0: the whole price
}


def brand_name(row: int) -> str:
    category, bb = divmod(row, BRANDS_PER_CATEGORY)
    return f"{category_name(category)}{bb + 1:02d}"


def category_name(row: int) -> str:
    m, c = divmod(row, 5)
    return f"MFGR#{m + 1}{c + 1}"


def shares(column: str) -> list[float]:
    """The share of the line items in each row of a set column: what the
    configuration's ``shares`` have to say."""
    rows = SET_COLUMNS[column]
    return [1.0 / rows] * rows


def _revenue(items: dict) -> np.ndarray:
    price = items["quantity"].astype(np.int64) * items["unit_price"]
    return (price * (100 - items["discount"]) // 100).astype(np.int32)


@functools.lru_cache(maxsize=2)
def _columns(seed: int, shard: int) -> dict[str, np.ndarray]:
    """The brand of each line item's part, the region of its supplier and
    its revenue. Kept for the shard's other fields, which are built next."""
    rng = np.random.default_rng([seed, shard, TAG])
    brand = rng.integers(0, SET_COLUMNS["p_brand1"], size=SHARD_WIDTH, dtype=np.uint16)
    region = rng.integers(0, len(REGIONS), size=SHARD_WIDTH, dtype=np.uint8)
    return {
        "p_brand1": brand,
        "p_category": (brand // BRANDS_PER_CATEGORY).astype(np.uint8),
        "s_region": region,
        "lo_revenue": _revenue(ssb_lineorder._line_items(seed, shard)),
    }


def star(seed: int, shard: int) -> dict[str, np.ndarray]:
    """A shard's line items joined with date, part and supplier, under
    the paper's column names: ``ssb_lineorder.lineorder``'s columns, the
    brand, category and region as strings, ``lo_revenue`` in cents."""
    cols = _columns(seed, shard)
    brands = np.array([brand_name(b) for b in range(SET_COLUMNS["p_brand1"])])
    categories = np.array([category_name(c) for c in range(SET_COLUMNS["p_category"])])
    return {
        **ssb_lineorder.lineorder(seed, shard),
        "p_brand1": brands[cols["p_brand1"]],
        "p_category": categories[cols["p_category"]],
        "s_region": np.array(REGIONS)[cols["s_region"]],
        "lo_revenue": cols["lo_revenue"],
    }


def _check(cfg: dict) -> str:
    column = cfg["column"]
    if column in SET_COLUMNS:
        want = shares(column)
        if cfg.get("rows") != len(want) or not np.allclose(cfg.get("shares", ()), want, atol=1e-12):
            raise ValueError(f"field {cfg['name']}: rows and shares are not uniform over {column}")
    elif column in INT_COLUMNS:
        if (cfg.get("min"), cfg.get("max")) != INT_COLUMNS[column]:
            raise ValueError(f"field {cfg['name']}: {column} ranges over {INT_COLUMNS[column]}")
    else:
        raise ValueError(f"field {cfg['name']}: unknown star column {column!r}")
    return column


def generate(cfg: dict, rng: np.random.Generator, shard: int) -> dict:
    column = _check(cfg)
    seed, at, _ = rng.bit_generator.seed_seq.entropy
    if at != shard:
        raise ValueError(f"field {cfg['name']}: generator of shard {at} handed to shard {shard}")
    values = _columns(int(seed), shard)[column]
    return {"vals": values} if column in INT_COLUMNS else {"codes": values}


def fragments(cfg: dict, data: dict, shard: int):
    kind = _int if cfg["column"] in INT_COLUMNS else categorical
    return kind.fragments(cfg, data, shard)


def meta(cfg: dict):
    return _int.meta(cfg) if cfg["column"] in INT_COLUMNS else None


def reference(cfg: dict, stacked: dict):
    if cfg["column"] in INT_COLUMNS:
        return ShardwiseInts(stacked["vals"], None)
    return ShardwiseCodes(stacked["codes"], cfg["rows"])


def row_bits(cfg: dict, shards: int):
    if cfg["column"] in INT_COLUMNS:
        return _int.row_bits(cfg, shards)
    return [(1, s * SHARD_WIDTH) for s in shares(cfg["column"])]
