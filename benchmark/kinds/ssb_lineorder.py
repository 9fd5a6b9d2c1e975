"""Star Schema Benchmark ``lineorder`` joined with ``date`` and
denormalised: every field of this kind holds one ``column`` of the same
2^20 line items a shard. The line items are drawn from the seed and the
shard alone, so the six fields of a configuration agree: a month's row
implies its year's, ``lo_revenue_computed`` is ``lo_extendedprice`` x
``lo_discount`` of the same item.

The harness hands each field its own generator,
``default_rng([seed, shard, i])``; the kind reads seed and shard back
from it and draws the shard's line items from ``[seed, shard, TAG]``.
``lineorder`` gives a shard's raw columns, the way the benchmark's paper
names them, for a test that computes flight 1 as the paper writes it.

Columns (``column`` in a field's configuration): the set fields
``d_year`` (row = year - 1992), ``d_yearmonthnum`` (row = 12 x (year -
1992) + month - 1), ``d_weeknuminyear`` (row = week - 1; week = (day of
the year - 1) // 7 + 1); the int fields ``lo_discount``, ``lo_quantity``
and ``lo_revenue_computed``. A set field's ``shares`` are written out in
the configuration (the traffic generator ranks rows by them) and have
to be the calendar's own, ``shares``.
"""

from __future__ import annotations

import datetime
import functools

import numpy as np

from benchmark.kinds import _int, categorical
from benchmark.reference import SHARD_WIDTH, Codes, IntValues

TAG = 0x55B10  # the line items' own stream, beside the fields' 0..5

FIRST_DAY = datetime.date(1992, 1, 1)
LAST_DAY = datetime.date(1998, 8, 2)  # dbgen's last order date
DAYS = (LAST_DAY - FIRST_DAY).days + 1
UNIT_PRICE_CENTS = (90_000, 209_899)  # dbgen's retail-price range

SET_COLUMNS = {"d_year": 7, "d_yearmonthnum": 84, "d_weeknuminyear": 53}
INT_COLUMNS = {
    "lo_discount": (0, 10),
    "lo_quantity": (1, 50),
    "lo_revenue_computed": (0, 50 * UNIT_PRICE_CENTS[1] * 10),
}


@functools.cache
def calendar() -> dict[str, np.ndarray]:
    """For each order date, counted in days from ``FIRST_DAY``: its row
    in each set field, and the paper's own numbers for it."""
    days = [FIRST_DAY + datetime.timedelta(d) for d in range(DAYS)]
    year = np.array([d.year for d in days])
    month = np.array([d.month for d in days])
    week = np.array([(d.timetuple().tm_yday - 1) // 7 + 1 for d in days])
    return {
        "d_year": (year - 1992).astype(np.uint8),
        "d_yearmonthnum": (12 * (year - 1992) + month - 1).astype(np.uint8),
        "d_weeknuminyear": (week - 1).astype(np.uint8),
        "year": year,
        "yearmonthnum": year * 100 + month,
        "weeknuminyear": week,
        "datekey": np.array([d.year * 10000 + d.month * 100 + d.day for d in days]),
    }


def shares(column: str) -> list[float]:
    """The share of the order dates that falls in each row of a set
    column: what the configuration's ``shares`` have to say."""
    counts = np.bincount(calendar()[column], minlength=SET_COLUMNS[column])
    return (counts / DAYS).tolist()


@functools.lru_cache(maxsize=2)
def _line_items(seed: int, shard: int) -> dict[str, np.ndarray]:
    """Day, quantity, discount and unit price of a shard's line items.
    Kept for the shard's other fields, which are built next."""
    rng = np.random.default_rng([seed, shard, TAG])
    return {
        "day": rng.integers(0, DAYS, size=SHARD_WIDTH, dtype=np.int32),
        "quantity": rng.integers(1, 51, size=SHARD_WIDTH, dtype=np.int32),
        "discount": rng.integers(0, 11, size=SHARD_WIDTH, dtype=np.int32),
        "unit_price": rng.integers(
            UNIT_PRICE_CENTS[0], UNIT_PRICE_CENTS[1] + 1, size=SHARD_WIDTH, dtype=np.int32
        ),
    }


def lineorder(seed: int, shard: int) -> dict[str, np.ndarray]:
    """A shard's line items under the paper's column names (prices in
    cents), the date's attributes joined in."""
    items, cal = _line_items(seed, shard), calendar()
    day = items["day"]
    return {
        "lo_orderdate": cal["datekey"][day],
        "d_year": cal["year"][day],
        "d_yearmonthnum": cal["yearmonthnum"][day],
        "d_weeknuminyear": cal["weeknuminyear"][day],
        "lo_quantity": items["quantity"],
        "lo_discount": items["discount"],
        "lo_extendedprice": items["quantity"] * items["unit_price"],
    }


AT_ONCE = 4  # shards answered at a time: 4 MB temporaries (1 is 2.5 times slower in eight threads, 8 no faster)


def _spans(shards: int):
    return [slice(s, s + AT_ONCE) for s in range(0, shards, AT_ONCE)]


class ShardwiseCodes(Codes):
    """``Codes``, answering a few shards at a time. Over 58 shards at
    once a row is a 60 MB temporary and a flight-1 request half a
    gigabyte of them; the 462 requests of a window's comparison then
    allocate and free 230 GB in eight threads, and the machine that
    holds the chip does not get the freed pages back as fast: its memory
    in use grew by 1.5 GB a second to the 40 GiB limit while the process
    stayed at 2 GB (my chip run, PR 33)."""

    def row(self, r: int) -> np.ndarray:
        return np.concatenate([Codes(self.codes[at], self.n_rows).row(r) for at in _spans(len(self.codes))])


class ShardwiseInts(IntValues):
    """``IntValues`` with every column present, a few shards at a time,
    for the same reason."""

    def compare(self, op: str, *args) -> np.ndarray:
        return np.concatenate([IntValues(self.vals[at], None).compare(op, *args) for at in _spans(len(self.vals))])

    def sum(self, src) -> dict:
        parts = [
            IntValues(self.vals[at], None).sum(None if src is None else src[at])
            for at in _spans(len(self.vals))
        ]
        return {"value": sum(p["value"] for p in parts), "count": sum(p["count"] for p in parts)}


def _check(cfg: dict) -> str:
    column = cfg["column"]
    if column in SET_COLUMNS:
        want = shares(column)
        if cfg.get("rows") != len(want) or not np.allclose(cfg.get("shares", ()), want, atol=1e-9):
            raise ValueError(f"field {cfg['name']}: rows and shares are not the calendar's for {column}")
    elif column in INT_COLUMNS:
        if (cfg.get("min"), cfg.get("max")) != INT_COLUMNS[column]:
            raise ValueError(f"field {cfg['name']}: {column} ranges over {INT_COLUMNS[column]}")
    else:
        raise ValueError(f"field {cfg['name']}: unknown lineorder column {column!r}")
    return column


def generate(cfg: dict, rng: np.random.Generator, shard: int) -> dict:
    column = _check(cfg)
    seed, at, _ = rng.bit_generator.seed_seq.entropy
    if at != shard:
        raise ValueError(f"field {cfg['name']}: generator of shard {at} handed to shard {shard}")
    items = _line_items(int(seed), shard)
    if column in SET_COLUMNS:
        return {"codes": calendar()[column][items["day"]]}
    if column == "lo_revenue_computed":
        return {"vals": items["quantity"] * items["unit_price"] * items["discount"]}
    return {"vals": items[column.removeprefix("lo_")]}


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
    kind = _int if cfg["column"] in INT_COLUMNS else categorical
    return kind.row_bits(cfg, shards)
