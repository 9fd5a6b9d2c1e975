"""The plain reference: numpy over the generated data. Imports nothing
of the program and takes nothing the program made.

Expressions are JSON lists, in ``chip_smoke.py``'s tuple form with field
names: ``["Row", field, r]``, ``["Range", field, op, x]`` (``op`` one of
``> >= < <= == !=``) or ``["Range", field, "><", lo, hi]``, and
``["Intersect" | "Union" | "Difference" | "Xor", e1, e2, ...]``. Calls
are ``["Count", e]``, ``["Sum", field, e | null]``,
``["TopN", field, e | null, {"n": k}]``,
``["GroupBy", [dim, ...], e | null, {"sum": field?, "limit": k?}]`` with
``dim`` ``["Rows", field]`` or ``["Rows", field, [ids...]]``, and a bare
bitmap expression.

A bitmap is ``u64[S, WORDS64]``, packed little-endian as the program's
shards are. Each field's data sits in one of three small classes, which
a data ``kind`` (``benchmark/kinds/``) fills from what it generated.
"""

from __future__ import annotations

import itertools
import operator

import numpy as np

SHARD_WIDTH = 1 << 20
WORDS64 = SHARD_WIDTH // 64
# shards a GroupBy over code fields reads at a time: whole-array
# temporaries of many shards in the comparison's eight threads ran the
# chip's machine out of memory once (ssb_lineorder.ShardwiseCodes)
GROUP_SPAN = 8

BITMAP_OPS = ("Intersect", "Union", "Difference", "Xor")
_COMPARE = {
    ">": operator.gt, ">=": operator.ge, "<": operator.lt,
    "<=": operator.le, "==": operator.eq, "!=": operator.ne,
}


def unpack(words: np.ndarray) -> np.ndarray:
    """u64[..., WORDS64] -> bool[..., SHARD_WIDTH]."""
    return np.unpackbits(
        np.ascontiguousarray(words).view(np.uint8), axis=-1, bitorder="little"
    ).astype(bool)


def pack(mask: np.ndarray) -> np.ndarray:
    return np.packbits(mask, axis=-1, bitorder="little").view("<u8")


class PackedRows:
    """A set field kept as packed rows: ``rows`` is u64[S, R, WORDS64]
    for row ids 0..R-1. ``tail_max`` is the most bits any further row of
    the field holds in all (rows the reference does not keep): a TopN
    whose cut does not clear it cannot be decided and raises."""

    def __init__(self, rows: np.ndarray, tail_max: int = 0) -> None:
        self.rows = rows
        self.tail_max = tail_max

    def row(self, r: int) -> np.ndarray:
        return self.rows[:, r, :]

    def counts(self, src) -> np.ndarray:
        """Bits each kept row shares with ``src`` (None: all its bits)."""
        return np.array([
            int(np.bitwise_count(self.row(r) if src is None else self.row(r) & src).sum())
            for r in range(self.rows.shape[1])
        ])


class Codes:
    """A set field in which every column holds exactly one row:
    ``codes`` is u8[S, W], the row id of each column."""

    tail_max = 0

    def __init__(self, codes: np.ndarray, n_rows: int) -> None:
        self.codes = codes
        self.n_rows = n_rows

    def row(self, r: int) -> np.ndarray:
        return pack(self.codes == r)

    def counts(self, src) -> np.ndarray:
        picked = self.codes.ravel() if src is None else self.codes[unpack(src)]
        return np.bincount(picked, minlength=self.n_rows).astype(np.int64)


class IntValues:
    """An int field: ``vals`` is i32[S, W]; ``exists`` bool[S, W], or
    None where every column holds a value."""

    def __init__(self, vals: np.ndarray, exists) -> None:
        self.vals = vals
        self.exists = exists

    def compare(self, op: str, *args) -> np.ndarray:
        if op == "><":
            m = (self.vals >= args[0]) & (self.vals <= args[1])
        else:
            m = _COMPARE[op](self.vals, args[0])
        return pack(m if self.exists is None else m & self.exists)

    def sum(self, src) -> dict:
        if src is None:
            m = np.ones(self.vals.shape, bool) if self.exists is None else self.exists
        else:
            m = unpack(src)
            if self.exists is not None:
                m &= self.exists
        return {"value": int(self.vals[m].sum(dtype=np.int64)), "count": int(m.sum())}


class Undecidable(Exception):
    """The reference cannot say what the exact answer is."""


class Reference:
    """Answers calls by field name over ``fields``: name -> PackedRows |
    Codes | IntValues."""

    def __init__(self, fields: dict) -> None:
        self.fields = fields

    def words(self, e) -> np.ndarray:
        tag = e[0]
        if tag == "Row":
            return self.fields[e[1]].row(e[2])
        if tag == "Range":
            return self.fields[e[1]].compare(e[2], *e[3:])
        if tag not in BITMAP_OPS:
            raise ValueError(f"unknown bitmap expression {tag!r}")
        acc = self.words(e[1])
        for c in e[2:]:
            w = self.words(c)
            if tag == "Intersect":
                acc = acc & w
            elif tag == "Union":
                acc = acc | w
            elif tag == "Xor":
                acc = acc ^ w
            else:
                acc = acc & ~w
        return acc

    def answer(self, call):
        """The value the server's ``results`` list holds for one call."""
        tag = call[0]
        if tag == "Count":
            return int(np.bitwise_count(self.words(call[1])).sum())
        if tag == "Sum":
            src = None if call[2] is None else self.words(call[2])
            return self.fields[call[1]].sum(src)
        if tag == "TopN":
            return self.topn(call[1], call[2], call[3].get("n", 0))
        if tag == "GroupBy":
            return self.groupby(call[1], call[2], call[3])
        mask = unpack(self.words(call))
        shard, col = np.nonzero(mask)
        cols = (shard.astype(np.int64) * SHARD_WIDTH + col).tolist()
        return {"attrs": {}, "columns": cols}

    def topn(self, field: str, e, n: int) -> list[dict]:
        f = self.fields[field]
        counts = f.counts(None if e is None else self.words(e))
        order = np.lexsort((np.arange(counts.size), -counts))
        order = order[counts[order] > 0]
        if n and order.size > n:
            if counts[order[n - 1]] == counts[order[n]]:
                raise Undecidable(f"TopN({field}, n={n}) has a tie at the cut")
            order = order[:n]
        elif f.tail_max:
            raise Undecidable(f"TopN({field}) reaches the rows the reference does not keep")
        if f.tail_max and counts[order[-1]] <= f.tail_max:
            raise Undecidable(f"TopN({field}, n={n}): the cut does not clear the tail rows")
        return [{"id": int(r), "count": int(counts[r])} for r in order]

    def groupby(self, dims: list, e, opts: dict) -> list[dict]:
        """GroupBy as ``executor/analytics.py`` defines it (its module
        docstring, ``finalize_groups``, ``emit_device_groups``): groups in
        cross-product order, the first ``Rows`` slowest; a dimension's
        rows are its ``ids`` in their order, or else every row of the
        field that holds a bit, ascending; a group's ``count`` is its
        columns, those with no value of the ``sum`` field among them, and
        its ``sum`` the total of the values it holds; groups of count 0
        are dropped, then the first ``limit`` kept."""
        fields = [self.fields[d[1]] for d in dims]
        for d, f in zip(dims, fields):
            if isinstance(f, PackedRows):  # no cell groups by one yet: add nested ANDs with the first
                raise Undecidable(f"GroupBy: Rows({d[1]}) is packed rows, which the reference does not group")
            if not isinstance(f, Codes):
                raise ValueError(f"GroupBy: Rows({d[1]}) is not a set field")
        rows = [list(d[2]) if len(d) > 2 else np.flatnonzero(f.counts(None)).tolist() for d, f in zip(dims, fields)]
        src = None if e is None else self.words(e)
        vals = self.fields[opts["sum"]] if opts.get("sum") else None
        counts, sums = _codes_groups(fields, rows, src, vals)
        out = []
        for k, key in enumerate(itertools.product(*rows)):
            if counts[k]:
                group = {"group": [{"field": d[1], "rowID": int(r)} for d, r in zip(dims, key)],
                         "count": int(counts[k])}
                if vals is not None:
                    group["sum"] = int(sums[k])
                out.append(group)
        return out[: opts["limit"]] if opts.get("limit") else out


def _codes_groups(fields: list, rows: list, src, vals) -> tuple[np.ndarray, np.ndarray]:
    """Every dimension a ``Codes`` field: each column's group is one
    number, ``(p0 * n1 + p1) * n2 + p2`` of its rows' places in the
    dimensions' lists, and the counts and sums one ``bincount`` and one
    ``add.at`` over the filter's columns, ``GROUP_SPAN`` shards a step."""
    sizes = [len(r) for r in rows]
    k = int(np.prod(sizes))
    luts = []
    for f, ids in zip(fields, rows):
        lut = np.full(f.n_rows, -1, np.int64)  # a row the field does not have holds no column
        for i, r in enumerate(ids):
            if r < f.n_rows:
                lut[r] = i
        luts.append(lut)
    counts, sums = np.zeros(k, np.int64), np.zeros(k, np.int64)
    for a in range(0, len(fields[0].codes), GROUP_SPAN):
        at = slice(a, a + GROUP_SPAN)
        m = None if src is None else unpack(src[at])

        def cols(x):
            return x[at].ravel() if m is None else x[at][m]

        key, ok = 0, True
        for f, lut, n in zip(fields, luts, sizes):
            pos = lut[cols(f.codes)]
            key, ok = key * n + pos, ok & (pos >= 0)
        counts += np.bincount(key[ok], minlength=k)
        if vals is not None:
            held = ok if vals.exists is None else ok & cols(vals.exists)
            np.add.at(sums, key[held], cols(vals.vals)[held].astype(np.int64))
    return counts, sums


def same_answer(call, got, want) -> bool:
    """Exact equality (a GroupBy's order is defined), but for a TopN:
    there the pairs must be the reference's as a set wherever counts are
    equal (order among equal counts is not the answer's to fix), and the
    counts non-increasing."""
    if call[0] != "TopN":
        return got == want
    if not isinstance(got, list) or len(got) != len(want):
        return False
    try:
        pairs = [(int(p["count"]), int(p["id"])) for p in got]
    except (KeyError, TypeError, ValueError):
        return False
    if any(a[0] < b[0] for a, b in zip(pairs, pairs[1:])):
        return False
    return sorted(pairs) == sorted((p["count"], p["id"]) for p in want)
