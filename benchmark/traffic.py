"""One general traffic generator. A traffic file
(``benchmark/traffic/<name>.json``) holds parameters only:

- ``loop``: ``closed`` (``clients`` callers, each sending its next
  request when the last is answered). ``open`` belongs to the PR that
  brings the first open-loop cell and is refused until then.
- ``clients``, ``cache`` (false: every request carries ``cache=false``),
  ``deck`` (how many requests one whole pass of the mix holds).
- ``mix``: weighted templates. A template's ``call`` is a reference
  expression (``benchmark/reference.py``) in which a string ``"$x"``
  stands for a value drawn as ``draw["x"]`` says: ``field``; ``from``
  ``rows`` (every row of a categorical field) or ``group_base`` (the
  first row of each group of a ``hot_groups_with_tail`` field); ``dist``
  ``uniform`` or ``zipf`` with exponent ``s`` over the rows ranked by
  share, largest first.

Every seed gets the same work in another order: the mix is dealt into a
deck of ``deck`` requests (each distinct request at least once, the rest
by largest remainder), and the seed only shuffles the deck, again for
each pass. The program sees only PQL over HTTP.
"""

from __future__ import annotations

import itertools
import json

import numpy as np

from benchmark.datagen import field_of


def load(path: str) -> dict:
    with open(path) as f:
        t = json.load(f)
    if t.get("loop") != "closed":
        raise ValueError(f"{path}: loop {t.get('loop')!r} is not implemented (closed only)")
    if not t.get("mix") or t.get("clients", 0) < 1:
        raise ValueError(f"{path}: needs clients >= 1 and a mix")
    return t


def _values(config: dict, draw: dict) -> list[tuple[int, float]]:
    """[(row id, probability)] for one placeholder."""
    f = field_of(config, draw["field"])
    if draw["from"] == "group_base":
        rows = list(range(0, f["hot_rows"], f["group"]))
    elif draw["from"] == "rows":
        shares = f["shares"]
        rows = sorted(range(f["rows"]), key=lambda r: (-shares[r], r))
    else:
        raise ValueError(f"draw from {draw['from']!r}")
    if draw["dist"] == "uniform":
        p = np.ones(len(rows))
    elif draw["dist"] == "zipf":
        p = np.arange(1, len(rows) + 1, dtype=np.float64) ** -float(draw["s"])
    else:
        raise ValueError(f"draw dist {draw['dist']!r}")
    return list(zip(rows, (p / p.sum()).tolist()))


def _substitute(e, values: dict):
    if isinstance(e, str) and e.startswith("$"):
        return values[e[1:]]
    if isinstance(e, list):
        return [_substitute(c, values) for c in e]
    return e


def by_template(config: dict, traffic: dict) -> list[list[tuple[list, float]]]:
    """For each template of the mix, its requests with their probabilities."""
    total = sum(t["weight"] for t in traffic["mix"])
    out = []
    for t in traffic["mix"]:
        names = sorted(t.get("draw", {}))
        axes = [_values(config, t["draw"][n]) for n in names]
        out.append([
            (
                _substitute(t["call"], {n: r for n, (r, _) in zip(names, combo)}),
                t["weight"] / total * float(np.prod([q for _, q in combo])),
            )
            for combo in itertools.product(*axes)
        ])
    return out


def pool(config: dict, traffic: dict) -> list[tuple[list, float]]:
    """Every distinct request of the mix with its probability."""
    out: dict[str, tuple[list, float]] = {}
    for requests in by_template(config, traffic):
        for call, p in requests:
            key = json.dumps(call)
            out[key] = (call, out.get(key, (call, 0.0))[1] + p)
    return list(out.values())


def pairs(config: dict, traffic: dict) -> list[str]:
    """One two-call query for every ordered pair of templates, the two
    calls distinct: the shapes a wave of two concurrent requests takes
    (the program fuses a wave as it fuses a multi-call query)."""
    firsts = [[c for c, _ in requests[:2]] for requests in by_template(config, traffic)]
    out = []
    for a in firsts:
        for b in firsts:
            second = next((c for c in b if c != a[0]), None)
            if second is not None:
                out.append(pql(a[0]) + pql(second))
    return out


def deck(requests: list[tuple[list, float]], size: int) -> list[int]:
    """Request indices, ``size`` of them: one each, then the rest by
    largest remainder of the probabilities."""
    n = len(requests)
    if size < n:
        raise ValueError(f"a deck of {size} cannot hold {n} distinct requests")
    p = np.array([q for _, q in requests])
    want = p * size
    counts = np.maximum(1, np.floor(want)).astype(int)
    while counts.sum() > size:  # the forced ones took too many
        counts[np.argmax(counts - want)] -= 1
    short = size - counts.sum()
    for i in np.argsort(-(want - counts), kind="stable")[:short]:
        counts[i] += 1
    return [i for i in range(n) for _ in range(counts[i])]


def schedule(deck_: list[int], seed: int):
    """An endless sequence of request indices: the deck shuffled by the
    seed, pass after pass."""
    rng = np.random.default_rng([seed, 0x7AFF1C])
    cards = np.array(deck_)
    while True:
        yield from rng.permutation(cards).tolist()


# -- PQL ----------------------------------------------------------------------


def _bitmap(e) -> str:
    tag = e[0]
    if tag == "Row":
        return f"Row({e[1]}={e[2]})"
    if tag == "Range":
        if e[2] == "><":
            return f"Range({e[1]} >< [{e[3]}, {e[4]}])"
        return f"Range({e[1]} {e[2]} {e[3]})"
    return f"{tag}({', '.join(_bitmap(c) for c in e[1:])})"


def _rows(dim) -> str:
    if len(dim) > 2:
        return f"Rows({dim[1]}, ids=[{', '.join(str(r) for r in dim[2])}])"
    return f"Rows({dim[1]})"


def pql(call) -> str:
    """A call in PQL, children first and named arguments last
    (``docs/query-language.md``)."""
    tag = call[0]
    if tag == "Count":
        return f"Count({_bitmap(call[1])})"
    if tag == "Sum":
        if call[2] is None:
            return f"Sum(field={call[1]})"
        return f"Sum({_bitmap(call[2])}, field={call[1]})"
    if tag == "TopN":
        parts = [call[1]]
        if call[2] is not None:
            parts.append(_bitmap(call[2]))
        if call[3].get("n"):
            parts.append(f"n={call[3]['n']}")
        return f"TopN({', '.join(parts)})"
    if tag == "GroupBy":
        parts = [_rows(d) for d in call[1]]
        if call[2] is not None:
            parts.append(_bitmap(call[2]))
        if call[3].get("sum"):
            parts.append(f"Sum(field={call[3]['sum']})")
        if call[3].get("limit"):
            parts.append(f"limit={call[3]['limit']}")
        return f"GroupBy({', '.join(parts)})"
    return _bitmap(call)
