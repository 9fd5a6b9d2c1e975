"""The control: the plain reference put in the program's place with one
stated guarantee broken. Both configurations state that answers are
exact; the control answers from a replica that is stale by the last
shard's writes, which is what a cached or sampled answer would be. It
has to come out as not correct: every answer it gives is compared as a
served answer is.

    python3 benchmark/tests/control.py --workload <cell> --seed <n> --seconds <s>

runs the cell as ``benchmark/run.py`` does (on the chip, at the cell's
own size) and then reads the control on the same data, printing
``{"phase": "control", "wrong_answers": k, "of": n}`` before the result.
"""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference import (  # noqa: E402
    Codes, IntValues, PackedRows, Reference, Undecidable, same_answer,
)


def stale(ref: Reference) -> Reference:
    """The same data without its last shard."""
    fields = {}
    for name, f in ref.fields.items():
        if isinstance(f, PackedRows):
            fields[name] = PackedRows(f.rows[:-1], f.tail_max)
        elif isinstance(f, Codes):
            fields[name] = Codes(f.codes[:-1], f.n_rows)
        else:
            fields[name] = IntValues(f.vals[:-1], None if f.exists is None else f.exists[:-1])
    return Reference(fields)


def wrong_answers(ref: Reference, calls: list, expected: list) -> tuple[int, int]:
    """(answers of the control that differ from the reference's, answers compared)."""
    control = stale(ref)
    wrong = compared = 0
    for call, want in zip(calls, expected):
        if isinstance(want, Undecidable):
            continue
        compared += 1
        try:
            got = control.answer(call)
        except Undecidable:
            wrong += 1  # it gives no answer: failed
            continue
        wrong += not same_answer(call, got, want)
    return wrong, compared


def main() -> int:
    from benchmark import run

    def read(ref, calls, expected):
        wrong, of = wrong_answers(ref, calls, expected)
        run.emit("control", wrong_answers=wrong, of=of, limit=0)

    return run.main(after_compare=read)


if __name__ == "__main__":
    sys.exit(main())
