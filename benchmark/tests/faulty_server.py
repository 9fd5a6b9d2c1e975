"""The program with one fault planted where an answer is produced:
``python -m benchmark.tests.faulty_server server ...`` is ``python -m
pilosa_tpu server ...`` whose every third TopN reports its first row
one too high, and whose every third non-empty GroupBy, fused or not,
reports its first group's count one too high. For ``test_served.py``
and ``tests/test_bench_mesh_cell.py`` only."""

import itertools
import sys

from pilosa_tpu.cli.main import main
from pilosa_tpu.executor.executor import Executor

_calls = itertools.count(1)
_groupbys = itertools.count(1)
_sound = Executor._execute_topn
_sound_execute = Executor.execute


def _altered(self, *args, **kwargs):
    pairs = _sound(self, *args, **kwargs)
    if pairs and next(_calls) % 3 == 0:
        pairs = [dict(pairs[0], count=pairs[0]["count"] + 1)] + list(pairs[1:])
    return pairs


def _altered_groupbys(self, *args, **kwargs):
    return [
        [dict(r[0], count=r[0]["count"] + 1)] + list(r[1:])
        if isinstance(r, list) and r and isinstance(r[0], dict) and "group" in r[0] and next(_groupbys) % 3 == 0
        else r
        for r in _sound_execute(self, *args, **kwargs)
    ]


if __name__ == "__main__":
    Executor._execute_topn = _altered
    Executor.execute = _altered_groupbys
    sys.exit(main())
