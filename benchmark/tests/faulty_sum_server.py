"""The program with one fault planted where a ``Sum`` is answered:
``python -m benchmark.tests.faulty_sum_server server ...`` is ``python
-m pilosa_tpu server ...`` whose every third non-empty ``Sum``, lone or
fused into a wave, reports a value one too high. For
``tests/test_bench_ssb_cell.py`` only."""

import itertools
import sys

from pilosa_tpu.cli.main import main
from pilosa_tpu.executor.executor import Executor, ValCount

_sums = itertools.count(1)
_sound = Executor.execute


def _altered(self, *args, **kwargs):
    return [
        ValCount(r.val + 1, r.count)
        if isinstance(r, ValCount) and r.count and next(_sums) % 3 == 0
        else r
        for r in _sound(self, *args, **kwargs)
    ]


if __name__ == "__main__":
    Executor.execute = _altered
    sys.exit(main())
