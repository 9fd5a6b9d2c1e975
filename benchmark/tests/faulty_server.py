"""The program with one fault planted where an answer is produced:
``python -m benchmark.tests.faulty_server server ...`` is ``python -m
pilosa_tpu server ...`` whose every third TopN reports its first row
one too high. For ``test_faults.py`` only."""

import itertools
import sys

from pilosa_tpu.cli.main import main
from pilosa_tpu.executor.executor import Executor

_calls = itertools.count(1)
_sound = Executor._execute_topn


def _altered(self, *args, **kwargs):
    pairs = _sound(self, *args, **kwargs)
    if pairs and next(_calls) % 3 == 0:
        pairs = [dict(pairs[0], count=pairs[0]["count"] + 1)] + list(pairs[1:])
    return pairs


if __name__ == "__main__":
    Executor._execute_topn = _altered
    sys.exit(main())
