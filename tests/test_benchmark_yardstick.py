"""The benchmark's own arithmetic, guarded in tier-1: the trace
reduction, the roofline's bytes, the deck, the comparison and the
control (benchmark/tests/test_yardstick.py, which nothing else runs).
The whole rehearsal runs through a served child stay in
benchmark/tests/test_served.py, by hand."""

import pytest

pytest.register_assert_rewrite("benchmark.tests.test_yardstick")

from benchmark.tests.test_yardstick import *  # noqa: E402,F401,F403
