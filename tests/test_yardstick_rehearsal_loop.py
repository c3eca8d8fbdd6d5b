"""Tier-1 collects benchmark/tests/test_rehearsal_loop.py (ROADMAP D9): the
looped cell's CPU rehearsal and its planted faults.  The tests are the
yardstick's own; nothing is defined here."""

import pytest

pytest.register_assert_rewrite("benchmark.tests.test_rehearsal_loop")
from benchmark.tests.test_rehearsal_loop import *  # noqa: E402,F401,F403
