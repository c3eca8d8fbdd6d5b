"""Tier-1 collects benchmark/tests/test_rehearsal_hybrid.py (ROADMAP D9): the
hybrid cell's CPU rehearsal and its planted faults.  The tests are the
yardstick's own; nothing is defined here."""

import pytest

pytest.register_assert_rewrite("benchmark.tests.test_rehearsal_hybrid")
from benchmark.tests.test_rehearsal_hybrid import *  # noqa: E402,F401,F403
