"""Tier-1 collects benchmark/tests/test_rehearsal_ssm.py (ROADMAP D9): the new
cell's files and its comparison are the yardstick's own.  The tests are
the yardstick's own; nothing is defined here."""

import pytest

pytest.register_assert_rewrite("benchmark.tests.test_rehearsal_ssm")
from benchmark.tests.test_rehearsal_ssm import *  # noqa: E402,F401,F403
