"""Tier-1 collects benchmark/tests/test_rehearsal_mla.py (ROADMAP D9): the new
cell's comparison has to turn ``correct`` false under each planted
fault.  The tests are the yardstick's own; nothing is defined here."""

import pytest

pytest.register_assert_rewrite("benchmark.tests.test_rehearsal_mla")
from benchmark.tests.test_rehearsal_mla import *  # noqa: E402,F401,F403
