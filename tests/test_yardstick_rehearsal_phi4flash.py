"""Tier-1 collects benchmark/tests/test_rehearsal_phi4flash.py (ROADMAP D9):
the decoder-hybrid-decoder cell's CPU rehearsal, its planted faults and its
readers on a rehearsal trace.  The tests are the yardstick's own; nothing is
defined here."""

import pytest

pytest.register_assert_rewrite("benchmark.tests.test_rehearsal_phi4flash")
from benchmark.tests.test_rehearsal_phi4flash import *  # noqa: E402,F401,F403
