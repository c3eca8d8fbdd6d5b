"""Tier-1 collects benchmark/tests/test_manifest_phi4flash.py (ROADMAP D9):
the decoder-hybrid-decoder cell's manifest entries, readers and hand
counts.  The tests are the yardstick's own; nothing is defined here."""

import pytest

pytest.register_assert_rewrite("benchmark.tests.test_manifest_phi4flash")
from benchmark.tests.test_manifest_phi4flash import *  # noqa: E402,F401,F403
