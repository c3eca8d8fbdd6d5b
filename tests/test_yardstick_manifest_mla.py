"""Tier-1 collects benchmark/tests/test_manifest_mla.py (ROADMAP D9): every PR is
judged by the yardstick's readers, so a PR that breaks one turns the
gate red.  The tests are the yardstick's own; nothing is defined here."""

import pytest

pytest.register_assert_rewrite("benchmark.tests.test_manifest_mla")
from benchmark.tests.test_manifest_mla import *  # noqa: E402,F401,F403
