"""Tier-1 collects benchmark/tests/test_spans.py (ROADMAP D9): every PR is
judged by the yardstick's readers, so a PR that breaks one turns the
gate red.  The tests are the yardstick's own; nothing is defined here."""

import pytest

pytest.register_assert_rewrite("benchmark.tests.test_spans")
from benchmark.tests.test_spans import *  # noqa: E402,F401,F403

# It asserts that every PR 25 metric lists the image cell alone; PR 28
# added the token cell to those lists.  Strict, so the repair shows up.
test_the_nine_entries_load_and_read = pytest.mark.xfail(
    strict=True,
    reason="stale since PR 28: a `benchmark` PR repairs it")(
        test_the_nine_entries_load_and_read)  # noqa: F405
