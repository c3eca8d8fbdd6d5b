"""tools/dsodlint.py — the AST invariant linter (docs/STATIC_ANALYSIS.md).

Per checker: one deliberate violation in a synthetic tree fires it
(true positive) and the clean skeleton stays silent (true negative).
Plus the waiver pragma contract (reason required), the baseline
discipline (seed / compare / --fail-on-new exit 2 / never seed from a
crashed run), and the gate the t1 leg runs: the REAL repo at HEAD
lints clean against the checked-in baseline.
"""

from __future__ import annotations

import json
import os
import sys
import textwrap

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))

import dsodlint  # noqa: E402


# -- fixture tree ------------------------------------------------------

def _write(root, rel, text):
    p = os.path.join(root, rel)
    os.makedirs(os.path.dirname(p), exist_ok=True)
    with open(p, "w") as f:
        f.write(textwrap.dedent(text))


def make_clean_tree(root):
    """A minimal repo skeleton that exercises every checker's
    true-NEGATIVE: a pure jitted step, a correctly-locked thread
    class, a registered env read, a fully-constructible inventory, and
    a terminal counter inside its declared seam."""
    _write(root, "distributed_sod_project_tpu/utils/envvars.py", '''
        class EnvVar:
            def __init__(self, *a):
                pass

        _ENTRIES = (
            EnvVar("DSOD_KNOB", None, True, "a program knob", "x.py"),
            EnvVar("DSOD_HOSTY", "d", False, "a host knob", "y.py"),
        )

        def read(name, env=None):
            import os

            return os.environ.get(name)
    ''')
    _write(root, "tools/metrics_inventory.json", json.dumps({
        "fleet": {"dsod_serve_ok_total": "counter",
                  "dsod_serve_dyn_total": "counter"}}))
    # traced-purity TN: pure step through a helper, jitted.
    _write(root, "distributed_sod_project_tpu/train/good_step.py", '''
        import jax
        import jax.numpy as jnp

        def helper(x):
            return jnp.tanh(x)

        def step_fn(state, batch):
            return state + helper(batch)

        step = jax.jit(step_fn)
    ''')
    # lock-discipline TN: cross-thread write, correctly guarded; plus
    # the *_locked caller-holds-the-lock convention.
    _write(root, "distributed_sod_project_tpu/serve/good_lock.py", '''
        import threading

        class Gauge:
            def __init__(self):
                self._lock = threading.Lock()
                self._n = 0
                self._t = None

            def start(self):
                self._t = threading.Thread(target=self._loop)

            def _loop(self):
                with self._lock:
                    self._bump_locked()

            def _bump_locked(self):
                self._n += 1

            def bump(self):
                with self._lock:
                    self._n += 1
    ''')
    # env TN (registered, via the accessor) + metrics TN: the exact
    # literal and a declared prefix that constructs the dyn family.
    _write(root, "distributed_sod_project_tpu/serve/good_env.py", '''
        from ..utils import envvars

        FAM = "dsod_serve_ok_total"

        def dyn(kind):
            return "dsod_serve_" + kind + "_total"

        def knob():
            return envvars.read("DSOD_KNOB")
    ''')
    # accounting TN: a terminal counter inside its declared seam.
    _write(root, "distributed_sod_project_tpu/serve/engine.py", '''
        class InferenceEngine:
            def _finish(self):
                self.stats.inc("served")
    ''')


def run_lint(root, *args, baseline=None):
    """dsodlint.main() in-process → (rc, parsed summary line)."""
    baseline = baseline or os.path.join(root, "baseline.json")
    import io
    from contextlib import redirect_stdout

    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = dsodlint.main(["--root", root, "--baseline", baseline,
                            *args])
    lines = [ln for ln in buf.getvalue().splitlines() if ln.strip()]
    summary = json.loads(lines[-1])
    return rc, summary, lines


@pytest.fixture()
def clean_root(tmp_path):
    root = str(tmp_path / "repo")
    make_clean_tree(root)
    return root


# -- clean tree: every checker's true negative -------------------------

def test_clean_tree_lints_clean_and_seeds_empty_baseline(clean_root):
    rc, summary, _ = run_lint(clean_root)
    assert rc == 0
    assert summary["findings"] == 0 and summary["waived"] == 0
    with open(os.path.join(clean_root, "baseline.json")) as f:
        assert json.load(f)["findings"] == []
    # and the gate agrees
    rc, summary, _ = run_lint(clean_root, "--fail-on-new")
    assert rc == 0 and summary["new"] == []


# -- per-checker true positives ----------------------------------------

def _keys(summary):
    return "\n".join(summary["new"])


def test_traced_purity_fires_through_the_call_graph(clean_root):
    """print/float/np.asarray in a HELPER reachable from a jitted
    step_fn — the violation is not at the root, proving the call-graph
    walk."""
    _write(clean_root, "distributed_sod_project_tpu/train/bad_step.py", '''
        import jax
        import numpy as np

        def helper(x):
            print("dbg")
            return float(np.asarray(x))

        def step_fn(state, batch):
            return helper(batch)

        step = jax.jit(step_fn)
    ''')
    rc, summary, _ = run_lint(clean_root, "--fail-on-new")
    assert rc == 2
    assert "traced-purity" in _keys(summary)
    assert "helper" in _keys(summary)
    assert "print()" in _keys(summary) and "np.asarray" in _keys(summary)


def test_traced_purity_env_read_in_traced_code(clean_root):
    _write(clean_root, "distributed_sod_project_tpu/train/bad_env_step.py",
           '''
        import jax
        from ..utils import envvars

        def step_fn(state, batch):
            if envvars.read("DSOD_KNOB"):
                return state
            return batch

        step = jax.jit(step_fn)
    ''')
    rc, summary, _ = run_lint(clean_root, "--fail-on-new")
    assert rc == 2
    assert "environment read" in _keys(summary)


def test_lock_discipline_cross_thread_unguarded_write(clean_root):
    _write(clean_root, "distributed_sod_project_tpu/serve/bad_lock.py", '''
        import threading

        class Gauge:
            def __init__(self):
                self._lock = threading.Lock()
                self._n = 0
                self._t = None

            def start(self):
                self._t = threading.Thread(target=self._loop)

            def _loop(self):
                self._n += 1

            def bump(self):
                with self._lock:
                    self._n += 1
    ''')
    rc, summary, _ = run_lint(clean_root, "--fail-on-new")
    assert rc == 2
    assert "lock-discipline" in _keys(summary)
    assert "self._n" in _keys(summary)
    # the correctly-guarded sibling stayed silent
    assert "good_lock" not in _keys(summary)


def test_lock_discipline_mixed_guard_rule(clean_root):
    """An attr written under the lock in one method and bare in
    another fires even without a visible thread entry — the PR-7
    check-then-put class."""
    _write(clean_root, "distributed_sod_project_tpu/utils/bad_mixed.py", '''
        import threading

        class Book:
            def __init__(self):
                self._lock = threading.Lock()
                self._total = 0

            def add(self, n):
                with self._lock:
                    self._total += n

            def reset(self):
                self._total = 0
    ''')
    rc, summary, _ = run_lint(clean_root, "--fail-on-new")
    assert rc == 2
    assert "self._total" in _keys(summary)
    # classified under the mixed-guard rule, at the bare write site
    assert "Book.reset" in _keys(summary)


def test_env_coherence_direct_read_and_unregistered(clean_root):
    _write(clean_root, "distributed_sod_project_tpu/serve/bad_env.py", '''
        import os

        def f():
            return os.environ.get("DSOD_SNEAKY")
    ''')
    rc, summary, _ = run_lint(clean_root, "--fail-on-new")
    assert rc == 2
    keys = _keys(summary)
    assert "bypass:DSOD_SNEAKY" in keys  # direct read, outside envvars.py
    assert "unregistered:DSOD_SNEAKY" in keys  # and the name is unknown


def test_metrics_coherence_both_directions(clean_root):
    # a literal the inventory doesn't know
    _write(clean_root, "distributed_sod_project_tpu/serve/bad_metric.py",
           '''
        FAM = "dsod_serve_bogus_total"
    ''')
    rc, summary, _ = run_lint(clean_root, "--fail-on-new")
    assert rc == 2 and "dsod_serve_bogus_total" in _keys(summary)
    os.remove(os.path.join(
        clean_root, "distributed_sod_project_tpu/serve/bad_metric.py"))
    # an inventory family nothing could render
    _write(clean_root, "tools/metrics_inventory.json", json.dumps({
        "fleet": {"dsod_serve_ok_total": "counter",
                  "dsod_serve_dyn_total": "counter",
                  "dsod_probe_orphan_total": "counter"}}))
    rc, summary, _ = run_lint(clean_root, "--fail-on-new")
    assert rc == 2 and "dsod_probe_orphan_total" in _keys(summary)


def test_metrics_prefix_construction_is_understood(clean_root):
    """dsod_serve_dyn_total has no verbatim literal — only the
    declared prefix "dsod_serve_" — and lints clean (the
    f-string-constructed family idiom)."""
    rc, summary, _ = run_lint(clean_root, "--fail-on-new")
    assert rc == 0


def test_accounting_seam_ownership(clean_root):
    _write(clean_root, "distributed_sod_project_tpu/serve/bad_book.py", '''
        class Rogue:
            def somewhere(self):
                self.stats.inc("served")
    ''')
    rc, summary, _ = run_lint(clean_root, "--fail-on-new")
    assert rc == 2
    assert "accounting-seams" in _keys(summary)
    assert "Rogue.somewhere" in _keys(summary)
    # ...while the declared seam (engine._finish) stayed silent
    assert "InferenceEngine._finish" not in _keys(summary)


# -- pragmas -----------------------------------------------------------

def test_pragma_waives_with_reason(clean_root):
    _write(clean_root, "distributed_sod_project_tpu/serve/waived.py", '''
        class Rogue:
            def somewhere(self):
                self.stats.inc("served")  # dsodlint: disable=accounting-seams -- audited: test fixture
    ''')
    rc, summary, _ = run_lint(clean_root, "--fail-on-new")
    assert rc == 0
    assert summary["findings"] == 0 and summary["waived"] == 1


def test_pragma_without_reason_is_itself_a_finding(clean_root):
    _write(clean_root, "distributed_sod_project_tpu/serve/noreason.py", '''
        class Rogue:
            def somewhere(self):
                self.stats.inc("served")  # dsodlint: disable=accounting-seams
    ''')
    rc, summary, _ = run_lint(clean_root, "--fail-on-new")
    assert rc == 2
    assert "pragma" in _keys(summary)
    assert "missing-reason" in _keys(summary)


def test_pragma_on_def_line_waives_scope(clean_root):
    _write(clean_root, "distributed_sod_project_tpu/serve/scoped.py", '''
        class Rogue:
            def somewhere(self):  # dsodlint: disable=accounting-seams -- audited: scope waiver
                x = 1
                self.stats.inc("served")
    ''')
    rc, summary, _ = run_lint(clean_root, "--fail-on-new")
    assert rc == 0 and summary["waived"] == 1


# -- baseline discipline -----------------------------------------------

def test_baseline_compare_fail_on_new_and_fixed(clean_root):
    rc, _s, _ = run_lint(clean_root)  # seed (clean)
    assert rc == 0
    _write(clean_root, "distributed_sod_project_tpu/serve/bad_book.py", '''
        class Rogue:
            def somewhere(self):
                self.stats.inc("served")
    ''')
    rc, summary, _ = run_lint(clean_root)  # recorded, not gating
    assert rc == 0 and summary["delta"] == 1
    rc, summary, _ = run_lint(clean_root, "--fail-on-new")
    assert rc == 2 and len(summary["new"]) == 1
    # baseline the violation in (the PR that introduces it owns it)
    rc, _s, _ = run_lint(clean_root, "--update-baseline")
    assert rc == 0
    rc, summary, _ = run_lint(clean_root, "--fail-on-new")
    assert rc == 0 and summary["new"] == []
    # fix it: the run reports the repaired key, still exit 0
    os.remove(os.path.join(
        clean_root, "distributed_sod_project_tpu/serve/bad_book.py"))
    rc, summary, _ = run_lint(clean_root, "--fail-on-new")
    assert rc == 0 and len(summary["fixed"]) == 1


def test_never_seed_baseline_from_crashed_run(clean_root):
    baseline = os.path.join(clean_root, "baseline.json")
    # a checker crash (the registry gone → env-coherence raises) must
    # not write a baseline, not even with --update-baseline
    os.remove(os.path.join(
        clean_root, "distributed_sod_project_tpu/utils/envvars.py"))
    rc, summary, _ = run_lint(clean_root, "--update-baseline",
                              baseline=baseline)
    assert rc == 1
    assert "crashed" in summary
    assert not os.path.exists(baseline)


def test_parse_error_also_refuses_to_seed(clean_root):
    baseline = os.path.join(clean_root, "baseline.json")
    _write(clean_root, "distributed_sod_project_tpu/serve/broken.py",
           "def oops(:\n")
    rc, summary, _ = run_lint(clean_root, "--update-baseline",
                              baseline=baseline)
    assert rc == 1
    assert summary["parse_errors"]
    assert not os.path.exists(baseline)


def test_line_moves_do_not_churn_the_baseline(clean_root):
    """Finding keys are line-free: inserting code above a baselined
    violation must not read as new."""
    _write(clean_root, "distributed_sod_project_tpu/serve/bad_book.py", '''
        class Rogue:
            def somewhere(self):
                self.stats.inc("served")
    ''')
    rc, _s, _ = run_lint(clean_root)  # seed with the violation
    assert rc == 0
    _write(clean_root, "distributed_sod_project_tpu/serve/bad_book.py", '''
        # a comment pushing everything down


        class Rogue:
            def somewhere(self):
                x = 1
                self.stats.inc("served")
    ''')
    rc, summary, _ = run_lint(clean_root, "--fail-on-new")
    assert rc == 0 and summary["new"] == []


def test_default_baseline_follows_root(clean_root):
    """With --root and no --baseline, the baseline lives UNDER the
    root (tools/dsodlint_baseline.json) — a fixture-tree run can never
    clobber the repo's checked-in file."""
    import io
    from contextlib import redirect_stdout

    with redirect_stdout(io.StringIO()):
        rc = dsodlint.main(["--root", clean_root])
    assert rc == 0
    assert os.path.exists(os.path.join(clean_root, "tools",
                                       "dsodlint_baseline.json"))


# -- the real repo ------------------------------------------------------

def test_dsodlint_runs_clean_on_the_real_repo():
    """The t1 gate: the repo at HEAD has zero unwaived findings beyond
    the checked-in baseline (which is itself empty — every waiver is a
    reasoned pragma in source, not a baseline entry)."""
    repo = os.path.join(os.path.dirname(__file__), "..")
    baseline = os.path.join(repo, "tools", "dsodlint_baseline.json")
    rc, summary, _ = run_lint(os.path.abspath(repo), "--fail-on-new",
                              baseline=baseline)
    assert rc == 0, summary
    assert summary["new"] == []
    with open(baseline) as f:
        assert json.load(f)["findings"] == []
