"""``setup_s`` by phase: the arithmetic the seven ``setup_*`` readers
share.

The program records its set-up on the host clock (``time.perf_counter``,
the clock of ``run.py::T_START`` and of the runners' ticks) in two
places, both read IN-PROCESS after ``fit()`` has returned:

- ``distributed_sod_project_tpu.utils.tracing.setup_spans()``: the
  sibling spans ``dsod.setup.before_fit`` (the package's import to
  ``fit()``'s entry), ``.build`` (to the first call of the compiled
  step), ``.first_step`` (that call) and one candidate ``.warmup`` per
  logging boundary of the first eight (that call's return to the return
  of the ``on_metrics`` hook); the run's opening tick
  (``run["ticks"][0]["t"]``, read inside that hook) picks the candidate
  and cuts it;
- ``distributed_sod_project_tpu.utils.platform.CompileStats().before(t)``:
  seconds of the JAX monitoring events up to the return of the hook
  that read the opening tick ``t`` (the program keeps the counters at
  each warm-up candidate's end), by kind: ``trace`` (Python tracing to a
  jaxpr), ``lower`` (jaxpr to StableHLO) or ``compile`` (backend
  compile, a cache hit's retrieval included); each event's OWN seconds,
  nested events counted once.

A program that has neither (the parent of the PR that brought them), a
run without ticks, and a sink that holds another ``fit()``'s set-up all
read ``None``: nothing is reported, nothing raises.
"""

from __future__ import annotations

SIBLINGS = ("before_fit", "build", "first_step", "warmup")
PREFIX = "dsod.setup."


def _opening(run):
    ticks = run.get("ticks") or []
    return ticks[0]["t"] if ticks else None


def spans(run):
    """``{"before_fit", "build", "first_step", "warmup", "unattributed"}``
    in seconds, or ``None``.  ``unattributed`` is the opening tick less
    ``before_fit``'s start less the four siblings: what lies BETWEEN
    them, ~0 while they touch."""
    t_open = _opening(run)
    if t_open is None:
        return None
    try:
        from distributed_sod_project_tpu.utils import tracing

        recorded = tracing.setup_spans()
    except (ImportError, AttributeError):
        return None
    top = {}
    for name, t0, t1, parent in recorded:
        if parent is None and name.startswith(PREFIX):
            top.setdefault(name[len(PREFIX):], []).append((t0, t1))
    if any(len(top.get(k, ())) != 1 for k in SIBLINGS[:3]):
        return None
    # the candidate whose hook returned first after the opening tick was
    # read; none = the sink holds another run's set-up, or the window
    # opened past the boundaries the program keeps
    warm = [(t0, t1) for t0, t1 in top.get("warmup", ()) if t1 >= t_open]
    (t_import, _), (_, t_first_end) = top["before_fit"][0], \
        top["first_step"][0]
    if not warm or not t_import <= t_first_end <= t_open:
        return None
    out = {k: top[k][0][1] - top[k][0][0] for k in SIBLINGS[:3]}
    out["warmup"] = t_open - min(warm)[0]
    out["unattributed"] = (t_open - t_import) - sum(out.values())
    return out


def span_s(run, key):
    got = spans(run)
    return None if got is None else got[key]


def counter_s(run, kinds):
    """Seconds of the listener's events of ``kinds`` up to the opening
    tick's hook, or ``None``."""
    t_open = _opening(run)
    if t_open is None or spans(run) is None:
        return None
    from distributed_sod_project_tpu.utils.platform import CompileStats

    seconds = CompileStats().before(t_open)
    return None if seconds is None else sum(seconds[k] for k in kinds)
