"""Train loop on the host: the opening tick less ``before_fit``'s start
less the four sibling ``dsod.setup.*`` spans: ~0 while they touch — the
counter that shows the set-up spans rotting."""

from benchmark.harness import setup_phases


def read(run):
    return setup_phases.span_s(run, "unattributed")
