"""Compiled train step: the mean entropy of the exit distribution over
the passes, nats a token, mean over the measured ticks — the trainer's
own counter (``loop_exit_entropy`` beside ``grad_norm`` on the metric
stream).  ``ln R`` (1.386 at four passes) is a gate that says nothing,
0 one that has collapsed onto a single pass."""


def read(run):
    vals = [t["loop_exit_entropy"] for t in run.get("ticks") or []
            if "loop_exit_entropy" in t]
    return sum(vals) / len(vals) if vals else None
