"""Compiled train step: the busiest held expert's pairs over the mean
of the held experts', worst expert layer, mean over the measured ticks
— the trainer's own counter (``moe_load_max_over_mean`` beside
``grad_norm`` on the metric stream); 1.0 is balanced."""


def read(run):
    vals = [t["moe_load_max_over_mean"] for t in run.get("ticks") or []
            if "moe_load_max_over_mean" in t]
    return sum(vals) / len(vals) if vals else None
