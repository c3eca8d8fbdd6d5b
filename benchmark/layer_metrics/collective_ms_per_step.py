"""Collectives: union of the collective ops' intervals on a device
(mean over chips) per traced step.  The harness prints the part with
no compute running under it on an earlier line."""


def read(run):
    tr, n = run.get("trace"), run.get("traced_steps")
    if not tr or not n or run.get("chips", 1) < 2:
        return None
    print(f"collective: exposed {tr['collective_exposed_s'] * 1000.0 / n:.4f}"
          f" ms/step of {tr['collective_s'] * 1000.0 / n:.4f}", flush=True)
    return tr["collective_s"] * 1000.0 / n
