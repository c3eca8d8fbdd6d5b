"""The comparison that decides ``correct``.

Training: the program's first steps, as its own compiled step ran them
on the window's own feed (``runners/train.py`` taps them), against the
plain reference following the same rows from the same weights.  Each
number compared has a limit of its own, kept in the workload file, and
every run prints each number beside its limit.
"""

from __future__ import annotations

import functools
import importlib
import re
import statistics

import jax
import jax.numpy as jnp
import numpy as np

from ..reference import ops
from .weights import _path


def load_reference(name: str):
    """``benchmark/reference/<name>.py`` -> its ``forward``."""
    return importlib.import_module(f"benchmark.reference.{name}").forward


def _leaf_norms(tree):
    return jax.tree_util.tree_map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), tree)


def reference_follow(forward, make_variables, batches, ref: dict, *,
                     prec="f32", remat=True, sharding=None):
    """Follow ``len(batches)`` train steps in the plain reference from
    the variables ``make_variables()`` returns (called again at the end
    for the starting point: the step donates its arguments, so that the
    float32 program fits beside nothing but itself).  Returns losses,
    the per-leaf norms of the first gradient and of the parameters'
    change after the last step."""
    loss_w, opt = ref["loss"], ref["optimizer"]

    def loss_fn(params, stats, image, mask):
        outs = forward({"params": params, "batch_stats": stats}, image,
                       train=True, prec=prec, remat=remat)
        return ops.hybrid_loss(outs, mask, loss_w)

    @functools.partial(jax.jit, donate_argnums=(0, 2))
    def step(params, stats, opt_state, image, mask, i):
        loss, grads = jax.value_and_grad(loss_fn)(params, stats, image, mask)
        new, opt_state = ops.opt_update(opt, params, grads, opt_state, i)
        return new, opt_state, loss, _leaf_norms(grads)

    variables = make_variables()
    stats = variables["batch_stats"]  # train-mode BN ignores them
    params = variables["params"]
    opt_state = ops.opt_init(opt, params)
    del variables
    losses, g1 = [], None
    for i, b in enumerate(batches):
        image, mask = (jax.device_put(np.asarray(b[k], np.float32), sharding)
                       for k in ("image", "mask"))
        params, opt_state, loss, gn = step(params, stats, opt_state, image,
                                           mask, jnp.float32(i))
        losses.append(float(loss))
        if i == 0:
            g1 = jax.device_get(gn)
    dp = jax.device_get(_leaf_norms(
        jax.tree_util.tree_map(lambda a, b: a - b, params,
                               make_variables()["params"])))
    return {"loss": losses, "grad_norms": g1, "dparam_norms": dp}


def leaf_gaps(prog, ref) -> np.ndarray:
    """Per leaf, |‖prog‖ − ‖ref‖| over max(‖ref‖ of that leaf, ‖ref‖ of
    the median leaf): some leaves' norms are all but zero."""
    p = np.asarray(jax.tree_util.tree_leaves(prog), np.float64)
    r = np.asarray(jax.tree_util.tree_leaves(ref), np.float64)
    return np.abs(p - r) / np.maximum(r, statistics.median(r.tolist()))


def compare_training(tap: dict, ref_out: dict, limits: dict,
                     grad_leaves: str | None = None):
    """-> (ok, rows); a row is (name, value, limit, passed).  A number
    whose limit is None is reported beside the others and not judged
    (PERF.md says why for each).  ``grad_leaves``: a regular expression
    over the parameters' paths; the first gradient is also compared on
    the leaves it matches alone (``grad_norm_judged_*``)."""
    rows = []
    for i, (a, b) in enumerate(zip(tap["loss"], ref_out["loss"])):
        rows.append((f"loss_rel_gap.step{i + 1}", abs(a - b) / abs(b),
                     limits.get(f"loss_rel_gap.step{i + 1}")))
    for key in ("grad_norm", "dparam_norm"):
        g = leaf_gaps(tap[key + "s"], ref_out[key + "s"])
        rows.append((f"{key}_median_leaf_gap", float(np.median(g)),
                     limits.get(f"{key}_median_leaf_gap")))
        rows.append((f"{key}_worst_leaf_gap", float(np.max(g)),
                     limits.get(f"{key}_worst_leaf_gap")))
        if key == "grad_norm" and grad_leaves:
            pat = re.compile(grad_leaves)
            names = [_path(p) for p, _ in jax.tree_util.
                     tree_flatten_with_path(ref_out["grad_norms"])[0]]
            j = g[[bool(pat.search(n)) for n in names]]
            if not j.size:
                raise ValueError(f"grad_leaves {grad_leaves!r} matches no leaf")
            print(f"correct: grad_leaves {grad_leaves!r} matches {j.size} "
                  f"of {g.size} leaves", flush=True)
            for stat, v in (("median", np.median(j)), ("worst", np.max(j))):
                rows.append((f"grad_norm_judged_{stat}_leaf_gap", float(v),
                             limits.get(f"grad_norm_judged_{stat}_leaf_gap")))
    moved = np.asarray(jax.tree_util.tree_leaves(tap["dparam_norms"]))
    rows.append(("dparam_zero_leaf_share", float(np.mean(moved == 0.0)),
                 limits.get("dparam_zero_leaf_share")))
    rows = [(n, float(v), None if lim is None else float(lim),
             bool(lim is None or (np.isfinite(v) and v <= lim)))
            for n, v, lim in rows]
    return all(r[3] for r in rows), rows


def print_rows(rows) -> None:
    for name, value, limit, ok in rows:
        judged = "reported, not judged" if limit is None else (
            f"limit {limit:.6g}  {'ok' if ok else 'FAIL'}")
        print(f"correct: {name} = {value:.6g}  {judged}", flush=True)
