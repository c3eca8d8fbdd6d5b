"""The unified rule-driven train-step builder (ROADMAP item 1).

ONE builder subsumes the three hand-built ones (train/step.py DP,
parallel/tp.py GSPMD, parallel/sp.py SP): a preset from
``parallel/rules.py`` decides the per-preset seams — RNG fold, forward/
loss path, gradient reduction, and trace wrapper — while every shared
seam (steps_per_dispatch chunking, grad accumulation, EMA,
skip_nonfinite, PR 10's ``maybe_health_metrics``, PR 11's
capacity-ledger compile hook via ``.lower``) is threaded exactly ONCE.

Bitwise contract: with ``grad_compression='none'`` and a flat (single
level) reduction, the built step is bitwise (f32, CPU) identical to the
legacy builder of the same preset — proven in round 17 against all
three, after which the default flipped and the legacy builders were
deleted (round 18); the bucketed reducer computes per element exactly
what ``lax.pmean`` computes (tests/test_sharding_rules.py asserts it,
tools/t1.sh re-proves a smoke every round).

Perf deliverables on top of the rule layer:

- ``parallel.preset=fsdp`` — full parameter sharding as pure config:
  params shard over ``data`` (``rules.fsdp_fallback_rule`` picks each
  leaf's largest divisible dim), the GSPMD partitioner all-gathers
  them just-in-time per layer in forward/backward and reduce-scatters
  grads; optimizer buffers inherit the param layout, so weight-update
  sharding comes free at any ``zero`` level.
- ``parallel.zero=1|2`` — ZeRO-style weight-update sharding: optimizer
  moments + EMA shard over ``data`` (GSPMD presets; grads
  reduce-scatter into 1/N updates, params all-gather), level 2
  additionally pins the gradient tree to the sharded layout.  HBM
  saving is priced by ``comm_plan`` and reported through the capacity
  ledger.
- ``parallel.comm_bucket_mb`` — bucketed, backward-ordered gradient
  allreduce on the DP preset (``rules.bucketed_pmean``): one
  ``lax.psum`` per size-targeted bucket so early buckets' communication
  overlaps remaining backward compute.
- ``mesh.data_hosts>1`` — two-level ICI x DCN reduction on the DP
  preset: each bucket's psum becomes intra-host reduce-scatter ->
  inter-host all-reduce on 1/chips_per_host of the bytes -> intra-host
  all-gather (``rules._hier_psum``; groups from
  ``mesh.hier_data_groups``).
- ``parallel.grad_compression=bf16|int8_ef`` — wire compression on the
  bucketed reducer; int8_ef carries a persistent error-feedback
  residual in the train state (``TrainState.comm_residual``, sharded
  over ``data``).  Both gated by tools/grad_comm_gate.py.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..losses import deep_supervision_loss
from ..models import kind_of
from ..models.layers import resample_routes
from ..train.state import TrainState
from ..train.step import (_loss_kwargs, apply_update, chunk_batch_spec,
                          chunked_step_fn, maybe_health_metrics,
                          maybe_remat, notfinite_count, rescale_batch,
                          resolve_remat_policy)
from . import rules as rules_mod
from .mesh import (batch_sharding, batch_spec, hier_data_groups,
                   replicated_sharding)

PRESETS = ("dp", "tp", "sp", "fsdp")


def select_preset(cfg, mesh: Mesh) -> str:
    """The rules-engine preset for a config+mesh: an explicit
    ``parallel.preset`` wins (``fsdp`` can only be asked for — nothing
    about a mesh implies it); ``auto`` derives the historical routing —
    ``sp`` when the ``seq`` axis is sharded, ``tp`` (the GSPMD preset)
    when the ``model`` axis is sharded or any ZeRO level is on, else
    ``dp``."""
    explicit = getattr(cfg.parallel, "preset", "auto")
    if explicit != "auto":
        return explicit
    if mesh.shape.get("seq", 1) > 1:
        return "sp"
    if (mesh.shape.get("model", 1) > 1 or cfg.optim.zero1
            or cfg.parallel.zero > 0):
        return "tp"
    return "dp"


def effective_zero(cfg) -> int:
    """The ZeRO level the engine runs at: ``parallel.zero``, with the
    legacy ``optim.zero1`` spelling mapped to level 1 (validate_parallel
    rejects both being set)."""
    return cfg.parallel.zero or (1 if cfg.optim.zero1 else 0)


def make_unified_train_step(
    model,
    loss_cfg,
    tx: optax.GradientTransformation,
    mesh: Mesh,
    *,
    preset: str,
    schedule: Optional[optax.Schedule] = None,
    donate: bool = True,
    remat: bool = False,
    ema_decay: float = 0.0,
    scale_hw: Optional[Tuple[int, int]] = None,
    donate_batch: bool = False,
    remat_policy: str = "none",
    steps_per_dispatch: int = 1,
    health: bool = False,
    sp_strategy: str = "ring",
    state_shardings=None,
    zero: int = 0,
    comm_bucket_mb: float = 0.0,
    grad_compression: str = "none",
    data_hosts: int = 1,
    _always_scan: bool = False,
) -> Callable[[TrainState, Dict[str, jnp.ndarray]],
              Tuple[TrainState, Dict[str, jnp.ndarray]]]:
    """Build ``(state, batch) -> (state, metrics)`` for any preset.

    Sharding contracts: ``dp`` — state replicated (int8_ef's
    ``comm_residual`` sharded ``P('data')``), batch ``P('data')``,
    shard_map; ``sp`` — state replicated, batch ``P('data', 'seq')``,
    shard_map (vit_sod only; ``sp_strategy`` picks ring vs ulysses);
    ``tp``/``fsdp`` — GSPMD jit with ``state_shardings`` (required;
    from ``rules.shard_state_by_rules`` — the Megatron tables for tp,
    the empty-table + ``fsdp_fallback_rule`` layout for fsdp),
    collectives inserted by the partitioner.  ``steps_per_dispatch=k >
    1`` scans k steps per dispatch over a new leading stacked axis
    (``chunked_step_fn``) — the ONE chunking seam all presets share.
    ``data_hosts>1`` routes each dp bucket through the two-level
    ICI x DCN reduction (``mesh.hier_data_groups``).
    """
    if preset not in PRESETS:
        raise ValueError(f"preset must be one of {PRESETS}, got {preset!r}")
    gspmd = preset in ("tp", "fsdp")
    if gspmd and state_shardings is None:
        raise ValueError(
            f"the {preset} (GSPMD) preset needs state_shardings — build "
            "them with rules.shard_state_by_rules(state, mesh, "
            "zero=..., fallback=...)")
    if preset != "dp" and grad_compression != "none":
        raise ValueError(
            "grad_compression applies to the dp preset's bucketed "
            f"reducer only (preset={preset!r}: the GSPMD partitioner / "
            "SP reduction schedule their own collectives)")
    if preset != "dp" and data_hosts > 1:
        raise ValueError(
            "mesh.data_hosts>1 (the two-level ICI x DCN reduction) "
            f"applies to the dp preset's bucketed reducer only, got "
            f"preset={preset!r}")
    if preset == "sp":
        from .sp import validate_sp_strategy

        if getattr(loss_cfg, "fused_kernel", False):
            import logging

            logging.getLogger(__name__).warning(
                "loss.fused_kernel is a no-op on the sequence-parallel "
                "path: the SP loss already psums sufficient statistics "
                "inline (docs/PERFORMANCE.md)")
        validate_sp_strategy(model, mesh, sp_strategy)
    resolve_remat_policy(remat_policy)  # fail fast on typos, remat or not
    lkw = _loss_kwargs(loss_cfg)
    seq = mesh.shape.get("seq", 1)
    bucket_bytes = int(comm_bucket_mb * 2 ** 20)
    hierarchy = hier_data_groups(mesh, data_hosts)
    ef = grad_compression == "int8_ef"
    kind = kind_of(model)
    if kind.dp_only and (preset != "dp" or ef):
        raise ValueError(
            "the token model trains under the dp preset without "
            f"error-feedback compression, got preset={preset!r} "
            f"grad_compression={grad_compression!r}")
    # ZeRO-2: the gradient tree is pinned to the buffer layout so the
    # partitioner reduce-scatters instead of materializing the full
    # replicated tree between reduce and update.
    grad_constraint = None
    if gspmd and zero >= 2 and state_shardings is not None:
        grad_constraint = jax.tree_util.tree_map(
            lambda s: s, state_shardings.params)

    def _rng(step):
        # Per-preset RNG folds — each reproduced EXACTLY from its
        # legacy builder so dropout draws replay bit-identically.
        base = jax.random.fold_in(jax.random.PRNGKey(0), step)
        if preset == "dp":
            return jax.random.fold_in(base, lax.axis_index("data"))
        if preset == "sp":
            return jax.random.fold_in(
                base,
                lax.axis_index("data") * seq + lax.axis_index("seq"))
        return base  # tp/GSPMD: global semantics, no named axis

    def _forward_loss(state, batch, rng):
        """(grads, comps, new_stats) for the preset's forward+loss."""
        if preset == "sp":
            from .sp import _sp_apply, _sp_hybrid_loss, _sp_ssim_loss

            image, mask = batch["image"], batch["mask"]

            def apply_fn(params, image):
                return _sp_apply(model, {"params": params}, image,
                                 train=True, rngs={"dropout": rng},
                                 sp_strategy=sp_strategy)

            apply_fn = maybe_remat(apply_fn, remat, remat_policy)

            def loss_fn(params):
                outs = apply_fn(params, image)
                if not loss_cfg.deep_supervision:
                    outs = outs[:1]  # primary head only
                total = jnp.float32(0.0)
                comps: Dict[str, jnp.ndarray] = {}
                with jax.named_scope("dsod.loss"):
                    for level in outs:
                        t, c = _sp_hybrid_loss(
                            level, mask, bce_w=loss_cfg.bce,
                            iou_w=loss_cfg.iou, cel_w=loss_cfg.cel)
                        if getattr(loss_cfg, "ssim", 0.0):
                            c["ssim"] = _sp_ssim_loss(
                                level, mask,
                                window_size=getattr(loss_cfg,
                                                    "ssim_window", 11))
                            t = t + loss_cfg.ssim * c["ssim"]
                        total = total + t
                        for k, v in c.items():
                            if k != "total":
                                comps[k] = (comps.get(k, jnp.float32(0.0))
                                            + v)
                comps["total"] = total
                return total, comps

            grads, comps = jax.grad(loss_fn, has_aux=True)(state.params)
            return grads, comps, state.batch_stats

        def apply_fn(params, batch_stats, image, depth):
            return model.apply(
                {"params": params, "batch_stats": batch_stats},
                image, depth, train=True,
                mutable=["batch_stats"], rngs={"dropout": rng})

        apply_fn = maybe_remat(apply_fn, remat, remat_policy)

        def loss_fn(params):
            outs, mut = apply_fn(params, state.batch_stats,
                                 batch["image"], batch.get("depth"))
            if not loss_cfg.deep_supervision:
                outs = outs[:1]  # primary head only, uniform across steps
            with jax.named_scope("dsod.loss"):
                total, comps = deep_supervision_loss(outs, batch["mask"],
                                                     **lkw)
            return total, (comps, mut.get("batch_stats",
                                          state.batch_stats))

        grads, (comps, new_stats) = jax.grad(loss_fn, has_aux=True)(
            state.params)
        return grads, comps, new_stats

    def _forward_loss_counted(state, batch, rng):
        # Runs at trace time only: one "resample routes" log line per
        # compile, saying how many resample sites of the step took the
        # Pallas kernel / the lane-dense form / the slice-lerp path.
        with resample_routes(log_as=f"train step, {preset}"):
            return _forward_loss(state, batch, rng)

    @jax.named_scope("dsod.update")
    def _reduce(grads, comps, residual=None):
        """Per-preset gradient/metric reduction — the comm seam.
        Returns ``(grads, comps, new_residual)``; the residual is only
        live on the dp int8_ef arm."""
        if preset == "dp":
            if bucket_bytes > 0 or hierarchy is not None or ef:
                if ef:
                    grads, residual = rules_mod.bucketed_pmean(
                        grads, "data", bucket_bytes,
                        compression=grad_compression,
                        hierarchy=hierarchy, residual=residual)
                else:
                    grads = rules_mod.bucketed_pmean(
                        grads, "data", bucket_bytes,
                        compression=grad_compression,
                        hierarchy=hierarchy)
            else:
                grads = lax.pmean(grads, "data")
            comps = lax.pmean(comps, "data")
        elif preset == "sp":
            # SUM over seq recovered by pmean (see parallel/sp.py);
            # data is the usual DP mean.  comps are already seq-global.
            grads = lax.pmean(grads, ("data", "seq"))
            comps = lax.pmean(comps, "data")
        elif grad_constraint is not None:
            grads = lax.with_sharding_constraint(grads, grad_constraint)
        return grads, comps, residual

    @jax.named_scope("dsod.update")
    def _finish(state, grads, comps, new_stats):
        """Optimizer/EMA/metric tail — identical on every preset."""
        new_state = apply_update(state, grads, new_stats, tx,
                                 ema_decay=ema_decay)
        metrics = dict(comps)
        metrics["grad_norm"] = optax.global_norm(grads)
        maybe_health_metrics(metrics, state.params, grads,
                             new_state.params, health)
        nfc = notfinite_count(new_state.opt_state)
        if nfc is not None:
            metrics["notfinite_count"] = jnp.asarray(nfc, jnp.float32)
        if schedule is not None:
            metrics["lr"] = jnp.asarray(schedule(state.step), jnp.float32)
        return new_state, metrics

    def step_fn(state: TrainState, batch):
        if preset != "sp":
            batch = rescale_batch(batch, scale_hw)
        rng = _rng(state.step)
        grads, comps, new_stats = _forward_loss_counted(state, batch, rng)
        grads, comps, _ = _reduce(grads, comps)
        return _finish(state, grads, comps, new_stats)

    def step_fn_ef(carry, batch):
        # int8_ef: the carry is (state-without-residual, residual); the
        # residual's local block is (1, n_elems) — its replica row.
        state, residual = carry
        batch = rescale_batch(batch, scale_hw)
        rng = _rng(state.step)
        grads, comps, new_stats = _forward_loss_counted(state, batch, rng)
        grads, comps, new_res = _reduce(grads, comps, residual[0])
        new_state, metrics = _finish(state, grads, comps, new_stats)
        return (new_state, new_res[None]), metrics

    # A token model's forward + loss, the third beside ``sp`` and the
    # image branch of ``_forward_loss``: the batch is tokens/targets,
    # the forward returns the final hidden states and the expert
    # layers' counters, the loss is the chunked cross-entropy over the
    # tied embedding; everything after the gradients is the shared tail.

    def _token_loss(outputs, counters, params, targets):
        """-> (total, counters).  A model that names its own loss
        (``token_loss``: models/ouro.py, four heads' cross-entropies
        under a learned exit distribution) is given its outputs, the
        parameters and the targets; every other token model hands back
        ONE hidden state, whose loss is the chunked cross-entropy over
        the head's matrix: the embedding, unless the model names its
        own (``head``)."""
        from ..losses.token_ce import tied_cross_entropy

        own = getattr(model, "token_loss", None)
        if own is not None:
            total, more = own(outputs, params, targets)
            return total, dict(counters, **more)
        module, leaf = getattr(model, "head", ("embed", "embedding"))
        return tied_cross_entropy(outputs, params[module][leaf],
                                  targets), counters

    def _forward_loss_tokens(state, batch):
        # The buffers come back from the model, as BatchNorm's do from
        # an image model: a router balanced by rule moves its selection
        # bias every step (models/lfm2.py::ExpertLayer).

        def loss_fn(params):
            (hidden, counters), mut = model.apply(
                {"params": params, "batch_stats": state.batch_stats},
                batch["tokens"], train=True, mutable=["batch_stats"])
            total, counters = _token_loss(
                hidden, counters, params, batch["targets"])
            return total, (dict(counters, total=total),
                           mut.get("batch_stats", state.batch_stats))

        grads, (comps, new_stats) = jax.grad(loss_fn, has_aux=True)(
            state.params)
        return grads, comps, new_stats

    def step_fn_tokens(state: TrainState, batch):
        # No image to rescale, no dropout draw, no resample site to
        # count; the same reduce/finish.
        grads, comps, new_stats = _forward_loss_tokens(state, batch)
        grads, comps, _ = _reduce(grads, comps)
        return _finish(state, grads, comps, new_stats)

    inner_fn = (step_fn_tokens if kind.name == "tokens"
                else step_fn_ef if ef else step_fn)
    body = chunked_step_fn(inner_fn, steps_per_dispatch,
                           always_scan=_always_scan)
    donated = (0,) if donate else ()
    if donate_batch:  # fit feeds each prefetched batch exactly once
        donated = donated + (1,)
    if gspmd:
        batch_in = (batch_sharding(mesh) if body is inner_fn
                    else NamedSharding(mesh, chunk_batch_spec(batch_spec())))
        replicated = NamedSharding(mesh, P())
        return jax.jit(
            body,
            in_shardings=(state_shardings, batch_in),
            out_shardings=(state_shardings, replicated),
            donate_argnums=donated,
        )
    base = P("data") if preset == "dp" else P("data", "seq")
    batch_in = base if body is inner_fn else chunk_batch_spec(base)
    if ef:
        sharded = jax.shard_map(
            body,
            mesh=mesh,
            in_specs=((P(), P("data")), batch_in),
            out_specs=((P(), P("data")), P()),
            check_vma=False,
        )
        inner = jax.jit(sharded, donate_argnums=donated)

        def step(state: TrainState, batch):
            # The public contract stays (state, batch) -> (state,
            # metrics): split the residual out of the state for the
            # carry tuple and reattach it after.
            core = state.replace(comm_residual=None)
            (core, res), metrics = inner((core, state.comm_residual),
                                         batch)
            return core.replace(comm_residual=res), metrics

        # .lower keeps the AOT consumers working (capacity record_jit,
        # tools/dump_hlo.py) — same split, handed to the jit's lower.
        step.lower = lambda state, batch: inner.lower(
            (state.replace(comm_residual=None), state.comm_residual),
            batch)
        return step
    sharded = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(), batch_in),
        out_specs=(P(), P()),
        check_vma=False,
    )
    return jax.jit(sharded, donate_argnums=donated)


# -- comm/ZeRO accounting (feeds the PR 11 capacity ledger) -----------

def comm_plan(state, mesh: Mesh, *, preset: str, zero: int = 0,
              comm_bucket_mb: float = 0.0,
              grad_compression: str = "none",
              data_hosts: int = 1) -> Dict[str, Any]:
    """Price the step's gradient collectives + ZeRO HBM saving from
    shapes alone (no tracing): per-collective payload bytes, axis size
    and link level (``ici``/``dcn``), the bucket count, a structural
    overlap estimate, and the per-device optimizer/EMA bytes ZeRO
    removes.  The capacity ledger (``CapacityLedger.record_comm``)
    turns this into the ``dsod_capacity_comm_*`` families (DCN legs
    into the ``_dcn_*`` families); tools/roofline.py prices the same
    plan offline against ICI and DCN bandwidth.

    ``data_hosts>1`` expands each dp bucket into its three hierarchical
    legs: intra-host reduce-scatter (ici, full payload), inter-host
    all-reduce (dcn, payload/chips_per_host — the whole point), intra-
    host all-gather (ici).  int8_ef prices the achievable 1 B/elem wire
    (0.25 x f32) — XLA transports int32, so this is the contract for
    a wire-level int8 transport, stated in docs/PERFORMANCE.md.

    Overlap estimate is STRUCTURAL, not measured: with backward-ordered
    buckets every bucket except the final one (the earliest layers,
    reduced last) can overlap remaining backward compute, so
    ``overlap_frac = 1 - last_bucket_bytes / total``; a monolithic
    reduce (or the GSPMD presets, whose schedule the partitioner owns)
    reports 0.  Not measured on a chip.
    """
    leaves = jax.tree_util.tree_leaves(state.params)
    shapes = [(g.shape, g.dtype) for g in leaves]
    sizes = [int(np.prod(s or (1,))) * np.dtype(d).itemsize
             for s, d in shapes]
    wire_scale = {"bf16": 0.5, "int8_ef": 0.25}.get(grad_compression,
                                                    1.0)
    n_data = mesh.shape.get("data", 1)
    collectives = []
    if preset == "dp":
        bucket_bytes = int(comm_bucket_mb * 2 ** 20)
        buckets = rules_mod.grad_buckets(shapes, bucket_bytes)
        chips = n_data // data_hosts if data_hosts > 1 else n_data
        for i, bucket in enumerate(buckets):
            payload = int(sum(sizes[j] for j in bucket) * wire_scale)
            stem = (f"grad_bucket_{i:02d}" if len(buckets) > 1
                    else "grad_allreduce")
            if data_hosts > 1:
                collectives.extend([
                    {"name": f"{stem}_rs", "kind": "reduce_scatter",
                     "axis": "data", "axis_size": chips, "level": "ici",
                     "bytes": payload},
                    {"name": f"{stem}_ar", "kind": "psum",
                     "axis": "data", "axis_size": data_hosts,
                     "level": "dcn", "bytes": payload // chips},
                    {"name": f"{stem}_ag", "kind": "all_gather",
                     "axis": "data", "axis_size": chips, "level": "ici",
                     "bytes": payload},
                ])
            else:
                collectives.append({
                    "name": stem, "kind": "psum", "axis": "data",
                    "axis_size": n_data, "level": "ici",
                    "bytes": payload})
        last = sum(sizes[j] for j in buckets[-1]) if buckets else 0
        overlap = (1.0 - last / max(sum(sizes), 1)
                   if len(buckets) > 1 else 0.0)
    elif preset == "fsdp":
        # The partitioner all-gathers the sharded params just-in-time
        # in forward AND backward, and reduce-scatters grads into the
        # 1/N updates — the textbook FSDP schedule, priced at the param
        # payload per leg.
        payload = sum(sizes)
        for name, kind in (("param_allgather_fwd", "all_gather"),
                           ("param_allgather_bwd", "all_gather"),
                           ("grad_reduce_scatter", "reduce_scatter")):
            collectives.append({
                "name": name, "kind": kind, "axis": "data",
                "axis_size": n_data, "level": "ici",
                "bytes": payload})
        overlap = 0.0
    elif preset == "sp":
        n = n_data * mesh.shape.get("seq", 1)
        collectives.append({
            "name": "grad_allreduce", "kind": "psum",
            "axis": "data,seq", "axis_size": n,
            "bytes": sum(sizes)})
        overlap = 0.0
    else:  # tp/GSPMD: the partitioner owns the schedule; with ZeRO the
        # reduce becomes reduce-scatter + update + param all-gather.
        kind = "reduce_scatter+all_gather" if zero else "all_reduce"
        collectives.append({
            "name": "grad_allreduce", "kind": kind, "axis": "data",
            "axis_size": n_data, "bytes": sum(sizes)})
        overlap = 0.0
    saved = 0
    if preset == "fsdp":
        # FSDP sharding saves params + optimizer buffers + EMA: the
        # whole state except batch_stats shards over data.
        fallback = rules_mod.fsdp_fallback_rule(mesh)
        specs = rules_mod.state_specs(
            state, mesh, rules=rules_mod.PRESET_PARAM_RULES["fsdp"],
            zero=zero, fallback=fallback)
        for tree, spec in ((state.params, specs.params),
                           (state.opt_state, specs.opt_state),
                           (state.ema_params, specs.ema_params)):
            if tree is None:
                continue
            saved += (rules_mod.tree_bytes(tree)
                      - rules_mod.sharded_tree_bytes(tree, spec, mesh))
    elif zero and preset == "tp":
        specs = rules_mod.state_specs(state, mesh, zero=zero)
        for tree, spec in ((state.opt_state, specs.opt_state),
                           (state.ema_params, specs.ema_params)):
            if tree is None:
                continue
            saved += (rules_mod.tree_bytes(tree)
                      - rules_mod.sharded_tree_bytes(tree, spec, mesh))
    stems = {c["name"].rsplit("_rs", 1)[0].rsplit("_ar", 1)[0]
             .rsplit("_ag", 1)[0] for c in collectives
             if c["name"].startswith("grad_bucket")}
    return {
        "collectives": collectives,
        "n_buckets": len(stems) or 1,
        "overlap_frac": round(overlap, 6),
        "zero_hbm_saved_bytes": int(saved),
    }


def seed_comm_residual(state, mesh: Mesh) -> TrainState:
    """Seed the int8_ef error-feedback residual: a zero
    ``(n_data, n_grad_elems)`` f32 array sharded ``P('data')`` — row r
    is replica r's accumulated quantization error.  A state that
    already carries a residual (e.g. restored from a checkpoint) keeps
    its values; it is only (re)placed onto the mesh."""
    sharding = NamedSharding(mesh, P("data"))
    existing = getattr(state, "comm_residual", None)
    if existing is not None:
        return state.replace(
            comm_residual=jax.device_put(jnp.asarray(existing),
                                         sharding))
    shapes = [(g.shape, g.dtype)
              for g in jax.tree_util.tree_leaves(state.params)]
    n = rules_mod.comm_residual_size(shapes, 0)
    n_data = mesh.shape.get("data", 1)
    return state.replace(
        comm_residual=jax.device_put(jnp.zeros((n_data, n), jnp.float32),
                                     sharding))


def prepare_train_step(cfg, model, tx, mesh: Mesh, schedule, state, *,
                       steps_per_dispatch: int = 1,
                       scale_hw: Optional[Tuple[int, int]] = None,
                       donate: bool = True, donate_batch: bool = False):
    """One-call routing for chip_smoke.py / tools/dump_hlo.py: select the
    preset, place the state (replicated, or rule/ZeRO-sharded for the
    GSPMD presets — Megatron tables for tp, empty table +
    ``fsdp_fallback_rule`` for fsdp), seed the int8_ef residual when
    asked for, and build the unified step.  Returns ``(state, step,
    plan)`` where ``plan`` is ``comm_plan``'s dict.  fit() wires the
    presets itself (it owns validation + the multi-scale factory) but
    calls the SAME builder."""
    from ..configs.base import validate_parallel

    validate_parallel(cfg)
    preset = select_preset(cfg, mesh)
    zero = effective_zero(cfg)
    data_hosts = getattr(cfg.mesh, "data_hosts", 1)
    kw = dict(schedule=schedule, donate=donate, remat=cfg.model.remat,
              ema_decay=cfg.optim.ema_decay, scale_hw=scale_hw,
              donate_batch=donate_batch,
              remat_policy=cfg.model.remat_policy,
              steps_per_dispatch=steps_per_dispatch,
              health=cfg.health_numerics,
              comm_bucket_mb=cfg.parallel.comm_bucket_mb,
              grad_compression=cfg.parallel.grad_compression,
              data_hosts=data_hosts, zero=zero)
    if preset == "tp":
        state, shardings = rules_mod.shard_state_by_rules(
            state, mesh, zero=zero)
        kw["state_shardings"] = shardings
    elif preset == "fsdp":
        state, shardings = rules_mod.shard_state_by_rules(
            state, mesh, rules=rules_mod.PRESET_PARAM_RULES["fsdp"],
            zero=zero, fallback=rules_mod.fsdp_fallback_rule(mesh))
        kw["state_shardings"] = shardings
    else:
        # Replicate first, THEN seed the residual — seeding places the
        # residual P('data'), which a blanket replicate would undo.
        residual = getattr(state, "comm_residual", None)
        state = jax.device_put(state.replace(comm_residual=None),
                               replicated_sharding(mesh))
        if cfg.parallel.grad_compression == "int8_ef":
            state = seed_comm_residual(
                state.replace(comm_residual=residual), mesh)
        if preset == "sp":
            kw["sp_strategy"] = cfg.mesh.sp_strategy
    step = make_unified_train_step(model, cfg.loss, tx, mesh,
                                   preset=preset, **kw)
    plan = comm_plan(state, mesh, preset=preset, zero=zero,
                     comm_bucket_mb=cfg.parallel.comm_bucket_mb,
                     grad_compression=cfg.parallel.grad_compression,
                     data_hosts=data_hosts)
    return state, step, plan
