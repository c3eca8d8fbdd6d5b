"""Sequence-parallel building blocks (SURVEY.md §5 "long-context").

The SP train step itself is built by the rules engine
(parallel/engine.py, ``preset="sp"``) from the loss/apply/eval pieces
defined here.

The reference has no sequence axis to scale (fixed 320×320 CNNs); this
is the TPU build's long-context path: ``vit_sod``'s global attention is
quadratic in tokens, so past single-chip memory/FLOPs the token dim
must shard.  Layout (the ``seq`` mesh axis):

- every batch leaf is sharded ``P('data', 'seq')``: batch over
  ``data``, image ROWS over ``seq`` — patch rows map 1:1 to token
  blocks because the model's patchify is halo-free (models/vit_sod.py),
- each device runs the FULL module (patchify → blocks → head) on its
  row slice, with ``parallel.ring_attention`` as the attention core —
  the ppermute ring is the only cross-device traffic in the forward,
- the loss decomposes exactly: BCE pixel sums and the IoU/CEL
  per-image region sums are computed locally and ``psum``-ed over
  ``seq`` BEFORE the ratios, so the objective equals the single-device
  one to numerics (tests assert grad equivalence),
- gradients: every device's autodiff yields its token block's
  contribution, so the true gradient is ``psum`` over ``seq`` and
  ``pmean`` over ``data`` (DP semantics on the batch axis).

SSIM does not decompose pointwise over row blocks (its 11×11 windows
straddle block edges), but it is exactly computable with a 5-row halo
exchange: each device ppermutes its boundary rows of the five windowed
moment maps to its ``seq`` neighbors, blurs the extended block, and
keeps only the window outputs centred on its own rows.  ``ppermute``
leaves zeros where no neighbor exists, which is exactly the SAME
zero-padding the single-device blur applies at global image edges — so
the full BASNet hybrid loss (BCE+IoU+SSIM, [B:5]) trains under SP to
numerics (grad-equivalence asserted in tests/test_vit_sod.py).
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Dict

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..losses.ssim import _C1, _C2, _blur, gaussian_window
from .ring_attention import ring_attention


def sp_batch_sharding(mesh: Mesh) -> NamedSharding:
    """Batch over ``data``, image rows (dim 1) over ``seq``."""
    return NamedSharding(mesh, P("data", "seq"))


def _sp_hybrid_loss(logits, mask, *, bce_w, iou_w, cel_w,
                    iou_eps=1.0, cel_eps=1e-6, axis="seq"):
    """BCE + IoU + CEL over row-sharded logits/mask — exact: sufficient
    statistics psum over the ``seq`` axis before any ratio/mean."""
    x = logits.astype(jnp.float32).reshape(logits.shape[0], -1)
    t = mask.astype(jnp.float32).reshape(mask.shape[0], -1)
    bce_i = jnp.sum(jnp.maximum(x, 0.0) - x * t
                    + jnp.log1p(jnp.exp(-jnp.abs(x))), axis=-1)
    p = jax.nn.sigmoid(x)
    inter_i = jnp.sum(p * t, axis=-1)
    psum_i = jnp.sum(p, axis=-1)
    tsum_i = jnp.sum(t, axis=-1)
    # Global per-image sums: this device's rows + everyone else's.
    bce_i, inter_i, psum_i, tsum_i = lax.psum(
        (bce_i, inter_i, psum_i, tsum_i), axis)
    n_pix_total = x.shape[1] * lax.axis_size(axis)

    comps: Dict[str, jnp.ndarray] = {}
    total = jnp.float32(0.0)
    if bce_w:
        comps["bce"] = bce_i.mean() / n_pix_total
        total += bce_w * comps["bce"]
    if iou_w:
        union = psum_i + tsum_i - inter_i
        comps["iou"] = jnp.mean(
            1.0 - (inter_i + iou_eps) / (union + iou_eps))
        total += iou_w * comps["iou"]
    if cel_w:
        tot = psum_i + tsum_i
        comps["cel"] = jnp.mean((tot - 2.0 * inter_i) / (tot + cel_eps))
        total += cel_w * comps["cel"]
    comps["total"] = total
    return total, comps


def _exchange_row_halo(x, halo: int, axis: str):
    """Attach ``halo`` rows from each ``seq`` neighbor to a row-sharded
    NHWC block: ``[prev's bottom rows, x, next's top rows]``.  Devices
    with no neighbor on a side receive ppermute's zero fill — identical
    to the SAME zero padding the single-device blur sees at the global
    image edge, so no special-casing of edge devices is needed."""
    n = lax.axis_size(axis)
    top = lax.ppermute(x[:, -halo:], axis,
                       [(i, i + 1) for i in range(n - 1)])
    bot = lax.ppermute(x[:, :halo], axis,
                       [(i + 1, i) for i in range(n - 1)])
    return jnp.concatenate([top, x, bot], axis=1)


def _sp_ssim_loss(logits, mask, *, axis="seq", window_size=11, sigma=1.5):
    """Exact ``1 − SSIM`` over row-sharded maps (losses/ssim.py math).

    The five windowed moments (a, b, a², b², ab) are formed locally —
    products of rows live wholly on the row's owner — so ONE halo
    exchange of the stacked moment maps feeds the blur; outputs centred
    on halo rows are sliced away (they belong to the neighbor), and the
    map mean is a psum of local sums over the global pixel count.
    """
    halo = window_size // 2
    if logits.shape[1] < halo:
        raise ValueError(
            f"sequence-parallel SSIM needs >= {halo} image rows per "
            f"device (window {window_size}), got {logits.shape[1]} — "
            "use fewer seq shards or a larger image")
    a = jax.nn.sigmoid(logits.astype(jnp.float32))
    b = mask.astype(jnp.float32)
    c = a.shape[-1]
    stack = jnp.concatenate([a, b, a * a, b * b, a * b], axis=-1)
    ext = _exchange_row_halo(stack, halo, axis)
    blurred = _blur(ext, gaussian_window(window_size, sigma))
    blurred = blurred[:, halo:-halo]  # windows centred on OUR rows
    mu_a, mu_b, e_aa, e_bb, e_ab = (
        blurred[..., i * c:(i + 1) * c] for i in range(5))
    mu_aa, mu_bb, mu_ab = mu_a * mu_a, mu_b * mu_b, mu_a * mu_b
    num = (2.0 * mu_ab + _C1) * (2.0 * (e_ab - mu_ab) + _C2)
    den = (mu_aa + mu_bb + _C1) * ((e_aa - mu_aa) + (e_bb - mu_bb) + _C2)
    local_sum = jnp.sum(num / den)
    global_sum = lax.psum(local_sum, axis)
    n_global = (num.size) * lax.axis_size(axis)  # uniform row blocks
    return 1.0 - global_sum / n_global


def _sp_apply(model, variables, image, *, train: bool, rngs=None,
              sp_strategy: str = "ring"):
    """The shared SP forward: derive this device's (row offset, full
    grid) from its ``seq`` position and run the module on its row slice
    with a sequence-parallel attention core.  Single definition so
    train and eval geometry cannot diverge.

    ``sp_strategy`` picks the core: 'ring' (K/V blocks on a ppermute
    ring) or 'ulysses' (two all-to-alls redistribute heads, full
    sequence per device — needs heads % seq == 0).  Either composes
    with ``model.attn_impl``: 'flash' runs the Pallas kernel inside
    the strategy (per visiting block for the ring, on the full
    sequence for ulysses), 'xla' keeps materialized scores.
    """
    if sp_strategy == "ring":
        core = ring_attention
    elif sp_strategy == "ulysses":
        from .ulysses import ulysses_attention

        core = ulysses_attention
    else:
        raise ValueError(f"mesh.sp_strategy must be 'ring' or "
                         f"'ulysses', got {sp_strategy!r}")
    local_rows = image.shape[1] // model.patch
    seq = lax.axis_size("seq")
    row_off = lax.axis_index("seq") * local_rows
    full_grid = (local_rows * seq, image.shape[2] // model.patch)
    return model.apply(
        variables, image, None, train=train,
        attn_fn=partial(core, axis_name="seq",
                        attn_impl=getattr(model, "attn_impl", "xla")),
        full_grid=full_grid, pos_row_offset=row_off,
        **({"rngs": rngs} if rngs is not None else {}))


def validate_sp_strategy(model, mesh: Mesh, sp_strategy: str) -> None:
    """Build-time geometry check shared by every SP entry point (train
    step, eval step — so test.py gets the friendly error too, not a
    mid-trace shard_map failure).  The runtime check inside
    ``ulysses_attention`` stays as the backstop for direct callers."""
    if sp_strategy == "ulysses":
        seq = mesh.shape.get("seq", 1)
        heads = getattr(model, "heads", 0)
        if heads % seq:
            raise ValueError(
                f"mesh.sp_strategy=ulysses needs heads % seq == 0, got "
                f"heads={heads} seq={seq} — use sp_strategy=ring for "
                "this head count")


def make_sp_eval_step(model, mesh: Mesh,
                      sp_strategy: str = "ring") -> Callable:
    """Sequence-parallel forward-only step: ``(variables, batch) ->
    probs`` with image rows sharded over ``seq`` and the SP attention
    core crossing the blocks — the eval/inference path for resolutions
    whose full-attention scores ([B,h,N,N]) exceed one chip's memory.
    Output probs come back sharded the same way; a host ``np.asarray``
    gathers them.  Math is identical to the single-device forward
    (both strategies are exact)."""
    validate_sp_strategy(model, mesh, sp_strategy)

    def eval_fn(variables, batch):
        outs = _sp_apply(model, variables, batch["image"], train=False,
                         sp_strategy=sp_strategy)
        return jax.nn.sigmoid(outs[0][..., 0].astype(jnp.float32))

    sharded = jax.shard_map(
        eval_fn,
        mesh=mesh,
        in_specs=(P(), P("data", "seq")),
        out_specs=P("data", "seq"),
        check_vma=False,
    )
    return jax.jit(sharded)


def wants_sp_eval(model, mesh) -> bool:
    """Should eval route through the sequence-parallel forward?  True
    on a seq-sharded mesh when the model is SP-capable (halo-free
    patchify with an injectable attention core — ``vit_sod``'s
    ``patch`` attribute is the capability marker).  Single predicate
    shared by test.py's evaluate() and fit()'s inline eval so the two
    can never route the same model differently."""
    return (mesh is not None and mesh.shape.get("seq", 1) > 1
            and hasattr(model, "patch"))


def sp_eval_batch_size(mesh: Mesh, batch_size: int) -> int:
    """Round an eval batch to the ``data``-axis divisor (rows shard
    over ``seq``, so only ``data`` constrains the batch dim)."""
    div = mesh.shape.get("data", 1)
    return max(1, batch_size // div) * div


def make_sp_eval_forward(model, mesh: Mesh, sp_strategy: str = "ring"):
    """Compile the SP eval step once; returns ``bind(variables) ->
    forward(batch) -> probs`` so callers whose variables change between
    sweeps (the inline train eval) rebind without retracing."""
    sp_forward = make_sp_eval_step(model, mesh, sp_strategy)

    def bind(variables):
        from .mesh import replicated_sharding

        variables = jax.device_put(variables, replicated_sharding(mesh))
        return lambda b: sp_forward(
            variables, jax.device_put(b, sp_batch_sharding(mesh)))

    return bind
