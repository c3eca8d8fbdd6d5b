"""Train engine tests (SURVEY.md §4): mesh, schedules, shard_map train
step on 8 virtual devices, and 1-device vs 8-device DP equivalence."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

from distributed_sod_project_tpu.configs.base import (
    LossConfig,
    MeshConfig,
    OptimConfig,
)
from distributed_sod_project_tpu.models.layers import ConvBNAct
from distributed_sod_project_tpu.parallel import (
    global_batch_array,
    make_mesh,
)
from distributed_sod_project_tpu.parallel.engine import (
    make_unified_train_step,
)
from distributed_sod_project_tpu.train import (
    build_optimizer,
    build_schedule,
    create_train_state,
    make_eval_step,
)


class TinyNet(nn.Module):
    """Minimal ConvBN model with the zoo call convention, for fast
    engine tests (full zoo models are exercised in test_models.py)."""

    axis_name: str = "data"

    @nn.compact
    def __call__(self, image, depth=None, *, train: bool = False):
        del depth
        x = ConvBNAct(8, axis_name=self.axis_name)(image, train)
        x = ConvBNAct(8, axis_name=self.axis_name)(x, train)
        logit = nn.Conv(1, (3, 3), padding="SAME")(x)
        return [logit.astype(jnp.float32)]


def _batch(n=8, hw=16, seed=0):
    rng = np.random.default_rng(seed)
    img = rng.normal(size=(n, hw, hw, 3)).astype(np.float32)
    # Learnable target: salient = bright pixels (function of the input,
    # so the overfit test measures optimization, not memorization).
    mask = (img.mean(-1, keepdims=True) > 0).astype(np.float32)
    return {"image": img, "mask": mask}


def _setup(mesh, total_steps=10, lr=0.1):
    model = TinyNet()
    ocfg = OptimConfig(lr=lr, warmup_steps=0)
    tx, sched = build_optimizer(ocfg, total_steps)
    state = create_train_state(jax.random.key(0), model, tx, _batch(2))
    lcfg = LossConfig(ssim_window=5)
    step = make_unified_train_step(model, lcfg, tx, mesh, preset="dp",
                                   schedule=sched, donate=False)
    return model, state, step


# ---------------------------------------------------------------- mesh


def test_mesh_default_all_data(eight_devices):
    mesh = make_mesh(MeshConfig(), eight_devices)
    assert mesh.devices.shape == (8, 1, 1)
    assert mesh.axis_names == ("data", "model", "seq")


def test_mesh_mixed_axes(eight_devices):
    mesh = make_mesh(MeshConfig(data=-1, model=2), eight_devices)
    assert mesh.devices.shape == (4, 2, 1)


def test_mesh_bad_sizes(eight_devices):
    with pytest.raises(ValueError):  # wants more devices than exist
        make_mesh(MeshConfig(data=16), eight_devices)
    with pytest.raises(ValueError):  # two wildcard axes
        make_mesh(MeshConfig(data=-1, model=-1), eight_devices)


def test_mesh_pinned_subset(eight_devices):
    # A fully pinned config smaller than the host (e.g. the single-device
    # reference config on an 8-chip pod) runs on the first N devices.
    mesh = make_mesh(MeshConfig(data=3), eight_devices)
    assert mesh.devices.size == 3


# ----------------------------------------------------------- schedules


def test_poly_schedule_endpoints():
    ocfg = OptimConfig(lr=0.01, schedule="poly", poly_power=0.9)
    s = build_schedule(ocfg, 100)
    assert float(s(0)) == pytest.approx(0.01)
    assert float(s(100)) == pytest.approx(0.0, abs=1e-8)
    assert 0.0 < float(s(50)) < 0.01


def test_warmup_ramps():
    ocfg = OptimConfig(lr=0.01, warmup_steps=10)
    s = build_schedule(ocfg, 100)
    assert float(s(0)) == pytest.approx(0.0)
    assert float(s(5)) == pytest.approx(0.005)
    assert float(s(10)) == pytest.approx(0.01)


# ---------------------------------------------------------- train step


def test_train_step_runs_and_updates(eight_devices):
    mesh = make_mesh(MeshConfig(), eight_devices)
    _, state, step = _setup(mesh)
    batch = global_batch_array(_batch(8), mesh)
    new_state, metrics = step(state, batch)
    assert int(new_state.step) == 1
    for k in ("total", "bce", "iou", "ssim", "grad_norm", "lr"):
        assert np.isfinite(float(metrics[k])), k
    assert float(metrics["lr"]) == pytest.approx(0.1)
    # params moved
    diff = jax.tree_util.tree_map(
        lambda a, b: float(jnp.abs(a - b).max()), state.params, new_state.params
    )
    assert max(jax.tree_util.tree_leaves(diff)) > 0
    # batch_stats updated and replicated-consistent
    old = jax.tree_util.tree_leaves(state.batch_stats)
    new = jax.tree_util.tree_leaves(new_state.batch_stats)
    assert any(not np.allclose(a, b) for a, b in zip(old, new))


def test_dp_equivalence_1_vs_8_devices(eight_devices):
    """Same global batch through a 1-device and an 8-device mesh must
    produce identical updates (gradient pmean + SyncBN correctness)."""
    mesh8 = make_mesh(MeshConfig(), eight_devices)
    mesh1 = make_mesh(MeshConfig(data=1), eight_devices[:1])
    _, state, step8 = _setup(mesh8)
    _, _, step1 = _setup(mesh1)

    b = _batch(8, seed=3)
    s8, m8 = step8(state, global_batch_array(b, mesh8))
    s1, m1 = step1(state, global_batch_array(b, mesh1))

    assert float(m8["total"]) == pytest.approx(float(m1["total"]), rel=1e-5)
    chex_tol = 1e-5
    for a, b_ in zip(
        jax.tree_util.tree_leaves(s8.params), jax.tree_util.tree_leaves(s1.params)
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=chex_tol)
    for a, b_ in zip(
        jax.tree_util.tree_leaves(s8.batch_stats),
        jax.tree_util.tree_leaves(s1.batch_stats),
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=chex_tol)


def test_first_step_metrics_do_not_depend_on_lr(eight_devices):
    """Step 1's loss and gradient norm are those of the INITIAL
    parameters: the learning rate enters only the update that follows.
    chip_smoke.py --four-chips leans on this — it compares step 1 of
    two meshes under a turned-down lr and reads the result as the
    config's own-lr step 1."""
    mesh = make_mesh(MeshConfig(), eight_devices)
    b = global_batch_array(_batch(8, seed=3), mesh)
    first = {}
    for lr in (0.1, 1e-5):
        _, state, step = _setup(mesh, lr=lr)
        new_state, m = step(state, b)
        first[lr] = (float(m["total"]), float(m["grad_norm"]),
                     jax.tree_util.tree_leaves(new_state.params)[0])
    assert first[0.1][:2] == first[1e-5][:2]
    assert not np.array_equal(first[0.1][2], first[1e-5][2])


def test_overfit_smoke(eight_devices):
    """20 steps on one fixed batch must cut the loss (SURVEY.md §4
    integration prescription)."""
    mesh = make_mesh(MeshConfig(), eight_devices)
    _, state, step = _setup(mesh, total_steps=40, lr=0.05)
    batch = global_batch_array(_batch(8, seed=7), mesh)
    losses = []
    for _ in range(20):
        state, metrics = step(state, batch)
        losses.append(float(metrics["total"]))
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0] * 0.8, losses


def test_eval_step_shapes(eight_devices):
    mesh = make_mesh(MeshConfig(), eight_devices)
    model, state, _ = _setup(mesh)
    ev = make_eval_step(model, mesh)
    batch = global_batch_array(_batch(8), mesh)
    probs = ev(state, batch)
    assert probs.shape == (8, 16, 16)
    p = np.asarray(probs)
    assert p.min() >= 0.0 and p.max() <= 1.0


@pytest.mark.slow
def test_remat_step_matches_baseline(eight_devices):
    """jax.checkpoint must not change the numbers, only the memory."""
    from distributed_sod_project_tpu.configs import get_config
    from distributed_sod_project_tpu.models import build_model
    from distributed_sod_project_tpu.parallel.mesh import (
        batch_sharding, make_mesh, replicated_sharding)
    from distributed_sod_project_tpu.train import (
        build_optimizer, create_train_state)

    cfg = get_config("minet_vgg16_ref")
    model = build_model(cfg.model.__class__(
        name="minet", backbone="vgg16", sync_bn=True,
        compute_dtype="float32"))
    mesh = make_mesh(MeshConfig(data=8), eight_devices)
    tx, sched = build_optimizer(cfg.optim, 10)
    rng = np.random.RandomState(0)
    batch = {"image": jnp.asarray(rng.randn(8, 32, 32, 3), jnp.float32),
             "mask": jnp.asarray((rng.rand(8, 32, 32, 1) > 0.5),
                                 jnp.float32)}
    state0 = create_train_state(jax.random.key(0), model, tx, batch)
    outs = {}
    cases = [(False, "none"), (True, "none"), (True, "dots"),
             (True, "dots_no_batch")]
    for remat, policy in cases:
        state = jax.device_put(state0, replicated_sharding(mesh))
        step = make_unified_train_step(model, cfg.loss, tx, mesh,
                                       preset="dp", schedule=sched,
                               donate=False, remat=remat,
                               remat_policy=policy)
        db = jax.device_put(batch, batch_sharding(mesh))
        _, metrics = step(state, db)
        outs[(remat, policy)] = float(metrics["total"])
    base = outs[(False, "none")]
    for key, val in outs.items():
        assert val == pytest.approx(base, rel=1e-6), key


def test_remat_policy_validation():
    from distributed_sod_project_tpu.train.step import resolve_remat_policy

    with pytest.raises(ValueError, match="remat_policy"):
        resolve_remat_policy("everything")


def test_grad_accumulation_matches_large_batch():
    """k micro-steps at B/k with accum_steps=k == one step at B."""
    import dataclasses

    import optax

    from distributed_sod_project_tpu.configs.base import OptimConfig
    from distributed_sod_project_tpu.train import build_optimizer

    # plain quadratic: params p, grad = p - target
    p0 = jnp.asarray([2.0, -3.0])

    ocfg = OptimConfig(optimizer="sgd", lr=0.1, momentum=0.0,
                      weight_decay=0.0, nesterov=False, schedule="constant")
    tx_big, _ = build_optimizer(ocfg, 10)
    tx_acc, _ = build_optimizer(dataclasses.replace(ocfg, accum_steps=2), 10)

    grads = [jnp.asarray([1.0, 2.0]), jnp.asarray([3.0, -2.0])]
    mean_grad = (grads[0] + grads[1]) / 2

    s = tx_big.init(p0)
    upd, _ = tx_big.update(mean_grad, s, p0)
    p_big = optax.apply_updates(p0, upd)

    s = tx_acc.init(p0)
    p = p0
    for g in grads:
        upd, s = tx_acc.update(g, s, p)
        p = optax.apply_updates(p, upd)
    np.testing.assert_allclose(np.asarray(p), np.asarray(p_big), atol=1e-6)


def test_ema_tracks_and_eval_uses_it(eight_devices):
    """EMA follows e' = d·e + (1−d)·p each step, and the eval step
    runs on the EMA weights, not the raw ones."""
    from distributed_sod_project_tpu.parallel.mesh import (
        batch_sharding, replicated_sharding)

    mesh = make_mesh(MeshConfig(data=8), eight_devices)
    model = TinyNet()
    ocfg = OptimConfig(lr=0.5, warmup_steps=0, ema_decay=0.5)
    tx, sched = build_optimizer(ocfg, 10)
    state = create_train_state(jax.random.key(0), model, tx, _batch(2),
                               ema=True)
    state = jax.device_get(state)
    lcfg = LossConfig(ssim_window=5)
    step = make_unified_train_step(model, lcfg, tx, mesh, preset="dp",
                                   schedule=sched, donate=False,
                           ema_decay=0.5)

    batch = jax.device_put(_batch(8), batch_sharding(mesh))
    dstate = jax.device_put(state, replicated_sharding(mesh))
    s1, _ = step(dstate, batch)

    # Oracle: d·p0 + (1−d)·p1 (EMA seeded from the init params).
    p0 = jax.tree_util.tree_leaves(state.params)
    p1 = jax.tree_util.tree_leaves(jax.device_get(s1.params))
    ema = jax.tree_util.tree_leaves(jax.device_get(s1.ema_params))
    for a, b, e in zip(p0, p1, ema):
        np.testing.assert_allclose(e, 0.5 * a + 0.5 * b, rtol=1e-5,
                                   atol=1e-6)

    # eval_variables() must pick the EMA tree.
    ev = s1.eval_variables()
    got = jax.tree_util.tree_leaves(jax.device_get(ev["params"]))
    for g, e in zip(got, ema):
        np.testing.assert_allclose(g, e)

    # Disabled EMA stays None end-to-end.
    state_off = create_train_state(jax.random.key(0), model, tx, _batch(2))
    assert state_off.ema_params is None
    step_off = make_unified_train_step(model, lcfg, tx, mesh, preset="dp",
                                   schedule=sched, donate=False)
    s_off, _ = step_off(jax.device_put(state_off, replicated_sharding(mesh)),
                        batch)
    assert s_off.ema_params is None


def test_multiscale_step_resizes_on_device(eight_devices):
    """A scale_hw step trains at the scaled size from the same loader
    batch, producing finite loss and updated params."""
    from distributed_sod_project_tpu.parallel.mesh import (
        batch_sharding, replicated_sharding)

    mesh = make_mesh(MeshConfig(data=8), eight_devices)
    model = TinyNet()
    tx, sched = build_optimizer(OptimConfig(lr=0.1, warmup_steps=0), 10)
    state = create_train_state(jax.random.key(0), model, tx, _batch(2))
    lcfg = LossConfig(ssim_window=5)
    step = make_unified_train_step(model, lcfg, tx, mesh, preset="dp",
                                   schedule=sched, donate=False,
                           scale_hw=(8, 8))

    batch = jax.device_put(_batch(8, hw=16), batch_sharding(mesh))
    dstate = jax.device_put(state, replicated_sharding(mesh))
    s1, metrics = step(dstate, batch)
    assert np.isfinite(float(metrics["total"]))
    # Params moved.
    a = jax.tree_util.tree_leaves(jax.device_get(dstate.params))[0]
    b = jax.tree_util.tree_leaves(jax.device_get(s1.params))[0]
    assert not np.allclose(a, b)


def test_ema_every_gates_blend_under_accumulation(eight_devices):
    """Under accum_steps=k the EMA blends only on micro-steps where the
    params actually change (tree-diff gate), so the effective decay
    stays ema_decay — not ema_decay**k — and stays correct even when
    apply_if_finite rejects micro-steps."""
    from distributed_sod_project_tpu.parallel.mesh import (
        batch_sharding, replicated_sharding)

    mesh = make_mesh(MeshConfig(data=8), eight_devices)
    model = TinyNet()
    tx, sched = build_optimizer(
        OptimConfig(lr=0.5, warmup_steps=0, accum_steps=2), 10)
    state = jax.device_get(
        create_train_state(jax.random.key(0), model, tx, _batch(2),
                           ema=True))
    lcfg = LossConfig(ssim_window=5)
    step = make_unified_train_step(model, lcfg, tx, mesh, preset="dp",
                                   schedule=sched, donate=False,
                           ema_decay=0.5)
    batch = jax.device_put(_batch(8), batch_sharding(mesh))

    s = jax.device_put(state, replicated_sharding(mesh))
    s, _ = step(s, batch)  # micro-step 1: accumulate only → EMA frozen
    ema1 = jax.tree_util.tree_leaves(jax.device_get(s.ema_params))
    p0 = jax.tree_util.tree_leaves(state.params)
    for e, a in zip(ema1, p0):
        np.testing.assert_allclose(e, a)

    s, _ = step(s, batch)  # micro-step 2: blends exactly once
    ema2 = jax.tree_util.tree_leaves(jax.device_get(s.ema_params))
    p2 = jax.tree_util.tree_leaves(jax.device_get(s.params))
    for e, a, b in zip(ema2, p0, p2):
        np.testing.assert_allclose(e, 0.5 * a + 0.5 * b, rtol=1e-5,
                                   atol=1e-6)


def test_skip_nonfinite_guards_updates():
    """A NaN gradient leaves params untouched; finite ones apply."""
    import dataclasses

    import optax

    ocfg = OptimConfig(optimizer="sgd", lr=0.1, momentum=0.0,
                       weight_decay=0.0, nesterov=False,
                       schedule="constant", skip_nonfinite=3)
    tx, _ = build_optimizer(ocfg, 10)
    p0 = jnp.asarray([1.0, 2.0])
    s = tx.init(p0)

    upd, s = tx.update(jnp.asarray([jnp.nan, 1.0]), s, p0)
    p1 = optax.apply_updates(p0, upd)
    np.testing.assert_allclose(np.asarray(p1), np.asarray(p0))  # skipped

    upd, s = tx.update(jnp.asarray([1.0, 1.0]), s, p1)
    p2 = optax.apply_updates(p1, upd)
    np.testing.assert_allclose(np.asarray(p2), np.asarray(p0) - 0.1,
                               atol=1e-6)


def test_skip_nonfinite_step_reports_counter_and_freezes(eight_devices):
    """A NaN batch: params/EMA frozen, notfinite_count=1 in metrics; a
    following good batch applies and resets the counter."""
    from distributed_sod_project_tpu.parallel.mesh import (
        batch_sharding, replicated_sharding)

    mesh = make_mesh(MeshConfig(data=8), eight_devices)
    model = TinyNet()
    tx, sched = build_optimizer(
        OptimConfig(lr=0.1, warmup_steps=0, skip_nonfinite=3), 10)
    state = jax.device_get(
        create_train_state(jax.random.key(0), model, tx, _batch(2),
                           ema=True))
    lcfg = LossConfig(ssim_window=5)
    step = make_unified_train_step(model, lcfg, tx, mesh, preset="dp",
                                   schedule=sched, donate=False,
                           ema_decay=0.5)

    bad = _batch(8)
    bad["image"][0, 0, 0, 0] = np.inf
    s = jax.device_put(state, replicated_sharding(mesh))
    s, m = step(s, jax.device_put(bad, batch_sharding(mesh)))
    assert float(m["notfinite_count"]) == 1.0
    for a, b in zip(jax.tree_util.tree_leaves(state.params),
                    jax.tree_util.tree_leaves(jax.device_get(s.params))):
        np.testing.assert_array_equal(a, b)  # bad update NOT applied
    for a, b in zip(jax.tree_util.tree_leaves(state.ema_params),
                    jax.tree_util.tree_leaves(jax.device_get(s.ema_params))):
        np.testing.assert_array_equal(a, b)  # EMA gate held too

    s, m = step(s, jax.device_put(_batch(8), batch_sharding(mesh)))
    assert float(m["notfinite_count"]) == 0.0  # reset by a finite step
    changed = any(
        not np.array_equal(a, b) for a, b in zip(
            jax.tree_util.tree_leaves(state.params),
            jax.tree_util.tree_leaves(jax.device_get(s.params))))
    assert changed


def test_lars_optimizer_trains(eight_devices):
    """LARS (large-batch DP) builds and reduces loss like the others."""
    from distributed_sod_project_tpu.parallel.mesh import (
        batch_sharding, replicated_sharding)

    mesh = make_mesh(MeshConfig(data=8), eight_devices)
    model = TinyNet()
    tx, sched = build_optimizer(
        OptimConfig(optimizer="lars", lr=1.0, warmup_steps=0,
                    weight_decay=1e-4), 20)
    state = create_train_state(jax.random.key(0), model, tx, _batch(2))
    lcfg = LossConfig(ssim_window=5)
    step = make_unified_train_step(model, lcfg, tx, mesh, preset="dp",
                                   schedule=sched, donate=False)
    batch = jax.device_put(_batch(8, seed=5), batch_sharding(mesh))
    s = jax.device_put(state, replicated_sharding(mesh))
    losses = []
    for _ in range(10):
        s, m = step(s, batch)
        losses.append(float(m["total"]))
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]
    # Biases must train too: standard LARS exempts rank<=1 params from
    # trust-ratio scaling (a default-masked optax.lars freezes them).
    p0 = {jax.tree_util.keystr(p): v for p, v in
          jax.tree_util.tree_leaves_with_path(state.params)}
    for path, v in jax.tree_util.tree_leaves_with_path(
            jax.device_get(s.params)):
        key = jax.tree_util.keystr(path)
        if v.ndim == 1 and "bias" in key:
            assert not np.allclose(v, p0[key], atol=1e-5), key


def test_layer_decay_scales_updates_per_layer():
    """optim.layer_decay: heads full LR, block i at decay^(n+1-(i+1)),
    embedding deepest — verified on a vit-shaped param tree with unit
    gradients through the full adamw chain."""
    import optax

    from distributed_sod_project_tpu.train.optim import (
        build_optimizer, scale_by_layer_decay)

    params = {
        "patch_embed": {"kernel": jnp.ones((2, 2))},
        "pos_embed": jnp.ones((4, 2)),
        "block0": {"q": {"kernel": jnp.ones((2, 2))}},
        "block1": {"q": {"kernel": jnp.ones((2, 2))}},
        "head": {"kernel": jnp.ones((2, 2))},
    }
    grads = jax.tree.map(jnp.ones_like, params)

    # Transform-level: exact expected scales (n_blocks=2 -> top=3).
    tx = scale_by_layer_decay(0.5)
    scaled, _ = tx.update(grads, tx.init(params))
    assert float(scaled["head"]["kernel"][0, 0]) == 1.0
    assert float(scaled["block1"]["q"]["kernel"][0, 0]) == 0.5
    assert float(scaled["block0"]["q"]["kernel"][0, 0]) == 0.25
    assert float(scaled["patch_embed"]["kernel"][0, 0]) == 0.125
    assert float(scaled["pos_embed"][0, 0]) == 0.125

    # Builder-level: the chain applies it (update magnitudes ordered).
    tx, _ = build_optimizer(
        OptimConfig(optimizer="adamw", lr=1e-3, weight_decay=0.0,
                    warmup_steps=0, layer_decay=0.5), 10)
    upd, _ = tx.update(grads, tx.init(params), params)
    head = abs(float(upd["head"]["kernel"][0, 0]))
    b1 = abs(float(upd["block1"]["q"]["kernel"][0, 0]))
    b0 = abs(float(upd["block0"]["q"]["kernel"][0, 0]))
    emb = abs(float(upd["patch_embed"]["kernel"][0, 0]))
    assert head > b1 > b0 > emb > 0
    np.testing.assert_allclose(b1 / head, 0.5, rtol=1e-5)
    np.testing.assert_allclose(b0 / head, 0.25, rtol=1e-5)


def test_layer_decay_rejected_for_lars():
    from distributed_sod_project_tpu.train.optim import build_optimizer

    with pytest.raises(ValueError, match="layer_decay"):
        build_optimizer(OptimConfig(optimizer="lars", layer_decay=0.9), 10)
