"""The unified partition-rule sharding engine (ISSUE 18).

Four contracts, each asserted here:

- **Rule matching** (parallel/rules.py): first-match-wins regex tables
  over '/'-joined param paths on REAL zoo trees (abstract init — no
  arrays), strict mode loud on unmatched leaves, the FSDP fallback
  sharding the largest divisible axis.
- **Engine self-consistency bitwise** (parallel/engine.py): the ONE
  rule-driven step builder (the only builder — ISSUE 19 deleted the
  legacy trio) agrees with itself across every execution strategy
  that must not change the arithmetic: bucketed/fused reduction vs
  monolithic pmean, scan-chunked vs sequential dispatch, rules-table
  TP shardings vs the hand Megatron layout, SP vs plain DP, and the
  shipped FSDP preset vs DP at rtol<=2e-6 — final state AND per-step
  metric streams, including accum_steps>1, steps_per_dispatch>1, EMA,
  skip_nonfinite, and health metrics.  The ``rules_smoke`` subset is
  re-proven every tools/t1.sh round.
- **Hierarchical ICI×DCN reduction** (``mesh.data_hosts``): the
  two-level intra-host reduce-scatter → inter-host all-reduce →
  intra-host all-gather is bitwise the flat psum on integer wire
  values (including the int8_ef integer wire) and allclose on floats.
- **int8_ef error feedback** (``parallel.grad_compression``): the
  residual is required by the builder, seeded by
  ``seed_comm_residual``, carried across steps, keeps the compressed
  trajectory within the grad-gate budget, and survives a checkpoint
  round-trip bitwise.
- **ZeRO** (``parallel.zero``): optimizer moments + EMA sharded over
  the ``data`` axis (spec correctness + actual placement), priced HBM
  saving positive, and the zero=1 trajectory bitwise the zero=0 GSPMD
  trajectory (weight-update sharding must not change the update).
- **Bucketed allreduce** (``parallel.comm_bucket_mb``): every gradient
  leaf in exactly one backward-ordered bucket, the fused flat-buffer
  psum bitwise ``lax.pmean``, and the bucket count VISIBLE in lowered
  HLO (B buckets ⇒ B more ``all_reduce`` ops than one flat bucket).
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn
from jax.sharding import NamedSharding, PartitionSpec as P

from distributed_sod_project_tpu.configs import get_config
from distributed_sod_project_tpu.configs.base import (
    LossConfig, MeshConfig, OptimConfig, ParallelConfig,
    validate_parallel)
from distributed_sod_project_tpu.models.layers import ConvBNAct
from distributed_sod_project_tpu.parallel import make_mesh
from distributed_sod_project_tpu.parallel.engine import (
    comm_plan, effective_zero, make_unified_train_step,
    seed_comm_residual, select_preset)
from distributed_sod_project_tpu.parallel.mesh import (
    batch_sharding, global_batch_array, replicated_sharding)
from distributed_sod_project_tpu.parallel.rules import (
    DEFAULT_TP_RULES, REPLICATE_REST, bucketed_pmean, fsdp_fallback_rule,
    grad_buckets, match_partition_rules, shard_state_by_rules,
    sharded_tree_bytes, state_specs, tree_bytes, tree_paths,
    zero_state_specs)
from distributed_sod_project_tpu.train import (
    build_optimizer, create_train_state)


class TinyNet(nn.Module):
    """Conv+SyncBN micro-model with the zoo call convention (the same
    harness as test_step_chunking.py)."""

    axis_name: str = "data"

    @nn.compact
    def __call__(self, image, depth=None, *, train: bool = False):
        del depth
        x = ConvBNAct(8, axis_name=self.axis_name)(image, train)
        logit = nn.Conv(1, (3, 3), padding="SAME")(x)
        return [logit.astype(jnp.float32)]


def _vit_tiny():
    from distributed_sod_project_tpu.models.vit_sod import ViTSOD

    return ViTSOD(patch=8, dim=32, depth=2, heads=2, mlp_ratio=2)


def _batch(n=8, hw=16, seed=0):
    rng = np.random.default_rng(seed)
    img = rng.normal(size=(n, hw, hw, 3)).astype(np.float32)
    mask = (img.mean(-1, keepdims=True) > 0).astype(np.float32)
    return {"image": img, "mask": mask}


def _leaves(tree):
    return [(jax.tree_util.keystr(p), np.asarray(v)) for p, v in
            jax.tree_util.tree_leaves_with_path(jax.device_get(tree))]


def assert_trees_bitwise(a, b, context=""):
    for (pa, xa), (pb, xb) in zip(_leaves(a), _leaves(b)):
        assert np.array_equal(xa, xb, equal_nan=True), (
            f"{context}: leaf {pa} not bitwise equal")


def assert_trees_close(a, b, context="", rtol=2e-6, atol=1e-7):
    for (pa, xa), (pb, xb) in zip(_leaves(a), _leaves(b)):
        np.testing.assert_allclose(
            xa, xb, rtol=rtol, atol=atol,
            err_msg=f"{context}: leaf {pa} beyond tolerance")


def _metrics_bitwise(ma, mb, context=""):
    ma, mb = jax.device_get(ma), jax.device_get(mb)
    assert set(ma) == set(mb), f"{context}: metric keys differ"
    for k in ma:
        assert np.array_equal(np.asarray(ma[k]), np.asarray(mb[k]),
                              equal_nan=True), (
            f"{context}: metric {k!r}: {ma[k]} != {mb[k]}")


def _abstract_params(config_name, hw=64):
    """A real zoo param tree without allocating it (shape-only init)."""
    from distributed_sod_project_tpu.models import build_model

    model = build_model(get_config(config_name).model)
    variables = jax.eval_shape(
        lambda k, img: model.init(k, img, None, train=False),
        jax.random.key(0), jnp.zeros((1, hw, hw, 3), jnp.float32))
    return variables["params"]


# ------------------------------------------------------------ matching


def test_rule_matching_first_match_wins(eight_devices):
    mesh = make_mesh(MeshConfig(data=2, model=2), eight_devices[:4])
    params = _abstract_params("vit_sod_sp", hw=64)
    specs = match_partition_rules(DEFAULT_TP_RULES + (REPLICATE_REST,),
                                  params, mesh)
    flat = {path: spec for path, spec in
            zip(tree_paths(params), jax.tree_util.tree_leaves(
                specs, is_leaf=lambda x: isinstance(x, P)))}
    # The Megatron layout actually landed: at least one column shard.
    assert any("model" in str(s) for s in flat.values())
    # First-match-wins: a replicate-everything rule prepended must
    # shadow the TP table entirely.
    shadowed = match_partition_rules(
        ((r".*", P()),) + DEFAULT_TP_RULES, params, mesh)
    assert all(s == P() for s in jax.tree_util.tree_leaves(
        shadowed, is_leaf=lambda x: isinstance(x, P)))


def test_rule_matching_real_zoo_trees_total(eight_devices):
    """Every preset table is total (with its replicate-rest tail) on
    real zoo param trees — no silent holes, strict mode included."""
    mesh = make_mesh(MeshConfig(), eight_devices)
    for config_name in ("minet_r50_dp", "minet_vgg16_ref", "vit_sod_sp"):
        params = _abstract_params(config_name)
        # strict + total table: must NOT raise.
        match_partition_rules(DEFAULT_TP_RULES + (REPLICATE_REST,),
                              params, mesh, strict=True)


def test_rule_matching_strict_is_loud_on_unmatched(eight_devices):
    mesh = make_mesh(MeshConfig(), eight_devices)
    params = _abstract_params("minet_vgg16_ref")
    with pytest.raises(ValueError, match="matched by NO"):
        match_partition_rules((), params, mesh, strict=True)


def test_fsdp_fallback_shards_largest_divisible_axis(eight_devices):
    mesh = make_mesh(MeshConfig(), eight_devices)  # data=8
    fb = fsdp_fallback_rule(mesh, min_leaf_size=64)
    big = jax.ShapeDtypeStruct((48, 64), jnp.float32)
    assert fb("a/kernel", big) == P(None, "data")  # 64 > 48, both /8
    small = jax.ShapeDtypeStruct((8,), jnp.float32)
    assert fb("a/bias", small) == P()  # under min_leaf_size
    odd = jax.ShapeDtypeStruct((33, 65), jnp.float32)
    assert fb("a/odd", odd) == P()  # nothing divides 8
    # and wired through match_partition_rules for unmatched leaves:
    specs = match_partition_rules((), {"w": big}, mesh, fallback=fb)
    assert specs["w"] == P(None, "data")


# ------------------------------------------------------------- buckets


def test_grad_buckets_partition_invariants():
    shapes = [((64, 64), jnp.float32), ((64,), jnp.float32),
              ((3, 3, 8, 8), jnp.float32), ((128, 16), jnp.float32),
              ((1,), jnp.float32)]
    buckets = grad_buckets(shapes, 2048)
    got = [i for b in buckets for i in b]
    # Every leaf in EXACTLY one bucket, in backward (reversed) order.
    assert sorted(got) == list(range(len(shapes)))
    assert got == list(range(len(shapes) - 1, -1, -1))
    # Every bucket except possibly the last reaches the target.
    for b in buckets[:-1]:
        assert sum(int(np.prod(s or (1,))) * 4 for s, _ in
                   (shapes[i] for i in b)) >= 2048
    # Monolithic spelling: one bucket, same order.
    assert grad_buckets(shapes, 0) == [[4, 3, 2, 1, 0]]
    assert grad_buckets([], 2048) == []


def test_bucketed_pmean_bitwise_lax_pmean(eight_devices):

    mesh = make_mesh(MeshConfig(), eight_devices)
    tree = {"a": np.linspace(-3, 3, 8 * 64, dtype=np.float32
                             ).reshape(8, 64),
            "b": np.float32(np.arange(8 * 7).reshape(8, 7) * 0.13)}
    sharded = {k: jax.device_put(v, NamedSharding(mesh, P("data")))
               for k, v in tree.items()}

    def ref(t):
        return jax.lax.pmean(t, "data")

    def bucketed(t):
        return bucketed_pmean(t, "data", 64)

    run = lambda f: jax.device_get(jax.jit(jax.shard_map(  # noqa: E731
        f, mesh=mesh, in_specs=(P("data"),), out_specs=P("data"),
        check_vma=False))(sharded))
    a, b = run(ref), run(bucketed)
    for k in tree:
        assert np.array_equal(a[k], b[k]), f"leaf {k} not bitwise"


def test_bucketed_allreduce_hlo_bucket_count(eight_devices):
    """The countable structure signal: a B-bucket plan lowers to
    exactly B−1 more ``stablehlo.all_reduce`` ops than the one-flat-
    bucket plan, and far fewer than the per-leaf monolithic pmean —
    the same invariant tools/hlo_guard.py's comm arms gate on the
    flagship."""
    mesh = make_mesh(MeshConfig(), eight_devices)
    model = _vit_tiny()
    tx, sched = build_optimizer(OptimConfig(lr=0.05, warmup_steps=0), 10)
    state = jax.device_put(
        create_train_state(jax.random.key(0), model, tx,
                           _batch(2, hw=32)),
        replicated_sharding(mesh))
    batch = global_batch_array(_batch(8, hw=32), mesh)
    lcfg = LossConfig(ssim=0.0)

    def n_all_reduce(comm_bucket_mb):
        step = make_unified_train_step(
            model, lcfg, tx, mesh, preset="dp", schedule=sched,
            donate=False, comm_bucket_mb=comm_bucket_mb)
        return len(re.findall(r"stablehlo\.all_reduce\b",
                              step.lower(state, batch).as_text()))

    shapes = [(g.shape, g.dtype) for g in
              jax.tree_util.tree_leaves(state.params)]
    bucket_bytes = int(0.05 * 2 ** 20)
    n_buckets = len(grad_buckets(shapes, bucket_bytes))
    assert n_buckets >= 2
    mono, flat, bucketed = n_all_reduce(0.0), n_all_reduce(1e5), \
        n_all_reduce(0.05)
    assert bucketed - flat == n_buckets - 1
    assert mono > bucketed  # fusion collapsed the per-leaf reduces


# ------------------------------------------- engine DP contracts


def _dp_setup(eight_devices):
    mesh = make_mesh(MeshConfig(), eight_devices)
    model = TinyNet()
    # The carries the step must thread exactly: MultiSteps
    # accumulation, the apply_if_finite failure counter, EMA.
    tx, sched = build_optimizer(
        OptimConfig(lr=0.1, warmup_steps=0, ema_decay=0.5,
                    accum_steps=2, skip_nonfinite=3), 10)
    state = jax.device_put(
        create_train_state(jax.random.key(0), model, tx, _batch(2),
                           ema=True),
        replicated_sharding(mesh))
    return mesh, model, tx, sched, state


@pytest.mark.parametrize("comm_bucket_mb", [0.001, 1e5])
def test_dp_bucketed_reduce_bitwise_rules_smoke(comm_bucket_mb,
                                                eight_devices):
    """t1.sh sharding-equivalence smoke: the engine's fused flat-buffer
    reduction (many small buckets AND one flat bucket) is bitwise the
    monolithic per-leaf pmean step — state and metric streams,
    rich-optim carries + health metrics on, a NaN batch mid-run
    exercising skip_nonfinite."""
    mesh, model, tx, sched, state = _dp_setup(eight_devices)
    lcfg = LossConfig(ssim_window=5)
    mono = make_unified_train_step(
        model, lcfg, tx, mesh, preset="dp", schedule=sched,
        donate=False, ema_decay=0.5, health=True)
    fused = make_unified_train_step(
        model, lcfg, tx, mesh, preset="dp", schedule=sched,
        donate=False, ema_decay=0.5, health=True,
        comm_bucket_mb=comm_bucket_mb)
    sl, sr = state, state
    for i in range(3):
        host = _batch(8, seed=i)
        if i == 1:
            host["image"][0, 0, 0, 0] = np.nan  # skip_nonfinite carry
        batch = global_batch_array(host, mesh)
        sl, ml = mono(sl, batch)
        sr, mr = fused(sr, batch)
        _metrics_bitwise(ml, mr, f"DP step {i} (bucket={comm_bucket_mb})")
    assert_trees_bitwise(sl, sr, f"DP state (bucket={comm_bucket_mb})")


def test_dp_rules_chunked_bitwise(eight_devices):
    """steps_per_dispatch>1 through the engine: the ONE chunking seam
    — scan(2) over a stacked chunk is bitwise two dispatches of the
    degenerate scan(1) program, metric streams stacked (k,)."""
    from distributed_sod_project_tpu.train.step import chunk_batch_spec

    mesh, model, tx, sched, state = _dp_setup(eight_devices)
    lcfg = LossConfig(ssim_window=5)
    ref = make_unified_train_step(
        model, lcfg, tx, mesh, preset="dp", schedule=sched,
        donate=False, ema_decay=0.5, health=True, _always_scan=True)
    rules = make_unified_train_step(
        model, lcfg, tx, mesh, preset="dp", schedule=sched,
        donate=False, ema_decay=0.5, health=True, steps_per_dispatch=2)
    batches = [_batch(8, seed=i) for i in range(2)]
    stacked = {k: np.stack([b[k] for b in batches])
               for k in batches[0]}
    chunk = global_batch_array(stacked, mesh,
                               spec=chunk_batch_spec(P("data")))
    sl, ms = state, []
    for b in batches:
        one = {k: v[None] for k, v in b.items()}
        sl, m = ref(sl, global_batch_array(
            one, mesh, spec=chunk_batch_spec(P("data"))))
        ms.append(jax.device_get(
            jax.tree_util.tree_map(lambda x: x[0], m)))
    sr, mr = rules(state, chunk)
    assert np.asarray(jax.device_get(mr)["total"]).shape == (2,)
    mr_host = jax.device_get(mr)
    for i, m_i in enumerate(ms):
        _metrics_bitwise(m_i, jax.tree_util.tree_map(
            lambda x, i=i: np.asarray(x)[i], mr_host),
            f"DP chunked step {i}")
    assert_trees_bitwise(sl, sr, "DP chunked state")
    # k=1 identity: the engine's unchunked step IS the plain callable
    # (body is step_fn), same as the legacy contract.
    plain = make_unified_train_step(model, lcfg, tx, mesh, preset="dp",
                                    schedule=sched, donate=False)
    s1, m1 = plain(state, global_batch_array(batches[0], mesh))
    assert np.asarray(jax.device_get(m1)["total"]).ndim == 0


# ---------------------------------------- engine TP / SP contracts


def test_tp_rules_sharding_paths_bitwise(eight_devices):
    """The rule table IS the Megatron layout: the SAME engine TP step,
    started once from tp.shard_state's hand-written shardings and once
    from shard_state_by_rules' table-driven shardings, is bitwise over
    a 2-step trajectory — state and metric streams."""
    from distributed_sod_project_tpu.parallel.tp import shard_state

    model = _vit_tiny()
    mesh = make_mesh(MeshConfig(data=2, model=2), eight_devices[:4])
    tx, sched = build_optimizer(OptimConfig(lr=0.05, warmup_steps=0), 10)
    state0 = jax.device_get(
        create_train_state(jax.random.key(0), model, tx,
                           _batch(4, hw=32)))
    sl, sh_l = shard_state(state0, mesh)
    sr, sh_r = shard_state_by_rules(state0, mesh)
    lcfg = LossConfig(ssim=0.0, ssim_window=5)
    hand = make_unified_train_step(
        model, lcfg, tx, mesh, preset="tp", schedule=sched,
        donate=False, health=True, state_shardings=sh_l)
    rules = make_unified_train_step(
        model, lcfg, tx, mesh, preset="tp", schedule=sched,
        donate=False, health=True, state_shardings=sh_r)
    for i in range(2):
        batch = jax.device_put(_batch(4, hw=32, seed=i),
                               batch_sharding(mesh))
        sl, ml = hand(sl, batch)
        sr, mr = rules(sr, batch)
        _metrics_bitwise(ml, mr, f"TP step {i}")
    assert_trees_bitwise(sl, sr, "TP state")


def test_sp_rules_vs_dp_parity(eight_devices):
    """Sequence parallelism is an execution strategy, not a model
    change: the SP preset on (data=2, seq=4) lands within float
    tolerance of the plain DP shard_map step on the same global batch
    (ring attention recomposes exact attention; only associativity
    moves the last ulps)."""
    from distributed_sod_project_tpu.parallel.sp import sp_batch_sharding

    model = _vit_tiny()
    sp_mesh = make_mesh(MeshConfig(data=2, seq=4), eight_devices)
    dp_mesh = make_mesh(MeshConfig(data=2), eight_devices[:2])
    tx, sched = build_optimizer(OptimConfig(lr=0.05, warmup_steps=0), 10)
    state0 = jax.device_get(
        create_train_state(jax.random.key(0), model, tx,
                           _batch(4, hw=32)))
    lcfg = LossConfig(bce=1.0, iou=1.0, ssim=0.0)
    sp = make_unified_train_step(model, lcfg, tx, sp_mesh, preset="sp",
                                 schedule=sched, donate=False)
    dp = make_unified_train_step(model, lcfg, tx, dp_mesh, preset="dp",
                                 schedule=sched, donate=False)
    s_sp = jax.device_put(state0, replicated_sharding(sp_mesh))
    s_dp = jax.device_put(state0, replicated_sharding(dp_mesh))
    for i in range(2):
        host = _batch(4, hw=32, seed=i)
        s_sp, m_sp = sp(s_sp, jax.device_put(
            host, sp_batch_sharding(sp_mesh)))
        s_dp, m_dp = dp(s_dp, global_batch_array(host, dp_mesh))
        np.testing.assert_allclose(
            float(jax.device_get(m_sp["total"])),
            float(jax.device_get(m_dp["total"])), rtol=1e-5,
            err_msg=f"SP vs DP loss, step {i}")
    assert_trees_close(s_sp.params, s_dp.params, "SP vs DP params",
                       rtol=1e-4, atol=1e-6)


# ---------------------------------------------------------------- ZeRO


def test_zero_state_specs_shard_moments_and_ema(eight_devices):
    mesh = make_mesh(MeshConfig(data=4), eight_devices[:4])
    model = _vit_tiny()
    tx, _ = build_optimizer(
        OptimConfig(lr=0.05, warmup_steps=0, ema_decay=0.5), 10)
    state = jax.device_get(
        create_train_state(jax.random.key(0), model, tx,
                           _batch(4, hw=32), ema=True))
    is_spec = lambda x: isinstance(x, P)  # noqa: E731
    param_specs = match_partition_rules(
        DEFAULT_TP_RULES + (REPLICATE_REST,), state.params, mesh)
    buf_specs = zero_state_specs(state.params, param_specs, mesh)
    for leaf, pspec, bspec in zip(
            jax.tree_util.tree_leaves(state.params),
            jax.tree_util.tree_leaves(param_specs, is_leaf=is_spec),
            jax.tree_util.tree_leaves(buf_specs, is_leaf=is_spec)):
        if pspec != P():
            # Explicit rule shards ARE the buffer shards (TP Megatron
            # layout carries straight through to moments/EMA).
            assert bspec == pspec
        elif any(s % 4 == 0 and s >= 4 for s in leaf.shape):
            # Replicated param with a data-divisible dim: the buffer
            # takes the ZeRO shard.
            assert "data" in str(bspec), f"{leaf.shape}: {bspec}"
    specs = state_specs(state, mesh, zero=1)
    # Params are never data-sharded (ZeRO-1/2 shards the UPDATE, not
    # the weights): the 'data' axis appears only in moments and EMA.
    assert all("data" not in str(s) for s in jax.tree_util.tree_leaves(
        specs.params, is_leaf=is_spec))
    assert any("data" in str(s) for s in jax.tree_util.tree_leaves(
        specs.ema_params, is_leaf=lambda x: isinstance(x, P)))
    assert any("data" in str(s) for s in jax.tree_util.tree_leaves(
        specs.opt_state, is_leaf=lambda x: isinstance(x, P)))
    # And the priced HBM saving is real and ledger-visible.
    saved = (tree_bytes(state.ema_params)
             - sharded_tree_bytes(state.ema_params, specs.ema_params,
                                  mesh))
    assert saved > 0
    plan = comm_plan(state, mesh, preset="tp", zero=1)
    assert plan["zero_hbm_saved_bytes"] > 0
    assert plan["collectives"][0]["kind"] == "reduce_scatter+all_gather"


@pytest.mark.parametrize("zero", [1, 2])
def test_zero_trajectory_bitwise_vs_unsharded_gspmd(zero,
                                                    eight_devices):
    """fit(zero) ≡ fit(dp) at the step level: sharding the weight
    UPDATE (moments/EMA over ``data``, zero=2 also pinning grads) must
    not change what is computed.  Documented tolerance (also in
    docs/MULTIHOST.md): GSPMD re-partitions reductions when buffers
    shard, so scalar reductions (grad_norm) move by ~1 ULP — rtol 2e-6
    on the trajectory, not bitwise."""
    model = _vit_tiny()
    mesh = make_mesh(MeshConfig(data=4), eight_devices[:4])
    tx, sched = build_optimizer(
        OptimConfig(lr=0.05, warmup_steps=0, ema_decay=0.5), 10)
    state0 = jax.device_get(
        create_train_state(jax.random.key(0), model, tx,
                           _batch(4, hw=32), ema=True))
    lcfg = LossConfig(ssim=0.0)
    s_ref, sh_ref = shard_state_by_rules(state0, mesh, zero=0)
    s_z, sh_z = shard_state_by_rules(state0, mesh, zero=zero)
    ref = make_unified_train_step(
        model, lcfg, tx, mesh, preset="tp", schedule=sched,
        donate=False, ema_decay=0.5, state_shardings=sh_ref)
    zstep = make_unified_train_step(
        model, lcfg, tx, mesh, preset="tp", schedule=sched,
        donate=False, ema_decay=0.5, state_shardings=sh_z, zero=zero)
    for i in range(3):
        batch = jax.device_put(_batch(4, hw=32, seed=i),
                               batch_sharding(mesh))
        s_ref, m_ref = ref(s_ref, batch)
        s_z, m_z = zstep(s_z, batch)
        for k in ("total", "lr", "grad_norm"):
            np.testing.assert_allclose(
                np.asarray(jax.device_get(m_ref[k])),
                np.asarray(jax.device_get(m_z[k])), rtol=2e-6,
                err_msg=f"zero={zero} metric {k} step {i}")
    assert_trees_close(s_ref, s_z, f"zero={zero} trajectory")
    # The moments really live sharded: each buffer leaf with a
    # divisible dim carries a 'data' sharding on device.
    mu = [x for x in jax.tree_util.tree_leaves(s_z.opt_state)
          if hasattr(x, "sharding") and x.ndim >= 2]
    assert any("data" in str(x.sharding.spec) for x in mu)


# ---------------------------------------------- bf16 gradient wire arm


def test_bf16_grad_compression_runs_close_not_bitwise(eight_devices):
    """The compression arm is NOT bitwise (that is why it is gated by
    tools/grad_comm_gate.py) but must run, stay finite, and land near
    the f32 trajectory on one tiny step."""
    mesh, model, tx, sched, state = _dp_setup(eight_devices)
    lcfg = LossConfig(ssim_window=5)
    f32 = make_unified_train_step(
        model, lcfg, tx, mesh, preset="dp", schedule=sched,
        donate=False, ema_decay=0.5, comm_bucket_mb=0.001)
    bf16 = make_unified_train_step(
        model, lcfg, tx, mesh, preset="dp", schedule=sched,
        donate=False, ema_decay=0.5, comm_bucket_mb=0.001,
        grad_compression="bf16")
    batch = global_batch_array(_batch(8), mesh)
    _, m32 = f32(state, batch)
    _, mbf = bf16(state, batch)
    a, b = (float(jax.device_get(m32["grad_norm"])),
            float(jax.device_get(mbf["grad_norm"])))
    assert np.isfinite(b)
    np.testing.assert_allclose(b, a, rtol=0.05)


# ------------------------------------- FSDP / hierarchical / int8_ef


def test_fsdp_fwd_bwd_parity_vs_dp(eight_devices):
    """ISSUE 19 acceptance: the shipped FSDP preset is the DP
    computation with a different parameter residency.  On a real zoo
    tree (ViTSOD) with parameters VISIBLY sharded over ``data`` (small
    ``min_leaf_size`` so the tiny tree shards), a 2-step FSDP
    trajectory matches the shard_map DP trajectory at rtol<=2e-6 —
    forward (loss), backward (grad_norm), and the updated params."""
    model = _vit_tiny()
    mesh = make_mesh(MeshConfig(data=4), eight_devices[:4])
    tx, sched = build_optimizer(OptimConfig(lr=0.05, warmup_steps=0), 10)
    state0 = jax.device_get(
        create_train_state(jax.random.key(0), model, tx,
                           _batch(4, hw=32)))
    lcfg = LossConfig(ssim=0.0)
    s_dp = jax.device_put(state0, replicated_sharding(mesh))
    dp = make_unified_train_step(model, lcfg, tx, mesh, preset="dp",
                                 schedule=sched, donate=False)
    from distributed_sod_project_tpu.parallel.rules import (
        PRESET_PARAM_RULES)

    s_f, sh = shard_state_by_rules(
        state0, mesh, rules=PRESET_PARAM_RULES["fsdp"],
        fallback=fsdp_fallback_rule(mesh, min_leaf_size=2 ** 8))
    sharded = [x for x in jax.tree_util.tree_leaves(s_f.params)
               if "data" in str(x.sharding.spec)]
    assert sharded, "FSDP layout left every param replicated"
    fsdp = make_unified_train_step(
        model, lcfg, tx, mesh, preset="fsdp", schedule=sched,
        donate=False, state_shardings=sh)
    for i in range(2):
        host = _batch(4, hw=32, seed=i)
        s_dp, m_dp = dp(s_dp, global_batch_array(host, mesh))
        s_f, m_f = fsdp(s_f, jax.device_put(host, batch_sharding(mesh)))
        for k in ("total", "grad_norm"):
            np.testing.assert_allclose(
                float(jax.device_get(m_dp[k])),
                float(jax.device_get(m_f[k])), rtol=2e-6,
                err_msg=f"FSDP vs DP metric {k}, step {i}")
    assert_trees_close(s_dp.params, s_f.params, "FSDP vs DP params",
                       rtol=2e-6)
    # Updated params still live sharded (the preset never gathered the
    # persistent copy).
    still = [x for x in jax.tree_util.tree_leaves(s_f.params)
             if "data" in str(x.sharding.spec)]
    assert len(still) == len(sharded)


def test_hier_psum_bitwise_flat_on_integer_wire(eight_devices):
    """The two-level ICI×DCN reduction (intra-host reduce-scatter →
    inter-host all-reduce on 1/chips of the bytes → intra-host
    all-gather) computes the pair-tree association
    ``sum_hosts(sum_chips(.))`` — bitwise the flat psum whenever wire
    values are exactly representable (integer-valued f32, the int8_ef
    integer wire), allclose on arbitrary floats.  2 hosts × 2 chips on
    a 4-device CPU mesh; odd leaf sizes exercise the chip-pad path."""
    from distributed_sod_project_tpu.parallel.mesh import hier_data_groups

    mesh = make_mesh(MeshConfig(data=4), eight_devices[:4])
    hier = hier_data_groups(mesh, 2)
    rng = np.random.default_rng(0)
    ints = {"w": rng.integers(-64, 64, size=(4, 33, 5)
                              ).astype(np.float32),
            "b": rng.integers(-8, 8, size=(4, 7)).astype(np.float32)}
    floats = {"w": rng.normal(size=(4, 257)).astype(np.float32)}

    def run(tree, hierarchy):
        sharded = {k: jax.device_put(v, NamedSharding(mesh, P("data")))
                   for k, v in tree.items()}
        f = lambda t: bucketed_pmean(  # noqa: E731
            t, "data", 256, hierarchy=hierarchy)
        return jax.device_get(jax.jit(jax.shard_map(
            f, mesh=mesh, in_specs=(P("data"),), out_specs=P("data"),
            check_vma=False))(sharded))

    flat, two = run(ints, None), run(ints, hier)
    for k in ints:
        assert np.array_equal(flat[k], two[k]), (
            f"hier vs flat not bitwise on integer wire, leaf {k}")
    f_flat, f_two = run(floats, None), run(floats, hier)
    np.testing.assert_allclose(f_two["w"], f_flat["w"], rtol=2e-6,
                               err_msg="hier vs flat beyond float tol")


def test_hier_int8_ef_step_bitwise_flat_int8_ef(eight_devices):
    """End-to-end: the int8_ef wire is integers, so routing it through
    the hierarchical two-level reduction changes NOTHING — params AND
    residual bitwise vs the flat int8_ef step over a 2-step
    trajectory (the property that lets a pod turn on data_hosts
    without re-running the quality gate)."""
    from distributed_sod_project_tpu.parallel.mesh import hier_data_groups

    mesh = make_mesh(MeshConfig(), eight_devices)
    hier = hier_data_groups(mesh, 2)
    model = TinyNet()
    tx, sched = build_optimizer(OptimConfig(lr=0.05, warmup_steps=0), 10)
    state = seed_comm_residual(jax.device_put(
        create_train_state(jax.random.key(0), model, tx, _batch(2)),
        replicated_sharding(mesh)), mesh)
    lcfg = LossConfig(ssim_window=5)
    flat = make_unified_train_step(
        model, lcfg, tx, mesh, preset="dp", schedule=sched,
        donate=False, comm_bucket_mb=0.001, grad_compression="int8_ef")
    two = make_unified_train_step(
        model, lcfg, tx, mesh, preset="dp", schedule=sched,
        donate=False, comm_bucket_mb=0.001, grad_compression="int8_ef",
        data_hosts=2)
    sa, sb = state, state
    for i in range(2):
        batch = global_batch_array(_batch(8, seed=i), mesh)
        sa, ma = flat(sa, batch)
        sb, mb = two(sb, batch)
        _metrics_bitwise(ma, mb, f"int8_ef hier step {i}")
    assert_trees_bitwise(sa, sb, "int8_ef hier state")
    assert np.abs(np.asarray(
        jax.device_get(sb.comm_residual))).max() > 0


def test_int8_ef_residual_carry_and_checkpoint_roundtrip(
        tmp_path, eight_devices):
    """ISSUE 19 int8_ef contract: the builder REQUIRES the residual;
    ``seed_comm_residual`` provides it zeroed and P('data')-placed; a
    compressed k-step trajectory carries a changing nonzero residual
    while staying within the grad-gate-style budget of the f32
    trajectory; and the residual survives a checkpoint round-trip
    bitwise, so resuming continues the exact trajectory."""
    mesh = make_mesh(MeshConfig(), eight_devices)
    model = TinyNet()
    tx, sched = build_optimizer(OptimConfig(lr=0.05, warmup_steps=0), 20)
    base = jax.device_put(
        create_train_state(jax.random.key(0), model, tx, _batch(2)),
        replicated_sharding(mesh))
    lcfg = LossConfig(ssim_window=5)
    build = lambda **kw: make_unified_train_step(  # noqa: E731
        model, lcfg, tx, mesh, preset="dp", schedule=sched,
        donate=False, comm_bucket_mb=0.001, **kw)
    ef = build(grad_compression="int8_ef")
    ref = build()

    # The builder's step refuses a residual-less state.
    with pytest.raises((ValueError, TypeError, AttributeError)):
        jax.block_until_ready(
            ef(base, global_batch_array(_batch(8), mesh)))

    state = seed_comm_residual(base, mesh)
    assert state.comm_residual.shape[0] == 8
    assert "data" in str(state.comm_residual.sharding.spec)
    s32, sef, res_seen = base, state, []
    for i in range(4):
        batch = global_batch_array(_batch(8, seed=i), mesh)
        s32, m32 = ref(s32, batch)
        sef, mef = ef(sef, batch)
        res_seen.append(np.asarray(jax.device_get(sef.comm_residual)))
    assert np.abs(res_seen[0]).max() > 0  # error feedback populated
    assert not np.array_equal(res_seen[0], res_seen[-1])  # and carried
    # Grad-gate-style budget on the tiny smoke: trajectory stays close.
    a = float(jax.device_get(m32["total"]))
    b = float(jax.device_get(mef["total"]))
    assert abs(b - a) < 5e-3, f"int8_ef final loss drifted: {a} vs {b}"
    pn = np.sqrt(sum(float(np.sum(np.square(x))) for x in
                     jax.tree_util.tree_leaves(
                         jax.device_get(s32.params))))
    dn = np.sqrt(sum(float(np.sum(np.square(
        np.asarray(x) - np.asarray(y)))) for x, y in zip(
        jax.tree_util.tree_leaves(jax.device_get(s32.params)),
        jax.tree_util.tree_leaves(jax.device_get(sef.params)))))
    assert dn / pn < 0.01, f"int8_ef param drift {dn / pn:.4f}"

    # Checkpoint round-trip: residual is state, so it persists.
    from distributed_sod_project_tpu.ckpt import CheckpointManager

    mgr = CheckpointManager(str(tmp_path), keep=2)
    assert mgr.save(4, sef, force=True)
    mgr.wait()
    restored = mgr.restore(jax.device_get(sef), step=4)
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(sef.comm_residual)),
        np.asarray(restored.comm_residual),
        err_msg="comm_residual not bitwise through checkpoint")
    s_resume = seed_comm_residual(jax.device_put(
        restored, replicated_sharding(mesh)).replace(
            comm_residual=restored.comm_residual), mesh)
    batch = global_batch_array(_batch(8, seed=9), mesh)
    s_a, _ = ef(sef, batch)
    s_b, _ = ef(s_resume, batch)
    assert_trees_bitwise(s_a, s_b, "post-restore int8_ef step")


# -------------------------------------------------- config + routing


def test_select_preset_and_effective_zero():
    cfg = get_config("minet_vgg16_ref")
    devs = jax.devices()[:8]
    assert select_preset(cfg, make_mesh(MeshConfig(), devs)) == "dp"
    assert select_preset(
        cfg, make_mesh(MeshConfig(data=2, model=2), devs[:4])) == "tp"
    assert select_preset(
        cfg, make_mesh(MeshConfig(data=2, seq=4), devs)) == "sp"
    zcfg = cfg.replace(parallel=ParallelConfig(engine="rules", zero=1))
    assert select_preset(zcfg, make_mesh(MeshConfig(), devs)) == "tp"
    assert effective_zero(zcfg) == 1
    legacy_z = cfg.replace(
        optim=dataclasses.replace(cfg.optim, zero1=True))
    assert effective_zero(legacy_z) == 1
    assert effective_zero(cfg) == 0


def test_validate_parallel_rejections():
    cfg = get_config("minet_vgg16_ref")
    validate_parallel(cfg)  # defaults fine
    # Round 18: rules is the default AND only engine — zero and
    # grad_compression are first-class, legacy is a loud error.
    validate_parallel(cfg.replace(parallel=ParallelConfig(zero=1)))
    validate_parallel(cfg.replace(
        parallel=ParallelConfig(grad_compression="bf16")))
    with pytest.raises(ValueError, match="legacy"):
        validate_parallel(cfg.replace(
            parallel=ParallelConfig(engine="legacy")))
    with pytest.raises(ValueError, match="preset"):
        validate_parallel(cfg.replace(
            parallel=ParallelConfig(preset="pipeline")))
    with pytest.raises(ValueError, match="data_hosts"):
        validate_parallel(cfg.replace(
            mesh=dataclasses.replace(cfg.mesh, data_hosts=0)))
    with pytest.raises(ValueError, match="fsdp"):
        validate_parallel(cfg.replace(
            parallel=ParallelConfig(preset="fsdp"),
            mesh=dataclasses.replace(cfg.mesh, model=2)))
    with pytest.raises(ValueError):
        validate_parallel(cfg.replace(
            parallel=ParallelConfig(engine="rules", zero=3)))
    with pytest.raises(ValueError):
        validate_parallel(cfg.replace(
            parallel=ParallelConfig(engine="bogus")))
    both = cfg.replace(parallel=ParallelConfig(engine="rules", zero=1),
                       optim=dataclasses.replace(cfg.optim, zero1=True))
    with pytest.raises(ValueError, match="both"):
        validate_parallel(both)
    bn = cfg.replace(parallel=ParallelConfig(engine="rules", zero=1))
    if bn.model.sync_bn:
        with pytest.raises(ValueError, match="sync_bn"):
            validate_parallel(bn)


def test_comm_plan_buckets_and_bytes(eight_devices):
    mesh = make_mesh(MeshConfig(), eight_devices)
    model = TinyNet()
    tx, _ = build_optimizer(OptimConfig(lr=0.1, warmup_steps=0), 10)
    state = jax.device_get(
        create_train_state(jax.random.key(0), model, tx, _batch(2)))
    total = tree_bytes(state.params)
    plan = comm_plan(state, mesh, preset="dp", comm_bucket_mb=0.001)
    assert plan["n_buckets"] >= 2
    assert sum(c["bytes"] for c in plan["collectives"]) == total
    assert all(c["axis_size"] == 8 for c in plan["collectives"])
    assert 0.0 < plan["overlap_frac"] < 1.0
    mono = comm_plan(state, mesh, preset="dp", comm_bucket_mb=0.0)
    assert mono["n_buckets"] == 1
    assert mono["overlap_frac"] == 0.0
    assert mono["collectives"][0]["name"] == "grad_allreduce"
    bf = comm_plan(state, mesh, preset="dp", comm_bucket_mb=0.0,
                   grad_compression="bf16")
    assert bf["collectives"][0]["bytes"] == total // 2
