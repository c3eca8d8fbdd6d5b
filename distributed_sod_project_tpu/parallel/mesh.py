"""Device mesh construction + sharding rules.

This module is the TPU-native replacement for the reference's entire
distributed runtime (SURVEY.md §2 C3/C4: ``init_process_group('nccl')``,
``DistributedDataParallel``, ``DistributedSampler``).  There is no
hand-written communication backend: the "backend" is a
``jax.sharding.Mesh`` plus the PartitionSpecs below; XLA emits the
collectives (psum over ICI within a host/pod slice, DCN across hosts)
when the train step is compiled (SURVEY.md §5 "distributed communication
backend").

Axes (SURVEY.md §2.3):

- ``data``  — the load-bearing axis: batch-sharded inputs, replicated
  params, gradient psum.  Parity with the reference's DDP.
- ``model`` — tensor-parallel axis for the Swin attention heads
  (stretch config); size 1 in every DP config.
- ``seq``   — sequence/context-parallel axis (ring attention); size 1
  for the 320×320 CNN zoo.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

MeshAxes: Tuple[str, str, str] = ("data", "model", "seq")


def _resolve_axis_sizes(n_devices: int, data: int, model: int, seq: int):
    sizes = {"data": data, "model": model, "seq": seq}
    wild = [k for k, v in sizes.items() if v == -1]
    if len(wild) > 1:
        raise ValueError(f"at most one mesh axis may be -1, got {sizes}")
    fixed = int(np.prod([v for v in sizes.values() if v != -1]))
    if wild:
        if n_devices % fixed:
            raise ValueError(
                f"{n_devices} devices not divisible by fixed axes {sizes}"
            )
        sizes[wild[0]] = n_devices // fixed
    total = int(np.prod(list(sizes.values())))
    if total > n_devices:
        raise ValueError(
            f"mesh {sizes} wants {total} devices, have {n_devices}"
        )
    # total < n_devices is allowed: a fully pinned config (e.g. the
    # single-device reference config) runs on the first `total` devices.
    return sizes["data"], sizes["model"], sizes["seq"]


def make_mesh(
    mesh_cfg=None,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build the (data, model, seq) mesh.

    Axis order puts ``model``/``seq`` innermost so tensor/sequence
    shards land on ICI-adjacent chips and the (large, per-step) DP
    gradient psum rides the remaining links.
    """
    devices = list(devices if devices is not None else jax.devices())
    data = getattr(mesh_cfg, "data", -1) if mesh_cfg is not None else -1
    model = getattr(mesh_cfg, "model", 1) if mesh_cfg is not None else 1
    seq = getattr(mesh_cfg, "seq", 1) if mesh_cfg is not None else 1
    d, m, s = _resolve_axis_sizes(len(devices), data, model, seq)
    arr = np.asarray(devices[: d * m * s]).reshape(d, m, s)
    return Mesh(arr, MeshAxes)


def hier_data_groups(mesh: Mesh, data_hosts: int):
    """axis_index_groups for the two-level (ICI x DCN) data reduction.

    Factors the ``data`` axis as ``(data_hosts, chips_per_host)`` —
    make_mesh's row-major device order puts consecutive data indices on
    the same host, so host h owns data indices
    ``[h*chips, ..., (h+1)*chips - 1]``.  Returns
    ``(intra_groups, inter_groups)``:

    - ``intra_groups`` — one group per host (its chips): the fast ICI
      legs (reduce-scatter, then the final all-gather).
    - ``inter_groups`` — one group per chip position (its peers across
      hosts): the slow DCN all-reduce, carrying only 1/chips_per_host
      of the bucket bytes after the scatter.

    Returns ``None`` when ``data_hosts <= 1`` (flat single-level psum).
    """
    if data_hosts <= 1:
        return None
    data = int(mesh.shape.get("data", 1))
    if data % data_hosts:
        raise ValueError(
            f"mesh.data_hosts={data_hosts} does not divide the data "
            f"axis (size {data}) — the two-level reduction needs equal "
            "chips_per_host on every host")
    chips = data // data_hosts
    if chips == 1:
        raise ValueError(
            f"mesh.data_hosts={data_hosts} leaves 1 chip per host — "
            "the hierarchical reduction degenerates to the flat psum; "
            "use data_hosts=1")
    intra = [[h * chips + j for j in range(chips)]
             for h in range(data_hosts)]
    inter = [[h * chips + j for h in range(data_hosts)]
             for j in range(chips)]
    return intra, inter


def batch_spec() -> P:
    """Batch dim sharded over ``data``; everything else replicated."""
    return P("data")


def replicated_spec() -> P:
    return P()


def batch_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, batch_spec())


def eval_batch_sharding(mesh: Mesh) -> NamedSharding:
    """Eval-forward batch sharding: the batch dim shards over the
    flattened (data, seq) axes so sequence-parallel meshes share eval
    work across every chip instead of replicating it per seq group.
    The ``model`` axis is left out — TP evals keep it for the weight
    sharding.  Equals ``batch_sharding`` on pure-DP meshes."""
    axes = tuple(a for a in ("data", "seq") if mesh.shape.get(a, 1) > 1)
    return NamedSharding(mesh, P(axes or ("data",)))


def eval_batch_divisor(mesh: Mesh) -> int:
    """Round eval batch sizes to a multiple of this so the eval
    sharding divides evenly."""
    return int(np.prod([mesh.shape.get(a, 1) for a in ("data", "seq")]))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, replicated_spec())


def host_shard() -> Tuple[int, int]:
    """(shard_id, num_shards) for the host data pipeline — the analogue
    of the reference's ``DistributedSampler(rank, world_size)``, except
    sharding is per-*host* (each host feeds all its local devices)."""
    return jax.process_index(), jax.process_count()


def host_axis_blocks(mesh: Mesh):
    """This process's contiguous index block along every mesh axis.

    ``{axis: [ids...]}`` where ids are the positions of this host's
    devices on that axis.  The multi-host data plane is only
    well-defined when each host's devices form an axis-aligned
    contiguous block (the default device order gives exactly that);
    anything else raises rather than silently mis-sharding batches.
    Cached per mesh — the result is a constant, and the per-device
    Python scan must not run per batch in the prefetch worker.
    """
    return _host_axis_blocks_cached(mesh)


@functools.lru_cache(maxsize=16)
def _host_axis_blocks_cached(mesh: Mesh):
    local = {d.id for d in jax.local_devices()}
    dev = mesh.devices  # ndarray shaped by mesh.axis_names
    mask = np.vectorize(lambda d: d.id in local)(dev)
    coords = np.argwhere(mask)
    if not len(coords):
        raise ValueError(
            "this host owns none of the mesh's devices (a pinned mesh "
            "smaller than the pod excludes whole hosts) — every "
            "participating process must contribute devices to the mesh")
    blocks = {}
    for i, name in enumerate(mesh.axis_names):
        ids = sorted({int(c[i]) for c in coords})
        if ids != list(range(ids[0], ids[0] + len(ids))):
            raise ValueError(
                f"host devices are non-contiguous on mesh axis "
                f"{name!r}: {ids} — reorder the mesh so each host is "
                "an axis-aligned block")
        blocks[name] = ids
    if len(coords) != int(np.prod([len(v) for v in blocks.values()])):
        raise ValueError(
            "host devices do not form an axis-aligned block on the "
            f"mesh (got {len(coords)} devices vs block "
            f"{ {k: len(v) for k, v in blocks.items()} }) — per-host "
            "batch sharding is undefined for this layout")
    return blocks


def host_batch_shard(mesh: Mesh) -> Tuple[int, int]:
    """(shard_id, num_shards) for the TRAIN loader, derived from where
    this host sits on the ``data`` axis — NOT from process_index: when
    a non-data axis (``seq``, ``model``) spans processes, several hosts
    share one data block and must load IDENTICAL batches (their devices
    hold different row/weight shards of the same images).  For pure-DP
    meshes this reduces to (process_index, process_count)."""
    blocks = host_axis_blocks(mesh)
    data_ids = blocks.get("data") or [0]
    data_size = mesh.shape.get("data", 1)
    if data_size % len(data_ids) or data_ids[0] % len(data_ids):
        # E.g. a pinned data=6 mesh over 2 hosts of 4: host A would
        # cover ids 0-3 (2/3 of the batch) and host B ids 4-5 — no
        # uniform (shard_id, num_shards) describes that; raise per the
        # module contract instead of mis-sharding.
        raise ValueError(
            f"host data block {data_ids} does not tile the data axis "
            f"(size {data_size}) uniformly — size the mesh so every "
            "host covers an equal, aligned data block")
    return data_ids[0] // len(data_ids), data_size // len(data_ids)


def global_batch_array(batch, mesh: Mesh, spec: Optional[P] = None):
    """Assemble per-host numpy batches into global batch-sharded
    ``jax.Array``s (multi-host: each host contributes its slice via
    ``make_array_from_process_local_data``; single-host this is just a
    sharded device_put).  ``spec`` overrides the default batch-only
    sharding (e.g. ``P('data', 'seq')`` for sequence parallelism).

    The host batch must be this host's DATA block (``host_batch_shard``
    is the loader contract).  When ``spec`` row-shards dim 1 over a
    ``seq`` axis that spans processes, each host hands
    ``make_array_from_process_local_data`` only its row block — the
    local data must exactly cover the host's addressable shards.
    """
    sharding = (NamedSharding(mesh, spec) if spec is not None
                else batch_sharding(mesh))
    sp = spec if spec is not None else batch_spec()
    # Which dim (if any) rows shard over ``seq`` — dim 1 for the plain
    # SP spec P('data', 'seq'), dim 2 for the step-chunked spec
    # P(None, 'data', 'seq') (stacked batches, leading k axis).
    row_slice = None
    seq_dim = next((i for i, names in enumerate(sp) if names == "seq"), None)
    if seq_dim is not None:
        seq_ids = host_axis_blocks(mesh).get("seq") or [0]
        seq_size = mesh.shape.get("seq", 1)
        if len(seq_ids) < seq_size:
            row_slice = (seq_dim, seq_ids[0], len(seq_ids), seq_size)

    def place(x):
        x = np.asarray(x)
        if row_slice is not None:
            dim, first, n, total = row_slice
            if x.shape[dim] % total:
                raise ValueError(
                    f"dim {dim} ({x.shape[dim]}) not divisible by the "
                    f"seq axis ({total})")
            blk = x.shape[dim] // total
            idx = [slice(None)] * x.ndim
            idx[dim] = slice(first * blk, (first + n) * blk)
            x = x[tuple(idx)]
        return jax.make_array_from_process_local_data(sharding, x)

    return jax.tree_util.tree_map(place, batch)
