"""Host-side input pipeline with per-host sharding.

Replaces the reference's ``DataLoader`` + ``DistributedSampler`` pair
(SURVEY.md §2 C4/C7) with the TPU idiom: every host materialises only
its 1/num_shards slice of each global batch, and epoch-seeded shuffling
plays the role of ``sampler.set_epoch`` — identical permutations on all
hosts without any cross-host coordination.

The data plane is multi-stage (docs/PERFORMANCE.md "Host data plane"):

  decode workers → batch buffers (ring) → vectorized augment
      → staging (ordered futures) → H2D thread (prefetch_to_device)

- ``num_workers`` build threads assemble whole batches in parallel
  (``lookahead`` batches in flight), writing samples straight into
  preallocated output buffers — no per-step ``np.stack``.
- augmentation is the whole-batch vectorized path in data/augment.py
  (same per-(seed, epoch, idx) draws as the scalar reference).
- ``ring_buffers`` > 0 recycles the batch buffers instead of
  allocating per step.  CONTRACT: a yielded batch's arrays are valid
  until ``_RING_KEEP`` further batches have been yielded; consumers
  that hold batches longer (tests collecting an epoch) must copy or
  run with the ring off (the default).
- ``decode_procs`` > 0 decodes samples in a process pool writing into
  shared-memory ring slots — sidesteps the GIL for the PIL decode path
  when the C++ runtime in ``native/`` is unbuilt.
- every blocking point feeds ``PipelineStats``
  (utils/observability.py), so "input-bound" is a number
  (``data_starved_ms``), not a guess.
"""

from __future__ import annotations

import collections
import concurrent.futures as cf
import contextlib
import queue
import threading
from typing import Dict, Iterator, Optional

import numpy as np


def _timed(stats, key: str, **attrs):
    """``stats.timed(key)`` — counter and ``dsod.data.*`` span from one
    timed region (utils/observability.PipelineStats) — or nothing
    where the caller passed no stats."""
    if stats is None:
        return contextlib.nullcontext()
    return stats.timed(key, **attrs)

# A yielded batch stays valid for this many further yields in ring mode
# (the consumer typically holds the current batch while requesting the
# next: keep = 2 covers "current + one downstream stage").
_RING_KEEP = 2


class BatchRing:
    """Preallocated ring of reusable batch buffers (dicts of arrays).

    ``acquire`` blocks until a slot is free (natural producer
    backpressure, the wait is recorded as ``data_ring_wait_ms``);
    ``release`` returns a slot to the pool.  With ``shared=True`` the
    arrays live in ``multiprocessing.shared_memory`` segments so
    process-pool decode workers can write rows directly — zero-copy
    transport instead of pickling every sample back.
    """

    def __init__(self, nslots: int, spec: Dict[str, tuple],
                 shared: bool = False, stats=None):
        self.nslots = int(nslots)
        self.spec = dict(spec)
        self._stats = stats
        self._free: "queue.Queue" = queue.Queue()
        self._shm = []
        self._shm_spec: Dict[int, Dict[str, tuple]] = {}
        self.slots = []
        for _ in range(self.nslots):
            slot: Dict[str, np.ndarray] = {}
            sspec: Dict[str, tuple] = {}
            for k, (shape, dtype) in self.spec.items():
                if shared:
                    from multiprocessing import shared_memory

                    nbytes = int(np.prod(shape)) * np.dtype(dtype).itemsize
                    seg = shared_memory.SharedMemory(
                        create=True, size=max(nbytes, 1))
                    self._shm.append(seg)
                    slot[k] = np.ndarray(shape, dtype, buffer=seg.buf)
                    sspec[k] = (seg.name, shape, np.dtype(dtype).str)
                else:
                    slot[k] = np.empty(shape, dtype)
            self.slots.append(slot)
            self._shm_spec[id(slot)] = sspec
            self._free.put(slot)

    def acquire(self) -> Dict[str, np.ndarray]:
        try:
            return self._free.get_nowait()
        except queue.Empty:
            pass
        with _timed(self._stats, "data_ring_wait_ms"):
            return self._free.get()

    def release(self, slot: Dict[str, np.ndarray]) -> None:
        self._free.put(slot)

    def shm_spec(self, slot) -> Dict[str, tuple]:
        """Picklable {key: (shm_name, shape, dtype)} for proc workers."""
        return self._shm_spec[id(slot)]

    def close(self) -> None:
        for seg in self._shm:
            try:
                seg.close()
                seg.unlink()
            except Exception:  # noqa: BLE001 — already unlinked / torn down
                pass
        self._shm = []


# --- process-pool decode workers (shared-memory transport) -----------------
# Module-level so they pickle under both fork and spawn; the dataset
# rides the initializer once per worker, not once per task.

_PROC_DS = None
_PROC_SHM: Dict[str, "object"] = {}


def _proc_init(dataset) -> None:
    global _PROC_DS
    _PROC_DS = dataset


def _proc_decode_into(task) -> int:
    """Decode one sample into row ``row`` of the shm-backed slot
    described by ``spec``; returns the dataset index (ack)."""
    idx, row, spec = task
    from multiprocessing import shared_memory

    sample = _PROC_DS[int(idx)]
    for k, (name, shape, dtype) in spec.items():
        seg = _PROC_SHM.get(name)
        if seg is None:
            seg = _PROC_SHM[name] = shared_memory.SharedMemory(name=name)
        arr = np.ndarray(shape, np.dtype(dtype), buffer=seg.buf)
        arr[row] = sample[k]
    return int(idx)


class HostDataLoader:
    """Epoch-based, shard-aware, deterministic batch iterator.

    Yields dicts of numpy arrays with leading dim = per-host batch size
    (= global_batch_size // num_shards).  Batch content is a pure
    function of (seed, epoch, step) — identical for any ``num_workers``,
    ``lookahead``, ``ring_buffers`` or ``decode_procs`` setting
    (asserted in tests/test_data_plane.py).
    """

    def __init__(
        self,
        dataset,
        global_batch_size: int,
        shard_id: int = 0,
        num_shards: int = 1,
        shuffle: bool = True,
        seed: int = 0,
        drop_last: bool = True,
        hflip: bool = False,
        rotate_degrees: float = 0.0,
        color_jitter: float = 0.0,
        num_workers: int = 0,
        lookahead: int = 2,
        ring_buffers: int = 0,
        decode_procs: int = 0,
        cache_decoded: int = -1,
        cache_budget_mb: int = 1024,
        stats=None,
    ):
        if global_batch_size % num_shards != 0:
            raise ValueError(
                f"global_batch_size={global_batch_size} not divisible by "
                f"num_shards={num_shards}"
            )
        self.dataset = dataset
        self.global_batch_size = global_batch_size
        self.local_batch_size = global_batch_size // num_shards
        self.shard_id = shard_id
        self.num_shards = num_shards
        self.shuffle = shuffle
        self.seed = seed
        self.drop_last = drop_last
        self.hflip = hflip
        self.rotate_degrees = float(rotate_degrees)
        self.color_jitter = float(color_jitter)
        self.num_workers = num_workers
        # lookahead = batches in flight; below num_workers it would
        # silently idle configured build threads, so it saturates them.
        self.lookahead = max(int(lookahead), 1, int(num_workers))
        # decode_procs needs shm slots to write into → implies a ring.
        self.ring_buffers = int(ring_buffers)
        if decode_procs > 0 and self.ring_buffers == 0:
            self.ring_buffers = self.lookahead + _RING_KEEP + 2
        if self.ring_buffers:
            # Slots must cover in-flight builds + the validity window +
            # one being handed over, or builders deadlock on acquire.
            self.ring_buffers = max(self.ring_buffers,
                                    self.lookahead + _RING_KEEP + 1)
        self.decode_procs = int(decode_procs)
        self.cache_decoded = int(cache_decoded)
        self.cache_budget_mb = int(cache_budget_mb)
        self.stats = stats
        self._epoch = 0
        self._skip = 0
        self._ring: Optional[BatchRing] = None
        self._proc_pool = None
        self._cache: Optional[Dict[int, dict]] = None
        self._cache_max = 0

    def set_epoch(self, epoch: int) -> None:
        self._epoch = epoch

    def skip_steps(self, n: int) -> None:
        """Start the NEXT iteration ``n`` batches into the epoch (exact
        mid-epoch resume: order is a pure function of (seed, epoch), so
        skipping is index arithmetic, no data is touched).  One-shot —
        consumed by the next ``__iter__``."""
        self._skip = int(n)

    @property
    def steps_per_epoch(self) -> int:
        n = len(self.dataset)
        if self.drop_last:
            return n // self.global_batch_size
        return -(-n // self.global_batch_size)

    def _epoch_order(self, epoch: int) -> np.ndarray:
        n = len(self.dataset)
        if self.shuffle:
            rng = np.random.default_rng(np.random.SeedSequence([self.seed, epoch]))
            order = rng.permutation(n)
        else:
            order = np.arange(n)
        if not self.drop_last and n % self.global_batch_size:
            pad = self.global_batch_size - n % self.global_batch_size
            order = np.concatenate([order, order[:pad]])
        return order

    # ------------------------------------------------------------------
    # batch assembly
    # ------------------------------------------------------------------

    def _batch_spec(self) -> Dict[str, tuple]:
        """{key: (batch_shape, dtype)} probed from sample 0 — the shapes
        are static per dataset (XLA contract), so one probe serves the
        whole run."""
        sample = self.dataset[0]
        return {
            k: ((self.local_batch_size,) + np.asarray(v).shape,
                np.asarray(v).dtype)
            for k, v in sample.items()
        }

    def _decode_into(self, buf: Dict[str, np.ndarray], idxs) -> None:
        """Fill buffer rows with RAW (unaugmented) samples — the decode
        stage.  Corrupt-sample handling stays in the dataset wrapper
        (resilience/dataguard.py), which this calls through."""
        if self._proc_pool is not None and self._ring is not None:
            spec = self._ring.shm_spec(buf)
            if spec:
                try:
                    tasks = [(int(i), j, spec) for j, i in enumerate(idxs)]
                    # The timeout converts a wedged worker (fork-
                    # inherited lock, dead child) into the in-thread
                    # fallback instead of an eternal hang.
                    list(self._proc_pool.map(_proc_decode_into, tasks,
                                             timeout=300))
                    return
                except Exception as e:  # noqa: BLE001 — broken pool/
                    # pickle: permanent for this run; fall back to
                    # in-process.  Data-integrity raises are NOT infra
                    # failures and must keep propagating.
                    from ..resilience.dataguard import SkipBudgetExhausted

                    if isinstance(e, SkipBudgetExhausted):
                        raise
                    self._teardown_procs()
                    from ..utils.logging import get_logger

                    get_logger().warning(
                        "process-pool decode failed — falling back to "
                        "in-thread decode for the rest of the run")
        cache = self._cache
        for j, i in enumerate(idxs):
            ii = int(i)
            sample = cache.get(ii) if cache is not None else None
            if sample is None:
                sample = self.dataset[ii]
                if cache is not None and len(cache) < self._cache_max:
                    cache[ii] = sample
            for k in buf:
                buf[k][j] = sample[k]

    def _setup_cache(self) -> None:
        """Raw-decoded-sample memoization (the tf.data ``cache()``
        analogue): when the dataset fits the RAM budget, every epoch
        after the first costs a row copy instead of a decode.  Safe by
        construction — augmentation always runs AFTER the copy into the
        batch buffer, so cached samples are never mutated and the
        per-epoch draw streams stay exact."""
        if self._cache is not None or self.cache_decoded == 0:
            return
        n = len(self.dataset)
        want = n if self.cache_decoded < 0 else min(n, self.cache_decoded)
        if self.cache_decoded < 0:
            probe = self.dataset[0]
            nbytes = sum(np.asarray(v).nbytes for v in probe.values())
            if nbytes * n > self.cache_budget_mb * (1 << 20):
                want = 0  # auto mode: dataset exceeds the budget
        self._cache_max = want
        self._cache = {} if want > 0 else None
        if want <= 0:
            self.cache_decoded = 0  # resolved: don't re-probe each epoch

    def _build(self, step: int, order: np.ndarray, aug_seed: int
               ) -> Dict[str, np.ndarray]:
        """One full batch: acquire buffers → decode → vectorized
        augment.  Runs on a build worker; pure function of step."""
        from .augment import augment_batch

        lo = (step * self.global_batch_size
              + self.shard_id * self.local_batch_size)
        idxs = order[lo:lo + self.local_batch_size]
        if self._ring is not None:
            buf = self._ring.acquire()
        else:
            buf = {k: np.empty(shape, dtype)
                   for k, (shape, dtype) in self._spec.items()}
        with _timed(self.stats, "data_build_ms", batch=step):
            self._decode_into(buf, idxs)
            return augment_batch(
                buf, idxs, aug_seed, hflip=self.hflip,
                rotate_degrees=self.rotate_degrees,
                color_jitter=self.color_jitter,
                norm_mean=getattr(self.dataset, "mean", None),
                norm_std=getattr(self.dataset, "std", None),
                reuse_buffers=self._ring is not None)

    def _build_native(self, idxs, native_batch, aug_seed: int):
        """C++ data plane: whole-batch decode (+hflip) without the GIL,
        then the same vectorized jitter/rotation.  Returns None when the
        library bows out (unbuilt / unsupported format)."""
        from .augment import augment_batch, hflip_draw_batch

        flags = (hflip_draw_batch(aug_seed, idxs) if self.hflip
                 else [False] * len(idxs))
        batch = native_batch(idxs, hflip=list(map(bool, flags)))
        if batch is None:
            return None
        return augment_batch(
            batch, idxs, aug_seed, hflip=False, skip_hflip=True,
            rotate_degrees=self.rotate_degrees,
            color_jitter=self.color_jitter,
            norm_mean=getattr(self.dataset, "mean", None),
            norm_std=getattr(self.dataset, "std", None))

    def _setup_procs(self) -> None:
        if self.decode_procs <= 0 or self._proc_pool is not None:
            return
        from ..resilience.dataguard import GuardedDataset

        if isinstance(self.dataset, GuardedDataset):
            # Each worker process would get its own COPY of the guard,
            # so corrupt-sample counts would never reach the parent's
            # skip-budget accounting (data_skipped metric, budget
            # exhaustion) — the PR-1 bounded-corruption invariant.
            # Decode in-thread instead, loudly.
            self.decode_procs = 0
            from ..utils.logging import get_logger

            get_logger().warning(
                "data.decode_procs is incompatible with the corrupt-"
                "sample skip budget (GuardedDataset state is per-"
                "process) — decoding in-thread instead")
            return
        import multiprocessing as mp

        try:
            # spawn, not fork: the pool starts lazily from a worker
            # thread of an already-multithreaded (jax-initialized)
            # process, where fork can inherit held locks and deadlock
            # children.  Workers import only numpy-level modules, so
            # spawn startup is cheap and paid once per run.
            from ..utils import envvars

            ctx = mp.get_context(envvars.read("DSOD_DECODE_MP"))
            self._proc_pool = cf.ProcessPoolExecutor(
                max_workers=self.decode_procs, mp_context=ctx,
                initializer=_proc_init, initargs=(self.dataset,))
        except Exception:  # noqa: BLE001 — unpicklable dataset etc.
            self._teardown_procs()
            from ..utils.logging import get_logger

            get_logger().warning(
                "could not start %d decode processes — decoding "
                "in-thread instead", self.decode_procs)

    def _teardown_procs(self) -> None:
        pool, self._proc_pool = self._proc_pool, None
        if pool is not None:
            pool.shutdown(wait=False)

    def close(self) -> None:
        """Release ring shm + decode processes (idempotent)."""
        self._teardown_procs()
        if self._ring is not None:
            self._ring.close()
            self._ring = None

    def __del__(self):  # best-effort: shm segments must not leak
        try:
            self.close()
        except Exception:  # noqa: BLE001
            pass

    # ------------------------------------------------------------------

    def __iter__(self) -> Iterator[Dict[str, np.ndarray]]:
        epoch = self._epoch
        order = self._epoch_order(epoch)
        steps = self.steps_per_epoch
        start, self._skip = self._skip, 0
        aug_seed = hash((self.seed, epoch)) & 0x7FFFFFFF

        # C++ data plane: whole-batch decode without the GIL.  Probed on
        # the first step; None is sticky for the run (lib unbuilt /
        # format unsupported) and the Python pipeline takes over.
        native_batch = getattr(self.dataset, "load_batch", None)
        if native_batch is not None:
            while start < steps:
                lo = (start * self.global_batch_size
                      + self.shard_id * self.local_batch_size)
                idxs = order[lo:lo + self.local_batch_size]
                with _timed(self.stats, "data_build_ms", batch=start):
                    batch = self._build_native(idxs, native_batch, aug_seed)
                if batch is None:
                    break  # Python pipeline takes over from `start`
                if self.stats is not None:
                    self.stats.add("data_batches", 1.0)
                start += 1
                yield batch
            if start >= steps:
                return  # native served the whole epoch

        if self.ring_buffers and self._ring is None:
            self._ring = BatchRing(self.ring_buffers, self._batch_spec(),
                                   shared=self.decode_procs > 0,
                                   stats=self.stats)
        if self._ring is None:
            self._spec = self._batch_spec()
        self._setup_procs()
        self._setup_cache()

        yielded: "collections.deque" = collections.deque()

        def emit(batch):
            if self._ring is not None:
                yielded.append(batch)
                if len(yielded) > _RING_KEEP:
                    self._ring.release(yielded.popleft())
            if self.stats is not None:
                self.stats.add("data_batches", 1.0)
            return batch

        if self.num_workers <= 0:
            try:
                for step in range(start, steps):
                    yield emit(self._build(step, order, aug_seed))
            finally:
                while yielded:
                    self._ring.release(yielded.popleft())
            return

        pool = cf.ThreadPoolExecutor(max_workers=self.num_workers)
        inflight: "collections.deque" = collections.deque()
        try:
            horizon = min(self.lookahead, self.num_workers)
            nxt = start
            while nxt < min(start + horizon, steps):
                inflight.append(pool.submit(self._build, nxt, order,
                                            aug_seed))
                nxt += 1
            while inflight:
                fut = inflight.popleft()
                with _timed(self.stats, "data_build_wait_ms"):
                    batch = fut.result()
                if nxt < steps:
                    inflight.append(pool.submit(self._build, nxt,
                                                order, aug_seed))
                    nxt += 1
                # Register BEFORE yielding: if the consumer closes the
                # generator at this yield, the slot is still tracked
                # and the finally below reclaims it.
                emit(batch)
                yield batch
        finally:
            # Early close must not strand ring slots: release the
            # validity window first (unblocks builders waiting in
            # acquire), then reclaim the in-flight builds' slots.
            if self._ring is not None:
                while yielded:
                    self._ring.release(yielded.popleft())
            for fut in inflight:
                if not fut.cancel() and self._ring is not None:
                    try:
                        self._ring.release(fut.result(timeout=60))
                    except Exception:  # noqa: BLE001 — builder died; its
                        pass  # slot is lost but the ring stays usable
            pool.shutdown(wait=False)


def chunk_batches(iterator, steps_per_dispatch: int, stats=None):
    """Stack ``steps_per_dispatch`` consecutive host batches along a new
    leading axis — the chunk-assembly stage feeding the scanned train
    step (``train.steps_per_dispatch``; docs/PERFORMANCE.md).

    Sits BETWEEN the loader and ``prefetch_to_device`` so one H2D
    transfer ships a whole chunk.  Ring-buffer-aware: each incoming
    batch is copied into the chunk buffer the moment it is yielded, so
    the loader's ``_RING_KEEP``-yield validity window is honored for
    any k (the assembler never holds a loader batch across a yield).

    Chunk buffers rotate as a pair, mirroring ``prefetch_to_device``'s
    cast buffers and inheriting the same safety argument: a yielded
    chunk is consumed by the H2D thread, which blocks until the (async)
    transfer lands before pulling the next chunk, so buffer i is only
    rewritten after chunk i's copy completed (on the CPU backend the
    prefetch worker snapshots host arrays instead — ``device_put`` may
    alias — so reuse is safe there too).

    A trailing partial chunk (epoch length not divisible by k — fit()
    validates this never happens) is dropped, counted into the
    ``data_partial_chunks_dropped`` stat rather than silently shipped
    with stale rows.
    """
    k = int(steps_per_dispatch)
    if k < 1:
        raise ValueError(f"steps_per_dispatch must be >= 1, got {k}")
    if k == 1:
        yield from iterator
        return
    bufs: list = [None, None]
    flip = 0
    filled = 0
    for batch in iterator:
        if filled == 0:
            buf = bufs[flip]
            stale = (buf is None or set(buf) != set(batch) or any(
                buf[key].shape[1:] != np.asarray(v).shape
                or buf[key].dtype != np.asarray(v).dtype
                for key, v in batch.items()))
            if stale:
                bufs[flip] = {
                    key: np.empty((k,) + np.asarray(v).shape,
                                  np.asarray(v).dtype)
                    for key, v in batch.items()}
        with _timed(stats, "data_chunk_assemble_ms", batch=filled):
            for key, v in batch.items():
                bufs[flip][key][filled] = v
        filled += 1
        if filled == k:
            if stats is not None:
                stats.add("data_chunks", 1.0)
            out = bufs[flip]
            flip ^= 1
            filled = 0
            yield out
    if filled and stats is not None:
        stats.add("data_partial_chunks_dropped", 1.0)


def prefetch_to_device(iterator, size: int = 2, sharding=None, mesh=None,
                       transfer_dtype=None, drop_keys=(), spec=None,
                       stats=None):
    """Wrap a host batch iterator with a background H2D thread that
    stages batches onto device ahead of consumption (the final stage of
    the multi-stage pipeline; the TPU analogue of the reference's
    pinned-memory ``non_blocking`` H2D copies in SURVEY.md §3.1).

    Pass ``mesh`` for a batch-sharded global array built from each
    host's local slice (``make_array_from_process_local_data`` — the
    multi-host-correct path); ``sharding`` is the single-host
    device_put path.

    ``transfer_dtype`` (e.g. ``"bfloat16"``) casts image/depth on the
    host before the copy — halves H2D bytes when the input pipeline is
    transfer-bound; the model computes in its own ``compute_dtype``
    regardless.  Masks stay f32 (binary values are exact either way,
    but the loss reduces in f32).  The cast reuses a rotating pair of
    preallocated buffers per key (cast-into-buffer, not a second
    malloc+copy per step) — safe because the H2D thread blocks until
    each (async) transfer lands before touching the sibling buffer
    again; on the CPU backend, where ``device_put`` may alias host
    memory outright, the reuse is disabled and batches are snapshotted
    instead.

    ``stats`` (utils/observability.PipelineStats) records
    ``data_starved_ms`` (consumer blocked on an empty queue — the
    "input-bound" number), ``data_h2d_ms`` (device_put time),
    ``data_prefetch_full_ms`` (producer blocked on a full queue: the
    healthy, compute-bound direction) and queue-depth samples.

    Producer-thread exceptions propagate to the consumer; closing the
    generator early unblocks and stops the producer.
    """
    import jax

    cast = None
    if transfer_dtype and str(transfer_dtype) != "float32":
        import ml_dtypes  # ships with jax

        cast = np.dtype(getattr(ml_dtypes, str(transfer_dtype), None)
                        or transfer_dtype)

    # CPU jax may make device arrays that alias the source numpy buffer
    # (zero-copy device_put): never recycle cast buffers there, and
    # snapshot every host array before the put so upstream buffer
    # recycling (BatchRing) can never mutate an in-flight device batch.
    # Real accelerators copy host->HBM, so neither cost exists there.
    on_cpu = jax.default_backend() == "cpu"
    reuse_cast = cast is not None and not on_cpu
    cast_bufs: Dict[tuple, list] = {}

    def cast_into(k, arr, flip):
        if not reuse_cast:
            return np.asarray(arr).astype(cast)
        pair = cast_bufs.get(k)
        if pair is None or pair[0].shape != arr.shape:
            pair = cast_bufs[k] = [np.empty(arr.shape, cast),
                                   np.empty(arr.shape, cast)]
        buf = pair[flip]
        np.copyto(buf, arr, casting="unsafe")
        return buf

    def maybe_cast(batch, flip):
        if cast is None and not drop_keys:
            return batch
        out = dict(batch)
        for k in drop_keys:  # loader metadata the step never reads
            out.pop(k, None)
        if cast is not None:
            for k in ("image", "depth"):
                if k in out:
                    out[k] = cast_into(k, out[k], flip)
        return out

    q: "queue.Queue" = queue.Queue(maxsize=size)
    stop = threading.Event()
    _END = object()

    def worker():
        flip = 0
        try:
            for batch in iterator:
                batch = maybe_cast(batch, flip)
                flip ^= 1
                if on_cpu:
                    # cast outputs are already fresh on cpu (reuse_cast
                    # off) — don't copy those twice.
                    fresh = {"image", "depth"} if cast is not None else ()
                    batch = {k: (np.array(v)
                                 if isinstance(v, np.ndarray)
                                 and k not in fresh else v)
                             for k, v in batch.items()}
                if stop.is_set():
                    return
                with _timed(stats, "data_h2d_ms"):
                    if mesh is not None:
                        from ..parallel.mesh import global_batch_array

                        batch = global_batch_array(batch, mesh, spec=spec)
                    elif sharding is not None:
                        batch = jax.device_put(batch, sharding)
                    else:
                        batch = jax.device_put(batch)
                    if not on_cpu:
                        # H2D transfers are ASYNC: the host buffers
                        # (ring slots, rotating cast buffers) must stay
                        # immutable until the copy lands.  Waiting
                        # here, on the H2D thread, bounds in-flight
                        # reuse without stalling the consumer — the
                        # device batch had to finish transferring
                        # before a step could read it anyway.
                        jax.block_until_ready(batch)
                with _timed(stats, "data_prefetch_full_ms"):
                    while not stop.is_set():
                        try:
                            q.put(batch, timeout=0.1)
                            break
                        except queue.Full:
                            continue
                if stop.is_set():
                    return
            q.put(_END)
        except BaseException as e:  # noqa: BLE001 — relayed to the consumer
            while not stop.is_set():
                try:
                    q.put(e, timeout=0.1)
                    break
                except queue.Full:
                    continue

    t = threading.Thread(target=worker, daemon=True)
    t.start()
    try:
        while True:
            if stats is not None:
                stats.observe_depth(q.qsize(), size)
            with _timed(stats, "data_starved_ms"):
                item = q.get()
            if item is _END:
                break
            if isinstance(item, BaseException):
                raise item
            yield item
    finally:
        stop.set()
        # Drain so a producer blocked on a full queue can observe `stop`,
        # then join: a daemon thread torn down mid device transfer at
        # interpreter exit aborts the process with a C++ exception.
        try:
            while True:
                q.get_nowait()
        except queue.Empty:
            pass
        t.join(timeout=5.0)
