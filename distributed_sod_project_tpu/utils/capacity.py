"""Live per-compiled-program capacity ledger
(docs/OBSERVABILITY.md "Capacity & SLO").

``tools/roofline.py`` prices the flagship step OFFLINE (closed-form
FLOPs/bytes, ``--xla-check`` against XLA's cost model).  This module
makes those numbers a LIVE surface: every AOT-compiled executable the
serve engine caches (and, opted in, the train step program) is asked
for its own ``cost_analysis()`` / ``memory_analysis()`` at warmup, and
the measured device time the stacks already track (the engine's
per-(res, batch, arm) EWMA; the trainer's StepTimer) turns static cost
into live utilization:

- ``MFU = flops / measured_s / peak_flops`` per program — the
  model-FLOPs-utilization dial, continuously, per compiled program;
- ``roofline utilization = max(flop util, bandwidth util)`` — how close
  the program runs to ITS binding roofline (the tools/roofline.py
  ``t >= max(F/peak, B/bw)`` bound, inverted);
- HBM: each program's analyzed peak working set plus the device's live
  ``memory_stats`` headroom (``bytes_limit − bytes_in_use``);
- a stage-share attribution gauge (device / queue / host fractions of
  the measured end-to-end, from the PR-9 stage splits) — the
  scale-out-vs-futile signal ROADMAP item 2 names: deep queues with a
  high device share mean the device is the bottleneck (scale out);
  deep queues with a low device share mean the host is (scaling out is
  futile).

Off by default (``serve.capacity_ledger`` / ``capacity_ledger``):
nothing records, nothing renders, /metrics is byte-identical.  The
peaks are the published ones of the chip the process RUNS ON
(utils/chips.py, keyed by ``device_kind``): an unknown TPU kind is an
error at construction, and on the CPU there is no peak — the static
cost and measured time still render, MFU / roofline utilization and
the comm time estimates read 0 rather than a share of some chip's
peak.
"""

from __future__ import annotations

import threading
from typing import Callable, Dict, Optional

from .chips import DCN_BW, ChipPeaks, local_chip_peaks
from .logging import get_logger


def ring_wire_bytes(payload_bytes: float, axis_size: int) -> float:
    """Bytes each chip moves for a ring allreduce of ``payload_bytes``:
    ``2(n-1)/n × payload`` (reduce-scatter + all-gather halves).  For
    n=1 this is 0 — a single-replica 'collective' is free."""
    n = max(int(axis_size), 1)
    return 2.0 * (n - 1) / n * float(payload_bytes)


def collective_wire_bytes(c: Dict) -> float:
    """Per-chip wire bytes for ONE comm_plan collective dict: a full
    allreduce (``psum``/``all_reduce``) moves ``2(n-1)/n × payload``,
    a lone reduce-scatter or all-gather leg half that — the split the
    hierarchical ICI×DCN plan needs so each leg prices its own link."""
    n = max(int(c.get("axis_size", 1)), 1)
    payload = float(c.get("bytes", 0))
    kind = c.get("kind", "psum")
    if kind in ("reduce_scatter", "all_gather"):
        return (n - 1) / n * payload
    return 2.0 * (n - 1) / n * payload


def collective_est_ms(c: Dict, ici_bw: Optional[float]) -> float:
    """Wire bytes over the link they traverse, in ms: ``level='dcn'``
    (the inter-host hop of ``mesh.data_hosts>1`` plans) prices against
    ``DCN_BW``, everything else against the chip's ICI (plans from
    before the level field default to ici) — 0 where the chip, and so
    its ICI, is unknown (CPU)."""
    bw = DCN_BW if c.get("level", "ici") == "dcn" else ici_bw
    return collective_wire_bytes(c) / bw * 1e3 if bw else 0.0


def program_cost(compiled) -> Dict[str, float]:
    """``{flops, bytes, peak_hbm_bytes}`` from one compiled executable's
    own analyses.  Backends that omit a key (or the whole API) report
    0 — the ledger renders what XLA actually said, never a guess."""
    flops = bytes_ = 0.0
    try:
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        if cost:
            flops = float(cost.get("flops", 0.0) or 0.0)
            bytes_ = float(cost.get("bytes accessed", 0.0) or 0.0)
    except Exception:  # noqa: BLE001 — analysis is best-effort telemetry
        pass
    peak = 0.0
    try:
        mem = compiled.memory_analysis()
        if mem is not None:
            peak = float(
                getattr(mem, "argument_size_in_bytes", 0)
                + getattr(mem, "output_size_in_bytes", 0)
                + getattr(mem, "temp_size_in_bytes", 0))
    except Exception:  # noqa: BLE001
        pass
    return {"flops": flops, "bytes": bytes_, "peak_hbm_bytes": peak}


def device_hbm_gauges():
    """Per-device ``(label, in_use, headroom)`` from jax
    ``memory_stats()``; one zero row when the platform reports none
    (CPU) so the family set is platform-stable."""
    rows = []
    try:
        import jax

        for d in jax.local_devices():
            try:
                ms = d.memory_stats() or {}
            except Exception:  # noqa: BLE001 — platform without the API
                ms = {}
            in_use = int(ms.get("bytes_in_use", 0))
            limit = int(ms.get("bytes_limit", 0))
            rows.append((str(d.id), in_use,
                         max(limit - in_use, 0) if limit else 0))
    except Exception:  # noqa: BLE001 — no backend at all
        rows = []
    return rows or [("0", 0, 0)]


class CapacityLedger:
    """Cost/memory analysis per compiled program + measured-time EWMA →
    live utilization gauges.  Thread-safe; renders through the standard
    ``prom_families(labels)`` provider contract."""

    def __init__(self, *, peaks: Optional[ChipPeaks] = None,
                 share_fn: Optional[Callable[[], Dict[str, float]]] = None,
                 device_memory: bool = True):
        # None = the chip this process runs on (None again on the CPU).
        self.peaks = peaks if peaks is not None else local_chip_peaks()
        self._share_fn = share_fn
        self._device_memory = device_memory
        self._lock = threading.Lock()
        # key → {flops, bytes, peak_hbm_bytes, ewma_ms (None until
        # observed)}
        self._programs: Dict[str, Dict[str, float]] = {}
        # key → comm plan (parallel/engine.comm_plan dict: collectives
        # with payload bytes + axis size, overlap estimate, ZeRO HBM
        # saving) — static shape accounting, no tracing.
        self._comm: Dict[str, Dict] = {}
        self._log = get_logger()

    # -- ingest --------------------------------------------------------

    def record(self, key: str, compiled) -> Dict[str, float]:
        """Record one AOT-compiled executable's static cost under
        ``key`` (idempotent: a re-warm keeps the measured EWMA)."""
        cost = program_cost(compiled)
        with self._lock:
            prev = self._programs.get(key)
            if prev is not None:
                cost["ewma_ms"] = prev.get("ewma_ms")
            else:
                cost["ewma_ms"] = None
            self._programs[key] = cost
        return cost

    def record_jit(self, key: str, fn, *args) -> bool:
        """Train-side convenience: AOT lower+compile ``fn(*args)`` just
        for its analyses (one extra compile, paid only with the ledger
        opted in) and record it.  False (logged) when the callable has
        no AOT path."""
        lower = getattr(fn, "lower", None)
        if lower is None:
            self._log.warning(
                "capacity: %s has no .lower() — ledger stays empty for "
                "this program", key)
            return False
        try:
            self.record(key, lower(*args).compile())
            return True
        except Exception:  # noqa: BLE001 — telemetry must not kill a run
            self._log.exception("capacity: cost analysis failed for %s",
                                key)
            return False

    def record_comm(self, key: str, plan: Dict) -> None:
        """Record one program's communication plan under ``key`` —
        ``parallel/engine.comm_plan``'s dict (per-collective payload
        bytes + axis size, bucket count, structural overlap fraction,
        ZeRO HBM saving).  Rendered as the ``dsod_capacity_comm_*``
        families (DCN-level legs as ``dsod_capacity_comm_dcn_*``);
        wire bytes and estimated milliseconds are derived here against
        the chip's ICI / ``DCN_BW`` (utils/chips.py)."""
        if not isinstance(plan, dict) or "collectives" not in plan:
            raise ValueError("record_comm wants a comm_plan dict "
                             "(missing 'collectives')")
        with self._lock:
            self._comm[key] = plan

    def observe(self, key: str, device_ms: float, alpha: float = 0.2
                ) -> None:
        """Fold one measured device time (ms) into ``key``'s EWMA —
        the same 0.8/0.2 blend as the engine's SLO-expiry estimate."""
        with self._lock:
            p = self._programs.get(key)
            if p is None:
                return
            old = p.get("ewma_ms")
            p["ewma_ms"] = (float(device_ms) if old is None
                            else (1.0 - alpha) * old
                            + alpha * float(device_ms))

    # -- derived -------------------------------------------------------

    def _util(self, p: Dict[str, float]) -> Dict[str, float]:
        ms = p.get("ewma_ms")
        if not ms or self.peaks is None:
            return {"mfu": 0.0, "roofline": 0.0}
        s = ms / 1000.0
        mfu = p["flops"] / s / self.peaks.flops_bf16
        bwu = p["bytes"] / s / self.peaks.hbm_bw
        return {"mfu": mfu, "roofline": max(mfu, bwu)}

    def mfu(self, key: str) -> float:
        with self._lock:
            p = self._programs.get(key)
            return self._util(p)["mfu"] if p else 0.0

    def snapshot(self) -> Dict:
        """The /stats capacity block."""
        with self._lock:
            programs = {k: dict(p) for k, p in
                        sorted(self._programs.items())}
        out = {}
        for k, p in programs.items():
            u = self._util(p)
            out[k] = {
                "flops": p["flops"],
                "bytes": p["bytes"],
                "peak_hbm_bytes": p["peak_hbm_bytes"],
                "device_ms_ewma": (round(p["ewma_ms"], 3)
                                   if p["ewma_ms"] else None),
                "mfu": round(u["mfu"], 6),
                "roofline_util": round(u["roofline"], 6),
            }
        snap = {"programs": out,
                "peak_flops": self.peaks and self.peaks.flops_bf16,
                "hbm_bw": self.peaks and self.peaks.hbm_bw}
        with self._lock:
            comm = {k: dict(p) for k, p in sorted(self._comm.items())}
        if comm:
            for plan in comm.values():
                for c in plan.get("collectives", ()):
                    wire = collective_wire_bytes(c)
                    c["wire_bytes"] = int(wire)
                    c["est_ms"] = round(
                        collective_est_ms(c, self._ici_bw()), 6)
            snap["comm"] = comm
            snap["ici_bw"] = self._ici_bw()
            snap["dcn_bw"] = DCN_BW
        if self._share_fn is not None:
            try:
                snap["stage_share"] = {
                    k: round(v, 6)
                    for k, v in (self._share_fn() or {}).items()}
            except Exception:  # noqa: BLE001 — telemetry must not throw
                pass
        return snap

    def _ici_bw(self) -> Optional[float]:
        return self.peaks.ici_bw if self.peaks is not None else None

    # -- exposition ----------------------------------------------------

    def prom_families(self, labels: str = ""):
        """The ``dsod_capacity_*`` families: per-program static cost +
        live utilization (one ``program=`` sample each), the stage-share
        attribution, and per-device HBM headroom.  Core families render
        unconditionally while the ledger exists (inventory-stable); the
        ledger itself only exists when the knob is on."""
        with self._lock:
            rows = [(k, dict(p)) for k, p in
                    sorted(self._programs.items())]
        pre = f"{labels}," if labels else ""

        def plbl(k):
            return f'{pre}program="{k}"'

        flops, bts, peak, ms, mfu, roof = [], [], [], [], [], []
        for k, p in rows:
            u = self._util(p)
            flops.append('dsod_capacity_program_flops{%s} %g'
                         % (plbl(k), p["flops"]))
            bts.append('dsod_capacity_program_hbm_bytes{%s} %g'
                       % (plbl(k), p["bytes"]))
            peak.append('dsod_capacity_program_peak_hbm_bytes{%s} %g'
                        % (plbl(k), p["peak_hbm_bytes"]))
            ms.append('dsod_capacity_device_ms{%s} %g'
                      % (plbl(k), p["ewma_ms"] or 0.0))
            mfu.append('dsod_capacity_mfu{%s} %g' % (plbl(k), u["mfu"]))
            roof.append('dsod_capacity_roofline_util{%s} %g'
                        % (plbl(k), u["roofline"]))
        fams = []
        for name, samples in (
                ("dsod_capacity_program_flops", flops),
                ("dsod_capacity_program_hbm_bytes", bts),
                ("dsod_capacity_program_peak_hbm_bytes", peak),
                ("dsod_capacity_device_ms", ms),
                ("dsod_capacity_mfu", mfu),
                ("dsod_capacity_roofline_util", roof)):
            if samples:
                fams.append((name, "gauge", samples))
        # Comm ledger (ROADMAP item 4): per-collective payload/wire
        # bytes and the ICI-bandwidth time estimate, plus per-program
        # overlap + ZeRO-saving gauges.  Rendered only once a plan is
        # recorded — like the per-program families, `if samples`.
        with self._lock:
            comm_rows = [(k, p) for k, p in sorted(self._comm.items())]
        cb, cw, cms, cov, czs = [], [], [], [], []
        db, dw, dms = [], [], []
        for k, plan in comm_rows:
            for c in plan.get("collectives", ()):
                cl = (f'{pre}program="{k}",collective="{c["name"]}",'
                      f'axis="{c.get("axis", "")}"')
                payload = float(c.get("bytes", 0))
                wire = collective_wire_bytes(c)
                est = collective_est_ms(c, self._ici_bw())
                if c.get("level", "ici") == "dcn":
                    # The slow hop gets its own families so a dashboard
                    # can alarm on DCN pressure without parsing labels.
                    db.append('dsod_capacity_comm_dcn_bytes{%s} %g'
                              % (cl, payload))
                    dw.append('dsod_capacity_comm_dcn_wire_bytes{%s} %g'
                              % (cl, wire))
                    dms.append('dsod_capacity_comm_dcn_est_ms{%s} %g'
                               % (cl, est))
                    continue
                cb.append('dsod_capacity_comm_bytes{%s} %g'
                          % (cl, payload))
                cw.append('dsod_capacity_comm_wire_bytes{%s} %g'
                          % (cl, wire))
                cms.append('dsod_capacity_comm_est_ms{%s} %g'
                           % (cl, est))
            cov.append('dsod_capacity_comm_overlap_frac{%s} %g'
                       % (plbl(k), plan.get("overlap_frac", 0.0)))
            czs.append('dsod_capacity_comm_zero_hbm_saved_bytes{%s} %g'
                       % (plbl(k), plan.get("zero_hbm_saved_bytes", 0)))
        for name, samples in (
                ("dsod_capacity_comm_bytes", cb),
                ("dsod_capacity_comm_wire_bytes", cw),
                ("dsod_capacity_comm_est_ms", cms),
                ("dsod_capacity_comm_dcn_bytes", db),
                ("dsod_capacity_comm_dcn_wire_bytes", dw),
                ("dsod_capacity_comm_dcn_est_ms", dms),
                ("dsod_capacity_comm_overlap_frac", cov),
                ("dsod_capacity_comm_zero_hbm_saved_bytes", czs)):
            if samples:
                fams.append((name, "gauge", samples))
        # Stage-share attribution (device/queue/host fractions of the
        # measured e2e): rendered whenever a share source exists, 0
        # before traffic.
        if self._share_fn is not None:
            try:
                shares = self._share_fn() or {}
            except Exception:  # noqa: BLE001
                shares = {}
            fams.append(("dsod_capacity_stage_share", "gauge", [
                'dsod_capacity_stage_share{%sstage="%s"} %g'
                % (pre, s, shares.get(s, 0.0))
                for s in ("device", "queue", "host")]))
        if self._device_memory:
            in_use, headroom = [], []
            for dev, used, head in device_hbm_gauges():
                dl = f'{pre}device="{dev}"'
                in_use.append('dsod_capacity_hbm_bytes_in_use{%s} %d'
                              % (dl, used))
                headroom.append('dsod_capacity_hbm_headroom_bytes{%s} %d'
                                % (dl, head))
            fams.append(("dsod_capacity_hbm_bytes_in_use", "gauge",
                         in_use))
            fams.append(("dsod_capacity_hbm_headroom_bytes", "gauge",
                         headroom))
        return fams
