"""Open/closed-loop load generator for the serving engine
(docs/SERVING.md "Measuring throughput vs p99").

Stdlib-only (urllib + threads) so it runs anywhere the server does.
Two disciplines, because they answer different questions:

- **closed** loop — N workers, each sending back-to-back.  Measures
  capacity: the throughput the service sustains at a given concurrency
  and the latency it costs.  Latency under closed load is flattering
  (the generator slows down with the server — coordinated omission).
- **open** loop — requests fired on a fixed schedule at ``rps``
  regardless of completions, the arrival process real traffic has.
  Measures SLO behavior: p99 and shed rate at an offered rate (the
  throughput-vs-p99 curve; not measured on a chip).

Either discipline can offer **mixed traffic** against a fleet router
(``mix=``: weighted per-model/per-tenant request mix via X-Model /
X-Tenant headers), with per-SERVED-model p50/p95/p99 broken out in the
summary next to the per-arm breakdown — the fleet's mixed-model curve
(not measured on a chip) is one command.

**Duplicate traffic** (``zipf=(s, catalog)``): instead of cycling a
small body pool, each request draws its payload from a ``catalog`` of
distinct pre-encoded images with Zipf popularity p(k) ∝ 1/k^s — the
skewed repeat distribution real image traffic has, and the workload
the router cache (serve/cache.py) is built for.  ``perturb`` sends
that fraction of draws as a resize-perturbed re-encode of their
catalog image (same content, different bytes/resolution — misses the
exact arm, hits the near-dup arm).  The summary gains hit-rate and a
per-terminal-class breakdown read from the X-Cache response header.
"""

from __future__ import annotations

import heapq
import http.client
import io
import json
import threading
import time
import urllib.error
import urllib.request
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..utils.tracing import mint_trace_id, parse_timing


def encode_image(rng: np.random.RandomState, h: int, w: int) -> bytes:
    buf = io.BytesIO()
    np.save(buf, rng.randint(0, 256, size=(h, w, 3), dtype=np.uint8))
    return buf.getvalue()


def _encode_arr(arr: np.ndarray) -> bytes:
    buf = io.BytesIO()
    np.save(buf, arr)
    return buf.getvalue()


def structured_image(rng: np.random.RandomState, h: int, w: int
                     ) -> np.ndarray:
    """A smooth low-frequency test image (8x8 noise upsampled
    bilinearly).  Pure uint8 noise is the WRONG payload for near-dup
    experiments — its perceptual hash is not resize-stable (every
    pixel is independent, so resampling rewrites the block means);
    natural images are dominated by low frequencies, which survive a
    resize, and this generator keeps that property on purpose."""
    from PIL import Image

    base = rng.randint(0, 256, size=(8, 8, 3)).astype(np.uint8)
    return np.asarray(Image.fromarray(base).resize((w, h), Image.BILINEAR))


def _zipf_bodies(rng: np.random.RandomState, zipf, perturb: float,
                 sizes, n_total: int) -> List[bytes]:
    """Per-request payloads for a duplicate-traffic run: a catalog of
    distinct structured images drawn with Zipf popularity
    p(k) ∝ 1/k^s, plus (with probability ``perturb``) a resize-
    perturbed re-encode of the drawn image — same content at a nearby
    resolution, so it misses the exact cache arm and exercises the
    near-dup arm.  All draws are seeded: two runs with the same seed
    offer the SAME request stream."""
    from PIL import Image

    s, catalog = float(zipf[0]), int(zipf[1])
    if catalog < 1:
        raise ValueError(f"zipf catalog must be >= 1, got {catalog}")
    if s < 0:
        raise ValueError(f"zipf exponent must be >= 0, got {s}")
    if not 0.0 <= float(perturb) <= 1.0:
        raise ValueError(f"perturb must be in [0, 1], got {perturb}")
    imgs = []
    for k in range(catalog):
        h, w = sizes[k % len(sizes)]
        imgs.append(structured_image(rng, h, w))
    bodies = [_encode_arr(a) for a in imgs]
    variants: Dict[int, List[bytes]] = {}
    if perturb > 0:
        # Pre-encode the perturbed variants up front — the hot loop
        # must never bottleneck on PIL while it is offering load.
        for k, a in enumerate(imgs):
            h, w = a.shape[:2]
            variants[k] = [
                _encode_arr(np.asarray(Image.fromarray(a).resize(
                    (max(int(w * f), 8), max(int(h * f), 8)),
                    Image.BILINEAR)))
                for f in (0.875, 1.125)]
    p = 1.0 / np.arange(1, catalog + 1, dtype=np.float64) ** s
    p /= p.sum()
    ks = rng.choice(catalog, size=n_total, p=p)
    flips = rng.random_sample(n_total) < float(perturb)
    out: List[bytes] = []
    for i in range(n_total):
        k = int(ks[i])
        if flips[i] and variants:
            out.append(variants[k][int(rng.randint(len(variants[k])))])
        else:
            out.append(bodies[k])
    return out


def wait_ready(base_url: str, timeout_s: float = 60.0,
               poll_s: float = 0.25) -> bool:
    """Poll /healthz until it answers 200 (engine warmed and serving)."""
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        try:
            with urllib.request.urlopen(base_url + "/healthz",
                                        timeout=5.0) as r:
                if r.status == 200:
                    return True
        except (urllib.error.URLError, OSError):
            pass
        time.sleep(poll_s)
    return False


def _one(base_url: str, body: bytes, slo_ms: Optional[float],
         timeout_s: float, precision: Optional[str] = None,
         model: Optional[str] = None, tenant: Optional[str] = None,
         request_id: Optional[str] = None,
         stream: Optional[str] = None
         ) -> Tuple[str, float, Dict[str, Optional[str]]]:
    """One /predict round-trip → (outcome, latency_ms, info).
    Outcomes: ok | shed | expired | unhealthy | error | transport —
    ``transport`` is a connection-level failure (refused, reset,
    timeout, short body) as opposed to an HTTP-status ``error``; the
    split is what makes failover/chaos experiments readable (a killed
    replica produces transports, a sick one produces 5xx errors).
    ``info`` holds the response's X-Precision / X-Model headers (what
    the server actually SERVED — the ladder may adjust the arm, the
    router names the model), None values on non-200s, plus the echoed
    X-Request-ID (``rid``) and raw X-Timing (``timing`` — the
    server-side stage split; docs/OBSERVABILITY.md).
    ``model``/``tenant`` ride as X-Model / X-Tenant request headers
    (fleet routing + tenancy); ``request_id`` rides as X-Request-ID so
    the client's latency record and the server's trace share an id."""
    headers = {"Content-Type": "application/x-npy"}
    if slo_ms:
        headers["X-SLO-MS"] = str(slo_ms)
    if precision:
        headers["X-Precision"] = str(precision)
    if model:
        headers["X-Model"] = str(model)
    if tenant:
        headers["X-Tenant"] = str(tenant)
    if request_id:
        headers["X-Request-ID"] = str(request_id)
    if stream:
        # Per-stream session key (serve/streams.py): frames of one
        # stream share it, so the router opens a session, pins the
        # stream to a replica, and may serve the reuse fast path.
        headers["X-Stream-ID"] = str(stream)
    req = urllib.request.Request(base_url + "/predict", data=body,
                                 headers=headers, method="POST")
    t0 = time.monotonic()
    info: Dict[str, Optional[str]] = {"arm": None, "model": None,
                                      "rid": None, "timing": None,
                                      "cache": None, "reuse": None}
    try:
        with urllib.request.urlopen(req, timeout=timeout_s) as r:
            r.read()
            out = "ok" if r.status == 200 else "error"
            if out == "ok":
                info["arm"] = r.headers.get("X-Precision")
                info["model"] = r.headers.get("X-Model")
                info["rid"] = r.headers.get("X-Request-ID")
                info["timing"] = r.headers.get("X-Timing")
                # exact | near | coalesced on a router-cache hit,
                # absent on a real forward (serve/cache.py).
                info["cache"] = r.headers.get("X-Cache")
                # "1" on a temporal-coherence replay (serve/streams.py),
                # absent on a full forward — the streaming summary
                # splits its latency curves on this.
                info["reuse"] = r.headers.get("X-Stream-Reuse")
    except urllib.error.HTTPError as e:
        e.read()
        out = {429: "shed", 504: "expired", 503: "unhealthy"}.get(
            e.code, "error")
    except (urllib.error.URLError, OSError, http.client.HTTPException):
        # Connection-level death (incl. IncompleteRead on a mid-body
        # reset): counted apart from HTTP-status errors.
        out = "transport"
    return out, (time.monotonic() - t0) * 1000.0, info


def _percentile(sorted_ms: List[float], p: float) -> float:
    if not sorted_ms:
        return 0.0
    i = min(int(p * len(sorted_ms)), len(sorted_ms) - 1)
    return sorted_ms[i]


def _normalize_mix(mix) -> List[Dict]:
    """Mixed-traffic spec → ``[{"model", "tenant", "weight"}, ...]``.
    Accepts dicts (``model`` required, ``tenant``/``weight`` optional)
    or ``(model, weight)`` tuples."""
    out = []
    for entry in mix:
        if isinstance(entry, dict):
            e = {"model": entry.get("model"),
                 "tenant": entry.get("tenant"),
                 "weight": float(entry.get("weight", 1.0))}
        else:
            model, weight = entry
            e = {"model": model, "tenant": None, "weight": float(weight)}
        if not e["model"]:
            raise ValueError(f"mix entry {entry!r} needs a model")
        if e["weight"] <= 0:
            raise ValueError(f"mix entry {entry!r} needs weight > 0")
        out.append(e)
    if not out:
        raise ValueError("mix must not be empty")
    return out


def _profile_offsets(rps: float, duration_s: float,
                     ramp: Optional[Tuple[float, float, float]],
                     bursts) -> Tuple[List[float], float]:
    """Arrival offsets (seconds from t0) for a shaped open-loop run →
    ``(offsets, duration)``.  ``ramp=(r0, r1, T)`` sweeps the base rate
    linearly from r0 to r1 over T seconds (holding r1 after); with no
    ramp the base rate is flat ``rps``.  Each ``(extra, start, dur)``
    burst adds ``extra`` rps inside its window on top of the base.  The
    run covers ``max(duration_s, T, last burst end)`` so a ramp or a
    late burst is never truncated by the default duration.  Offsets
    come from integrating rate(t) in 5 ms slices and emitting an
    arrival per accumulated unit — exact arrival COUNT under any shape
    (a 1/rate(t) stepper overshoots wildly when a ramp starts near
    zero), with arrival times quantized to the slice, which is noise
    next to network jitter at any rate worth sweeping."""
    bursts = tuple(bursts or ())
    dur = float(duration_s)
    if ramp is not None:
        dur = max(dur, float(ramp[2]))
    for _extra, b0, bdur in bursts:
        dur = max(dur, float(b0) + float(bdur))

    def rate(t: float) -> float:
        if ramp is not None:
            r0, r1, T = ramp
            r = (float(r1) if T <= 0
                 else float(r0) + (float(r1) - float(r0)) * min(t / T, 1.0))
        else:
            r = float(rps)
        for extra, b0, bdur in bursts:
            if float(b0) <= t < float(b0) + float(bdur):
                r += float(extra)
        return r

    offsets: List[float] = []
    t, credit, dt = 0.0, 0.0, 0.005
    while t < dur:
        credit += rate(t) * dt
        while credit >= 1.0:
            offsets.append(t)
            credit -= 1.0
        t += dt
    return (offsets or [0.0]), dur


def run_loadgen(
    base_url: str,
    mode: str = "closed",
    concurrency: int = 4,
    requests: int = 50,
    rps: float = 10.0,
    duration_s: float = 5.0,
    sizes: Tuple[Tuple[int, int], ...] = ((320, 320),),
    seed: int = 0,
    slo_ms: float = 0.0,
    timeout_s: float = 60.0,
    precision: Optional[str] = None,
    model: Optional[str] = None,
    tenant: Optional[str] = None,
    mix=None,
    slowest: int = 0,
    quality: bool = False,
    slo: bool = False,
    ramp: Optional[Tuple[float, float, float]] = None,
    bursts=None,
    zipf: Optional[Tuple[float, int]] = None,
    perturb: float = 0.0,
) -> Dict[str, float]:
    """Drive ``base_url`` and return a summary dict (see module doc for
    the open/closed semantics).  Closed loop sends exactly ``requests``
    total across ``concurrency`` workers; open loop offers ``rps`` for
    ``duration_s``.  ``precision`` rides every request as X-Precision;
    ``model``/``tenant`` ride as X-Model / X-Tenant (fleet routing).

    **Mixed traffic** (``mix``): a weighted list of
    ``{"model", "tenant", "weight"}`` entries — each request draws its
    (model, tenant) from the mix (deterministic under ``seed``), so ONE
    loadgen run produces the fleet's mixed-model curve.  Latency
    percentiles are exact over OK responses (client-side e2e, incl.
    HTTP); the summary additionally breaks p50/p95/p99 down per SERVED
    arm (X-Precision) and per SERVED model (X-Model — the router echo),
    mirroring the per-arm breakdown, so the mixed-model
    throughput-vs-p99 curve is one command.

    ``quality=True``: the summary ends with one /metrics scrape of the
    per-model shadow-disagreement and drift gauges
    (:func:`scrape_quality`) under ``"quality"`` — a chaos or agenda
    leg records model quality alongside its latency curve from the
    same command.  Omitted when the endpoint exports none (monitors
    off).

    ``slo=True``: the summary ends with one /slo scrape
    (:func:`scrape_slo`) under ``"slo"`` — per-objective (per-model/
    per-tenant scoped) budget-remaining and fast/slow burn rates next
    to the latency summary, the PR-10 ``--quality`` pattern for the
    error-budget surface.  Omitted when the endpoint has no objectives
    (knob off).

    ``slowest > 0``: every request carries a generated ``X-Request-ID``
    and the summary reports the N slowest OK responses with their
    request/trace ids and the SERVER-side stage breakdown parsed from
    ``X-Timing`` (queue/device/resize/e2e ms) — "which requests were
    slow and WHERE" without a server round trip; when a row's trace
    was sampled, its id keys straight into /debug/traces.

    **Shaped load** (open mode only): ``ramp=(r0, r1, seconds)`` sweeps
    the offered rate linearly from r0 to r1 rps over the window;
    ``bursts=[(extra_rps, start_s, dur_s), ...]`` adds step bursts on
    top of the base rate.  Shaped runs append a ``"curve"`` — per
    time-bucket offered/done/ok counts and p99 next to the overall
    latency summary — the response curve an autoscaler leg reads to see
    the controller catch up with (or shed) a moving offered rate, and
    ``offered_rps`` becomes the profile's true average.

    **Duplicate traffic** (``zipf=(s, catalog)``): payloads draw from
    a catalog of distinct structured images with Zipf popularity
    p(k) ∝ 1/k^s instead of cycling the body pool; ``perturb`` sends
    that fraction of draws as resize-perturbed re-encodes (near-dup
    arm fodder).  The summary gains ``"cache"`` — hit count/rate and
    per-kind (exact/near/coalesced) split from the X-Cache response
    header — and ``"terminals"``, the client-observed mirror of the
    router book's five terminal classes (docs/SERVING.md "Router
    cache")."""
    if mode not in ("open", "closed"):
        raise ValueError(f"mode must be open|closed, got {mode!r}")
    if mode == "closed" and (ramp is not None or bursts):
        raise ValueError("ramp/bursts are open-loop shapes (mode='open')")
    rng = np.random.RandomState(seed)
    # Pre-encode a body pool: the generator must never bottleneck on
    # numpy/npy encoding while it is supposed to be offering load.
    pool = [encode_image(rng, h, w)
            for h, w in (sizes * ((16 // max(len(sizes), 1)) + 1))[:16]]
    offsets: Optional[List[float]] = None
    profile_dur = float(duration_s)
    if mode == "open" and (ramp is not None or bursts):
        offsets, profile_dur = _profile_offsets(rps, duration_s, ramp,
                                                bursts)
        n_total = len(offsets)
    else:
        n_total = (int(requests) if mode == "closed"
                   else max(int(float(duration_s) * float(rps)), 1))
    if perturb and zipf is None:
        raise ValueError("perturb > 0 needs zipf duplicate traffic")
    body_of: Optional[List[bytes]] = None
    if zipf is not None:
        body_of = _zipf_bodies(rng, zipf, perturb, sizes, n_total)
    if mix is not None:
        entries = _normalize_mix(mix)
        w = np.asarray([e["weight"] for e in entries], np.float64)
        draws = rng.choice(len(entries), size=n_total, p=w / w.sum())
        assignment = [entries[int(j)] for j in draws]
    else:
        assignment = [{"model": model, "tenant": tenant}] * n_total
    lock = threading.Lock()
    outcomes: Dict[str, int] = {"ok": 0, "shed": 0, "expired": 0,
                                "unhealthy": 0, "error": 0,
                                "transport": 0}
    ok_ms: List[float] = []
    # OK responses per cache disposition ("forward" = no X-Cache
    # header, i.e. a real engine forward), plus hit-path latencies so
    # the summary can put the hit p50 next to the forward p50.
    cache_kinds: Dict[str, int] = {}
    cache_hit_ms: List[float] = []
    arm_ms: Dict[str, List[float]] = {}
    model_ms: Dict[str, List[float]] = {}
    model_sent: Dict[str, int] = {}
    # Failures per ASSIGNED model (the response names no model on a
    # failed request): the per-model half of a failover/chaos read.
    # "unhealthy" (503 — a dead replica set) belongs here too, or a
    # killed single-replica model's failures vanish from its row.
    _MODEL_FAIL_OUTCOMES = ("error", "transport", "unhealthy")
    model_fail: Dict[Tuple[str, str], int] = {}
    # slowest-N tracking: a min-heap bounded at N, so a long soak holds
    # N rows, not one per OK response.  Entries are (ms, seq, info);
    # seq breaks latency ties (dicts don't compare).
    slow_rows: List[Tuple[float, int, Dict]] = []
    slow_seq = [0]
    # Response-curve buckets for shaped runs: each request books into
    # the bucket of its SCHEDULED offset (offered time, not completion
    # time), so a bucket's offered count is exact even when responses
    # straggle past its edge.
    curve: Optional[List[Dict]] = None
    bucket_of: List[int] = []
    if offsets is not None:
        n_buckets = min(8, max(1, int(profile_dur)))
        width = profile_dur / n_buckets
        curve = [{"t0": round(k * width, 2),
                  "t1": round((k + 1) * width, 2),
                  "offered": 0, "done": 0, "ok": 0, "_ms": []}
                 for k in range(n_buckets)]
        for off in offsets:
            k = min(int(off / width), n_buckets - 1)
            bucket_of.append(k)
            curve[k]["offered"] += 1

    def record(out: str, ms: float, info=None, sent_model=None) -> None:
        info = info or {}
        with lock:
            outcomes[out] += 1
            if out == "ok":
                ok_ms.append(ms)
                ck = info.get("cache") or "forward"
                cache_kinds[ck] = cache_kinds.get(ck, 0) + 1
                if ck != "forward":
                    cache_hit_ms.append(ms)
                if info.get("arm"):
                    arm_ms.setdefault(info["arm"], []).append(ms)
                if info.get("model"):
                    model_ms.setdefault(info["model"], []).append(ms)
                if slowest > 0:
                    slow_seq[0] += 1
                    row = (ms, slow_seq[0], info)
                    if len(slow_rows) < slowest:
                        heapq.heappush(slow_rows, row)
                    elif ms > slow_rows[0][0]:
                        heapq.heapreplace(slow_rows, row)
            elif out in _MODEL_FAIL_OUTCOMES and sent_model:
                key = (sent_model, out)
                model_fail[key] = model_fail.get(key, 0) + 1

    def fire(i: int) -> None:
        a = assignment[i]
        if a["model"]:
            with lock:
                model_sent[a["model"]] = model_sent.get(a["model"], 0) + 1
        # A request id per request (the X-Request-ID header) so the
        # slowest-N rows key into the server's /debug/traces; ids do
        # not perturb the seeded (model, tenant) draws above.
        rid = mint_trace_id() if slowest > 0 else None
        body = body_of[i] if body_of is not None else pool[i % len(pool)]
        res = _one(base_url, body, slo_ms or None,
                   timeout_s, precision=precision, model=a["model"],
                   tenant=a.get("tenant") or tenant, request_id=rid)
        record(*res, sent_model=a["model"])
        if curve is not None:
            b = curve[bucket_of[i]]
            with lock:
                b["done"] += 1
                if res[0] == "ok":
                    b["ok"] += 1
                    b["_ms"].append(res[1])

    t_start = time.monotonic()
    if mode == "closed":
        remaining = [n_total]

        def worker() -> None:
            while True:
                with lock:
                    if remaining[0] <= 0:
                        return
                    remaining[0] -= 1
                    i = n_total - remaining[0] - 1
                fire(i)

        threads = [threading.Thread(target=worker, daemon=True)
                   for _ in range(max(int(concurrency), 1))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        sent = n_total
    else:
        # Fixed worker pool, not thread-per-request: at a few hundred
        # rps the spawn cost inflates the very p99 the sweep measures,
        # and thread exhaustion kills the leg.  The pool bounds
        # client-side concurrency; a scheduled arrival that finds every
        # worker blocked queues in the executor and its lateness shows
        # up in latency — the open-loop signal, not a generator stall.
        from concurrent.futures import ThreadPoolExecutor

        if offsets is None:
            interval = 1.0 / max(float(rps), 1e-6)
            offsets = [i * interval for i in range(n_total)]
            peak_rps = float(rps)
        else:
            # Size the pool for the PEAK of the shaped profile, not the
            # flat rps knob — a burst that outruns the pool would queue
            # in the generator and smear the very step it measures.
            peak_rps = ((max(float(ramp[0]), float(ramp[1]))
                         if ramp is not None else float(rps))
                        + max((float(b[0]) for b in (bursts or ())),
                              default=0.0))
        workers = min(256, max(8, int(peak_rps * min(timeout_s, 10.0))))
        futures = []
        with ThreadPoolExecutor(max_workers=workers) as ex:
            for i, off in enumerate(offsets):
                delay = (t_start + off) - time.monotonic()
                if delay > 0:
                    time.sleep(delay)
                futures.append(ex.submit(fire, i))
            for f in futures:
                f.result()
        sent = n_total
    elapsed = time.monotonic() - t_start

    ok_ms.sort()
    done = sum(outcomes.values())
    out = {
        "mode": mode,
        "sent": sent,
        "done": done,
        "elapsed_s": round(elapsed, 3),
        "throughput_rps": round(outcomes["ok"] / elapsed, 2) if elapsed
        else 0.0,
        "p50_ms": round(_percentile(ok_ms, 0.50), 2),
        "p95_ms": round(_percentile(ok_ms, 0.95), 2),
        "p99_ms": round(_percentile(ok_ms, 0.99), 2),
        "mean_ms": round(sum(ok_ms) / len(ok_ms), 2) if ok_ms else 0.0,
        **outcomes,
    }
    if precision:
        out["precision"] = precision
    if model:
        out["model"] = model
    if tenant:
        out["tenant"] = tenant
    if mix is not None:
        out["mix"] = [{k: v for k, v in e.items() if v is not None}
                      for e in _normalize_mix(mix)]
    hits = sum(v for k, v in cache_kinds.items() if k != "forward")
    if zipf is not None or hits:
        # Cache disposition of the OK responses (X-Cache header) plus
        # the client-observed mirror of the router book's terminal
        # classes — served+shed+expired+errors+cache_hit is the
        # identity /stats asserts server-side (docs/SERVING.md).
        if zipf is not None:
            out["zipf"] = {"s": float(zipf[0]), "catalog": int(zipf[1]),
                           "perturb": round(float(perturb), 4)}
        cache_hit_ms.sort()
        out["cache"] = {
            "hits": hits,
            "hit_rate": (round(hits / outcomes["ok"], 4)
                         if outcomes["ok"] else 0.0),
            "hit_p50_ms": round(_percentile(cache_hit_ms, 0.50), 2),
            "hit_p99_ms": round(_percentile(cache_hit_ms, 0.99), 2),
            "kinds": {k: cache_kinds[k] for k in sorted(cache_kinds)},
        }
        out["terminals"] = {
            "served": outcomes["ok"] - hits,
            "cache_hit": hits,
            "shed": outcomes["shed"],
            "expired": outcomes["expired"],
            "errors": (outcomes["error"] + outcomes["unhealthy"]
                       + outcomes["transport"]),
        }
    if arm_ms:
        # Per-SERVED-arm latency breakdown: under the degraded ladder a
        # single offered arm can come back as several served arms, and
        # the curve per arm is the number the r8 agenda sweeps.
        out["arms"] = {}
        for arm in sorted(arm_ms):
            ms = sorted(arm_ms[arm])
            out["arms"][arm] = {
                "ok": len(ms),
                "p50_ms": round(_percentile(ms, 0.50), 2),
                "p95_ms": round(_percentile(ms, 0.95), 2),
                "p99_ms": round(_percentile(ms, 0.99), 2),
            }
    if model_ms or model_sent:
        # Per-SERVED-model latency breakdown (the response's X-Model —
        # the router's echo), mirroring the per-arm breakdown: under a
        # mixed-model run this is the per-model half of the fleet's
        # throughput-vs-p99 curve, from ONE command.
        out["models"] = {}
        for name in sorted(set(model_ms) | set(model_sent)):
            ms = sorted(model_ms.get(name, []))
            out["models"][name] = {
                "sent": model_sent.get(name, 0),
                "ok": len(ms),
                "error": model_fail.get((name, "error"), 0),
                "transport": model_fail.get((name, "transport"), 0),
                "unhealthy": model_fail.get((name, "unhealthy"), 0),
                "p50_ms": round(_percentile(ms, 0.50), 2),
                "p95_ms": round(_percentile(ms, 0.95), 2),
                "p99_ms": round(_percentile(ms, 0.99), 2),
            }
    if slowest > 0 and slow_rows:
        # The N slowest OK responses, server-side stage split attached:
        # client e2e minus the X-Timing e2e is the network + front-door
        # share, and a sampled row's trace id keys into /debug/traces.
        slow_rows.sort(key=lambda e: -e[0])
        rows = []
        for ms, _seq, info in slow_rows[:slowest]:
            trace_id, stages = parse_timing(info.get("timing"))
            rows.append({
                "ms": round(ms, 2),
                "request_id": info.get("rid"),
                "trace": trace_id,  # None = not sampled server-side
                "model": info.get("model"),
                "arm": info.get("arm"),
                "stages": {k: round(v, 3) for k, v in stages.items()},
            })
        out["slowest"] = rows
    if mode == "open":
        if curve is not None:
            out["offered_rps"] = (round(n_total / profile_dur, 2)
                                  if profile_dur else 0.0)
            rendered = []
            for b in curve:
                ms = sorted(b.pop("_ms"))
                b["p99_ms"] = round(_percentile(ms, 0.99), 2)
                rendered.append(b)
            # The response curve: offered vs completed vs ok per time
            # bucket with the bucket's p99 — "did the fleet keep up as
            # the rate moved", readable without replaying the run.
            out["curve"] = rendered
        else:
            out["offered_rps"] = round(float(rps), 2)
    if quality:
        q = scrape_quality(base_url)
        if q:
            out["quality"] = q
    if slo:
        s = scrape_slo(base_url)
        if s:
            out["slo"] = s
    return out


def stream_frames(rng: np.random.RandomState, h: int, w: int,
                  n_frames: int, perturb: float = 0.0) -> List[bytes]:
    """A temporally-coherent pre-encoded frame train for ONE stream:
    frame i+1 is frame i's scene under a small uniform brightness
    jitter (bytes differ, the perceptual hash barely moves — the
    workload the temporal-coherence fast path is built for), and with
    probability ``perturb`` a SCENE CUT replaces the base image (a cut
    must miss the reuse gate and force a full forward).  Fully seeded:
    the same (seed, h, w, n, perturb) always yields the same bytes —
    the determinism tests/test_streams.py asserts."""
    if not 0.0 <= float(perturb) <= 1.0:
        raise ValueError(f"perturb must be in [0, 1], got {perturb}")
    frames: List[bytes] = []
    base = structured_image(rng, h, w).astype(np.int16)
    for i in range(int(n_frames)):
        if i > 0 and perturb > 0 \
                and rng.random_sample() < float(perturb):
            base = structured_image(rng, h, w).astype(np.int16)
        arr = np.clip(base + int(rng.randint(-2, 3)), 0, 255)
        frames.append(_encode_arr(arr.astype(np.uint8)))
    return frames


def run_stream_loadgen(
    base_url: str,
    streams: int = 4,
    fps: float = 10.0,
    duration_s: float = 5.0,
    sizes: Tuple[Tuple[int, int], ...] = ((320, 320),),
    seed: int = 0,
    perturb: float = 0.0,
    slo_ms: float = 0.0,
    timeout_s: float = 60.0,
    precision: Optional[str] = None,
    model: Optional[str] = None,
    tenant: Optional[str] = None,
) -> Dict:
    """Streaming-video mode (docs/SERVING.md "Streaming"): ``streams``
    concurrent clients, each pushing a temporally-coherent frame train
    at a fixed ``fps`` under its own ``X-Stream-ID``.  Frames within a
    stream are SEQUENTIAL (a video client never races its own frames):
    each client sends frame i at its scheduled instant ``t0 + i/fps``,
    waits for the answer, and sleeps until the next slot — a late
    answer makes the next frame fire immediately, which is exactly the
    freshness pressure a real stream applies.

    ``perturb`` is the per-frame SCENE-CUT probability (a cut forces a
    full forward past the reuse gate); between cuts frames carry only
    a small brightness jitter, the reuse-arm fodder.  Deterministic
    under ``seed``: payload bytes and schedule are identical across
    runs (latencies, of course, are not).

    The summary reports the streaming triple the r19 agenda records:
    **per-stream p99** (each stream's own tail, plus the fleet-worst
    under ``per_stream_p99_ms``), **inter-frame jitter** (stddev of
    completion-to-completion intervals per stream, ms), and **reuse
    rate** (X-Stream-Reuse answers / OK), with the reuse-vs-forward
    p50 split alongside."""
    if int(streams) < 1:
        raise ValueError(f"streams must be >= 1, got {streams}")
    if float(fps) <= 0:
        raise ValueError(f"fps must be > 0, got {fps}")
    n_frames = max(int(float(duration_s) * float(fps)), 1)
    interval = 1.0 / float(fps)
    specs = []
    for si in range(int(streams)):
        srng = np.random.RandomState((int(seed) * 9973 + si) % (2**31))
        h, w = sizes[si % len(sizes)]
        specs.append({
            "sid": f"lg{int(seed)}-{si}",
            "frames": stream_frames(srng, h, w, n_frames, perturb)})
    lock = threading.Lock()
    outcomes: Dict[str, int] = {"ok": 0, "shed": 0, "expired": 0,
                                "unhealthy": 0, "error": 0,
                                "transport": 0}
    reuse_ms: List[float] = []
    fwd_ms: List[float] = []
    rows: List[Dict] = []

    def client(spec: Dict) -> None:
        lats: List[float] = []
        done_t: List[float] = []
        reused = 0
        t0 = time.monotonic()
        for i, body in enumerate(spec["frames"]):
            delay = (t0 + i * interval) - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            out, ms, info = _one(base_url, body, slo_ms or None,
                                 timeout_s, precision=precision,
                                 model=model, tenant=tenant,
                                 stream=spec["sid"])
            with lock:
                outcomes[out] += 1
                if out == "ok":
                    if info.get("reuse") == "1":
                        reused += 1
                        reuse_ms.append(ms)
                    else:
                        fwd_ms.append(ms)
            if out == "ok":
                lats.append(ms)
                done_t.append(time.monotonic())
        lats.sort()
        gaps = [(done_t[k] - done_t[k - 1]) * 1000.0
                for k in range(1, len(done_t))]
        jitter = float(np.std(gaps)) if len(gaps) >= 2 else 0.0
        with lock:
            rows.append({
                "stream": spec["sid"],
                "sent": len(spec["frames"]),
                "ok": len(lats),
                "reused": reused,
                "reuse_rate": (round(reused / len(lats), 4)
                               if lats else 0.0),
                "p50_ms": round(_percentile(lats, 0.50), 2),
                "p99_ms": round(_percentile(lats, 0.99), 2),
                "jitter_ms": round(jitter, 2),
            })

    t_start = time.monotonic()
    threads = [threading.Thread(target=client, args=(s,), daemon=True)
               for s in specs]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    elapsed = time.monotonic() - t_start
    all_ms = sorted(reuse_ms + fwd_ms)
    reuse_ms.sort()
    fwd_ms.sort()
    rows.sort(key=lambda r: r["stream"])
    hits = len(reuse_ms)
    return {
        "mode": "streaming",
        "streams": int(streams),
        "fps": float(fps),
        "frames_per_stream": n_frames,
        "perturb": round(float(perturb), 4),
        "sent": int(streams) * n_frames,
        "done": sum(outcomes.values()),
        "elapsed_s": round(elapsed, 3),
        "p50_ms": round(_percentile(all_ms, 0.50), 2),
        "p95_ms": round(_percentile(all_ms, 0.95), 2),
        "p99_ms": round(_percentile(all_ms, 0.99), 2),
        "mean_ms": (round(sum(all_ms) / len(all_ms), 2)
                    if all_ms else 0.0),
        **outcomes,
        "reuse": {
            "hits": hits,
            "rate": (round(hits / outcomes["ok"], 4)
                     if outcomes["ok"] else 0.0),
            "reuse_p50_ms": round(_percentile(reuse_ms, 0.50), 2),
            "forward_p50_ms": round(_percentile(fwd_ms, 0.50), 2),
        },
        "per_stream": rows,
        "per_stream_p99_ms": (max(r["p99_ms"] for r in rows)
                              if rows else 0.0),
        "jitter_ms": (round(sum(r["jitter_ms"] for r in rows)
                            / len(rows), 2) if rows else 0.0),
    }


def fetch_stats(base_url: str, timeout_s: float = 10.0) -> Dict[str, float]:
    with urllib.request.urlopen(base_url + "/stats", timeout=timeout_s) as r:
        return json.loads(r.read().decode())


def scrape_slo(base_url: str, timeout_s: float = 10.0) -> Dict:
    """End-of-run /slo scrape, condensed per objective (the objective's
    scope IS the per-model/per-tenant key — the router tracks one book,
    so unlike the quality gauges there are no replica-labeled series to
    disambiguate):

        {name: {"scope", "kind", "budget_remaining",
                "burn_fast", "burn_slow", "good", "bad", "active"}}

    Empty when the endpoint is unreachable or exports no objectives —
    an agenda leg records error-budget state exactly when there is an
    SLO to record."""
    try:
        with urllib.request.urlopen(base_url.rstrip("/") + "/slo",
                                    timeout=timeout_s) as r:
            snap = json.loads(r.read().decode())
    except (urllib.error.URLError, OSError, ValueError):
        return {}
    active = set(snap.get("active", []))
    out = {}
    for o in snap.get("objectives", []):
        burns = o.get("burn_rate", {})
        out[o["name"]] = {
            "scope": o.get("scope"),
            "kind": o.get("kind"),
            "budget_remaining": o.get("budget_remaining"),
            "burn_fast": burns.get("fast"),
            "burn_slow": burns.get("slow"),
            "good": o.get("good"),
            "bad": o.get("bad"),
            # Exact rule-name membership (utils/slo.py names them
            # slo_<name>_burn / slo_<name>_budget): a prefix match
            # would cross-attribute when one objective's name prefixes
            # another's.
            "active": sorted(active & {f"slo_{o['name']}_burn",
                                       f"slo_{o['name']}_budget"}),
        }
    return out


# Quality gauges worth carrying into a load summary (serve/quality.py;
# docs/OBSERVABILITY.md "Model health").
_QUALITY_FAMILIES = ("dsod_quality_psi", "dsod_quality_shadow_mae_avg",
                     "dsod_quality_shadow_flip_avg",
                     "dsod_quality_shadow_total",
                     "dsod_quality_shadow_dropped_total",
                     "dsod_quality_scored_total")


def _parse_labels(frag: str) -> Dict[str, str]:
    """Label fragment → dict.  Split-on-comma is sufficient for the
    quality families: every label value here (model/arm/signal/replica
    names) comes from validated identifier-like config fields — none
    may contain a comma or an escaped quote."""
    out = {}
    for part in frag.split(","):
        k, sep, v = part.partition("=")
        if sep:
            out[k.strip()] = v.strip().strip('"')
    return out


def scrape_quality(base_url: str, timeout_s: float = 10.0) -> Dict:
    """End-of-run /metrics scrape of the model-health quality gauges,
    grouped per model label (the single-engine server exports no
    ``model=`` label — those series land under ``""``; a multi-member
    replica set's series carry ``replica=`` and land under
    ``model[replica]`` so replicas never overwrite each other):

        {model: {"psi": {signal: v}, "shadow": {arm: {...}},
                 "scored": n, "shadow_dropped": n}}

    Empty when the endpoint is unreachable or the quality monitors are
    off — a chaos/agenda leg records quality alongside latency exactly
    when there is quality telemetry to record."""
    from ..utils.observability import parse_prom_text

    try:
        with urllib.request.urlopen(base_url.rstrip("/") + "/metrics",
                                    timeout=timeout_s) as r:
            text = r.read().decode()
    except (urllib.error.URLError, OSError):
        return {}
    out: Dict[str, Dict] = {}

    def model_entry(labels):
        key = labels.get("model", "")
        if "replica" in labels:
            key = f'{key}[{labels["replica"]}]'
        return out.setdefault(key, {})

    samples = []
    for fam_name, _typ, fam_samples in parse_prom_text(text):
        if fam_name in _QUALITY_FAMILIES:
            samples.extend(fam_samples)
    for line in samples:
        head, _, rest = line.partition(" ")
        name, _, frag = head.partition("{")
        labels = _parse_labels(frag.rstrip("}"))
        try:
            value = float(rest.split()[0])
        except (ValueError, IndexError):
            continue
        entry = model_entry(labels)
        if name == "dsod_quality_psi":
            entry.setdefault("psi", {})[labels.get("signal", "")] = value
        elif name == "dsod_quality_scored_total":
            entry["scored"] = value
        elif name == "dsod_quality_shadow_dropped_total":
            entry["shadow_dropped"] = value
        else:
            arm = labels.get("arm", "")
            key = {"dsod_quality_shadow_mae_avg": "mae_avg",
                   "dsod_quality_shadow_flip_avg": "flip_avg",
                   "dsod_quality_shadow_total": "n"}[name]
            entry.setdefault("shadow", {}).setdefault(arm, {})[key] = value
    return {m: v for m, v in out.items() if v}
