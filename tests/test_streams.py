"""Streaming-video SOD serving tests (serve/streams.py +
serve/batcher.py affinity + the router's session door —
docs/SERVING.md "Streaming").

Invariants proven here:

- the StreamTable is bounded + TTL-evicted under a fake clock: live
  sessions are never evicted to make room (a NEW stream sheds instead),
  idle sessions expire in LRU order and are counted;
- a re-home (pin moving a homed session) is counted; a first pin is not;
- the temporal-coherence reuse gate answers ONLY within the Hamming
  budget, and the EMA blend never loses a frame (shape mismatch or
  undecodable previous mask falls back to the engine's own bytes);
- the batcher's per-stream affinity map is written on put, LRU-capped,
  and a stream-FILLED bucket dispatches immediately WITHOUT waiting out
  an unrelated older head's max-wait window (the stall regression) —
  while that older head still dispatches at its own deadline;
- over live HTTP: a temporally-coherent frame replays the previous mask
  byte-for-byte with ``X-Stream-Reuse: 1`` and books the SIXTH terminal
  class (served + shed + expired + errors + cache_hit + stream_reuse ==
  submitted); a full stream table 429s a NEW stream with
  ``kind=stream_budget``; killing a stream's home replica re-homes the
  session (counted) with the identity still exact;
- RGB-D channel contract: an (H, W, 3) payload to a depth model — and
  (H, W, 4) to an RGB model — 400s BEFORE submit, with the engine book
  untouched and the fleet identity still consistent;
- with streaming off (the default) the ``X-Stream-ID`` header is inert
  and no ``dsod_stream`` family exists anywhere in /metrics;
- ``stream_frames`` is deterministic under its seed and temporally
  coherent (consecutive frames stay inside the reuse Hamming gate).
"""

import io
import json
import threading
import time
import urllib.error
import urllib.request

import flax.linen as nn
import jax
import numpy as np
import pytest

from distributed_sod_project_tpu.configs import (DataConfig,
                                                 ExperimentConfig,
                                                 FleetConfig, ModelConfig,
                                                 ServeConfig,
                                                 fleet_config_from_dict)
from distributed_sod_project_tpu.serve import batcher as batcher_mod
from distributed_sod_project_tpu.serve.batcher import DynamicBatcher, Request
from distributed_sod_project_tpu.serve.cache import (_decode_mask,
                                                     _encode_mask, hamming,
                                                     payload_fingerprint)
from distributed_sod_project_tpu.serve.engine import InferenceEngine
from distributed_sod_project_tpu.serve.fleet import EngineBackend, Fleet
from distributed_sod_project_tpu.serve.loadgen import stream_frames
from distributed_sod_project_tpu.serve.router import make_fleet_server
from distributed_sod_project_tpu.serve.streams import (StreamTable,
                                                       sanitize_stream_id)


class TinySOD(nn.Module):
    """Minimal model with the zoo forward signature (depth accepted and
    ignored, so the SAME module serves both RGB and RGB-D configs)."""

    @nn.compact
    def __call__(self, image, depth=None, train=False):
        x = nn.Conv(4, (3, 3), name="c1")(image)
        x = nn.relu(x)
        return (nn.Conv(1, (1, 1), name="head")(x),)


def _cfg(mname="minet", use_depth=False, **serve_kw):
    serve_kw.setdefault("batch_buckets", (1, 2))
    serve_kw.setdefault("resolution_buckets", (16,))
    serve_kw.setdefault("max_wait_ms", 5.0)
    serve_kw.setdefault("watchdog_deadline_s", 30.0)
    return ExperimentConfig(
        data=DataConfig(image_size=(16, 16), use_depth=use_depth),
        model=ModelConfig(name=mname),
        serve=ServeConfig(**serve_kw))


@pytest.fixture(scope="module")
def two_tiny():
    model = TinySOD()
    probe = np.zeros((1, 16, 16, 3), np.float32)
    va = model.init(jax.random.key(0), probe, None, train=False)
    vb = model.init(jax.random.key(1), probe, None, train=False)
    return model, va, vb


def _start_http(fleet):
    srv = make_fleet_server(fleet, "127.0.0.1", 0)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    return srv, f"http://127.0.0.1:{srv.server_address[1]}"


def _img(seed, h, w, c=3):
    return np.random.RandomState(seed).randint(0, 256, (h, w, c), np.uint8)


def _post(url, img, model=None, stream=None, timeout=60.0):
    buf = io.BytesIO()
    np.save(buf, img)
    headers = {"Content-Type": "application/x-npy"}
    if model:
        headers["X-Model"] = model
    if stream:
        headers["X-Stream-ID"] = stream
    req = urllib.request.Request(url + "/predict", data=buf.getvalue(),
                                 headers=headers, method="POST")
    with urllib.request.urlopen(req, timeout=timeout) as r:
        body = r.read()
        return body, dict(r.headers)


def _get_json(url, path):
    with urllib.request.urlopen(url + path, timeout=10) as r:
        return json.loads(r.read().decode())


def _metrics(url):
    return urllib.request.urlopen(url + "/metrics", timeout=10
                                  ).read().decode()


def _consistent_stats(url, tries=100):
    """The identity is eventually consistent (terminals book around the
    response write) — poll briefly before asserting on it."""
    stats = None
    for _ in range(tries):
        stats = _get_json(url, "/stats")
        if stats["fleet"]["consistent"]:
            return stats
        time.sleep(0.05)
    return stats


# ------------------------------------------------------ session table


def test_sanitize_stream_id():
    assert sanitize_stream_id(None) is None
    assert sanitize_stream_id("") is None
    assert sanitize_stream_id("  ") is None
    assert sanitize_stream_id("cam-1.front:a_b") == "cam-1.front:a_b"
    # Hostile charset is flattened, never passed through.
    assert sanitize_stream_id("a b\nc{d}") == "a_b_c_d_"
    # Bounded: a giant id truncates to the prefix.
    assert sanitize_stream_id("x" * 500) == "x" * 64


def test_stream_table_rejects_bad_max_sessions():
    with pytest.raises(ValueError, match="max_sessions"):
        StreamTable(0, 30.0)


def test_stream_table_budget_sheds_new_streams_only():
    clk = [0.0]
    t = StreamTable(2, ttl_s=10.0, clock=lambda: clk[0])
    assert t.touch("a")[0] == "ok"
    assert t.touch("b")[0] == "ok"
    # Table full of LIVE sessions: a NEW stream sheds (never evicts).
    verdict, sess = t.touch("c")
    assert (verdict, sess) == ("budget", None)
    # Existing streams still refresh fine.
    assert t.touch("a")[0] == "ok"
    raw = t.stats.raw()
    assert raw["opened"] == 2
    assert raw["budget_shed"] == 1
    assert raw["expired"] == 0


def test_stream_table_ttl_evicts_lru_and_counts():
    clk = [0.0]
    t = StreamTable(2, ttl_s=10.0, clock=lambda: clk[0])
    t.touch("a")
    clk[0] = 1.0
    t.touch("b")
    clk[0] = 5.0
    t.touch("a")  # refresh: LRU order is now [b, a]
    clk[0] = 12.0  # b idle 11 s (expired), a idle 7 s (alive)
    verdict, sess = t.touch("c")  # eviction freed the slot
    assert verdict == "ok" and sess is not None
    assert t.get("b") is None
    assert t.get("a") is not None
    raw = t.stats.raw()
    assert raw["expired"] == 1
    assert raw["opened"] == 3
    assert t.snapshot()["sessions"] == 2


def test_stream_table_pin_counts_rehomes():
    t = StreamTable(4, 30.0)
    _, sess = t.touch("s")
    t.pin(sess, "m#0")
    assert (sess.rehomes, t.stats.raw()["rehomed"]) == (0, 0)
    t.pin(sess, "m#0")  # same home: not a move
    assert (sess.rehomes, t.stats.raw()["rehomed"]) == (0, 0)
    t.pin(sess, "m#1")  # failover move: counted
    assert (sess.rehomes, t.stats.raw()["rehomed"]) == (1, 1)
    assert sess.home_rid == "m#1"


def test_reuse_body_answers_only_inside_the_hamming_gate():
    t = StreamTable(4, 30.0, reuse_hamming=4)
    _, sess = t.touch("s")
    # No warm state yet: never a hit.
    assert t.reuse_body(sess, 0b1111) is None
    t.note_result(sess, body=b"MASK", content_type="application/x-npy",
                  precision="f32", res_bucket="16", phash=0b1111,
                  latency_ms=10.0)
    assert t.reuse_body(sess, 0b1111) == b"MASK"          # distance 0
    assert t.reuse_body(sess, 0b1111 ^ 0b1010) == b"MASK"  # distance 2
    assert t.reuse_body(sess, 0b1111 ^ 0b11111000) is None  # distance 5
    assert t.reuse_body(sess, None) is None
    # Gate off: state is tracked but the fast path never answers.
    t_off = StreamTable(4, 30.0, reuse_hamming=0)
    _, s2 = t_off.touch("s")
    t_off.note_result(s2, body=b"MASK", content_type="application/x-npy",
                      precision="f32", res_bucket="16", phash=0b1111,
                      latency_ms=10.0)
    assert t_off.reuse_body(s2, 0b1111) is None


def test_stream_table_latency_ewma_and_frame_counters():
    t = StreamTable(4, 30.0, reuse_hamming=8)
    _, sess = t.touch("s")
    t.note_result(sess, body=b"M", content_type="application/x-npy",
                  precision="f32", res_bucket="16", phash=1,
                  latency_ms=100.0)
    assert sess.lat_ewma_ms == 100.0  # first sample seeds the EWMA
    t.note_reuse(sess, 10.0)
    assert sess.lat_ewma_ms == pytest.approx(0.8 * 100.0 + 0.2 * 10.0)
    assert (sess.frames, sess.reused) == (2, 1)
    raw = t.stats.raw()
    assert (raw["frames"], raw["reused"]) == (2, 1)


def test_blend_body_ema_and_fallbacks():
    t = StreamTable(4, 30.0, ema_blend=0.25)
    _, sess = t.touch("s")
    new = _encode_mask(np.full((2, 2), 0.8, np.float32))
    # No previous mask: the engine's own bytes pass through.
    assert t.blend_body(sess, new) == (new, False)
    t.note_result(sess, body=_encode_mask(np.full((2, 2), 0.4, np.float32)),
                  content_type="application/x-npy", precision="f32",
                  res_bucket="16", phash=1, latency_ms=1.0)
    out, blended = t.blend_body(sess, new)
    assert blended
    want = np.float32(0.25) * np.full((2, 2), 0.4, np.float32) \
        + np.float32(0.75) * np.full((2, 2), 0.8, np.float32)
    assert np.array_equal(_decode_mask(out), want)
    # Shape mismatch and undecodable bytes both fall back losslessly.
    other = _encode_mask(np.zeros((3, 3), np.float32))
    assert t.blend_body(sess, other) == (other, False)
    assert t.blend_body(sess, b"\x00garbage") == (b"\x00garbage", False)
    assert t.stats.raw()["blended"] == 1
    # Blend fully off: untouched even with warm state present.
    t_off = StreamTable(4, 30.0, ema_blend=0.0)
    _, s2 = t_off.touch("s")
    t_off.note_result(s2, body=new, content_type="application/x-npy",
                      precision="f32", res_bucket="16", phash=1,
                      latency_ms=1.0)
    assert t_off.blend_body(s2, new) == (new, False)


def test_stream_table_prom_families_render_the_eight_families():
    t = StreamTable(4, 30.0, reuse_hamming=8)
    _, sess = t.touch("s")
    t.pin(sess, "m")
    t.note_reuse(sess, 1.0)
    fams = t.prom_families()
    names = [f[0] for f in fams]
    assert names == [
        "dsod_stream_sessions", "dsod_stream_opened_total",
        "dsod_stream_expired_total", "dsod_stream_frames_total",
        "dsod_stream_reused_total", "dsod_stream_rehomed_total",
        "dsod_stream_budget_shed_total", "dsod_stream_blended_total"]
    by_name = {f[0]: f for f in fams}
    assert by_name["dsod_stream_sessions"][1] == "gauge"
    assert by_name["dsod_stream_sessions"][2] == ["dsod_stream_sessions 1"]
    assert by_name["dsod_stream_reused_total"][2] == \
        ["dsod_stream_reused_total 1"]
    assert all(f[1] == "counter" for n, f in by_name.items()
               if n != "dsod_stream_sessions")


# ------------------------------------------------------ config knobs


@pytest.mark.parametrize("knobs,msg", [
    ({"stream_sessions": -1}, "stream_sessions"),
    ({"stream_sessions": 4, "stream_ttl_s": 0}, "stream_ttl_s"),
    ({"stream_sessions": 4, "stream_reuse_hamming": 300},
     "stream_reuse_hamming"),
    ({"stream_reuse_hamming": 8}, "stream_sessions is 0"),
    ({"stream_sessions": 4, "stream_ema_blend": 1.0}, "stream_ema_blend"),
    ({"stream_ema_blend": 0.5}, "stream_sessions is 0"),
])
def test_fleet_config_rejects_bad_stream_knobs(knobs, msg):
    with pytest.raises(ValueError, match=msg):
        fleet_config_from_dict(dict(
            {"models": [{"name": "m", "config": "c"}]}, **knobs))


# ------------------------------------------------- batcher affinity


def _req(clk, stream=None, precision="f32"):
    return Request(tensor=np.zeros((16, 16, 3), np.float32),
                   orig_hw=(16, 16), res_bucket=16, arrival=clk[0],
                   precision=precision, stream=stream)


def test_batcher_affinity_written_on_put_and_lru_capped(monkeypatch):
    monkeypatch.setattr(batcher_mod, "AFFINITY_CAP", 3)
    clk = [0.0]
    b = DynamicBatcher((1, 2), max_wait_s=1.0, clock=lambda: clk[0])
    assert b.affinity_bucket(None) is None
    assert b.affinity_bucket("ghost") is None
    for i in range(5):
        b.put(_req(clk, stream=f"s{i}"))
    # The two oldest entries were LRU-evicted at the cap.
    assert b.affinity_bucket("s0") is None
    assert b.affinity_bucket("s1") is None
    assert b.affinity_bucket("s4") == (16, "f32")
    # A later frame at a different arm moves the stream's program.
    b.put(_req(clk, stream="s4", precision="bf16"))
    assert b.affinity_bucket("s4") == (16, "bf16")


def test_stream_filled_bucket_dispatches_without_stalling_on_old_head():
    """The max-wait stall regression (serve/batcher.py): a pinned
    stream fills its (res, precision) bucket while an UNRELATED older
    head sits in another bucket inside its max-wait window.  The full
    group must dispatch immediately (no clock advance); the older head
    still dispatches at exactly its OWN arrival + max_wait."""
    clk = [0.0]
    b = DynamicBatcher((1, 2), max_wait_s=1.0, clock=lambda: clk[0])
    b.put(_req(clk))  # the older, in-window head (bucket (16, f32))
    clk[0] = 0.2
    b.put(_req(clk, stream="cam", precision="bf16"))
    assert b.poll_batch() is None  # neither full nor past max-wait
    b.put(_req(clk, stream="cam", precision="bf16"))  # bucket now FULL
    got = b.poll_batch()  # same instant: no wait charged to the stream
    assert got is not None
    key, reqs = got
    assert key == (16, "bf16")
    assert len(reqs) == 2 and all(r.stream == "cam" for r in reqs)
    # The old head was untouched and is NOT releasable early ...
    assert b.pending() == 1
    assert b.poll_batch() is None
    clk[0] = 0.999
    assert not b.ready()
    # ... but its own deadline is also not extended by the stream.
    clk[0] = 1.0
    got = b.poll_batch()
    assert got is not None and got[0] == (16, "f32")
    assert len(got[1]) == 1 and got[1][0].stream is None
    assert b.pending() == 0


# ------------------------------------------------------ loadgen frames


def test_stream_frames_deterministic_and_temporally_coherent():
    a = stream_frames(np.random.RandomState(7), 24, 32, 6)
    b = stream_frames(np.random.RandomState(7), 24, 32, 6)
    assert a == b  # byte-identical under the same seed
    assert len(a) == 6
    phashes = []
    for frame in a:
        arr = np.load(io.BytesIO(frame), allow_pickle=False)
        assert arr.shape == (24, 32, 3) and arr.dtype == np.uint8
        phashes.append(payload_fingerprint(frame)[0])
    # Jitter-only trains stay inside the default smoke gate (h=16).
    assert all(hamming(p, q) <= 16 for p, q in zip(phashes, phashes[1:]))
    # perturb=1.0 cuts the scene every frame: different bytes.
    cuts = stream_frames(np.random.RandomState(7), 24, 32, 6, perturb=1.0)
    assert len(set(cuts)) == 6
    with pytest.raises(ValueError, match="perturb"):
        stream_frames(np.random.RandomState(0), 8, 8, 2, perturb=1.5)


# ------------------------------------------------------ live HTTP


def test_stream_reuse_roundtrip_books_the_sixth_terminal(two_tiny):
    model, va, vb = two_tiny
    eng = InferenceEngine(_cfg("tiny_a"), model, va)
    fleet = Fleet([EngineBackend("a", eng)],
                  FleetConfig(stream_sessions=4, stream_reuse_hamming=16))
    fleet.start()
    srv, url = _start_http(fleet)
    try:
        img = _img(0, 16, 16)
        body1, h1 = _post(url, img, model="a", stream="cam-1")
        assert "X-Stream-Reuse" not in h1  # first frame: full forward
        # Same scene again: phash distance 0, replayed without a forward.
        body2, h2 = _post(url, img, model="a", stream="cam-1")
        assert h2["X-Stream-Reuse"] == "1"
        assert body2 == body1  # byte-for-byte the previous mask
        assert h2["X-Precision"] == h1["X-Precision"]
        assert h2["X-Res-Bucket"] == h1["X-Res-Bucket"]
        # The engine saw ONE submission; the router booked both.
        assert eng.stats.counter("submitted") == 1
        stats = _consistent_stats(url)
        f = stats["fleet"]
        assert f["submitted"] == 2
        assert f["served"] == 1
        assert f["stream_reuse"] == 1
        assert f["consistent"] is True
        st = stats["streams"]
        assert (st["opened"], st["frames"], st["reused"]) == (1, 2, 1)
        per = {s["stream"]: s for s in st["per_stream"]}
        assert per["cam-1"]["frames"] == 2
        assert per["cam-1"]["reused"] == 1
        assert per["cam-1"]["home"] == "a"
        prom = _metrics(url)
        assert "dsod_stream_reused_total 1" in prom
        assert "dsod_stream_opened_total 1" in prom
        assert prom.count("# TYPE dsod_stream_sessions ") == 1
    finally:
        srv.shutdown()
        srv.server_close()
        fleet.stop()


def test_new_stream_past_the_cap_sheds_429_stream_budget(two_tiny):
    model, va, vb = two_tiny
    eng = InferenceEngine(_cfg("tiny_a"), model, va)
    fleet = Fleet([EngineBackend("a", eng)],
                  FleetConfig(stream_sessions=1))
    fleet.start()
    srv, url = _start_http(fleet)
    try:
        _post(url, _img(0, 16, 16), model="a", stream="cam-1")
        with pytest.raises(urllib.error.HTTPError) as exc:
            _post(url, _img(1, 16, 16), model="a", stream="cam-2")
        assert exc.value.code == 429
        body = json.loads(exc.value.read().decode())
        assert body["kind"] == "stream_budget"
        # The shed never reached an engine; the book still balances.
        assert eng.stats.counter("submitted") == 1
        stats = _consistent_stats(url)
        assert stats["fleet"]["submitted"] == 2
        assert stats["fleet"]["shed"] == 1
        assert stats["fleet"]["consistent"] is True
        assert stats["streams"]["budget_shed"] == 1
        # The EXISTING stream keeps flowing past the full table.
        _post(url, _img(2, 16, 16), model="a", stream="cam-1")
    finally:
        srv.shutdown()
        srv.server_close()
        fleet.stop()


def test_home_replica_death_rehomes_the_stream_exactly(two_tiny):
    """Two in-process replicas of ONE model (rids a#0/a#1); the frame
    after the home replica is wedged must re-home (counted) with the
    six-term identity still exact."""
    model, va, vb = two_tiny
    ea = InferenceEngine(_cfg("tiny_a"), model, va)
    eb = InferenceEngine(_cfg("tiny_a"), model, vb)
    fleet = Fleet([EngineBackend("a", ea), EngineBackend("a", eb)],
                  FleetConfig(stream_sessions=4))
    fleet.start()
    srv, url = _start_http(fleet)
    try:
        _post(url, _img(0, 16, 16), model="a", stream="cam-1")
        stats = _consistent_stats(url)  # the home is pinned as it books
        per = {s["stream"]: s for s in stats["streams"]["per_stream"]}
        home = per["cam-1"]["home"]
        assert home in ("a#0", "a#1")
        # Wedge the home; the next frame must land on the survivor.
        fleet.backends[home].engine.stats.set_health(False, "wedged")
        _post(url, _img(1, 16, 16), model="a", stream="cam-1")
        stats = _consistent_stats(url)
        per = {s["stream"]: s for s in stats["streams"]["per_stream"]}
        new_home = per["cam-1"]["home"]
        assert new_home != home and new_home in ("a#0", "a#1")
        assert per["cam-1"]["rehomes"] == 1
        assert stats["streams"]["rehomed"] == 1
        f = stats["fleet"]
        assert (f["submitted"], f["served"]) == (2, 2)
        assert f["consistent"] is True
        # Both engines together saw both frames, one each.
        assert ea.stats.counter("submitted") \
            + eb.stats.counter("submitted") == 2
    finally:
        srv.shutdown()
        srv.server_close()
        fleet.stop()


def test_ema_blend_rewrites_the_full_forward_response(two_tiny):
    model, va, vb = two_tiny
    eng = InferenceEngine(_cfg("tiny_a"), model, va)
    fleet = Fleet([EngineBackend("a", eng)],
                  FleetConfig(stream_sessions=4, stream_ema_blend=0.5))
    fleet.start()
    srv, url = _start_http(fleet)
    try:
        img1, img2 = _img(0, 16, 16), _img(1, 16, 16)
        # The engine's own answers, via the independent (session-less)
        # path — full forwards are bitwise the engine's answer there.
        raw1 = _decode_mask(_post(url, img1, model="a")[0])
        raw2 = _decode_mask(_post(url, img2, model="a")[0])
        body1, _ = _post(url, img1, model="a", stream="cam-1")
        assert np.array_equal(_decode_mask(body1), raw1)  # first frame
        body2, h2 = _post(url, img2, model="a", stream="cam-1")
        assert "X-Stream-Reuse" not in h2  # a real forward, blended
        want = np.float32(0.5) * raw1 + np.float32(0.5) * raw2
        assert np.array_equal(_decode_mask(body2), want)
        stats = _consistent_stats(url)
        assert stats["streams"]["blended"] == 1
        assert stats["fleet"]["consistent"] is True
    finally:
        srv.shutdown()
        srv.server_close()
        fleet.stop()


def test_streaming_off_header_inert_and_no_stream_families(two_tiny):
    model, va, vb = two_tiny
    eng = InferenceEngine(_cfg("tiny_a"), model, va)
    fleet = Fleet([EngineBackend("a", eng)])  # defaults: streaming OFF
    fleet.start()
    srv, url = _start_http(fleet)
    try:
        assert fleet.streams is None
        body_h, headers = _post(url, _img(0, 16, 16), model="a",
                                stream="cam-1")
        body_p, _ = _post(url, _img(0, 16, 16), model="a")
        assert body_h == body_p  # the header changed NOTHING
        assert "X-Stream-Reuse" not in headers
        stats = _consistent_stats(url)
        assert "streams" not in stats
        assert stats["fleet"]["stream_reuse"] == 0
        assert stats["fleet"]["consistent"] is True
        assert "dsod_stream" not in _metrics(url)
    finally:
        srv.shutdown()
        srv.server_close()
        fleet.stop()


def test_rgbd_channel_contract_rejects_before_submit(two_tiny):
    """(H, W, 3) to a depth model / (H, W, 4) to an RGB model: 400 at
    the door, engine book untouched, fleet identity exact; a correct
    (H, W, 4) RGBD payload serves normally."""
    model, va, vb = two_tiny
    ergb = InferenceEngine(_cfg("tiny_a"), model, va)
    ed = InferenceEngine(_cfg("tiny_d", use_depth=True), model, vb)
    assert ed.wants_depth and not ergb.wants_depth
    fleet = Fleet([EngineBackend("rgb", ergb), EngineBackend("rgbd", ed)])
    fleet.start()
    srv, url = _start_http(fleet)
    try:
        # The happy RGBD path: 4-channel payload, mask at (H, W).
        body, headers = _post(url, _img(0, 16, 16, c=4), model="rgbd")
        assert _decode_mask(body).shape == (16, 16)
        rejects = 0
        for mname, c in (("rgbd", 3), ("rgb", 4)):
            with pytest.raises(urllib.error.HTTPError) as exc:
                _post(url, _img(1, 16, 16, c=c), model=mname)
            assert exc.value.code == 400
            detail = json.loads(exc.value.read().decode())
            assert detail["kind"] == "rejected"
            assert "RGB-D" in detail["error"] or "RGB" in detail["error"]
            rejects += 1
        # Neither reject reached a batcher or an engine book.
        assert ed.stats.counter("submitted") == 1
        assert ergb.stats.counter("submitted") == 0
        stats = _consistent_stats(url)
        f = stats["fleet"]
        assert f["submitted"] == 1 + rejects
        assert f["served"] == 1
        assert f["errors"] == rejects  # router rejects join errors
        assert f["consistent"] is True
    finally:
        srv.shutdown()
        srv.server_close()
        fleet.stop()
