"""Fleet control-plane tests (serve/controller.py — docs/SERVING.md
"Fleet control plane").

Invariants proven here:

- **Heal is dwell-free**: a supervised replica below target is
  respawned on the next tick and admitted into routing; the restart is
  booked per model.
- **Scale-out hysteresis is fake-clock provable**: SLO burn + queue
  share must PERSIST for ``ctrl_dwell_s`` before a spawn, and the
  post-action cooldown blocks a second spawn — the degraded-ladder
  dwell idiom, one layer up.
- **Burn without queue depth is refused, with attribution**: the
  controller records ``host_bound``/``device_bound`` instead of
  spawning a replica that would split the same roofline; ``at-max`` is
  refused too.  Refusals are decisions — they land in
  ``dsod_ctrl_decisions_total`` and the flight recorder.
- **Scale-in and preemption drain, never kill**: the victim leaves
  routing IMMEDIATELY (``pick()`` exclusion) but its process is only
  retired after ``ctrl_drain_grace_s``; a PreemptionGuard notice drains
  every supervised replica and pins scale-out/heal to ``preempted``
  refusals.  The live-HTTP variant proves zero lost requests across a
  mid-load drain: every in-flight and queued request completes and the
  router book stays exact.
- **Crash-loop backoff**: consecutive spawn failures double the
  per-model backoff on an injected clock; the supervisor refuses to
  spawn inside the window.
- **Off by default**: an unarmed fleet renders no ``dsod_ctrl_*``
  family and reports no controller/rollout stats sections.
"""

import sys
import threading
import time
from types import SimpleNamespace

import pytest

from distributed_sod_project_tpu.configs import FleetConfig
from distributed_sod_project_tpu.serve.controller import (
    FleetController, ReplicaSupervisor, SupervisedReplica,
    default_spawn_cmd)
from distributed_sod_project_tpu.serve.fleet import Fleet
from distributed_sod_project_tpu.serve.rollout import (deny_step,
                                                       read_step_denylist)

from test_failover import FakeRemote, _mk_remote_fleet, _post_npy


class FakeSupervisor:
    """Supervisor seam: hands out pre-wired fake backends instead of
    subprocesses (``SupervisedReplica.backend`` short-circuits the
    HTTP admission probe), records retire calls."""

    def __init__(self):
        self.spawn_cmd = ("fake-replica", "{port}", "{port_file}")
        self._procs = {}
        self.spawned = []
        self.retired = []
        self._n = 0

    def can_spawn(self, model):
        return True

    def backoff_remaining(self, model):
        return 0.0

    def spawn(self, model):
        self._n += 1
        rep = SupervisedReplica(model, 0, f"fake://{model}/{self._n}",
                                None, "", backend=FakeRemote(model))
        self.spawned.append(rep)
        return rep

    def adopt(self, rid, rep):
        self._procs[rid] = rep

    def owns(self, rid):
        return rid in self._procs

    def owned(self):
        return dict(self._procs)

    def poll(self):
        return []

    def retire(self, rid, grace_s=10.0):
        self.retired.append(rid)
        self._procs.pop(rid, None)

    def stop(self, grace_s=10.0):
        self._procs.clear()


def _mk_ctrl(fleet, clk, signals, guard=None, **cfg_kw):
    sup = FakeSupervisor()
    cfg = FleetConfig(**cfg_kw)
    ctrl = FleetController(fleet, cfg, supervisor=sup,
                           clock=lambda: clk[0], guard=guard,
                           signals_fn=lambda name, g: signals[0])
    return ctrl, sup


# ------------------------------------------------- fake-clock policy


def test_heal_respawns_unhealthy_group_dwell_free():
    r0 = FakeRemote("m")
    fleet = Fleet([r0], FleetConfig())
    clk = [0.0]
    signals = [(0.0, {})]
    ctrl, sup = _mk_ctrl(fleet, clk, signals)
    ctrl.tick()
    assert not sup.spawned  # healthy at target: nothing to do
    r0._healthy = False
    ctrl.tick()  # a hole in the fleet is healed on THIS tick
    assert len(sup.spawned) == 1
    assert len(fleet.groups["m"]) == 2
    assert sup.owns("m#1")
    snap = ctrl.stats.snapshot()
    assert snap["decisions"]["spawn:heal"] == 1
    assert snap["decisions"]["restart:heal"] == 1
    assert snap["restarts"] == {"m": 1}
    assert snap["supervised_gauge"]["m:running"] == 1


def test_scale_out_needs_dwell_then_cooldown_blocks_repeat():
    fleet = Fleet([FakeRemote("m")], FleetConfig())
    clk = [0.0]
    hot = (5.0, {"queue": 0.8, "host": 0.1, "device": 0.1})
    signals = [hot]
    ctrl, sup = _mk_ctrl(fleet, clk, signals,
                         ctrl_dwell_s=10.0, ctrl_cooldown_s=30.0)
    ctrl.tick()  # first sighting: pending, not acted
    assert not sup.spawned
    clk[0] = 9.9
    ctrl.tick()  # dwell not yet served
    assert not sup.spawned
    clk[0] = 10.1
    ctrl.tick()  # persisted past the dwell: scale out
    assert len(sup.spawned) == 1
    assert len(fleet.groups["m"]) == 2
    clk[0] = 15.0
    ctrl.tick()  # still burning, but inside the cooldown
    clk[0] = 35.0
    ctrl.tick()
    assert len(sup.spawned) == 1
    d = ctrl.stats.snapshot()["decisions"]
    assert d["spawn:scale_out"] == 1
    assert d["scale_out:scale_out"] == 1


def test_scale_out_dwell_resets_when_burn_clears():
    fleet = Fleet([FakeRemote("m")], FleetConfig())
    clk = [0.0]
    hot = (5.0, {"queue": 0.9})
    signals = [hot]
    ctrl, sup = _mk_ctrl(fleet, clk, signals, ctrl_dwell_s=10.0)
    ctrl.tick()
    clk[0] = 6.0
    signals[0] = (0.0, {"queue": 0.0})  # transient spike: burn cleared
    ctrl.tick()
    clk[0] = 11.0
    signals[0] = hot  # back — but the dwell must restart from zero
    ctrl.tick()
    clk[0] = 12.0
    ctrl.tick()
    assert not sup.spawned  # 1 s of persistence, not 10
    clk[0] = 21.1
    ctrl.tick()
    assert len(sup.spawned) == 1


def test_non_queue_bottleneck_refused_with_attribution():
    fleet = Fleet([FakeRemote("m")], FleetConfig())
    clk = [0.0]
    signals = [(5.0, {"queue": 0.05, "host": 0.6, "device": 0.3})]
    ctrl, sup = _mk_ctrl(fleet, clk, signals, ctrl_cooldown_s=30.0)
    ctrl.tick()
    ctrl.tick()  # refusals debounce to one per cooldown window
    clk[0] = 31.0
    signals[0] = (5.0, {"queue": 0.05, "host": 0.2, "device": 0.7})
    ctrl.tick()
    assert not sup.spawned
    assert not ctrl.stats.snapshot()["restarts"]
    d = ctrl.stats.snapshot()["decisions"]
    assert d["refuse_scale_out:host_bound"] == 1
    assert d["refuse_scale_out:device_bound"] == 1


def test_scale_out_at_max_replicas_refused():
    fleet = Fleet([FakeRemote("m")], FleetConfig())
    clk = [0.0]
    signals = [(5.0, {"queue": 0.9})]
    ctrl, sup = _mk_ctrl(fleet, clk, signals, ctrl_max_replicas=1)
    ctrl.tick()
    assert not sup.spawned
    d = ctrl.stats.snapshot()["decisions"]
    assert d["refuse_scale_out:at_max_replicas"] == 1


def test_scale_in_drains_supervised_then_retires_after_grace():
    r0 = FakeRemote("m")
    fleet = Fleet([r0], FleetConfig())
    clk = [0.0]
    signals = [(0.0, {})]
    ctrl, sup = _mk_ctrl(fleet, clk, signals, ctrl_dwell_s=10.0,
                         ctrl_drain_grace_s=5.0)
    # A supervised member attached AFTER the controller captured the
    # group's configured size (target=1), so len > target.
    extra = FakeRemote("m")
    rid = fleet.attach_replica("m", extra)
    sup.adopt(rid, SupervisedReplica("m", 0, "fake://m", None, "",
                                     backend=extra))
    ctrl.tick()  # scale-in pending
    clk[0] = 10.1
    ctrl.tick()  # dwell served: drain begins
    group = fleet.groups["m"]
    assert rid in group.draining()
    assert sup.retired == []  # out of routing, process still alive
    picks = {group.pick()[0] for _ in range(4)}
    assert picks == {"m"}  # lone config member keeps rid == name
    assert ctrl.stats.snapshot()["supervised_gauge"]["m:draining"] == 1
    clk[0] = 20.0
    ctrl.tick()  # grace elapsed: retire + detach
    assert sup.retired == [rid]
    assert len(group) == 1
    d = ctrl.stats.snapshot()["decisions"]
    assert d["drain:scale_in"] == 1
    assert d["retire"] == 1


def test_scale_in_never_retires_config_members():
    fleet = Fleet([FakeRemote("m"), FakeRemote("m")], FleetConfig())
    clk = [0.0]
    signals = [(0.0, {})]
    ctrl, sup = _mk_ctrl(fleet, clk, signals, ctrl_dwell_s=0.0,
                         ctrl_target_replicas=1)
    ctrl.tick()  # pending
    clk[0] = 1.0
    ctrl.tick()  # acts — but neither member is supervised
    assert len(fleet.groups["m"]) == 2
    d = ctrl.stats.snapshot()["decisions"]
    assert d["refuse_scale_out:no_supervised_member"] == 1


def test_preemption_guard_drains_supervised_and_pins_refusals():
    r0 = FakeRemote("m")
    fleet = Fleet([r0], FleetConfig())
    clk = [0.0]
    signals = [(0.0, {})]
    guard = SimpleNamespace(should_stop=False)
    ctrl, sup = _mk_ctrl(fleet, clk, signals, guard=guard,
                         ctrl_drain_grace_s=5.0)
    extra = FakeRemote("m")
    rid = fleet.attach_replica("m", extra)
    sup.adopt(rid, SupervisedReplica("m", 0, "fake://m", None, "",
                                     backend=extra))
    ctrl.tick()
    assert rid not in fleet.groups["m"].draining()
    guard.should_stop = True  # the spot notice lands
    ctrl.tick()
    assert rid in fleet.groups["m"].draining()
    d = ctrl.stats.snapshot()["decisions"]
    assert d["preemption_notice"] == 1
    assert d["drain:preemption"] == 1
    # Scale-out pressure while preempted: refused, attributed.
    signals[0] = (5.0, {"queue": 0.9})
    ctrl.tick()
    assert not sup.spawned
    # Heal pressure while preempted: also refused — a doomed host must
    # not spawn replacements onto itself.
    r0._healthy = False
    clk[0] = 31.0  # past the refusal debounce window
    ctrl.tick()
    assert not sup.spawned
    d = ctrl.stats.snapshot()["decisions"]
    assert d["refuse_scale_out:preempted"] >= 1
    clk[0] = 40.0
    ctrl.tick()  # grace elapsed: the drained replica is retired
    assert sup.retired == [rid]
    assert len(fleet.groups["m"]) == 1


# -------------------------------------------- supervisor crash loop


def test_supervisor_backoff_doubles_on_injected_clock():
    clk = [0.0]
    sup = ReplicaSupervisor(
        (sys.executable, "-c", "import sys; sys.exit(3)",
         "{port}", "{port_file}"),
        deadline_s=20.0, backoff_s=2.0, backoff_max_s=8.0,
        clock=lambda: clk[0])
    assert sup.can_spawn("m")
    assert sup.spawn("m") is None  # exits before publishing a port
    assert not sup.can_spawn("m")
    assert sup.backoff_remaining("m") == pytest.approx(2.0)
    clk[0] = 2.1
    assert sup.can_spawn("m")
    assert sup.spawn("m") is None
    assert sup.backoff_remaining("m") == pytest.approx(4.0)  # doubled
    clk[0] = 2.1 + 4.1
    assert sup.spawn("m") is None
    assert sup.backoff_remaining("m") == pytest.approx(8.0)
    clk[0] += 8.1
    assert sup.spawn("m") is None
    assert sup.backoff_remaining("m") == pytest.approx(8.0)  # capped


def test_supervisor_rejects_template_without_placeholders():
    with pytest.raises(ValueError):
        ReplicaSupervisor(("python", "serve.py"))
    cmd = default_spawn_cmd("u2net_ds")
    assert "{port}" in cmd and "{port_file}" in cmd
    ReplicaSupervisor(cmd)  # the default template is valid
    assert not ReplicaSupervisor(()).can_spawn("m")  # no cmd: never


# ------------------------------------------------------- denylist


def test_rollout_denylist_round_trip(tmp_path):
    d = str(tmp_path)
    assert read_step_denylist(d) == {}
    deny_step(d, 7, "canary_mae_degraded", mae=0.4)
    deny_step(d, 9, "canary_unscorable")
    deny = read_step_denylist(d)
    assert set(deny) == {7, 9}
    assert deny[7]["reason"] == "canary_mae_degraded"
    assert deny[7]["mae"] == 0.4
    # Corrupt file reads as empty, not a crash: the rollout loop must
    # survive a torn write by a dying process.
    (tmp_path / "reload_denylist.json").write_text("{nope")
    assert read_step_denylist(d) == {}


# ------------------------------------------------ off-by-default


def test_unarmed_fleet_renders_no_ctrl_families():
    fleet = Fleet([FakeRemote("m")], FleetConfig())
    assert fleet.controller is None
    assert fleet.rollout is None
    text = fleet.metrics_text()
    assert "dsod_ctrl_" not in text
    s = fleet.stats()
    assert "controller" not in s
    assert "rollout" not in s


def test_armed_fleet_renders_ctrl_families_and_stats():
    fleet = Fleet([FakeRemote("m")], FleetConfig(controller=True))
    assert fleet.controller is not None
    text = fleet.metrics_text()
    assert "dsod_ctrl_supervised_replicas" in text
    assert "controller" in fleet.stats()


# ------------------------------------------------- live-HTTP drain


def _consistent_stats(fleet, timeout=5.0):
    """Terminals are booked after the response bytes flush, so a stats
    read racing the handler thread can transiently see one more
    submission than terminals (tests/test_cache.py has the same wait).
    The final read is returned as-is so a REAL hole still fails."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        st = fleet.stats()
        if st["fleet"]["consistent"]:
            return st
        time.sleep(0.02)
    return fleet.stats()


def test_preemption_drain_loses_zero_requests_live_http():
    """The satellite's zero-lost proof over real HTTP: a preemption
    notice lands MID-LOAD, the drained replica leaves routing while
    its in-flight requests complete, and the router book stays exact —
    done == sent with every terminal a served."""
    r0 = FakeRemote("m", behaviors=[0.02])
    r1 = FakeRemote("m", behaviors=[0.02])
    fleet, srv, url = _mk_remote_fleet([r0, r1])
    clk = [0.0]
    sup = FakeSupervisor()
    sup.adopt("m#1", SupervisedReplica("m", 0, "fake://m", None, "",
                                       backend=r1))
    ctrl = FleetController(
        fleet, FleetConfig(ctrl_drain_grace_s=0.5),
        supervisor=sup, clock=lambda: clk[0],
        signals_fn=lambda name, g: (0.0, {}))
    statuses = []
    lock = threading.Lock()

    def worker(n):
        for _ in range(n):
            status, _h, _b = _post_npy(url)
            with lock:
                statuses.append(status)

    try:
        threads = [threading.Thread(target=worker, args=(6,))
                   for _ in range(4)]
        for t in threads:
            t.start()
        time.sleep(0.05)  # requests in flight on both replicas
        ctrl.notify_preemption()  # the spot notice: drain supervised
        ctrl.tick()
        for t in threads:
            t.join()
        assert statuses and all(s == 200 for s in statuses)
        assert "m#1" in fleet.groups["m"].draining()
        s = _consistent_stats(fleet)
        assert s["fleet"]["submitted"] == len(statuses)
        assert s["fleet"]["served"] == len(statuses)
        assert s["fleet"]["consistent"] is True
        clk[0] = 1.0
        ctrl.tick()  # grace elapsed: retire the drained process
        assert sup.retired == ["m#1"]
        assert len(fleet.groups["m"]) == 1
        # Post-drain traffic routes to the survivor only.
        status, headers, _ = _post_npy(url)
        assert status == 200
        assert headers["X-Replica"] == "m#0"
        s = _consistent_stats(fleet)
        assert s["fleet"]["served"] == len(statuses) + 1
        assert s["fleet"]["consistent"] is True
    finally:
        srv.shutdown()
        srv.server_close()
        fleet.stop()
