"""Serving SLO plane (ISSUE 13): burn-rate objectives, breach hooks,
the XLA recompile sentinel, and latency-outlier black-box capture.

Covers the contract end to end: fast/slow window burn math against a
fake clock, breach → policy/supervisor hook → recovery, the sticky
refcounted WARN rung, per-stage histogram bucket ladders, first-class
ring events, recompile-storm detection under a forced retune_entropy
rebuild loop, outlier-triggered bundle dumps with rate limiting and
correlation-id tagging, the bench perf ratchet, and the acceptance
path: an injected latency fault (SELKIES_FAULTS) breaching the fast
window on a live pipeline and dumping exactly one tagged bundle.
"""

from __future__ import annotations

import asyncio
import importlib.util
import json
import os
import subprocess
import sys
import time

import pytest

from selkies_tpu.models.stats import FrameStats
from selkies_tpu.monitoring import jitprof
from selkies_tpu.monitoring.flightrecorder import (
    FlightRecorder,
    OutlierTrigger,
)
from selkies_tpu.monitoring.slo import (
    OBJECTIVES,
    SessionSLO,
    SLOTargets,
    slo_enabled,
)
from selkies_tpu.monitoring.telemetry import telemetry
from selkies_tpu.pipeline.elements import SyntheticSource, VideoPipeline
from selkies_tpu.resilience import configure_faults, reset_faults
from selkies_tpu.resilience.supervisor import Rung, SlotSupervisor

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def tele(tmp_path):
    telemetry.reset()
    telemetry.enabled = True
    telemetry.recorder = FlightRecorder(out_dir=str(tmp_path / "bb"))
    yield telemetry
    telemetry.enabled = False
    telemetry.reset()


class FakeClock:
    def __init__(self, t: float = 1000.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def tick(self, dt: float) -> float:
        self.t += dt
        return self.t


def _slo(clock, *, p50=50.0, p95=100.0, fps_floor=0.0, down_kbps=0.0,
         fast_s=10.0, slow_s=60.0, **kw) -> SessionSLO:
    targets = {"unknown": SLOTargets(p50_ms=p50, p95_ms=p95,
                                     fps_floor=fps_floor,
                                     down_kbps=down_kbps)}
    kw.setdefault("outlier", OutlierTrigger(warmup=10 ** 9))
    return SessionSLO("0", targets=targets, fast_s=fast_s, slow_s=slow_s,
                      clock=clock, **kw)


def _feed(slo, clock, n, latency_ms, nbytes=1000, fps=30.0):
    for _ in range(n):
        clock.tick(1.0 / fps)
        slo.observe_frame(latency_ms, nbytes)
        slo.evaluate()


# -- outlier trigger ---------------------------------------------------------


def test_outlier_trigger_warmup_and_quantile():
    t = OutlierTrigger(window=100, warmup=50, quantile=0.99, factor=2.0,
                       floor_ms=1.0)
    # warmup: even an absurd sample is not judged
    for _ in range(49):
        assert not t.observe(10.0)
    assert not t.observe(10_000.0)  # sample 50: still inside warmup? no —
    # the ring had 49 entries when judged, below warmup, so not flagged
    assert t.outliers == 0
    # now the baseline holds one huge sample; flush it out of the window
    for _ in range(100):
        t.observe(10.0)
    assert abs(t.quantile_ms() - 10.0) < 1e-9
    assert t.observe(25.0)            # 10 * 2.0 = 20 < 25
    assert not t.observe(15.0)        # below threshold
    assert t.outliers >= 1


def test_outlier_trigger_rebaselines_on_sustained_shift():
    t = OutlierTrigger(window=64, warmup=32, quantile=0.99, factor=1.5,
                       floor_ms=1.0)
    for _ in range(64):
        t.observe(10.0)
    flagged = [t.observe(100.0) for _ in range(128)]
    assert flagged[0] is True
    # once the window is full of 100s the shift is the new baseline
    assert not any(flagged[70:])


# -- burn-rate windows -------------------------------------------------------


def test_breach_hooks_and_recovery():
    clock = FakeClock()
    sup = SlotSupervisor("slot-a", _DummyActions())
    slo = _slo(clock, supervisor=sup, recovery_evals=2)
    fired = []
    slo.on_pressure = lambda: fired.append("pressure")
    slo.on_relief = lambda: fired.append("relief")
    _feed(slo, clock, 600, 10.0)          # 20 s good
    assert not slo.health_view()["breached"] and fired == []
    assert sup.rung == Rung.HEALTHY
    _feed(slo, clock, 300, 500.0)         # 10 s everything over p50+p95
    assert set(slo.health_view()["breached"]) >= {"latency_p50",
                                                  "latency_p95"}
    # pressure fires on the edge and then RE-ASSERTS ~1/s while breached
    # (the congestion-overlay pattern: another controller's relief must
    # not strip the shed mid-breach); never relief while breached
    assert fired[0] == "pressure" and set(fired) == {"pressure"}
    assert sup.rung == Rung.WARN
    assert sup.stats()["slo_warns"] >= 1
    _feed(slo, clock, 600, 10.0)          # 20 s clean: fast window drains
    assert slo.health_view()["breached"] == []
    assert fired[-1] == "relief" and fired.count("relief") == 1
    assert sup.rung == Rung.HEALTHY
    assert slo.breaches >= 2              # p50 + p95 each crossed fast


def test_fast_recovers_while_slow_stays_chronic():
    clock = FakeClock()
    slo = _slo(clock, fast_s=10.0, slow_s=120.0, recovery_evals=1)
    _feed(slo, clock, 600, 10.0)
    _feed(slo, clock, 300, 500.0)         # 10 s bad burst
    _feed(slo, clock, 900, 10.0)          # 30 s clean
    view = slo.health_view()
    assert view["breached"] == []         # acute judged on the fast window
    assert "latency_p95" in view["chronic"]  # the slow window remembers
    st = slo.stats()["objectives"]["latency_p95"]
    assert st["slow_burn"] >= 1.0 and st["fast_burn"] < 2.0


def test_fps_floor_objective():
    clock = FakeClock()
    slo = _slo(clock, p50=10_000.0, p95=10_000.0, fps_floor=20.0)
    _feed(slo, clock, 120, 1.0, fps=30.0)     # above floor
    assert "fps" not in slo.health_view()["breached"]
    _feed(slo, clock, 120, 1.0, fps=5.0)      # 5 fps << 20 floor
    assert "fps" in slo.health_view()["breached"]


def test_downlink_budget_objective():
    clock = FakeClock()
    # 1000 kbps budget = 125_000 B/s; 30 fps * 10 KB = 300 KB/s = burn 2.4
    slo = _slo(clock, p50=10_000.0, p95=10_000.0, down_kbps=1000.0)
    _feed(slo, clock, 600, 1.0, nbytes=1_000)
    assert "downlink" not in slo.health_view()["breached"]
    _feed(slo, clock, 600, 1.0, nbytes=10_000)
    assert "downlink" in slo.health_view()["breached"]


def test_min_frames_gate_never_judges_sparse_windows():
    clock = FakeClock()
    slo = _slo(clock, fps_floor=30.0, min_frames=16)
    # 5 terrible frames: below min_frames, no objective may judge
    _feed(slo, clock, 5, 99_999.0, fps=1.0)
    assert slo.health_view() == {"scenario": "unknown", "breached": [],
                                 "chronic": []}


def test_scenario_retarget_switches_objectives():
    clock = FakeClock()
    slo = SessionSLO("0", clock=clock,
                     outlier=OutlierTrigger(warmup=10 ** 9))
    loose = slo.targets
    assert slo.scenario == "unknown"
    slo.set_scenario("typing")
    assert slo.targets.p50_ms < loose.p50_ms  # typing promises keystrokes
    slo.set_scenario("game")
    assert slo.targets.down_kbps > 0


def test_policy_engine_transition_retargets_slo():
    from selkies_tpu.policy import PolicyEngine, Scenario

    clock = FakeClock()
    slo = SessionSLO("0", clock=clock,
                     outlier=OutlierTrigger(warmup=10 ** 9))
    eng = PolicyEngine(session="0", confirm=1, dwell=0)
    eng.on_scenario = slo.set_scenario
    eng._transition(Scenario.VIDEO)
    assert slo.scenario == "video"
    assert slo.targets.fps_floor == 24.0


# -- supervisor WARN rung ----------------------------------------------------


class _DummyActions:
    def warn(self, msg):
        pass

    def force_idr(self):
        pass

    def restart_encoder(self):
        pass

    def degrade(self, level):
        pass

    def undegrade(self, level):
        pass

    def recycle(self):
        pass


def test_slo_warn_is_sticky_and_refcounted():
    sup = SlotSupervisor("slot-b", _DummyActions())
    sup.tick_ok()
    sup.slo_warn("session 0 burning", key="0")
    sup.slo_warn("session 1 burning", key="1")
    assert sup.rung == Rung.WARN
    # healthy ticks do NOT clear an SLO warn (it is not a tick failure)
    for _ in range(10):
        sup.tick_ok()
    assert sup.rung == Rung.WARN
    sup.slo_clear(key="0")
    assert sup.rung == Rung.WARN          # session 1 still holds it
    sup.slo_clear(key="1")
    assert sup.rung == Rung.HEALTHY
    assert sup.stats()["slo_warns"] == 2
    assert sup.stats()["slo_pressure"] == []


def test_slo_warn_never_blocks_real_escalation():
    sup = SlotSupervisor("slot-c", _DummyActions(), restart_after=2,
                         recycle_after=10 ** 6)
    sup.slo_warn("burning", key="0")
    sup.failure(RuntimeError("tick"))
    rung = sup.failure(RuntimeError("tick"))
    assert rung >= Rung.FORCE_IDR         # the ladder climbs through WARN
    # ...and a RECOVERED transient failure steps back down to the HELD
    # WARN (not frozen at the elevated rung, not cleared to HEALTHY)
    sup.tick_ok()
    assert sup.rung == Rung.WARN
    sup.slo_clear(key="0")
    sup.tick_ok()
    assert sup.rung == Rung.HEALTHY


def test_reset_zeroes_exported_gauges(tele):
    clock = FakeClock()
    slo = _slo(clock)
    _feed(slo, clock, 600, 10.0)
    _feed(slo, clock, 300, 500.0)         # acute breach, gauges at 2
    g = tele.rollup()["gauges"]
    assert g["selkies_slo_breached"]["session=0,objective=latency_p50"] == 2
    slo.reset()                           # client departed
    g = tele.rollup()["gauges"]
    assert g["selkies_slo_breached"]["session=0,objective=latency_p50"] == 0
    assert g["selkies_slo_burn_rate"][
        "session=0,objective=latency_p50,window=fast"] == 0.0
    assert not slo._any_breached()


# -- telemetry: gauges, healthz block, bucket ladders, ring events -----------


def test_breach_exports_gauges_and_healthz_detail(tele):
    clock = FakeClock()
    slo = _slo(clock)

    def slo_health():
        return {"0": slo.health_view()}

    tele.register_slo(slo_health)  # weakly held: the local ref keeps it
    _feed(slo, clock, 600, 10.0)
    _feed(slo, clock, 300, 500.0)
    roll = tele.rollup()
    burn = roll["gauges"]["selkies_slo_burn_rate"]
    assert burn["session=0,objective=latency_p50,window=fast"] >= 2.0
    assert "session=0,objective=latency_p50,window=slow" in burn
    breached = roll["gauges"]["selkies_slo_breached"]
    assert breached["session=0,objective=latency_p50"] == 2  # acute
    crossings = roll["counters"]["selkies_slo_breaches_total"]
    assert crossings["session=0,objective=latency_p50,window=fast"] >= 1
    health = tele.health()
    assert health["slo"]["0"]["breached"]  # the /healthz detail block
    # breach/recovery land in the flight-recorder ring as first-class
    # events (post-PR-3 subsystems appear in bundles)
    evs = {e["ev"] for e in tele.recorder.events("0")}
    assert "slo_breach" in evs


def test_per_stage_bucket_ladders(tele):
    tele.stage_ms("classify", 0.07, frame=1)
    tele.stage_ms("device", 5.0, frame=1)
    hists = tele.rollup()["histograms"]["selkies_stage_ms"]
    classify = hists["stage=classify,session=0"]["buckets"]
    device = hists["stage=device,session=0"]["buckets"]
    assert "0.05" in classify and "0.05" not in device  # per-stage edges
    # the 0.07 ms observation resolves to the 0.1 bucket, not a 0.5 floor
    assert classify["0.05"] == 0 and classify["0.1"] == 1
    # prometheus exposition carries per-series edges
    fams = {m.name: m for m in tele.registry.collect()}
    samples = fams["selkies_stage_ms"].samples
    les = {s.labels["le"] for s in samples
           if s.name.endswith("_bucket") and s.labels["stage"] == "classify"}
    assert "0.05" in les


def test_event_api_records_ring_only(tele):
    tele.event("codec_negotiated", session="3", codec="av1", reason="test")
    evs = tele.recorder.events("3")
    assert any(e["ev"] == "codec_negotiated" and e["codec"] == "av1"
               for e in evs)
    assert "codec_negotiated" not in str(tele.rollup()["counters"])
    tele.enabled = False
    tele.event("codec_negotiated", session="3", codec="vp9")
    assert not any(e.get("codec") == "vp9" for e in tele.recorder.events("3"))
    tele.enabled = True


# -- outlier capture ---------------------------------------------------------


def test_outlier_dump_rate_limit_and_correlation_id(tele, tmp_path):
    clock = FakeClock()
    slo = _slo(clock, p50=10_000.0, p95=10_000.0,
               outlier=OutlierTrigger(window=64, warmup=16, factor=2.0,
                                      floor_ms=20.0))
    for fid in range(1, 33):
        clock.tick(1 / 30)
        slo.observe_frame(5.0, 100, fid=fid)
    slo.observe_frame(500.0, 100, fid=777)     # the outlier frame
    slo.observe_frame(5000.0, 100, fid=778)    # second: rate-limited
    assert slo.outliers == 2                   # both DETECTED...
    bundles = [d for d in os.listdir(tmp_path / "bb")
               if "outlier" in d and not d.startswith(".")]
    assert len(bundles) == 1                   # ...but exactly one dumped
    with open(tmp_path / "bb" / bundles[0] / "meta.json") as f:
        meta = json.load(f)
    assert meta["frame_id"] == 777             # tagged with the frame's id
    assert meta["latency_ms"] == 500.0
    assert meta["rolling_p99_ms"] > 0
    # every ring event is in the bundle, so the tagged fid is greppable
    with open(tmp_path / "bb" / bundles[0] / "events.jsonl") as f:
        assert f.read().strip()
    counters = tele.rollup()["counters"]
    assert counters["selkies_slo_outliers_total"]["session=0"] == 2


# -- recompile sentinel ------------------------------------------------------


def test_compile_sentinel_counts_attributes_and_storms(tele):
    import jax
    import jax.numpy as jnp

    s = jitprof.CompileSentinel(storm_n=3, storm_window_s=600.0,
                                startup_grace_s=0.0)
    jitprof.install(s)
    try:
        @jax.jit
        def f(x):
            return x * 3 + 1

        f(jnp.ones((3,)))
        assert s.stats()["compiles"] >= 1
        assert "unattributed" in s.stats()["by_trigger"]
        s.mark("actuation", "entropy-retune")
        f(jnp.ones((7,)))
        assert s.stats()["by_trigger"].get("actuation", 0) >= 1
        with jitprof.scope("codec_switch", "av1"):
            f(jnp.ones((13,)))
        st = s.stats()
        assert st["by_trigger"].get("codec_switch", 0) >= 1
        assert st["storms"] >= 1               # 3+ compiles in the window
        counters = tele.rollup()["counters"]
        assert counters["selkies_compile_total"]["trigger=actuation"] >= 1
        assert "selkies_compile_storms_total" in counters
        assert "selkies_compile_ms" in tele.rollup()["histograms"]
        before = st["compiles"]
        jitprof.uninstall()
        f(jnp.ones((29,)))
        assert s.stats()["compiles"] == before  # detached
    finally:
        jitprof.uninstall()


def test_mark_ttl_expires_to_unattributed():
    clock = FakeClock()
    s = jitprof.CompileSentinel(mark_ttl_s=5.0, startup_grace_s=0.0,
                                clock=clock)
    s.mark("recarve", "session-1")
    s.on_duration(jitprof.COMPILE_EVENT, 0.01)
    clock.tick(60.0)
    s.on_duration(jitprof.COMPILE_EVENT, 0.01)
    assert s.by_trigger == {"recarve": 1, "unattributed": 1}
    assert s.by_site.get("recarve:session-1") == 1


def test_retune_entropy_loop_flags_recompile_storm(tele, tmp_path):
    """The acceptance check: a forced entropy-retune rebuild loop is a
    recompile storm, attributed to `actuation` (the PR 10 dwell is what
    normally prevents this — the sentinel is the production check that
    it held)."""
    import jax
    import numpy as np

    from selkies_tpu.models.h264.encoder import TPUH264Encoder

    # deterministic compiles: a fresh cache dir (the conftest-enabled
    # persistent cache would serve a previous RUN's executables) and a
    # prohibitive min-compile-time (so this test's own compiles are not
    # persisted and re-served across retunes)
    prev_dir = jax.config.jax_compilation_cache_dir
    prev_min = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "cc"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1e9)
    s = jitprof.CompileSentinel(storm_n=3, storm_window_s=600.0,
                                startup_grace_s=0.0)
    # the ring drops events older than its 10 s window on every record;
    # on a loaded host the loop's later compiles can outlast it
    tele.recorder.window_s = 600.0
    enc = None
    try:
        enc = TPUH264Encoder(192, 128, qp=28, frame_batch=1,
                             pipeline_depth=0)
        rng = np.random.default_rng(3)
        base = rng.integers(0, 255, (128, 192, 4), np.uint8)

        def delta_frame(i):
            f = base.copy()
            f[32:48, 32:64] = rng.integers(0, 255, (16, 32, 4), np.uint8)
            return f

        enc.submit(base, None, 0)       # IDR + the startup compiles
        enc.submit(delta_frame(0), None, 1)  # delta path compiles too
        enc.flush()
        # install AFTER the startup compiles: only the retune loop's
        # rebuilds land in the sentinel's storm window
        jitprof.install(s)
        # the rebuild loop: each entropy flip rebuilds the delta-scatter
        # partials, which recompile on their next delta frame
        for i, de in enumerate((True, False, True)):
            assert enc.retune_entropy(device_entropy=de, bits_min_mbs=0)
            enc.submit(delta_frame(i + 1), None, i + 2)
            enc.flush()
        st = s.stats()
        assert st["compiles"] >= 3, f"retunes did not recompile: {st}"
        assert st["by_trigger"].get("actuation", 0) >= 3
        assert st["storms"] >= 1
        counters = tele.rollup()["counters"]
        assert counters["selkies_compile_total"]["trigger=actuation"] >= 3
        assert any(k.startswith("trigger=")
                   for k in counters["selkies_compile_storms_total"])
        # the storm is also a first-class ring event (bundle evidence)
        evs = {e["ev"] for e in tele.recorder.events("0")}
        assert "compile_storm" in evs
    finally:
        jitprof.uninstall()
        if enc is not None:
            enc.close()
        jax.config.update("jax_compilation_cache_dir", prev_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          prev_min)


# -- pipeline integration (the acceptance path) ------------------------------


class TinyEncoder:
    width, height = 64, 48

    def __init__(self):
        self.n = 0
        self.last_stats = None

    def encode_frame(self, frame, qp):
        self.n += 1
        self.last_stats = FrameStats(
            frame_index=self.n, idr=self.n == 1, qp=qp,
            bytes=16, device_ms=1.0, pack_ms=0.5)
        return b"\x00\x00\x00\x01" + bytes([self.n % 251]) * 15

    def force_keyframe(self):
        pass


class TinyRC:
    def frame_qp(self):
        return 30

    def update(self, n, idr=False):
        pass

    def set_framerate(self, fps):
        pass


def test_injected_latency_fault_breaches_and_dumps_one_bundle(tele, tmp_path):
    """SELKIES_FAULTS latency injection -> fast-window breach -> policy
    pressure + supervisor WARN -> exactly one rate-limited outlier
    bundle tagged with the breaching frame's correlation id."""
    # every encoder tick from #40 stalls 40 ms (the documented
    # `delay:<ms>` action, now applied by the pipeline's fault sites)
    configure_faults("encoder@40-100000:delay:80")
    sup = SlotSupervisor("session", _DummyActions())
    slo = SessionSLO(
        "0",
        targets={"unknown": SLOTargets(p50_ms=8.0, p95_ms=20.0,
                                       fps_floor=0.0, down_kbps=0.0)},
        fast_s=1.0, slow_s=30.0, eval_interval_s=0.1, min_frames=8,
        recovery_evals=10 ** 6, supervisor=sup,
        outlier=OutlierTrigger(window=64, warmup=20, factor=2.0,
                               floor_ms=25.0))
    pressure = []
    slo.on_pressure = lambda: pressure.append(1)
    done = asyncio.Event()

    async def sink(ef):
        if slo._any_breached() and slo.outliers:
            done.set()

    p = VideoPipeline(source=SyntheticSource(64, 48), encoder=TinyEncoder(),
                      rate_controller=TinyRC(), sink=sink, fps=250)
    p.slo = slo

    async def drive():
        await p.start()
        try:
            await asyncio.wait_for(done.wait(), timeout=30.0)
        finally:
            await p.stop()

    try:
        asyncio.run(drive())
    finally:
        reset_faults()
    # the policy-style pressure hook fired (edge + ~1/s re-asserts)
    assert pressure
    assert sup.rung == Rung.WARN
    assert slo._any_breached()
    # exactly one outlier bundle (rate-limited), tagged with a real fid
    bb = tmp_path / "bb"
    bundles = [d for d in os.listdir(bb)
               if "outlier" in d and not d.startswith(".")]
    assert len(bundles) == 1
    with open(bb / bundles[0] / "meta.json") as f:
        meta = json.load(f)
    assert meta["frame_id"] > 0
    assert meta["latency_ms"] >= 25.0
    # the tagged frame's correlation id appears in the bundled events
    with open(bb / bundles[0] / "events.jsonl") as f:
        fids = {e.get("fid") for e in map(json.loads, f) if "fid" in e}
    assert meta["frame_id"] in fids


def test_slo_disabled_constructs_nothing(monkeypatch):
    monkeypatch.delenv("SELKIES_SLO", raising=False)
    assert not slo_enabled()
    p = VideoPipeline(source=SyntheticSource(64, 48), encoder=TinyEncoder(),
                      rate_controller=TinyRC(), sink=lambda ef: None)
    assert p.slo is None and p._t_by_ts == {}
    monkeypatch.setenv("SELKIES_SLO", "1")
    assert slo_enabled()


def test_fleet_wires_per_slot_slos_and_sheds_bitrate(tele, monkeypatch):
    """Fleet mode: SELKIES_SLO=1 builds one SessionSLO per slot sharing
    the fleet supervisor; an acute breach halves the slot's bitrate
    target (bytes shed before the lockstep tick rate) and relief
    restores it."""
    monkeypatch.setenv("SELKIES_SLO", "1")
    from selkies_tpu.parallel.fleet import SessionFleet, SessionSlot

    slots = [SessionSlot(k, bitrate_kbps=2000, fps=60) for k in range(2)]
    fleet = SessionFleet(slots, width=64, height=64, fps=60)
    try:
        assert fleet.slos is not None and len(fleet.slos) == 2
        assert fleet.slos[0].supervisor is fleet.supervisor
        assert telemetry.enabled          # the plane implies the bus
        fleet._slo_shed(0)
        assert slots[0].rc.bitrate_kbps == 1000
        assert slots[1].rc.bitrate_kbps == 2000   # only the breacher sheds
        fleet._slo_shed(0)                        # idempotent
        assert slots[0].rc.bitrate_kbps == 1000
        fleet._slo_restore(0)
        assert slots[0].rc.bitrate_kbps == 2000
        assert "0" in fleet._slo_rollup() and "1" in fleet._slo_rollup()
        # a session already at/below the 250 kbps floor never gets its
        # target RAISED by a "shed"
        slots[1].rc.set_bitrate(200)
        fleet._slo_shed(1)
        assert slots[1].rc.bitrate_kbps == 200
        assert 1 not in fleet._slo_shed_kbps
        # client departure: shed restored, windows + sticky WARN cleared
        fleet._slo_shed(0)
        fleet.supervisor.slo_warn("burning", key="0")
        fleet.slos[0]._state["latency_p50"].breached = True
        fleet.reset_session_slo(0)
        assert slots[0].rc.bitrate_kbps == 2000
        assert not fleet.slos[0]._any_breached()
        assert fleet.supervisor.rung == Rung.HEALTHY
    finally:
        fleet.service.close()


# -- statz rendering ---------------------------------------------------------


def test_statz_tool_renders_slo_policy_and_placement_blocks(tele):
    spec = importlib.util.spec_from_file_location(
        "statz", os.path.join(REPO, "tools", "statz.py"))
    statz = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(statz)

    clock = FakeClock()
    slo = _slo(clock)
    _feed(slo, clock, 600, 10.0)
    _feed(slo, clock, 300, 500.0)
    rollup = tele.rollup()
    rollup["providers"] = {
        "slo": {"0": slo.stats()},
        "compile": {"compiles": 7, "cache_hits": 2,
                    "compile_ms_total": 123.0, "storms": 1,
                    "by_trigger": {"actuation": 4, "startup": 3}},
        "policy": {"0": {"scenario": "scroll", "preset": "balanced",
                         "congested": False, "frames": 900,
                         "transitions": {"scroll": 1}, "disarmed": False,
                         "failures": 0, "window": {}}},
        "fleet": {"sessions": 2, "connected": 1, "ticks": 10, "fps": 60,
                  "last_tick_ms": 4.2, "software_mode": False,
                  "placement": {"chips": 8, "free": 2, "borrowed": 1,
                                "grid": None, "shared": False,
                                "draining": False, "queue": [],
                                "carve": {"0": ["cpu:0", "cpu:1"],
                                          "1": ["cpu:2"]},
                                "codecs": {"0": "h264", "1": "av1"},
                                "accepts": 3, "rejects": 1}},
    }
    rollup["health"]["slo"] = {"0": slo.health_view()}
    rollup["health"]["lifecycle"] = {"state": "serving", "deadline_s": 20.0,
                                     "slots": {"0": "serving", "1": "busy"}}
    text = statz.render(rollup, [])
    assert "latency_p50" in text and "ACUTE" in text       # slo table
    assert "scroll" in text and "balanced" in text         # policy table
    assert "storms=1" in text and "actuation" in text      # compile block
    assert "chips=8" in text and "av1" in text             # placement
    assert "lifecycle: state=serving" in text
    assert "slo 0:" in text                                # healthz detail


# -- perf ratchet ------------------------------------------------------------


def _run_ratchet(args):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "tools",
                                      "check_bench_regress.py"), *args],
        capture_output=True, text=True, cwd=REPO)


def test_check_bench_regress_tolerances(tmp_path):
    ok = tmp_path / "ok.jsonl"
    ok.write_text(json.dumps({
        "scenario": "idle", "policy": 0, "damage": 0, "resolution": "720p",
        "value": 45.0, "p50_latency_ms": 180.0, "compiles": 0}) + "\n")
    proc = _run_ratchet(["--run-file", str(ok)])
    assert proc.returncode == 0, proc.stdout + proc.stderr

    bad = tmp_path / "bad.jsonl"
    bad.write_text(json.dumps({
        "scenario": "idle", "policy": 0, "damage": 0, "resolution": "720p",
        "value": 5.0, "p50_latency_ms": 2000.0}) + "\n")
    proc = _run_ratchet(["--run-file", str(bad)])
    assert proc.returncode == 1
    assert "fps" in proc.stdout and "p50" in proc.stdout

    # the compile leg arms only once the BASELINE records a zero count
    # (the committed r02 rows predate the field)
    base2 = tmp_path / "base2.jsonl"
    base2.write_text(json.dumps({
        "metric": "x", "scenario": "idle", "policy": 0, "damage": 0,
        "resolution": "720p", "value": 45.0, "p50_latency_ms": 180.0,
        "compiles": 0}) + "\n")
    churn = tmp_path / "churn.jsonl"
    churn.write_text(json.dumps({
        "scenario": "idle", "policy": 0, "damage": 0, "resolution": "720p",
        "value": 45.0, "p50_latency_ms": 180.0, "compiles": 3}) + "\n")
    proc = _run_ratchet(["--run-file", str(churn),
                         "--baseline", str(base2)])
    assert proc.returncode == 1
    assert "compiles" in proc.stdout.lower()

    # a row with no committed baseline is skipped, not failed
    novel = tmp_path / "novel.jsonl"
    novel.write_text(json.dumps({
        "scenario": "idle", "policy": 9, "damage": 0, "resolution": "9k",
        "value": 0.01, "p50_latency_ms": 1e9}) + "\n")
    proc = _run_ratchet(["--run-file", str(novel)])
    assert proc.returncode == 0
    assert "skip" in proc.stdout


@pytest.mark.slow
def test_bench_regress_ratchet():
    """The real ratchet: a fresh bench.py --scenario run against the
    committed BENCH_scenarios_r02.json rows at their own frame count
    (slow: ~minutes on CPU)."""
    proc = _run_ratchet(["--scenario", "idle,typing"])
    sys.stdout.write(proc.stdout)
    assert proc.returncode == 0, proc.stdout + proc.stderr
