"""Resilience layer units: recovery ladder, backoff, fault injection.

Everything here runs with a fake clock and recording actions — no device,
no sockets — so ladder transitions and backoff gating are asserted
exactly (the chaos suite in tests/test_chaos.py drives the real loops).
"""

from __future__ import annotations

import pytest

from selkies_tpu.resilience import (
    Backoff,
    FaultInjector,
    InjectedFault,
    Rung,
    SlotSupervisor,
    configure_faults,
    get_injector,
    reset_faults,
)


class FakeClock:
    def __init__(self):
        self.t = 1000.0

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


class RecordingActions:
    """RecoveryActions double: records every call in order."""

    def __init__(self, fail_in: set[str] | None = None):
        self.calls: list[tuple] = []
        self.fail_in = fail_in or set()

    def _rec(self, name, *args):
        self.calls.append((name, *args))
        if name in self.fail_in:
            raise RuntimeError(f"action {name} broken")

    def warn(self, msg):
        self._rec("warn", msg)

    def force_idr(self):
        self._rec("force_idr")

    def restart_encoder(self):
        self._rec("restart_encoder")

    def degrade(self, level):
        self._rec("degrade", level)

    def undegrade(self, level):
        self._rec("undegrade", level)

    def recycle(self):
        self._rec("recycle")

    def names(self):
        return [c[0] for c in self.calls]


def make_supervisor(actions=None, clock=None, **kw):
    actions = actions if actions is not None else RecordingActions()
    clock = clock or FakeClock()
    kw.setdefault("warn_after", 1)
    kw.setdefault("idr_after", 2)
    kw.setdefault("restart_after", 3)
    kw.setdefault("degrade_after", 5)
    kw.setdefault("degrade_every", 2)
    kw.setdefault("recycle_after", 10)
    kw.setdefault("recover_after", 4)
    kw.setdefault("backoff", Backoff(base=1.0, cap=8.0))
    sup = SlotSupervisor("test", actions, fps=30.0, clock=clock, **kw)
    return sup, actions, clock


# -- ladder transitions ------------------------------------------------


def test_ladder_escalates_in_order():
    sup, acts, clock = make_supervisor()
    assert sup.failure(RuntimeError("a")) == Rung.WARN
    assert acts.names() == ["warn"]
    assert sup.failure(RuntimeError("b")) == Rung.FORCE_IDR
    assert acts.names() == ["warn", "force_idr"]
    assert sup.failure(RuntimeError("c")) == Rung.RESTART
    assert acts.names() == ["warn", "force_idr", "restart_encoder"]
    clock.advance(100)  # clear the restart backoff gate
    sup.failure(RuntimeError("d"))
    rung = sup.failure(RuntimeError("e"))
    assert rung == Rung.DEGRADE
    assert acts.calls[-1] == ("degrade", 1)
    # degrade_every=2: the next level lands two failures later
    sup.failure(RuntimeError("f"))
    sup.failure(RuntimeError("g"))
    assert acts.calls[-1] == ("degrade", 2)
    clock.advance(100)
    for _ in range(3):
        sup.failure(RuntimeError("h"))
    assert sup.rung == Rung.RECYCLE
    assert "recycle" in acts.names()
    # recycle resets the streak for the fresh session
    assert sup.failures == 0


def test_healthy_tick_resets_streak_but_not_degradation():
    sup, acts, clock = make_supervisor()
    for _ in range(5):
        sup.failure(RuntimeError("x"))
        clock.advance(1)
    assert sup.degrade_level == 1
    sup.tick_ok()
    assert sup.failures == 0
    assert sup.degrade_level == 1  # reversal needs SUSTAINED health
    # the next failure streak warns again from the start
    sup.failure(RuntimeError("y"))
    assert acts.calls[-1][0] == "warn"


def test_degradation_reverses_after_sustained_health():
    sup, acts, clock = make_supervisor()
    for _ in range(7):
        sup.failure(RuntimeError("x"))
        clock.advance(1)
    assert sup.degrade_level == 2
    # recover_after=4 healthy ticks per reversal step
    for _ in range(4):
        sup.tick_ok()
    assert sup.degrade_level == 1
    assert acts.calls[-1] == ("undegrade", 1)
    for _ in range(4):
        sup.tick_ok()
    assert sup.degrade_level == 0
    assert acts.calls[-1] == ("undegrade", 0)
    assert sup.rung == Rung.HEALTHY


def test_broken_recovery_action_does_not_raise():
    sup, acts, clock = make_supervisor(
        actions=RecordingActions(fail_in={"force_idr"}))
    sup.failure(RuntimeError("a"))
    sup.failure(RuntimeError("b"))  # force_idr raises inside — absorbed
    assert sup.rung == Rung.FORCE_IDR
    assert sup.counters["idrs_forced"] == 1


def test_thresholds_must_be_monotonic():
    with pytest.raises(ValueError):
        SlotSupervisor("bad", RecordingActions(), warn_after=5, idr_after=1)


# -- restart backoff gating (fake clock) -------------------------------


def test_restart_backoff_gates_rebuilds():
    sup, acts, clock = make_supervisor()
    for _ in range(3):
        sup.failure(RuntimeError("x"))
    assert acts.names().count("restart_encoder") == 1
    # still inside the 1 s backoff window: more failures, no new restart
    sup.failure(RuntimeError("y"))
    assert acts.names().count("restart_encoder") == 1
    clock.advance(1.5)  # past the first 1 s delay
    sup.failure(RuntimeError("z"))
    assert acts.names().count("restart_encoder") == 2
    # the second delay doubled to 2 s
    clock.advance(1.0)
    sup.failure(RuntimeError("w"))
    assert acts.names().count("restart_encoder") == 2
    clock.advance(1.5)
    sup.failure(RuntimeError("v"))
    assert acts.names().count("restart_encoder") == 3


def test_backoff_caps_and_resets():
    b = Backoff(base=1.0, cap=4.0)
    assert [b.next_delay() for _ in range(4)] == [1.0, 2.0, 4.0, 4.0]
    b.reset()
    assert b.next_delay() == 1.0


def test_backoff_jitter_deterministic():
    b = Backoff(base=1.0, cap=8.0, jitter=0.5, rand=lambda: 0.5)
    assert b.next_delay() == pytest.approx(1.25)
    assert b.next_delay() == pytest.approx(2.5)


def test_sustained_health_resets_restart_backoff():
    sup, acts, clock = make_supervisor()
    for _ in range(3):
        sup.failure(RuntimeError("x"))
    assert sup.backoff.attempts == 1
    for _ in range(4):  # recover_after
        sup.tick_ok()
    assert sup.backoff.attempts == 0


# -- deadline watchdog -------------------------------------------------


def test_deadline_requires_arming():
    sup, acts, clock = make_supervisor(arm_after=2, deadline_ticks=30.0)
    clock.advance(1e6)  # an eternity before the first tick (jit compile)
    assert not sup.check_deadline()
    sup.tick_ok()
    sup.tick_ok()  # armed now
    clock.advance(30.0 / 30.0 + 0.1)  # past deadline_ticks/fps = 1 s
    assert sup.check_deadline()
    assert sup.counters["deadline_misses"] == 1
    assert acts.names()[-1] == "warn"
    # re-armed: fires once per missed window, not every poll
    assert not sup.check_deadline()


def test_note_idle_suppresses_deadline():
    sup, acts, clock = make_supervisor(arm_after=1, deadline_ticks=30.0)
    sup.tick_ok()
    clock.advance(100.0)
    sup.note_idle()  # no client connected: not a stall
    assert not sup.check_deadline()


def test_compile_in_flight_holds_deadline():
    """A tick blocked in an XLA compile (JAX reports its start as a
    scalar event, its end as a duration event) is not a stall."""
    import jax.monitoring as jm

    from selkies_tpu.monitoring import jitprof

    sup, acts, clock = make_supervisor(arm_after=1, deadline_ticks=30.0)
    sup.tick_ok()
    event = "/jax/core/compile/backend_compile_duration"
    jm.record_scalar(event, clock())
    try:
        assert jitprof.compiling()
        clock.advance(100.0)
        assert not sup.check_deadline()
    finally:
        jm.record_event_duration_secs(event, 100.0)
    assert not jitprof.compiling()
    assert sup.counters["deadline_misses"] == 0 and acts.names() == []
    clock.advance(2.0)  # a stall after the compile still counts
    assert sup.check_deadline()


# -- fault injection ---------------------------------------------------


def test_fault_grammar_tick_list_and_ranges():
    fi = FaultInjector("encoder@2,4-5:raise")
    assert fi.check("encoder") is None  # tick 1
    with pytest.raises(InjectedFault):
        fi.check("encoder")  # tick 2
    assert fi.check("encoder") is None  # tick 3
    with pytest.raises(InjectedFault):
        fi.check("encoder")  # tick 4
    with pytest.raises(InjectedFault):
        fi.check("encoder")  # tick 5
    assert fi.check("encoder") is None
    assert fi.injected == [("encoder", 2, "raise"), ("encoder", 4, "raise"),
                           ("encoder", 5, "raise")]


def test_fault_actions_drop_delay_flap():
    fi = FaultInjector("send@1:drop;send@2:delay:25;signalling@1:flap")
    assert fi.check("send") == ("drop", 0.0)
    assert fi.check("send") == ("delay", 25.0)
    assert fi.check("send") is None
    assert fi.check("signalling") == ("flap", 0.0)


def test_fault_site_prefix_matches_with_separate_counters():
    fi = FaultInjector("send@2:drop")
    assert fi.check("send:0") is None
    assert fi.check("send:1") is None
    # each qualified site has its own tick clock
    assert fi.check("send:0") == ("drop", 0.0)
    assert fi.check("send:1") == ("drop", 0.0)
    # but an unrelated site never matches
    assert fi.check("sendx") is None
    assert fi.check("sendx") is None


def test_fault_every_and_seeded_probability():
    fi = FaultInjector("capture@every:3:raise")
    hits = []
    for i in range(1, 10):
        try:
            fi.check("capture")
            hits.append(False)
        except InjectedFault:
            hits.append(True)
    assert hits == [False, False, True] * 3

    a = FaultInjector("encoder@p:0.5,seed:7:raise")
    b = FaultInjector("encoder@p:0.5,seed:7:raise")

    def run(fi):
        out = []
        for _ in range(50):
            try:
                fi.check("encoder")
                out.append(0)
            except InjectedFault:
                out.append(1)
        return out

    ra, rb = run(a), run(b)
    assert ra == rb  # same seed -> identical schedule
    assert 5 < sum(ra) < 45


def test_fault_grammar_rejects_malformed():
    for bad in ("encoder@:raise", "encoder@1:explode", "encoder@1",
                "@1:raise", "encoder@p:2.0:raise", "encoder@1:delay"):
        with pytest.raises(ValueError):
            FaultInjector(bad)


def test_injector_env_round_trip(monkeypatch):
    reset_faults()
    monkeypatch.setenv("SELKIES_FAULTS", "encoder@1:raise")
    try:
        fi = get_injector()
        assert fi is not None
        with pytest.raises(InjectedFault):
            fi.check("encoder")
    finally:
        reset_faults()
    monkeypatch.delenv("SELKIES_FAULTS")
    assert get_injector() is None
    reset_faults()


def test_configure_overrides_env():
    try:
        fi = configure_faults("send@1:drop")
        assert get_injector() is fi
    finally:
        reset_faults()
