"""XLA recompile sentinel: count, time, and attribute executable compiles.

The serving stack goes to real lengths to keep XLA compiles off the hot
path — PR 7's ``lax.switch`` bucket discipline (one executable picks its
padded size per frame), PR 10's hysteresis + dwell (a scenario flap can
never thrash the device-entropy retune), the snap-to-compiled batch-cap
vocabulary — but nothing ever *checked* those disciplines in production.
A misconfigured bucket ladder or a flapping policy quietly turns every
Nth frame into a multi-second ``backend_compile``, which the latency
percentiles show only as an unexplained tail.

This sentinel closes that gap by listening to ``jax.monitoring``'s
duration events (``/jax/core/compile/backend_compile_duration`` fires
once per *actual* executable build — persistent compile-cache hits
record a cache-hit event instead and are tracked separately):

* every compile is **counted and timed** into the
  ``selkies_compile_total`` / ``selkies_compile_ms`` telemetry families;
* every compile is **attributed to a trigger** — the known rebuild
  sites mark themselves before doing anything that invalidates
  executables (``actuation`` for a policy entropy retune,
  ``recarve`` for a lifecycle chip re-carve, ``codec_switch`` for a
  per-client renegotiation, ``resize`` for a geometry rebuild,
  ``restart`` for a supervisor encoder restart). Because jitted
  partials compile *lazily* on their next call (usually on a worker
  thread, far from the mark site), attribution is a process-global
  mark with a TTL rather than a call-stack property: a compile
  observed within ``mark_ttl_s`` of the newest mark belongs to it.
  Eager compile sites (``prewarm``) can instead use the exact
  thread-local :meth:`CompileSentinel.scope`. Compiles inside the
  process's first ``startup_grace_s`` attribute to ``startup``;
  anything else is ``unattributed`` — a *non-zero unattributed rate in
  steady state is itself the finding* (an executable is being rebuilt
  by something no rebuild site owns).
* a **recompile storm** — ``storm_n`` compiles inside a
  ``storm_window_s`` dwell — is flagged as a first-class event: an
  error log, a ``selkies_compile_storms_total`` count labeled with the
  window's dominant trigger, and a flight-recorder ring event so the
  storm appears in any black-box bundle dumped around it.

``jax.monitoring`` offers no per-listener unregistration, so one
module-level dispatcher is registered at most once per process and
forwards to whichever sentinel :func:`install` made active (tests swap
in their own and :func:`uninstall` detaches without touching jax).
Everything is a no-op until :func:`install` runs — the SLO plane
(``SELKIES_SLO=1``, monitoring/slo.py) installs it, and ``mark()`` on
an uninstalled sentinel is a cheap bookkeeping write.
"""

from __future__ import annotations

import logging
import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import Callable

from selkies_tpu.monitoring.telemetry import telemetry

logger = logging.getLogger("jitprof")

__all__ = ["CompileSentinel", "sentinel", "install", "uninstall",
           "mark", "scope", "stats", "COMPILE_EVENT", "CACHE_HIT_EVENT"]

# the one duration event that means "XLA built an executable" (jax emits
# it around backend.compile, i.e. only on a compile-cache MISS)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# persistent-compile-cache hit (utils/jaxcache.py): executable churn
# that the cache absorbed — cheap, but still churn worth seeing
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

TRIGGERS = ("actuation", "recarve", "codec_switch", "resize", "restart",
            "startup", "unattributed")


class CompileSentinel:
    """Counts/times/attributes XLA compiles; flags recompile storms.

    All state mutations take ``_lock`` — jax fires duration events on
    whatever thread compiled (encode workers, the event loop, pack
    pools)."""

    def __init__(self, *, storm_n: int = 8, storm_window_s: float = 30.0,
                 mark_ttl_s: float = 30.0, startup_grace_s: float = 120.0,
                 clock: Callable[[], float] = time.monotonic):
        self.storm_n = max(2, int(storm_n))
        self.storm_window_s = float(storm_window_s)
        self.mark_ttl_s = float(mark_ttl_s)
        self.startup_grace_s = float(startup_grace_s)
        self.clock = clock
        self._t0 = clock()
        self._lock = threading.Lock()
        self._tls = threading.local()
        self._mark: tuple[str, str, float] | None = None  # trigger, detail, t
        self.compiles = 0
        self.cache_hits = 0
        self.compile_ms_total = 0.0
        self.storms = 0
        self.by_trigger: dict[str, int] = {}
        self.by_site: dict[str, int] = {}       # "trigger:detail" -> n
        self._recent: deque = deque()           # (t, trigger) inside window
        self._last_storm_at = -1e18
        self.last: dict | None = None           # last compile, for stats()

    # -- attribution ---------------------------------------------------

    def mark(self, trigger: str, detail: str = "") -> None:
        """Declare that executables were just invalidated by ``trigger``
        — compiles observed within ``mark_ttl_s`` attribute to it.
        Newest mark wins (the rebuild that happened last is the one the
        next lazy compile pays for)."""
        with self._lock:
            self._mark = (str(trigger), str(detail), self.clock())

    @contextmanager
    def scope(self, trigger: str, detail: str = ""):
        """Exact attribution for eager compile sites (``prewarm``):
        compiles on THIS thread inside the block belong to ``trigger``,
        overriding any process-global mark."""
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        stack.append((str(trigger), str(detail)))
        try:
            yield self
        finally:
            stack.pop()

    def _attribute(self, now: float) -> tuple[str, str]:
        stack = getattr(self._tls, "stack", None)
        if stack:
            return stack[-1]
        m = self._mark
        if m is not None and now - m[2] <= self.mark_ttl_s:
            return m[0], m[1]
        if now - self._t0 <= self.startup_grace_s:
            return "startup", ""
        return "unattributed", ""

    # -- the jax.monitoring listener ------------------------------------

    def on_duration(self, event: str, secs: float) -> None:
        if event != COMPILE_EVENT:
            return
        now = self.clock()
        ms = secs * 1e3
        with self._lock:
            trigger, detail = self._attribute(now)
            self.compiles += 1
            self.compile_ms_total += ms
            self.by_trigger[trigger] = self.by_trigger.get(trigger, 0) + 1
            site = f"{trigger}:{detail}" if detail else trigger
            self.by_site[site] = self.by_site.get(site, 0) + 1
            self.last = {"trigger": trigger, "detail": detail,
                         "ms": round(ms, 1), "t": round(now - self._t0, 1)}
            self._recent.append((now, trigger))
            cutoff = now - self.storm_window_s
            while self._recent and self._recent[0][0] < cutoff:
                self._recent.popleft()
            storm = (len(self._recent) >= self.storm_n
                     and now - self._last_storm_at >= self.storm_window_s)
            if storm:
                self._last_storm_at = now
                self.storms += 1
                dominant = max(set(t for _, t in self._recent),
                               key=[t for _, t in self._recent].count)
                n_window = len(self._recent)
        if telemetry.enabled:
            telemetry.count("selkies_compile_total", trigger=trigger)
            telemetry.observe("selkies_compile_ms", ms, trigger=trigger)
        if storm:
            logger.error(
                "recompile storm: %d XLA compiles inside %.0fs (dominant "
                "trigger %r, last %s/%s %.0f ms) — an executable-reuse "
                "discipline is broken", n_window, self.storm_window_s,
                dominant, trigger, detail or "-", ms)
            if telemetry.enabled:
                telemetry.count("selkies_compile_storms_total",
                                trigger=dominant)
                telemetry.event("compile_storm", trigger=dominant,
                                compiles=n_window,
                                window_s=self.storm_window_s)

    def on_event(self, event: str) -> None:
        if event == CACHE_HIT_EVENT:
            with self._lock:
                self.cache_hits += 1

    # -- read side -------------------------------------------------------

    def stats(self) -> dict:
        with self._lock:
            return {
                "compiles": self.compiles,
                "cache_hits": self.cache_hits,
                "compile_ms_total": round(self.compile_ms_total, 1),
                "storms": self.storms,
                "by_trigger": dict(self.by_trigger),
                "by_site": dict(self.by_site),
                "in_window": len(self._recent),
                "last": dict(self.last) if self.last else None,
            }


# -- process-global dispatch ------------------------------------------------
#
# jax.monitoring can only ever ADD listeners, so exactly one dispatcher is
# registered (lazily, on the first install) and forwards to the active
# sentinel; uninstall() just clears the active slot.

sentinel = CompileSentinel()
_active: CompileSentinel | None = None
_registered = False
_reg_lock = threading.Lock()


def _dispatch_duration(event: str, duration: float, **_kw) -> None:
    s = _active
    if s is not None:
        try:
            s.on_duration(event, duration)
        except Exception:  # the sentinel must never break a compile
            logger.exception("compile sentinel listener failed")


def _dispatch_event(event: str, **_kw) -> None:
    s = _active
    if s is not None:
        try:
            s.on_event(event)
        except Exception:
            logger.exception("compile sentinel listener failed")


def install(s: CompileSentinel | None = None) -> CompileSentinel:
    """Make ``s`` (default: the module sentinel) the active compile
    listener; registers the jax.monitoring hooks once per process.
    Idempotent. Returns the active sentinel."""
    global _active, _registered
    with _reg_lock:
        if not _registered:
            try:
                import jax.monitoring as jm

                jm.register_event_duration_secs_listener(_dispatch_duration)
                jm.register_event_listener(_dispatch_event)
                _registered = True
            except Exception:
                logger.exception("jax.monitoring unavailable; compile "
                                 "sentinel disabled")
                return s or sentinel
        _active = s or sentinel
        return _active


def uninstall() -> None:
    """Stop observing (the jax listener stays registered but forwards
    nowhere)."""
    global _active
    with _reg_lock:
        _active = None


def mark(trigger: str, detail: str = "") -> None:
    """Module-level convenience: mark on the *active* sentinel when one
    is installed, else on the default (so marks placed before install
    still attribute the startup compiles that follow)."""
    (_active or sentinel).mark(trigger, detail)


def scope(trigger: str, detail: str = ""):
    return (_active or sentinel).scope(trigger, detail)


def stats() -> dict:
    """The active sentinel's stats (the /statz ``compile`` provider)."""
    return (_active or sentinel).stats()


# -- compiles in flight -------------------------------------------------------
# The supervisor's tick-deadline watchdog (resilience/supervisor.py) reads
# compiling(): a tick blocked in an XLA compile — the lazy first use of an
# executable, ~100 s for a device-entropy step built for v5e — is not a
# wedged device. JAX reports a backend compile's start (a scalar event)
# and its end (a duration event) under the same name.

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_inflight = 0
_tracking = False


def _compile_started(event: str, _value, **_kw) -> None:
    global _inflight
    if event == _COMPILE_EVENT:
        with _reg_lock:
            _inflight += 1


def _compile_ended(event: str, _duration: float, **_kw) -> None:
    global _inflight
    if event == _COMPILE_EVENT:
        with _reg_lock:
            _inflight = max(0, _inflight - 1)


def track_compiles() -> None:
    """Register the listeners behind :func:`compiling`, once per process."""
    global _tracking
    with _reg_lock:
        if _tracking:
            return
        import jax.monitoring as jm

        jm.register_scalar_listener(_compile_started)
        jm.register_event_duration_secs_listener(_compile_ended)
        _tracking = True


def compiling() -> bool:
    """Whether an XLA backend compile is running in this process."""
    return _inflight > 0
