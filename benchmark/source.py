"""The benchmark's capture source and the record of what it handed out.

``ContentSource`` stands in for the X11 capture (``app.source``): a
capture at time ``t`` returns the newest content frame due by ``t``, where
content frame ``k`` is due at ``t0 + k / fps`` on the content clock the
harness starts. It publishes damage rects the way the X11 source does
(``SyntheticSource.last_damage``): the union of the changes since the
capture before the previous one, so one dropped tick still sees a
superset; the first two captures after a clock start report None.

``instrument`` wraps the encoder's ``submit`` (the same spy move the
repo's drives make) to record, per 90 kHz pts the pipeline stamps, which
capture the frame came from and the QP it was coded at, and to keep the
encoder's own per-frame ``FrameStats``. ``faults`` may break the timed
path there, for the control and the fault tests.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field

from benchmark.content import Scene


@dataclass
class Capture:
    k: int          # content index
    due: float      # perf_counter time the content frame was due
    t: float        # perf_counter time of the capture
    window: bool    # captured on the window's content clock


@dataclass
class Submitted:
    cap: Capture
    qp: int | None
    stats: object = None  # the encoder's FrameStats once the frame completed
    t_done: float | None = None  # when submit handed the completed frame out
    failed: bool = False  # submit raised: the frame will never be delivered


@dataclass
class Record:
    captures: list = field(default_factory=list)
    by_pts: dict = field(default_factory=dict)   # pts & 0xFFFFFFFF -> Submitted
    lock: threading.Lock = field(default_factory=threading.Lock)


def _annotation(name: str):
    import jax.profiler

    return jax.profiler.TraceAnnotation(name)


class ContentSource:
    def __init__(self, scene: Scene, fps: float, damage: bool, record: Record,
                 clock=time.perf_counter):
        self.scene, self.fps, self.record, self.clock = scene, float(fps), record, clock
        self.width, self.height = scene.width, scene.height
        self._damage = damage
        self._clock = (clock(), 0, False)  # (t0, base, window), swapped whole
        self._ks: list = [None, None]
        self.last: Capture | None = None
        self.last_damage = None

    def start_clock(self, t0: float, base: int, window: bool) -> None:
        """Content frame base + j is due at t0 + j / fps from now on."""
        self._clock = (t0, base, window)

    def capture(self):
        with _annotation("bench.capture"):
            t = self.clock()
            t0, base, window = self._clock
            # the epsilon keeps a capture exactly at a due time on that frame
            k = base + max(0, int((t - t0) * self.fps + 1e-9))
            frame = self.scene.frame(k)
            older = self._ks[0]
            if self._damage:
                self.last_damage = (None if older is None
                                    else self.scene.damage(older, k))
            self._ks = [self._ks[1], k]
            cap = Capture(k, t0 + (k - base) / self.fps, t, window)
            with self.record.lock:
                self.record.captures.append(cap)
            self.last = cap
            return frame


def instrument(encoder, source: ContentSource, record: Record, fault) -> None:
    """Wrap ``encoder.submit`` in place (see module docstring)."""
    submit = encoder.submit

    def recorded_submit(frame, qp=None, meta=None, damage=None):
        cap = source.last
        sub = Submitted(cap, qp)
        if meta is not None and cap is not None:
            with record.lock:
                record.by_pts[meta & 0xFFFFFFFF] = sub
        try:
            with _annotation("bench.submit"):
                done = submit(fault.frame(frame), qp, meta, damage=damage)
        except Exception:
            sub.failed = True
            raise
        fault.after_submit(encoder)
        t = time.perf_counter()
        with record.lock:
            for _au, stats, m in done:
                done_sub = record.by_pts.get(m & 0xFFFFFFFF) if m is not None else None
                if done_sub is not None:
                    done_sub.stats, done_sub.t_done = stats, t
        return fault.outputs(done)

    encoder.submit = recorded_submit
