"""Latency and rate arithmetic of the benchmark: due-time accounting in
the content source, skipped content, and percentiles over every AU."""

import numpy as np
import pytest

from benchmark.content import Scene
from benchmark.metrics import (delivered_fps, latency_p50_ms, latency_p95_ms,
                               tick_lag_ms)
from benchmark.run import Delivered, RunData
from benchmark.source import Capture, ContentSource, Record

MIX = {"layers": [{"kind": "band", "rect": [0, 0, 1920, 1080]}]}


class Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def test_source_returns_newest_content_due_and_records_due_times():
    clock, rec = Clock(), Record()
    src = ContentSource(Scene(MIX, 32, 16, 3), 10.0, True, rec, clock=clock)
    src.start_clock(100.0, 0, window=True)
    for t in (100.0, 100.05, 100.1, 100.35, 100.36):
        clock.t = t
        src.capture()
    assert [c.k for c in rec.captures] == [0, 0, 1, 3, 3]
    assert [c.due for c in rec.captures] == pytest.approx(
        [100.0, 100.0, 100.1, 100.3, 100.3])
    assert all(c.window for c in rec.captures)
    # damage: the first two captures after a start report the whole frame
    # (None); later ones cover the changes since the capture before last
    clock.t = 100.5
    src.capture()
    assert src.last_damage == [(0, 0, 32, 16)]
    src.start_clock(200.0, 1000, window=False)
    clock.t = 200.25
    src.capture()
    assert rec.captures[-1].k == 1002 and not rec.captures[-1].window
    assert rec.captures[-1].due == pytest.approx(200.2)
    # a jump back in content (window start after warm-up) is whole-frame
    src.start_clock(300.0, 0, window=True)
    clock.t = 300.0
    src.capture()
    assert src.last_damage is None


def _run(delivered, captures=(), seconds=2.0, fps=10.0):
    return RunData(seconds=seconds, fps=fps, width=32, height=16, setup_s=1.0,
                   t_open=0.0, captures=list(captures), delivered=delivered,
                   peaks={})


def test_delivered_fps_counts_distinct_content_frames():
    # content 0..4 due at k/10; content 2 arrives twice (two captures of
    # one content frame), content 3 never (a skipped tick)
    d = [Delivered(k, k / 10, k / 10 + 0.05, 30, False, None)
         for k in (0, 1, 2, 2, 4)]
    assert delivered_fps.read(_run(d)) == pytest.approx(4 / 2.0)


def test_latency_percentiles_cover_every_au():
    lat = np.arange(1, 101, dtype=float)  # ms
    d = [Delivered(i, 0.0, lat[i] / 1e3, 30, False, None) for i in range(100)]
    run = _run(d)
    assert latency_p50_ms.read(run) == pytest.approx(np.percentile(lat, 50))
    assert latency_p95_ms.read(run) == pytest.approx(np.percentile(lat, 95))
    # a stall shows: the newer frame that replaces skipped ones carries
    # their wait in its own latency (due time, not capture time)
    stalled = d + [Delivered(200, 0.0, 1.0, 30, False, None)]
    assert latency_p95_ms.read(_run(stalled)) > latency_p95_ms.read(run)
    assert latency_p50_ms.read(_run([])) is None


def test_tick_lag_counts_the_wait_of_skipped_content():
    caps = [Capture(0, 0.0, 0.01, True), Capture(1, 0.1, 0.11, True),
            Capture(1, 0.1, 0.15, True),   # no new content: not counted
            Capture(4, 0.4, 0.42, True)]   # content 2 and 3 were skipped
    lag = tick_lag_ms.read(_run([], caps))
    # (0.11 - due(1)) and (0.42 - due(2)): 10 ms and 220 ms
    assert lag == pytest.approx((10 + 220) / 2)
