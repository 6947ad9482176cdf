#!/usr/bin/env python3
"""tpuvp9enc hybrid measurement: encode CPU per frame on the 1080p
desktop trace with and without the front-end (show_existing_frame fast
path + per-MB active map from the dirty-tile classification), vs the
reference envelope (BASELINE: 1080p60 VP9 screen content; the reference
x264 row budgets '150% CPU' ~ 1.5 cores for 1080p60, docs/design.md:33).

CPU-only — needs no TPU.
"""
import sys, time
import importlib.util

import numpy as np

sys.path.insert(0, ".")
spec = importlib.util.spec_from_file_location("bench", "bench.py")
bench = importlib.util.module_from_spec(spec)
spec.loader.exec_module(bench)

from selkies_tpu.models.libvpx_enc import LibVpxEncoder
from selkies_tpu.models.vp9.encoder import TPUVP9Encoder

frames = bench._desktop_trace(60)
W, H = bench.W, bench.H


def run(enc, label):
    enc.encode_frame(frames[0])  # keyframe out of the timing
    t0 = time.process_time()
    w0 = time.perf_counter()
    n = 0
    for f in frames[1:]:
        enc.encode_frame(f)
        n += 1
    cpu = time.process_time() - t0
    wall = time.perf_counter() - w0
    stats = ""
    if hasattr(enc, "static_frames"):
        stats = (f"  [static 1-byte: {enc.static_frames}, "
                 f"active-map: {enc.active_map_frames}]")
    print(f"{label:28s} {1e3 * cpu / n:7.2f} ms CPU/frame "
          f"({1e3 * wall / n:6.2f} ms wall) -> "
          f"{cpu / n * 60 * 100:5.0f}% of one core at 60 fps{stats}")
    enc.close()
    return cpu / n


plain = run(LibVpxEncoder(width=W, height=H, fps=60, bitrate_kbps=3000),
            "plain libvpx vp9enc")
hybrid = run(TPUVP9Encoder(W, H, fps=60, bitrate_kbps=3000),
             "tpuvp9enc (delta front-end)")
print(f"front-end cut: {plain / hybrid:.2f}x less encode CPU on the desktop trace")


# idle-desktop profile: the dominant remote-desktop case is an unchanged
# screen (cursor parked). 80% static frames exercise the 1-byte
# show_existing_frame fast path that plain libvpx cannot take.
idle = []
for i, f in enumerate(frames):
    idle.append(f if i % 5 == 0 else idle[-1] if idle else f)

print()
enc = LibVpxEncoder(width=W, height=H, fps=60, bitrate_kbps=3000)
enc.encode_frame(idle[0])
t0 = time.process_time(); n = 0
for f in idle[1:]:
    enc.encode_frame(f); n += 1
plain_i = (time.process_time() - t0) / n
print(f"{'plain vp9enc, idle desktop':28s} {1e3 * plain_i:7.2f} ms CPU/frame")
enc.close()
enc = TPUVP9Encoder(W, H, fps=60, bitrate_kbps=3000)
enc.encode_frame(idle[0])
t0 = time.process_time(); n = 0
for f in idle[1:]:
    enc.encode_frame(f); n += 1
hyb_i = (time.process_time() - t0) / n
print(f"{'tpuvp9enc, idle desktop':28s} {1e3 * hyb_i:7.2f} ms CPU/frame  "
      f"[static 1-byte: {enc.static_frames}, active-map: {enc.active_map_frames}]")
enc.close()
print(f"idle-desktop cut: {plain_i / hyb_i:.2f}x less encode CPU")
