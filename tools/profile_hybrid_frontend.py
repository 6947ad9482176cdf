#!/usr/bin/env python3
"""Device time inside a tpuvp9enc/tpuav1enc encode (round-5 VERDICT
item 5 'Done' contract): run the hybrid rows with the DEVICE front-end
(models/hybrid_frontend.py — per-MB dirty classification + coarse ME
hints shared with the H.264 path) on the 1080p desktop trace and print
per-frame totals split into front-end device ms vs library encode ms.

Runs on whatever jax backend is active and prints its name.
"""
import sys
import time
import importlib.util

import numpy as np

sys.path.insert(0, ".")

spec = importlib.util.spec_from_file_location("bench", "bench.py")
bench = importlib.util.module_from_spec(spec)
spec.loader.exec_module(bench)

frames = bench._desktop_trace(40)
W, H = bench.W, bench.H


def run(enc, label):
    fe_ms, lib_ms, n = 0.0, 0.0, 0
    enc.encode_frame(frames[0])  # keyframe + front-end warmup/compile
    enc.encode_frame(frames[1])  # steady-state executable
    for f in frames[2:]:
        t0 = time.perf_counter()
        enc.encode_frame(f)
        total = (time.perf_counter() - t0) * 1e3
        fe_ms += enc.frontend_device_ms
        lib_ms += total - enc.frontend_device_ms
        n += 1
    print(f"{label}: frontend(device)={fe_ms / n:6.2f} ms/f  "
          f"library={lib_ms / n:7.2f} ms/f  "
          f"static={enc.static_frames} active_map={enc.active_map_frames}")
    enc.close()


def compute_only(n=30):
    """Front-end COMPUTE isolated from the host link: keep the frame
    resident on device and time the jitted step alone (uploads are
    deployment-dependent, while the compute term is the chip's own
    number; only the (mbh,mbw) bool map + (K,2) hints cross back per
    step)."""
    import jax
    from selkies_tpu.models.hybrid_frontend import DeviceDeltaFrontend

    fe = DeviceDeltaFrontend(W, H)
    fe.step(frames[0])                       # init reference
    # ALTERNATE two resident frames so every timed step sees a changed
    # frame and the lax.cond takes the vote branch — feeding one frame
    # would compare it against itself after the first step and time the
    # static-desktop fast path instead (dirty all-False, no SAD vote)
    f_a = jax.device_put(fe._jnp.asarray(frames[1]))
    f_b = jax.device_put(fe._jnp.asarray(frames[2]))
    prev, prev_luma = fe._prev, fe._prev_luma
    dirty, hints, prev, prev_luma = fe._step(f_a, prev, prev_luma)
    jax.block_until_ready((dirty, hints))    # compile
    t0 = time.perf_counter()
    for i in range(n):
        dirty, hints, prev, prev_luma = fe._step(
            f_b if i % 2 else f_a, prev, prev_luma)
        np.asarray(dirty), np.asarray(hints)
    dt = (time.perf_counter() - t0) * 1e3 / n
    assert np.asarray(dirty).any(), "timed path must exercise the vote branch"
    print(f"frontend compute-only (frame resident, dirty+hints fetched): "
          f"{dt:.2f} ms/f")
    # same step PIPELINED (one drain at the end): separates the chip's
    # execute time from the per-round-trip dispatch+fetch latency.
    t0 = time.perf_counter()
    for i in range(n):
        dirty, hints, prev, prev_luma = fe._step(
            f_b if i % 2 else f_a, prev, prev_luma)
    np.asarray(dirty)  # forces the chained queue to drain
    dt = (time.perf_counter() - t0) * 1e3 / n
    assert np.asarray(dirty).any(), "timed path must exercise the vote branch"
    print(f"frontend execute-only (pipelined x{n}, np.asarray drain): "
          f"{dt:.2f} ms/f")


import jax

print(f"backend={jax.default_backend()}  geometry={W}x{H}  frames={len(frames)}")
compute_only()
from selkies_tpu.models.vp9.encoder import TPUVP9Encoder

run(TPUVP9Encoder(width=W, height=H, fps=60, bitrate_kbps=3000,
                  frontend="device"), "tpuvp9enc")

from selkies_tpu.models.libaom_enc import libaom_available

if libaom_available():
    from selkies_tpu.models.av1.encoder import TPUAV1Encoder

    run(TPUAV1Encoder(width=W, height=H, fps=60, bitrate_kbps=3000,
                      frontend="device"), "tpuav1enc")
