#!/usr/bin/env python3
"""Fleet serving-glue overhead: SessionFleet tick vs bare
MultiSessionH264Service tick, same geometry, same mesh.

The 8x1080p60 projection rests on the bare service's device tick
(tools/profile_multisession.py). This measures what the PRODUCT path
adds on top — python fan-out, per-slot RC reads, capture batching —
so the projection's glue term is a number, not an assumption. Runs on
whatever jax backend is active; on the CPU set JAX_PLATFORMS=cpu and
XLA_FLAGS=--xla_force_host_platform_device_count=8.
"""
import os
import sys
import time

import numpy as np

sys.path.insert(0, ".")

N = int(os.environ.get("FLEET_PROFILE_SESSIONS", "2"))
W, H = (int(x) for x in os.environ.get(
    "FLEET_PROFILE_GEOMETRY", "640x384").split("x"))
TICKS = 20

from selkies_tpu.parallel.fleet import SessionFleet, SessionSlot
from selkies_tpu.parallel.serving import MultiSessionH264Service
from selkies_tpu.pipeline.elements import SyntheticSource


def bare_ms() -> float:
    svc = MultiSessionH264Service(N, W, H, qp=28, fps=60)
    srcs = [SyntheticSource(W, H, seed=k) for k in range(N)]
    batch = np.stack([s.capture() for s in srcs])
    svc.encode_tick(batch)  # IDR + compile
    batch = np.stack([s.capture() for s in srcs])
    svc.encode_tick(batch)  # P compile
    t0 = time.perf_counter()
    for _ in range(TICKS):
        batch = np.stack([s.capture() for s in srcs])
        svc.encode_tick(batch)
    dt = (time.perf_counter() - t0) / TICKS * 1e3
    svc.close()
    return dt


def fleet_ms() -> float:
    slots = [SessionSlot(k, bitrate_kbps=4000, fps=60) for k in range(N)]
    fleet = SessionFleet(slots, width=W, height=H, fps=60)
    fleet._capture_batch(); fleet._encode_tick()  # IDR + compile
    fleet._capture_batch(); fleet._encode_tick()  # P compile
    t0 = time.perf_counter()
    for _ in range(TICKS):
        fleet._capture_batch()
        aus, idrs, qps, _ = fleet._encode_tick()
        for slot, au, idr in zip(slots, aus, idrs):
            slot.rc.update(len(au), idr=idr)
    dt = (time.perf_counter() - t0) / TICKS * 1e3
    fleet.service.close()
    return dt


import jax

if len(jax.devices()) < N:
    sys.exit(f"backend={jax.default_backend()} has {len(jax.devices())} "
             f"device(s) < {N} sessions")

print(f"backend={jax.default_backend()}  sessions={N}  geometry={W}x{H}")
b = bare_ms()
f = fleet_ms()
print(f"bare service tick : {b:7.2f} ms")
print(f"fleet path tick   : {f:7.2f} ms  (glue {f - b:+.2f} ms)")
