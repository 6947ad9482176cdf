#!/usr/bin/env python3
"""v5e-8 capacity projection from the one real chip.

The 8-session BASELINE config places one 1080p60 stream per chip with no
cross-chip work (parallel/sessions.py), so per-chip sustained tick rate
== per-session rate on the full slice (host CAVLC packs on independent
worker threads; an 8-session host has 8x the pack work but it's off the
critical path). This measures MultiSessionH264Service(n=1) at 1080p on
the real chip: steady P ticks, plus a mixed tick with a forced keyframe
(the per-chip lax.cond path), and prints ticks/s.
"""
import sys, time
import numpy as np

sys.path.insert(0, ".")
import jax

from selkies_tpu.parallel.serving import MultiSessionH264Service

W, H = 1920, 1088
N_TICKS = 30
rng = np.random.default_rng(7)
base = np.kron(rng.integers(0, 255, (H // 16, (W + 128) // 16, 4), np.uint8),
               np.ones((16, 16, 1), np.uint8))
frames = [np.ascontiguousarray(base[:, 4 * i:4 * i + W]) for i in range(16)]

_tiny = jax.jit(lambda a: a.ravel()[:1])


def device_tick_ms(svc, frame, n=10):
    """Device-only mixed-tick time: the planes are PRE-uploaded (the
    serving layer converts BGRx->I420 host-side since round 4) and the
    step driven directly, so neither the h2d upload nor the bulk
    coefficient d2h (both absorbed at ~GB/s by a PCIe-local host) sit in
    the timed loop; sync is a 1-element fetch on the FIFO queue."""
    import jax
    import jax.numpy as jnp
    enc = svc.enc
    y, u, v = svc._preps[0].convert(frame)  # the production converter
    planes_d = tuple(jax.device_put(np.asarray(p)[None], enc._shard)
                     for p in (y, u, v))
    qps_d = jnp.asarray(np.array([28], np.int32))
    idrs_d = jnp.asarray(np.array([False]))
    ref = enc._ref
    enc._ref = None  # we manage donation manually below
    out = dict(enc._step_mixed(*planes_d, qps_d, idrs_d, *ref))
    ref = (out.pop("recon_y"), out.pop("recon_u"), out.pop("recon_v"))
    np.asarray(_tiny(out["luma_ac"]))
    t0 = time.perf_counter()
    for _ in range(n):
        out = dict(enc._step_mixed(*planes_d, qps_d, idrs_d, *ref))
        ref = (out.pop("recon_y"), out.pop("recon_u"), out.pop("recon_v"))
    np.asarray(_tiny(out["luma_ac"]))
    dt = 1e3 * (time.perf_counter() - t0) / n
    enc._ref = ref
    return dt


svc = MultiSessionH264Service(1, W, H, qp=28)
svc.encode_tick(frames[0][None])   # IDR + compile
svc.encode_tick(frames[1][None])   # P/mixed compile
svc.force_keyframe(0)
svc.encode_tick(frames[2][None])   # mixed-with-IDR compile path
dms = device_tick_ms(svc, frames[4])
print(f"device mixed-tick time: {dms:.1f} ms/tick (pipelined x10)")
print(f"v5e-8 projection: per-chip device step {dms:.1f} ms -> "
      f"{1e3 / dms:.0f} fps/session x 8 sessions (independent chips, "
      f"zero collectives; PCIe-local host absorbs the frame I/O)")

# end-to-end for reference (full BGRx upload + dense fetch per tick)
aus = []
t0 = time.perf_counter()
for i in range(6):
    aus.extend(svc.encode_tick(frames[3 + i][None]))
dt = time.perf_counter() - t0
print(f"end-to-end: {6 / dt:.2f} ticks/s ({1e3 * dt / 6:.0f} ms/tick; "
      f"~8 MB BGRx up + dense coeff down per tick)")

# mixed tick with one forced IDR mid-stream must not stall the cadence
svc.force_keyframe(0)
t0 = time.perf_counter()
svc.encode_tick(frames[5][None])
print(f"mixed IDR tick: {1e3 * (time.perf_counter() - t0):.1f} ms")
svc.close()
