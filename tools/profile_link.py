#!/usr/bin/env python3
"""Characterize the host<->TPU link: bandwidth vs latency, both directions,
various sizes — decides whether the encoder must minimize bytes/frame
(bandwidth-limited) or round trips (latency-limited)."""

import sys
import time

import numpy as np

sys.path.insert(0, ".")


def main():
    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    print("device:", dev)

    sizes = [1 << 12, 1 << 16, 1 << 20, 1 << 22, 1 << 23]
    for n in sizes:
        a = np.random.default_rng(0).integers(0, 255, n, np.uint8)
        # h2d
        x = jax.device_put(a, dev)
        jax.block_until_ready(x)
        t0 = time.perf_counter()
        reps = 3
        for _ in range(reps):
            x = jax.device_put(a, dev)
            jax.block_until_ready(x)
        dt = (time.perf_counter() - t0) / reps
        # d2h: force fresh copy each time via jnp.add result
        y = jax.block_until_ready(x + jnp.uint8(0))
        t1 = time.perf_counter()
        for _ in range(reps):
            y = jax.block_until_ready(x + jnp.uint8(1))
            _ = np.asarray(y)
        dt2 = (time.perf_counter() - t1) / reps
        print(f"{n/1e6:8.3f} MB  h2d {dt*1e3:8.1f} ms ({n/dt/1e6:7.1f} MB/s)   "
              f"d2h {dt2*1e3:8.1f} ms ({n/dt2/1e6:7.1f} MB/s)")

    # tiny-op round-trip latency
    one = jax.device_put(np.float32(1.0), dev)
    f = jax.jit(lambda v: v + 1)
    jax.block_until_ready(f(one))
    t0 = time.perf_counter()
    for _ in range(20):
        jax.block_until_ready(f(one))
    print(f"tiny jit dispatch+sync round trip: {(time.perf_counter()-t0)/20*1e3:.1f} ms")

    # d2h of tiny result after big compute (what encode_frame needs)
    big = jax.device_put(np.zeros((1088, 1920), np.float32), dev)
    g = jax.jit(lambda v: v.sum())
    jax.block_until_ready(g(big))
    t0 = time.perf_counter()
    for _ in range(10):
        float(g(big))
    print(f"scalar fetch after frame-size compute: {(time.perf_counter()-t0)/10*1e3:.1f} ms")


if __name__ == "__main__":
    main()
