#!/usr/bin/env python3
"""Profile the tpuh264enc frame step: device compute vs host<->device
transfers vs host CAVLC pack (the breakdown VERDICT r1 Weak#1 demands).

Run on the real chip:  python tools/profile_encoder.py
"""

from __future__ import annotations

import sys
import time

import numpy as np

sys.path.insert(0, ".")

H, W = 1080, 1920
ITERS = 10


def timeit(fn, iters=ITERS, warmup=2):
    for _ in range(warmup):
        fn()
    t0 = time.perf_counter()
    for _ in range(iters):
        fn()
    return (time.perf_counter() - t0) / iters * 1e3  # ms


def main():
    import jax

    print("jax devices:", jax.devices())
    from selkies_tpu.models.h264.encoder import TPUH264Encoder
    from selkies_tpu.models.h264.native import pack_slice_p_fast
    from selkies_tpu.models.h264.numpy_ref import PFrameCoeffs

    rng = np.random.default_rng(42)
    base = rng.integers(0, 256, size=(H // 8, W // 8, 4), dtype=np.uint8)
    frames = [
        np.ascontiguousarray(np.kron(np.roll(base, i, axis=1), np.ones((8, 8, 1), dtype=np.uint8)))
        for i in range(4)
    ]

    enc = TPUH264Encoder(W, H, qp=28)
    # warm both paths
    enc.encode_frame(frames[0])
    enc.encode_frame(frames[1])

    # 1. host->device: device_put of one BGRx frame
    f_np = frames[2]
    ms_h2d = timeit(lambda: jax.block_until_ready(jax.device_put(f_np)))
    print(f"h2d device_put 1080p BGRx ({f_np.nbytes/1e6:.1f} MB): {ms_h2d:.1f} ms")

    # 2. device step only (dispatch from numpy + block, NO host fetch)
    ref = enc._ref

    def step_only():
        out = enc._step_p(frames[2], np.int32(28), *[jnp_copy(r) for r in ref])
        jax.block_until_ready(out)
        return out

    import jax.numpy as jnp

    def jnp_copy(x):
        return jnp.copy(x)  # _step_p donates refs; keep originals alive

    ms_step = timeit(step_only)
    print(f"P device step (dispatch+compute, no fetch): {ms_step:.1f} ms")

    # 3. device->host fetch of the coefficient tensors
    out = enc._step_p(frames[3], np.int32(28), *[jnp.copy(r) for r in ref])
    jax.block_until_ready(out)
    fetch_keys = ["mvs", "skip", "luma_ac", "chroma_dc", "chroma_ac"]
    total_bytes = sum(np.prod(out[k].shape) * out[k].dtype.itemsize for k in fetch_keys)

    def fetch():
        return {k: np.asarray(out[k]) for k in fetch_keys}

    ms_fetch = timeit(fetch)
    print(f"d2h coeff fetch ({total_bytes/1e6:.1f} MB): {ms_fetch:.1f} ms")

    # 4. host CAVLC pack
    host = fetch()
    pfc = PFrameCoeffs(
        mvs=host["mvs"], skip=host["skip"], luma_ac=host["luma_ac"],
        chroma_dc=host["chroma_dc"], chroma_ac=host["chroma_ac"], qp=28,
    )
    ms_pack = timeit(lambda: pack_slice_p_fast(pfc, enc.params, frame_num=1))
    print(f"host CAVLC pack: {ms_pack:.1f} ms")

    # 5. end-to-end encode_frame for comparison
    i = [0]

    def e2e():
        enc.encode_frame(frames[i[0] % 4]); i[0] += 1

    ms_e2e = timeit(e2e)
    print(f"end-to-end encode_frame: {ms_e2e:.1f} ms  ({1000/ms_e2e:.2f} fps)")

    # 6. ME sub-step alone
    from selkies_tpu.models.h264.encoder_core import motion_search, MV_PAD
    y = jnp.asarray(rng.integers(0, 256, (1088, 1920), np.uint8).astype(np.int32))
    ry = jnp.pad(jnp.asarray(rng.integers(0, 256, (1088, 1920), np.uint8)), MV_PAD, mode="edge")
    ms_fn = jax.jit(motion_search)
    jax.block_until_ready(ms_fn(y, ry))
    ms_me = timeit(lambda: jax.block_until_ready(ms_fn(y, ry)))
    print(f"motion_search alone (jit): {ms_me:.1f} ms")


if __name__ == "__main__":
    main()
