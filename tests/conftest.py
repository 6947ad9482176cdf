"""Test harness configuration.

Tests run on the CPU backend with a virtual 8-device mesh, so multi-chip
sharding paths are exercised without a TPU; the chip itself is driven by
chip_smoke.py, one JAX process per chip.
"""

import os
import tempfile


def pytest_configure(config):
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    # identical encoder/service programs are rebuilt dozens of times
    # across the suite (and by the resilience RESTART rung under test);
    # the persistent cache keeps the whole run inside the tier-1 time
    # budget. A fixed path outside the checkout keeps CPU entries out of
    # the tree the chip tool copies.
    os.environ.setdefault(
        "JAX_COMPILATION_CACHE_DIR",
        os.path.join(tempfile.gettempdir(), "selkies-tpu-test-jax-cache"))
    from selkies_tpu.utils.jaxcache import enable_persistent_compilation_cache

    enable_persistent_compilation_cache()


# -- shared codec-test fixtures ---------------------------------------------

def codec_trace(n=8, w=320, h=192, static=(), seed=5):
    """Desktop-like BGRX trace shared by the codec row tests: a kron block
    wallpaper with a randomized 16x160 'typing' region; frames listed in
    `static` repeat their predecessor exactly."""
    import numpy as np

    rng = np.random.default_rng(seed)
    cur = np.kron(rng.integers(40, 200, (h // 16, w // 16, 4), np.uint8),
                  np.ones((16, 16, 1), np.uint8))
    frames = []
    for i in range(n):
        if i not in static:
            cur = cur.copy()
            cur[40:56, 40:200, :3] = rng.integers(0, 255, (16, 160, 1), np.uint8)
        frames.append(cur)
    return frames


def bgrx_luma(frame_bgrx):
    """Luma plane of a BGRX frame via the software encoders' exact
    conversion (float, for PSNR math)."""
    from selkies_tpu.models.libvpx_enc import _bgrx_to_i420_np

    return _bgrx_to_i420_np(frame_bgrx)[0].astype(float)
