"""Device (TPU) delta/ME front-end shared by the hybrid VP9/AV1 rows.

The hybrid rows (models/vp9/encoder.py, models/av1/encoder.py) keep their
normative entropy back-ends in libvpx/libaom — the probability tables are
spec DATA that cannot be derived computationally — but their FRONT-END
(what the reference gets from XDamage + the encoder's own ME,
gstwebrtc_app.py:544-574, 741-783) is framework work and can run where
the H.264 path proved it out: on device.

One jitted step per capture:

* **per-MB dirty classification** — ``any(frame != prev)`` over each
  16x16 block across all four BGRx channels, bit-exact with the host
  classifier's memcmp semantics (FramePrep.dirty_tiles) but at MB
  granularity rather than tile granularity;
* **coarse global-motion hints** — the H.264 device path's
  ``coarse_vote_candidates_jnp`` (encoder_core.py:406; the coarse stage
  of the Pallas ME pipeline) votes per-MB coarse MVs and returns the
  TOPK dominant candidates. Computed only on frames that changed
  (lax.cond) and surfaced as ``last_hints`` for the monitoring/profile
  layer — inside the H.264 path this same voting stage seeds the full
  Pallas ME; the library rows cannot inject external MVs, so for them
  the hints are an observability surface, not an encode input;
* the previous frame and previous luma stay resident in HBM (donated
  through the step, so steady state uploads one frame and downloads one
  (mbh, mbw) bool map + a (TOPK, 2) hint vector).

Deployment note: the step uploads the full BGRx capture (~8 MB @1080p),
the same upload the tpuh264enc row already pays. The rows use the device
front-end on TPU backends and the host memcmp classifier elsewhere
(``frontend="auto"``). ``SELKIES_HYBRID_FRONTEND``
(``host``/``device``/``auto``) overrides.
"""

from __future__ import annotations

import logging
import os
import sys
import time

import numpy as np

logger = logging.getLogger("models.hybrid_frontend")

__all__ = ["DeviceDeltaFrontend", "HybridFrontendMixin",
           "default_frontend_mode"]


def default_frontend_mode() -> str:
    """'device' on TPU backends, 'host' on CPU-only rigs."""
    env = os.environ.get("SELKIES_HYBRID_FRONTEND")
    if env in ("host", "device"):
        return env
    # only consult jax if this process already initialized it (the
    # tpuh264enc path does): a VP9/AV1-only deployment must not pay jax
    # backend init just to be told 'host'
    jax = sys.modules.get("jax")
    if jax is None:
        return "host"
    try:
        return "device" if jax.default_backend() == "tpu" else "host"
    except Exception:
        return "host"


class DeviceDeltaFrontend:
    """Jitted dirty-MB + global-motion-hint step with HBM-resident state."""

    def __init__(self, width: int, height: int):
        import jax
        import jax.numpy as jnp

        # import OUTSIDE the traced function: importing these during jit
        # tracing would turn their module-level jnp constants into leaked
        # tracers poisoning every later user of encoder_core
        from selkies_tpu.models.h264 import numpy_ref
        from selkies_tpu.models.h264.encoder_core import (
            coarse_vote_candidates_jnp,
        )
        from selkies_tpu.ops.colorspace import bgrx_to_i420

        self.width, self.height = width, height
        self.pad_w = (width + 15) // 16 * 16
        self.pad_h = (height + 15) // 16 * 16
        self.mbh, self.mbw = self.pad_h // 16, self.pad_w // 16
        self._prev = None        # (pad_h, pad_w, 4) u8 on device
        self._prev_luma = None   # (pad_h, pad_w) u8 on device
        self.last_device_ms = 0.0

        pad_h, pad_w = self.pad_h, self.pad_w
        mbh, mbw = self.mbh, self.mbw

        def step(frame, prev, prev_luma):
            f = jnp.zeros((pad_h, pad_w, 4), jnp.uint8)
            f = f.at[: frame.shape[0], : frame.shape[1]].set(frame)
            diff = (f != prev).reshape(mbh, 16, mbw, 16, 4)
            dirty = diff.any(axis=(1, 3, 4))
            y = bgrx_to_i420(f)[0]

            # coarse ME of current vs previous luma: TOPK dominant
            # candidate MVs in 4-px units (scroll/pan hints). Gated on
            # the frame actually changing — a static desktop must not
            # pay the SAD vote every tick.
            def vote(_):
                return coarse_vote_candidates_jnp(
                    y.astype(jnp.int32), prev_luma.astype(jnp.int32))

            hints = jax.lax.cond(
                dirty.any(), vote,
                lambda _: jnp.zeros((numpy_ref.TOPK, 2), jnp.int32), None)
            return dirty, hints, f, y

        self._step = jax.jit(step, donate_argnums=(1, 2))
        self._jnp = jnp
        self._jax = jax
        self._bgrx_to_i420 = bgrx_to_i420

    def reset(self) -> None:
        """Forget the reference (forced keyframe / stream restart)."""
        self._prev = None
        self._prev_luma = None

    def step(self, frame: np.ndarray):
        """BGRx capture -> (dirty (mbh,mbw) bool | None, hints (K,2) int
        in pixel units | None). None on the first frame (no reference).
        Hint MV convention matches the H.264 path: ``cur[p] ≈
        prev[p + mv]`` — content scrolling +d appears as (-d)."""
        jnp = self._jnp
        t0 = time.perf_counter()
        if self._prev is None:
            pad = jnp.zeros((self.pad_h, self.pad_w, 4), jnp.uint8)
            pad = pad.at[: frame.shape[0], : frame.shape[1]].set(
                jnp.asarray(frame))
            self._prev = self._jax.device_put(pad)
            self._prev_luma = self._bgrx_to_i420(self._prev)[0]
            self._prev.block_until_ready()
            self.last_device_ms = (time.perf_counter() - t0) * 1e3
            return None, None
        dirty, hints, self._prev, self._prev_luma = self._step(
            jnp.asarray(frame), self._prev, self._prev_luma)
        dirty_np = np.asarray(dirty)
        hints_np = np.asarray(hints) * 4  # downsampled -> pixel units
        self.last_device_ms = (time.perf_counter() - t0) * 1e3
        return dirty_np, hints_np


class HybridFrontendMixin:
    """Classification front-end shared by TPUVP9Encoder / TPUAV1Encoder.

    ``_init_frontend`` picks device or host per deployment;
    ``_classify_mbs`` returns the per-MB activity map for the capture
    ((mb_rows, mb_cols) bool, True = changed) or None when no reference
    exists yet — the row's show-existing / active-map policy consumes it
    identically either way."""

    def _make_device_frontend(self, width: int, height: int):
        """Hook: which device front-end serves this row.  The codec-mesh
        rows (parallel/codec_mesh.py) override this to shard the step
        one tile column per chip; everything else in the mixin —
        host-path fallback, classification contract — is shared."""
        return DeviceDeltaFrontend(width, height)

    def _init_frontend(self, width: int, height: int,
                       mode: str | None = None) -> None:
        from selkies_tpu.models import frameprep

        if mode in (None, "auto"):
            mode = default_frontend_mode()
        self.frontend_mode = mode
        self.last_hints: np.ndarray | None = None
        self.frontend_device_ms = 0.0
        if self.frontend_mode == "device":
            self._device_fe = self._make_device_frontend(width, height)
            self._prep = None
        else:
            pad_w = (width + 15) // 16 * 16
            pad_h = (height + 15) // 16 * 16
            self._device_fe = None
            self._prep = frameprep.FramePrep(width, height, pad_w, pad_h,
                                             nslots=2)
            self._tile_w = next(
                (t for t in (128, 64, 32, 16) if pad_w % t == 0), pad_w)

    def _mb_active_from_tiles(self, tiles: np.ndarray) -> np.ndarray:
        """(nbands, ntiles) dirty tiles -> (mb_rows, mb_cols) activity.
        Bands are 16 rows == one MB row; tiles are _tile_w luma cols, so
        MB col c maps to tile (c*16)//tile_w."""
        mb_rows = (self.height + 15) // 16
        mb_cols = (self.width + 15) // 16
        cols = (np.arange(mb_cols) * 16) // self._tile_w
        return tiles[:mb_rows][:, cols]

    def _classify_mbs(self, frame: np.ndarray) -> np.ndarray | None:
        """The host path rides the fused band-parallel front-end scan
        (ISSUE 12): one pass computes the dirty map and updates the
        previous-frame state, sharded across SELKIES_FRONTEND_WORKERS.
        (Damage-rect hints stop at the H.264 rows for now — the library
        rows' encode_frame surface has no hint plumbing, so threading a
        parameter this deep would be dead code until it does.)"""
        if self._device_fe is not None:
            dirty, hints = self._device_fe.step(frame)
            self.frontend_device_ms = self._device_fe.last_device_ms
            if dirty is None:
                return None
            self.last_hints = hints
            return dirty
        tiles = self._prep.dirty_tiles(frame, self._tile_w)
        if tiles is None:
            return None
        return self._mb_active_from_tiles(tiles).astype(bool)
