"""tpuvp9enc — the VP9 encoder row with the framework's capture-delta
front-end (reference row: vavp9enc / vp9enc, gstwebrtc_app.py:544-574,
685-722).

Architecture note (why this row is a hybrid, not a from-scratch TPU
bitstream like tpuh264enc): VP9 entropy coding is an adaptive arithmetic
coder whose default probability tables are normative DATA from the spec
— they cannot be derived computationally the way H.264's CAVLC tables
can (tables.py regenerates those from closed-form rules). This
deployment image has no VP9 spec/source to take them from, so the
entropy back-end is libvpx (exactly what the reference's vp9enc element
wraps). What the framework adds on top is the same front-end the TPU
H.264 path proved out:

* per-MB change classification against the previous capture — ON DEVICE
  (models/hybrid_frontend.py: a jitted dirty-MB step plus the H.264
  path's coarse_vote_candidates_jnp ME voting for scroll hints) on
  TPU backends, or FramePrep's native memcmp (the XDamage analogue)
  elsewhere;
* UNCHANGED frames never reach libvpx at all: they encode as a ONE-BYTE
  VP9 `show_existing_frame` header (uncompressed header only, no
  compressed data, so no bool coder involved) re-showing the last
  reference slot. The dominant idle-desktop case costs zero encode CPU
  and one byte of bitstream, mirroring the H.264 path's all-skip slice;
* PARTIALLY-changed frames hand libvpx a per-MB ACTIVE MAP from the
  classification (VP8E_SET_ACTIVEMAP): unchanged macroblocks are forced
  to skip-from-reference, so libvpx's motion search / RD / transform run
  only over the pixels that moved — front-end analysis decides per-MB
  work, the bool coder stays libvpx's.
  Measured (PERF.md): ~4.4x less encode CPU on an idle desktop (static
  frames ~free); only ~1.05x on a busy trace, where libvpx's per-frame
  fixed costs (loopfilter, frame setup) dominate.

Conformance: tests/test_vp9_hybrid.py decodes the mixed stream with
FFmpeg and asserts the re-shown frames are pixel-identical and active-
map frames reproduce the full-encode content where dirty.
"""

from __future__ import annotations

import logging
import time

import numpy as np

from selkies_tpu.models.hybrid_frontend import HybridFrontendMixin
from selkies_tpu.models.libvpx_enc import LibVpxEncoder
from selkies_tpu.models.stats import FrameStats

logger = logging.getLogger("models.vp9")

# VP9 uncompressed header, show_existing_frame form (spec 6.2):
#   frame_marker(2)=0b10, profile_low(1)=0, profile_high(1)=0,
#   show_existing_frame(1)=1, frame_to_show_map_idx(3)
# libvpx's realtime config keeps LAST in reference slot 0, so re-showing
# slot 0 repeats the previously decoded frame.
def show_existing_frame(map_idx: int = 0) -> bytes:
    if not 0 <= map_idx <= 7:
        raise ValueError(f"frame_to_show_map_idx {map_idx} out of range")
    return bytes([0b10001000 | map_idx])


class TPUVP9Encoder(HybridFrontendMixin, LibVpxEncoder):
    """LibVpxEncoder plus the capture-delta front-end (device or host —
    models/hybrid_frontend.py)."""

    codec = "vp9"

    def __init__(self, width: int, height: int, fps: int = 60,
                 bitrate_kbps: int = 2000, frontend: str | None = None,
                 tile_columns_log2: int | None = None,
                 threads: int | None = None):
        # tile_columns_log2/threads: the codec-mesh row pins libvpx's
        # tile split to the front-end's column carve (parallel/codec_mesh)
        super().__init__(width=width, height=height, fps=fps,
                         bitrate_kbps=bitrate_kbps, vp8=False,
                         tile_columns_log2=tile_columns_log2,
                         threads=threads)
        self._init_frontend(width, height, frontend)
        self._have_ref = False
        self._map_active = False  # whether a restrictive map is installed
        self.static_frames = 0
        self.active_map_frames = 0

    def force_keyframe(self) -> None:
        super().force_keyframe()
        # the next capture must re-encode even if unchanged
        self._have_ref = False

    def encode_frame(self, frame: np.ndarray, qp: int | None = None) -> bytes:
        dirty = self._classify_mbs(np.asarray(frame))
        unchanged = dirty is not None and not dirty.any()
        if unchanged and self._have_ref and not self._force_idr:
            t0 = time.perf_counter()
            au = show_existing_frame(0)
            self.static_frames += 1
            self.last_stats = FrameStats(
                frame_index=self.frame_index, idr=False, qp=self.qp,
                bytes=len(au),
                device_ms=self.frontend_device_ms or
                (time.perf_counter() - t0) * 1e3,
                pack_ms=0.0,
                skipped_mbs=(self.height // 16) * (self.width // 16),
            )
            self.frame_index += 1
            return au
        partial = (
            dirty is not None and self._have_ref and not self._force_idr
            and dirty.any() and not dirty.all()
        )
        if partial:
            # front-end decides per-MB work: unchanged MBs become
            # skip-from-reference inside libvpx (no ME/RD/transform)
            if self.set_active_map(dirty):
                self._map_active = True
                self.active_map_frames += 1
        try:
            au = super().encode_frame(frame, qp)
        finally:
            if self._map_active:
                # never leave a stale mask installed across keyframes or
                # error paths: correctness beats the tiny per-frame call
                self.set_active_map(None)
                self._map_active = False
        if self.last_stats is not None and self.frontend_device_ms:
            self.last_stats.device_ms += self.frontend_device_ms
        self._have_ref = True
        return au
