"""tpuav1enc — the AV1 encoder row with the framework's capture-delta
front-end (reference rows: av1enc/rav1enc/svtav1enc,
gstwebrtc_app.py:741-783; rtpav1pay :917-938).

Architecture note (why this row is a hybrid, mirroring tpuvp9enc): AV1
entropy coding is an adaptive multi-symbol arithmetic coder whose
default CDF tables are normative DATA from the spec — not derivable
computationally the way H.264's CAVLC tables are (tables.py regenerates
those from closed-form rules). The entropy back-end is therefore libaom
(exactly what the reference's av1enc element wraps; models/libaom_enc.py
is the ctypes row). What the framework adds is the same front-end the
TPU H.264 path proved out:

* per-MB change classification against the previous capture — ON DEVICE
  (models/hybrid_frontend.py: jitted dirty-MB step + the H.264 path's
  coarse ME voting for scroll hints) on TPU backends, or FramePrep's
  native memcmp (the XDamage analogue) elsewhere;
* UNCHANGED frames never reach libaom at all: they encode as a 5-byte
  show_existing_frame temporal unit (spec 5.9.2) re-showing the slot
  the previous frame landed in. Which slot that is comes from parsing
  refresh_frame_flags out of our own bitstream (models/av1/headers.py)
  — not from assuming libaom's slot rotation. Shown inter frames are
  always re-showable (spec derives showable_frame = frame_type !=
  KEY_FRAME); after a keyframe the first repeat falls back to an
  all-inactive ACTIVE MAP encode (every block skips from reference),
  which is cheap and immediately becomes re-showable. The re-show path
  is also bit-exact: unlike an all-skip encode, no loop filter / CDEF
  pass re-runs over the image, so idle desktops cannot blur over time;
* PARTIALLY-changed frames install a per-16x16-block active map from
  the dirty-tile classification (AOME_SET_ACTIVEMAP): libaom's ME/RD/
  transform run only over pixels that moved — the front-end decides
  per-block work, the entropy coder stays libaom's.

Conformance: tests/test_av1.py decodes the mixed stream with ctypes
libdav1d (an independent decoder — models/av1/dav1d.py) and asserts
re-shown frames are pixel-identical and active-map frames track the
source.
"""

from __future__ import annotations

import logging
import time

import numpy as np

from selkies_tpu.models.av1 import headers
from selkies_tpu.models.hybrid_frontend import HybridFrontendMixin
from selkies_tpu.models.libaom_enc import LibAomEncoder
from selkies_tpu.models.stats import FrameStats

logger = logging.getLogger("models.av1")


class TPUAV1Encoder(HybridFrontendMixin, LibAomEncoder):
    """LibAomEncoder plus the capture-delta front-end (device or host —
    models/hybrid_frontend.py)."""

    codec = "av1"

    def __init__(self, width: int, height: int, fps: int = 60,
                 bitrate_kbps: int = 2000, cpu_used: int = 10,
                 frontend: str | None = None):
        super().__init__(width=width, height=height, fps=fps,
                         bitrate_kbps=bitrate_kbps, cpu_used=cpu_used)
        self._init_frontend(width, height, frontend)
        self._have_ref = False
        self._map_active = False
        self._seq: headers.SequenceHeader | None = None
        self._show_slot: int | None = None  # re-showable slot, or None
        self.static_frames = 0
        self.active_map_frames = 0

    def force_keyframe(self) -> None:
        super().force_keyframe()
        # the next capture must re-encode even if unchanged
        self._have_ref = False
        self._show_slot = None

    def _track_output(self, au: bytes) -> None:
        """Parse our own bitstream: which slot can re-show this frame?"""
        try:
            self._seq, fh = headers.scan_temporal_unit(au, self._seq)
        except (ValueError, IndexError) as exc:
            # IndexError: truncated OBU drives the bit reader past the
            # end — same degrade as a malformed header
            logger.warning("AV1 header parse failed (%s); re-show disabled", exc)
            self._show_slot = None
            return
        if (fh is not None and fh.show_frame and fh.showable_frame
                and fh.refresh_frame_flags):
            self._show_slot = (fh.refresh_frame_flags
                               & -fh.refresh_frame_flags).bit_length() - 1
        else:
            self._show_slot = None

    def encode_frame(self, frame: np.ndarray, qp: int | None = None) -> bytes:
        dirty = self._classify_mbs(np.asarray(frame))
        unchanged = dirty is not None and not dirty.any()
        if (unchanged and self._have_ref and not self._force_idr
                and self._show_slot is not None):
            t0 = time.perf_counter()
            au = headers.show_existing_frame_tu(self._show_slot)
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
        restrict: np.ndarray | None = None
        if unchanged and self._have_ref and not self._force_idr:
            # post-keyframe repeat: keyframes can't be re-shown (spec
            # 5.9.2), so encode one all-skip inter frame — cheap, and
            # every later repeat rides the 5-byte path above
            restrict = np.zeros(((self.height + 15) // 16,
                                 (self.width + 15) // 16), np.uint8)
            self.static_frames += 1
        elif (dirty is not None and self._have_ref and not self._force_idr
              and dirty.any() and not dirty.all()):
            restrict = dirty
            self.active_map_frames += 1
        if restrict is not None and self.set_active_map(restrict):
            self._map_active = True
        try:
            au = super().encode_frame(frame, qp)
        finally:
            if self._map_active:
                # never leave a stale mask installed across keyframes or
                # error paths: correctness beats the tiny per-frame call
                self.set_active_map(None)
                self._map_active = False
        self._track_output(au)
        if self.last_stats is not None and self.frontend_device_ms:
            self.last_stats.device_ms += self.frontend_device_ms
        self._have_ref = True
        return au
