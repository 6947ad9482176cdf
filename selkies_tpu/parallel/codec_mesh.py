"""Codec-generic tile-column mesh encode: `tpuav1enc` / `tpuvp9enc` on
the chip carve that parallel/bands.py proved out for H.264.

AV1/VP9 tile columns are the codec-native analogue of the H.264 band
mesh (ROADMAP item 2): a frame splits into vertical columns whose
entropy coding is independent per column, so per-column work can run on
per-column chips.  For the hybrid rows the work splits in two:

* **device half** — the capture-delta front-end
  (models/hybrid_frontend.py: per-MB dirty classification + coarse-ME
  vote hints) shards one column per chip over a ``col`` mesh axis
  (MeshDeltaFrontend below).  Each chip compares only its column of the
  capture against its HBM-resident previous column; the per-column vote
  histograms are psum-merged over ``col`` before candidate selection,
  mirroring the 2D tile grid's slice-row merge (bands.py).
* **entropy half** — normative AV1/VP9 arithmetic coding stays in
  libaom/libvpx (see models/vp9/encoder.py for why), but the mesh's
  column carve drives it:

  - **AV1** (TileColumnAV1Encoder): one pinned lossless-intra
    AomStripEncoder per tile column, fanned across the pack pool; the
    per-column payloads are spliced into ONE spec-conformant frame by
    models/av1/stitch.py (tile-group OBU with N tile columns).  Columns
    the front-end classifies clean re-splice their CACHED payload —
    zero encode work, the tile-column analogue of the active-map path.
    Unchanged frames ship a 5-byte show_existing_frame TU.  The
    construction is pixel-exact by design (lossless ⇒ decode == source
    == single-encoder oracle), which tests verify through independent
    libdav1d.
  - **VP9** (TileColumnVP9Encoder): VP9's forward probability updates
    live in a bool-coded compressed frame header, so per-column
    bitstreams cannot be byte-spliced the way AV1 OBUs can.  The mesh
    still owns the front-end (column-sharded classification feeds the
    frame's active map) and the carve pins libvpx's own tile-column
    split + thread count to the mesh shape, so the encode is
    tile-parallel end-to-end with ONE bitstream-producing instance.
    The byte contract is front-end equivalence: the mesh-sharded
    classification must produce the same MB-granular active maps — and
    therefore byte-identical libvpx output — as the solo device
    front-end (the host FramePrep classifier is tile-granular and not
    byte-comparable).

``SELKIES_TILE_COLS`` picks the column count for both rows (registry
routes >1 here); the AV1 carve itself is 64px-superblock aligned via
stitch.tile_columns, so the requested count is rounded to the carve the
AV1 uniform-tile-spacing rules actually produce.
"""

from __future__ import annotations

import logging
import os
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from selkies_tpu.models.stats import FrameStats

logger = logging.getLogger("parallel.codec_mesh")

__all__ = [
    "MeshDeltaFrontend",
    "TileColumnAV1Encoder",
    "TileColumnVP9Encoder",
    "cols_from_env",
    "cols_log2_for",
]


def cols_from_env() -> int:
    """SELKIES_TILE_COLS: tile columns for the AV1/VP9 mesh rows (1 =
    single-column, the solo hybrid path)."""
    env = os.environ.get("SELKIES_TILE_COLS")
    if not env:
        return 1
    try:
        return max(1, min(64, int(env)))
    except ValueError:
        logger.warning("SELKIES_TILE_COLS=%r is not an integer; using 1", env)
        return 1


def cols_log2_for(cols: int) -> int:
    """Smallest log2 whose uniform tile spacing yields >= `cols` columns
    on a wide-enough frame (AV1 tile_info codes the count as a log2)."""
    k = 0
    while (1 << k) < cols:
        k += 1
    return k


def floor_cols_log2(cols: int) -> int:
    """Largest log2 with 2**k <= `cols` — the round-DOWN both mesh rows
    use so a non-power-of-two chip budget never carves more tile columns
    than the mesh has chips to shard."""
    k = 0
    while (2 << k) <= cols:
        k += 1
    return k


def budget_cols(chips: int) -> int:
    """A session's tile-column budget: the chips the placer granted it,
    clamped by SELKIES_TILE_COLS when the operator pins one.  Shared by
    negotiate.resolve and the fleet's per-session encoder builds so the
    documented clamp holds on both paths."""
    if os.environ.get("SELKIES_TILE_COLS"):
        return max(1, min(cols_from_env(), max(chips, 1)))
    return max(chips, 1)


# ---------------------------------------------------------------------------
# column-sharded device front-end


class MeshDeltaFrontend:
    """models/hybrid_frontend.DeviceDeltaFrontend sharded one tile column
    per chip over a ``col`` mesh axis.

    Same interface (step/reset/last_device_ms) so HybridFrontendMixin
    consumers can swap it in for the solo front-end.  The dirty map is
    bit-exact with the solo/host classifiers — column shards are
    16px-aligned so no MB straddles a shard seam, and the zero padding
    both frames share can never classify dirty.  The coarse-ME vote
    histograms are psum-merged over ``col`` before candidate selection
    (encoder_core.coarse_votes_jnp's slice-row contract); unlike the
    solo front-end the vote runs unconditionally — a lax.cond whose
    taken branch psums would need matching collectives in the untaken
    branch on every chip — at the cost of one downsampled-SAD pass per
    static tick.  Per-column SAD edge-pads at shard seams (halo_dcols=0:
    hints are an observability surface for the library rows, not an
    encode input — see hybrid_frontend.py)."""

    def __init__(self, width: int, height: int, cols: int, devices=None):
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        from selkies_tpu.models.h264.encoder_core import (
            _downsample4,
            coarse_votes_jnp,
            select_coarse_jnp,
        )
        from selkies_tpu.ops.colorspace import bgrx_to_i420

        if devices is None:
            # single source of chip enumeration (resilience/devhealth):
            # a rebuilt av1/vp9 tile-column mesh must land on the
            # surviving chips after a quarantine, like the h264 mesh
            from selkies_tpu.resilience.devhealth import get_device_pool

            devices = get_device_pool().healthy_devices()
        devs = np.array(devices)
        if len(devs) < cols:
            raise ValueError(
                f"need {cols} devices for the column mesh, have {len(devs)}")
        # the chips this front-end dispatches to
        self.devices = list(devs[:cols])
        self.width, self.height, self.cols = width, height, cols
        self.pad_h = (height + 15) // 16 * 16
        # every shard an equal multiple of 16 so MBs never straddle seams
        col_w = ((width + cols * 16 - 1) // (cols * 16)) * 16
        self.pad_w = col_w * cols
        self.mbh, self.mbw = self.pad_h // 16, (width + 15) // 16
        self._mesh = Mesh(devs[:cols], axis_names=("col",))
        self._frame_sharding = NamedSharding(self._mesh, P(None, "col", None))
        self._luma_sharding = NamedSharding(self._mesh, P(None, "col"))
        self._prev = None
        self._prev_luma = None
        self.last_device_ms = 0.0

        pad_h, pad_w = self.pad_h, self.pad_w
        mbh = self.mbh

        def col_body(f, prev, prev_luma):
            w = f.shape[1]
            diff = (f != prev).reshape(mbh, 16, w // 16, 16, 4)
            dirty = diff.any(axis=(1, 3, 4))
            y = bgrx_to_i420(f)[0]
            votes = coarse_votes_jnp(
                y.astype(jnp.int32),
                _downsample4(prev_luma.astype(jnp.int32)))
            votes = jax.lax.psum(votes, "col")
            hints = select_coarse_jnp(votes)
            return dirty, hints, f, y

        def step(frame, prev, prev_luma):
            f = jnp.zeros((pad_h, pad_w, 4), jnp.uint8)
            f = f.at[: frame.shape[0], : frame.shape[1]].set(frame)
            f = jax.lax.with_sharding_constraint(f, self._frame_sharding)
            return jax.shard_map(
                col_body,
                mesh=self._mesh,
                in_specs=(P(None, "col", None), P(None, "col", None),
                          P(None, "col")),
                out_specs=(P(None, "col"), P(), P(None, "col", None),
                           P(None, "col")),
                check_vma=False,
            )(f, prev, prev_luma)

        self._step = jax.jit(step, donate_argnums=(1, 2))
        self._jax = jax
        self._jnp = jnp
        self._bgrx_to_i420 = bgrx_to_i420

        def init(frame):
            pad = jnp.zeros((pad_h, pad_w, 4), jnp.uint8)
            pad = pad.at[: frame.shape[0], : frame.shape[1]].set(frame)
            pad = jax.lax.with_sharding_constraint(pad, self._frame_sharding)
            luma = jax.lax.with_sharding_constraint(
                bgrx_to_i420(pad)[0], self._luma_sharding)
            return pad, luma

        self._init = jax.jit(init)

    def reset(self) -> None:
        """Forget the reference (forced keyframe / stream restart)."""
        self._prev = None
        self._prev_luma = None

    def step(self, frame: np.ndarray):
        """BGRx capture -> (dirty (mbh,mbw) bool | None, hints (K,2) int
        in pixel units | None); None on the first frame.  Same contract
        as DeviceDeltaFrontend.step."""
        t0 = time.perf_counter()
        if self._prev is None:
            self._prev, self._prev_luma = self._init(
                self._jnp.asarray(frame))
            self._prev.block_until_ready()
            self.last_device_ms = (time.perf_counter() - t0) * 1e3
            return None, None
        dirty, hints, self._prev, self._prev_luma = self._step(
            self._jnp.asarray(frame), self._prev, self._prev_luma)
        dirty_np = np.asarray(dirty)[: (self.height + 15) // 16, : self.mbw]
        hints_np = np.asarray(hints) * 4  # downsampled -> pixel units
        self.last_device_ms = (time.perf_counter() - t0) * 1e3
        return dirty_np, hints_np


# ---------------------------------------------------------------------------
# AV1: per-column strip encoders + bitstream splice


from selkies_tpu.models.hybrid_frontend import HybridFrontendMixin


class TileColumnAV1Encoder(HybridFrontendMixin):
    """tpuav1enc's tile-column mesh mode (see module docstring).

    Interface-compatible with the other encoder rows
    (pipeline/elements.py: encode_frame(frame, qp), last_stats,
    force_keyframe, set_bitrate/set_qp, close).  Rate knobs are accepted
    for parity but ignored — the stitched mode is pinned lossless (the
    pixel-exactness contract); the registry documents the trade.
    Classification rides HybridFrontendMixin with the device front-end
    hook overridden to the column-sharded mesh step."""

    codec = "av1"

    def __init__(self, width: int, height: int, fps: int = 60,
                 cols: int = 2, frontend: str | None = None,
                 cpu_used: int = 6, devices=None,
                 keyframe_interval: int = 0, **_ignored):
        from selkies_tpu.models.av1 import stitch
        from selkies_tpu.models.libaom_enc import AomStripEncoder

        if width % 2 or height % 2:
            raise ValueError("4:2:0 requires even dimensions")
        self._stitch = stitch
        self.width, self.height, self.fps = width, height, fps
        # `cols` is a BUDGET (the session's chip row), not a demand: the
        # uniform-tile-spacing carve only yields power-of-two-ish column
        # counts, so round the log2 DOWN until the carve fits the budget
        # — a 3-chip row meshes 2 columns rather than failing to build a
        # 4-column mesh over 3 chips and degrading the session to h264
        k = cols_log2_for(cols)
        while k > 0 and len(stitch.tile_columns(width, k)) > cols:
            k -= 1
        self.cols_log2 = k
        self.carve = stitch.tile_columns(width, self.cols_log2)
        self.cols = len(self.carve)
        if self.cols != cols:
            logger.info(
                "AV1 uniform tile spacing carves %dpx into %d columns "
                "(budget %d)", width, self.cols, cols)
        self.keyframe_interval = keyframe_interval
        self._strips = [AomStripEncoder(w, height, cpu_used=cpu_used)
                        for (_x0, w) in self.carve]
        self._template = AomStripEncoder(width, height, cpu_used=cpu_used)
        self._pool = ThreadPoolExecutor(
            max_workers=max(1, min(self.cols, os.cpu_count() or 1)),
            thread_name_prefix="av1-strip")
        self._devices = devices
        self._init_frontend(width, height, frontend)
        # per-column splice state
        self._payloads: list[bytes | None] = [None] * self.cols
        self._fields = [None] * self.cols
        self._seq = None            # SequenceInfo of the stitched stream
        self._seq_payload = None    # full-dims sequence header OBU payload
        self._strip_seq = [None] * self.cols
        self._strip_seq_payload = [None] * self.cols
        self._have_ref = False
        self._show_ok = False       # slot 0 holds a re-showable frame
        self._force_idr = True
        self.frame_index = 0
        self.qp = 0
        self.last_stats: FrameStats | None = None
        self.static_frames = 0
        self.cached_columns = 0     # clean columns spliced without encode
        self.stitch_fallbacks = 0   # frames that left the splice envelope

    def _make_device_frontend(self, width: int, height: int):
        # HybridFrontendMixin hook: the column-sharded mesh step in
        # place of the solo full-frame one
        return MeshDeltaFrontend(width, height, self.cols,
                                 devices=self._devices)

    def close(self) -> None:
        pool = getattr(self, "_pool", None)
        if pool is not None:
            pool.shutdown(wait=True)
            self._pool = None
        for enc in getattr(self, "_strips", []):
            enc.close()
        self._strips = []
        tpl = getattr(self, "_template", None)
        if tpl is not None:
            tpl.close()
            self._template = None

    def force_keyframe(self) -> None:
        self._force_idr = True

    def set_qp(self, qp: int) -> None:
        """Interface parity; the splice is pinned lossless."""

    def set_bitrate(self, bitrate_kbps: int) -> None:
        """Interface parity; the splice is pinned lossless (rate follows
        content — static columns cost 0, clean frames 3 bytes)."""

    # -- encoding ------------------------------------------------------

    def _dirty_columns(self, dirty: np.ndarray | None) -> list[bool]:
        if dirty is None:
            return [True] * self.cols
        return [bool(dirty[:, x0 // 16: (x0 + w + 15) // 16].any())
                for (x0, w) in self.carve]

    def _encode_column(self, k: int, y, u, v) -> None:
        x0, w = self.carve[k]
        tu = self._strips[k].encode_planes(
            np.ascontiguousarray(y[:, x0:x0 + w]),
            np.ascontiguousarray(u[:, x0 // 2:(x0 + w) // 2]),
            np.ascontiguousarray(v[:, x0 // 2:(x0 + w) // 2]))
        s = self._stitch.extract_strip(tu, self._strip_seq[k],
                                       self._strip_seq_payload[k])
        self._strip_seq[k] = s.seq
        self._strip_seq_payload[k] = s.seq_payload
        self._payloads[k] = s.tile_payload
        self._fields[k] = s.frame

    def _ensure_template(self, y, u, v) -> None:
        """First frame: one full-width strip encode supplies the
        sequence header with full-frame max dims (strip sequence headers
        carry strip dims) and arms the fallback encoder."""
        if self._seq_payload is not None:
            return
        tu = self._template.encode_planes(y, u, v)
        s = self._stitch.extract_strip(tu)
        self._seq_payload, self._seq = s.seq_payload, s.seq

    def _fallback_au(self, y, u, v) -> bytes:
        """Splice left the envelope: ship one full-frame strip TU (its
        own KEY frame — still lossless, still conformant)."""
        self.stitch_fallbacks += 1
        self._show_ok = False
        self._payloads = [None] * self.cols  # cache keyed to splice state
        return self._template.encode_planes(y, u, v)

    def encode_frame(self, frame: np.ndarray, qp: int | None = None) -> bytes:
        from selkies_tpu.models.libvpx_enc import _bgrx_to_i420_np

        t0 = time.perf_counter()
        frame = np.asarray(frame)
        dirty = self._classify_mbs(frame)
        mb_total = ((self.height + 15) // 16) * ((self.width + 15) // 16)
        unchanged = dirty is not None and not dirty.any()
        if (unchanged and self._have_ref and not self._force_idr
                and self._show_ok):
            from selkies_tpu.models.av1 import headers

            # show_existing_frame_tu carries its own temporal delimiter
            au = headers.show_existing_frame_tu(0)
            self.static_frames += 1
            self.last_stats = FrameStats(
                frame_index=self.frame_index, idr=False, qp=0,
                bytes=len(au),
                device_ms=self.frontend_device_ms or
                (time.perf_counter() - t0) * 1e3,
                pack_ms=0.0, skipped_mbs=mb_total, cols=self.cols)
            self.frame_index += 1
            return au
        t1 = time.perf_counter()
        y, u, v = _bgrx_to_i420_np(frame)
        keyframe = self._force_idr or not self._have_ref or (
            self.keyframe_interval
            and self.frame_index % max(self.keyframe_interval, 1) == 0)
        dirty_cols = self._dirty_columns(None if keyframe else dirty)
        todo = [k for k in range(self.cols)
                if dirty_cols[k] or self._payloads[k] is None]
        t2 = time.perf_counter()
        try:
            self._ensure_template(y, u, v)
            if len(todo) > 1:
                list(self._pool.map(
                    lambda k: self._encode_column(k, y, u, v), todo))
            else:
                for k in todo:
                    self._encode_column(k, y, u, v)
            t3 = time.perf_counter()
            template = self._fields[0]
            for k in range(1, self.cols):
                if not template.splice_compatible(self._fields[k]):
                    raise self._stitch.StitchError(
                        f"column {k} frame fields diverged")
            for k in range(self.cols):
                if not self._seq.tile_compatible(self._strip_seq[k]):
                    raise self._stitch.StitchError(
                        f"column {k} sequence header diverged")
            from selkies_tpu.models.av1 import headers

            if keyframe:
                au = self._stitch.build_stitched_tu(
                    self._seq_payload, self._seq, template,
                    headers.KEY_FRAME, 0xFF, self.width, self.height,
                    self.cols_log2, list(self._payloads))
                self._show_ok = False
            else:
                au = self._stitch.build_stitched_tu(
                    None, self._seq, template, headers.INTRA_ONLY_FRAME,
                    0x01, self.width, self.height, self.cols_log2,
                    list(self._payloads))
                self._show_ok = True
        except (ValueError, IndexError) as exc:
            # StitchError plus the bit-reader's overrun errors: anything
            # outside the constrained envelope ships the full-frame TU
            logger.warning("AV1 splice fell back to full-frame encode: %s", exc)
            t3 = time.perf_counter()
            au = self._fallback_au(y, u, v)
            keyframe = True
        t4 = time.perf_counter()
        self.cached_columns += self.cols - len(todo)
        if keyframe:
            self._force_idr = False
        self._have_ref = True
        skipped = 0
        if dirty is not None and not keyframe:
            skipped = int(mb_total - dirty.sum())
        self.last_stats = FrameStats(
            frame_index=self.frame_index, idr=keyframe, qp=0,
            bytes=len(au),
            device_ms=(self.frontend_device_ms or (t1 - t0) * 1e3)
            + (t3 - t2) * 1e3,           # column strip encodes
            pack_ms=(t2 - t1) * 1e3 + (t4 - t3) * 1e3,  # convert + splice
            skipped_mbs=skipped, cols=self.cols)
        self.frame_index += 1
        return au


# ---------------------------------------------------------------------------
# VP9: mesh front-end + carve-pinned libvpx tile columns


def _vp9_encoder_cls():
    # deferred: models.vp9.encoder imports libvpx at module import
    from selkies_tpu.models.vp9.encoder import TPUVP9Encoder

    return TPUVP9Encoder


class TileColumnVP9Encoder:
    """tpuvp9enc's tile-column mesh mode: the hybrid VP9 row with (a)
    the column-sharded mesh front-end and (b) libvpx's tile-column split
    and thread count pinned to the mesh carve, so front-end shards and
    entropy tiles cover the same columns.  Byte contract: output is
    identical to the solo hybrid row configured with the same tile
    carve and the same device classifier — the mesh only changes WHERE
    classification runs (tests/test_codec_mesh.py)."""

    def __new__(cls, width: int, height: int, fps: int = 60,
                bitrate_kbps: int = 2000, cols: int = 2,
                frontend: str | None = None, devices=None, **_ignored):
        from selkies_tpu.models.hybrid_frontend import default_frontend_mode

        base = _vp9_encoder_cls()
        mode = (frontend if frontend in ("host", "device")
                else default_frontend_mode())
        # `cols` is a chip BUDGET: round DOWN to a power of two (like
        # the AV1 carve clamp) so libvpx's tile split and the front-end
        # shards cover the same columns on non-power-of-two rows
        log2 = floor_cols_log2(max(1, cols))
        eff_cols = 1 << log2
        # build on the host front-end (cheap), then swap in the mesh —
        # constructing the solo device front-end just to replace it
        # would pay a full-frame jit for nothing
        enc = base(width=width, height=height, fps=fps,
                   bitrate_kbps=bitrate_kbps, frontend="host",
                   tile_columns_log2=log2, threads=eff_cols)
        enc.cols = eff_cols
        if mode == "device":
            enc._device_fe = MeshDeltaFrontend(width, height, eff_cols,
                                               devices=devices)
            enc._prep = None
            enc.frontend_mode = "device"
        return enc
