"""Encoder registry — the TPU-native analogue of the encoder matrix.

The reference builds one of 15 encoder element chains by name
(gstwebrtc_app.py:260-783, supported list :1133) with an `ADD_ENCODER:`
grep-marker protocol for extensions (:257,943,1132). Here the matrix
collapses: every codec targets the same TPU compute core, so the registry
maps encoder names to factory callables, and legacy GStreamer encoder
names alias to their TPU equivalent so existing SELKIES_ENCODER configs
keep working.

ADD_ENCODER: register new encoders with @register("name") below.
"""

from __future__ import annotations

import logging
import os
from typing import Callable

logger = logging.getLogger("models.registry")

_FACTORIES: dict[str, Callable] = {}
_ALIASES: dict[str, str] = {}
_CODECS: dict[str, str] = {}

# codec -> RTP payloader (module, class) — resolved lazily so importing
# the registry never drags the transport stack in.  Every codec a row
# declares MUST map here: tools/check_codec_rows.py ratchets the
# invariant, because a registry row whose codec has no payloader can be
# negotiated but never streamed.
_PAYLOADERS: dict[str, tuple[str, str]] = {
    "h264": ("selkies_tpu.transport.rtp", "H264Payloader"),
    "h265": ("selkies_tpu.transport.rtp_h265", "H265Payloader"),
    "av1": ("selkies_tpu.transport.rtp_av1", "Av1Payloader"),
    "vp8": ("selkies_tpu.transport.rtp_vpx", "Vp8Payloader"),
    "vp9": ("selkies_tpu.transport.rtp_vpx", "Vp9Payloader"),
}


def register(name: str, codec: str = "") -> Callable[[Callable], Callable]:
    """Register an encoder factory.  ``codec`` declares the bitstream the
    row emits ("h264"/"av1"/...) — per-client negotiation
    (signalling/negotiate.py) and the payloader wiring key off it, and
    tools/check_codec_rows.py fails the build when a row forgets it."""

    def deco(factory: Callable) -> Callable:
        _FACTORIES[name] = factory
        if codec:
            _CODECS[name] = codec
        return factory

    return deco


def alias(name: str, target: str) -> None:
    _ALIASES[name] = target


def encoder_exists(name: str) -> bool:
    return name in _FACTORIES or name in _ALIASES


def supported_encoders() -> list[str]:
    return sorted(_FACTORIES) + sorted(_ALIASES)


def codec_for_encoder(name: str) -> str:
    """The codec a registry row (or alias) declares; "" if unknown."""
    name = _ALIASES.get(name, name)
    return _CODECS.get(name, "")


def payloader_for_codec(codec: str):
    """The RTP payloader class for a codec (lazy import)."""
    import importlib

    try:
        mod_name, cls_name = _PAYLOADERS[codec.lower()]
    except KeyError:
        raise ValueError(f"no payloader for codec {codec!r}") from None
    return getattr(importlib.import_module(mod_name), cls_name)


def create_encoder(name: str, *, width: int, height: int, fps: int = 60, **kw):
    # encoder (re)builds — including the resilience ladder's RESTART rung —
    # reuse compiled executables across instances and process restarts
    from selkies_tpu.utils.jaxcache import enable_persistent_compilation_cache

    enable_persistent_compilation_cache()
    if name in _ALIASES:
        target = _ALIASES[name]
        logger.info("encoder %r aliased to %r (TPU-native equivalent)", name, target)
        name = target
    if name not in _FACTORIES:
        raise ValueError(f"unknown encoder {name!r}; supported: {supported_encoders()}")
    return _FACTORIES[name](width=width, height=height, fps=fps, **kw)


# ADD_ENCODER: factories


def default_frame_batch() -> int:
    """Grouped-dispatch depth: consecutive delta frames per device step.
    SELKIES_FRAME_BATCH overrides — bench.py and the live pipeline share
    this."""
    env = os.environ.get("SELKIES_FRAME_BATCH")
    if env:
        try:
            return max(1, min(16, int(env)))
        except ValueError:
            logger.warning(
                "SELKIES_FRAME_BATCH=%r is not an integer; using default", env)
    return 4


def default_pipeline_depth() -> int:
    """In-flight device round-trip cap for the pipelined encoder.
    SELKIES_PIPELINE_DEPTH overrides."""
    env = os.environ.get("SELKIES_PIPELINE_DEPTH")
    if env:
        try:
            return max(0, min(8, int(env)))
        except ValueError:
            logger.warning(
                "SELKIES_PIPELINE_DEPTH=%r is not an integer; using default", env)
    return 2


@register("tpuh264enc", codec="h264")
def _tpuh264enc(*, width: int, height: int, fps: int = 60, qp: int = 28, **kw):
    from selkies_tpu.models.h264.encoder import TPUH264Encoder

    # the TPU row is QP-driven (the app's CbrRateController owns the
    # rate loop via set_qp); the library rows consume bitrate_kbps
    kw.pop("bitrate_kbps", None)
    bands = kw.pop("bands", None)
    cols = kw.pop("cols", None)
    if bands is None and cols is None:
        from selkies_tpu.parallel.bands import bands_from_env, grid_from_env

        grid = grid_from_env()
        if grid is not None:
            # SELKIES_TILE_GRID=RxC owns the carve: R band-rows × C tile
            # columns (C=1 degenerates to SELKIES_BANDS=R exactly)
            bands, cols = grid
        else:
            bands = bands_from_env()
    bands = 1 if bands is None else bands
    cols = 1 if cols is None else cols
    if bands > 1 or cols > 1:
        # SELKIES_BANDS>1 / SELKIES_TILE_GRID: the frame splits across
        # the chip mesh as independent slices (parallel/bands.py) — the
        # 4K / full-motion path where the FIFO-serialized device step is
        # the bottleneck. Falls back to the single-device sliced encode
        # (identical bytes) when the mesh is smaller than the carve.
        # Routed BEFORE the solo-knob setdefaults so `dropped` sees only
        # what the caller actually passed.
        from selkies_tpu.parallel.bands import BandedH264Encoder

        dropped = set(kw) - {"frame_batch", "pipeline_depth",
                             "keyframe_interval", "device_entropy",
                             "bits_min_mbs", "entropy_coder"}
        if dropped:
            # the solo encoder's uplink machinery (tile cache, delta
            # paths, LTR scenes, scene QP boost) does not apply to band
            # mode — say so instead of silently ignoring an explicitly-
            # passed knob
            logger.warning(
                "band-parallel encoder ignores encoder kwargs %s "
                "(solo-encoder knobs; see docs/bands.md)", sorted(dropped))
        return BandedH264Encoder(
            width=width, height=height, qp=qp, fps=fps, bands=bands,
            cols=cols,
            frame_batch=kw.get("frame_batch", default_frame_batch()),
            pipeline_depth=kw.get("pipeline_depth", default_pipeline_depth()),
            keyframe_interval=kw.get("keyframe_interval", 0),
            device_entropy=kw.get("device_entropy"),
            bits_min_mbs=kw.get("bits_min_mbs"),
            entropy_coder=kw.get("entropy_coder"),
        )
    kw.setdefault("frame_batch", default_frame_batch())
    kw.setdefault("pipeline_depth", default_pipeline_depth())
    kw.setdefault("scene_qp_boost", 6)
    return TPUH264Encoder(width=width, height=height, qp=qp, fps=fps, **kw)


@register("tpuvp9enc", codec="vp9")
def _tpuvp9enc(*, width: int, height: int, fps: int = 60, bitrate_kbps: int = 2000, **kw):
    """VP9 row with the framework's capture-delta front-end: unchanged
    frames short-circuit to 1-byte show_existing_frame headers, changed
    frames go through libvpx (see models/vp9/encoder.py for why VP9's
    entropy back-end cannot be rebuilt from scratch in this image).
    ``cols``/SELKIES_TILE_COLS > 1 routes to the tile-column mesh mode:
    column-sharded device front-end + libvpx tile columns pinned to the
    carve (parallel/codec_mesh.py)."""
    from selkies_tpu.parallel.codec_mesh import TileColumnVP9Encoder, cols_from_env

    cols = kw.pop("cols", None)
    cols = cols_from_env() if cols is None else max(1, int(cols))
    if cols > 1:
        return TileColumnVP9Encoder(
            width=width, height=height, fps=fps, bitrate_kbps=bitrate_kbps,
            cols=cols, frontend=kw.get("frontend"))
    from selkies_tpu.models.vp9.encoder import TPUVP9Encoder

    return TPUVP9Encoder(width=width, height=height, fps=fps, bitrate_kbps=bitrate_kbps)


@register("vp9enc", codec="vp9")
def _vp9enc(*, width: int, height: int, fps: int = 60, bitrate_kbps: int = 2000, **kw):
    from selkies_tpu.models.libvpx_enc import LibVpxEncoder

    return LibVpxEncoder(width=width, height=height, fps=fps, bitrate_kbps=bitrate_kbps)


@register("vp8enc", codec="vp8")
def _vp8enc(*, width: int, height: int, fps: int = 60, bitrate_kbps: int = 2000, **kw):
    from selkies_tpu.models.libvpx_enc import LibVpxEncoder

    return LibVpxEncoder(width=width, height=height, fps=fps, bitrate_kbps=bitrate_kbps, vp8=True)


@register("x264enc", codec="h264")
def _x264enc(*, width: int, height: int, fps: int = 60, bitrate_kbps: int = 2000, **kw):
    """The REAL x264 software row (ctypes libx264, reference tuning —
    gstwebrtc_app.py:609-639); degrades to the TPU encoder when the
    library/ABI probe fails (models/x264enc.py)."""
    from selkies_tpu.models.x264enc import X264Encoder, x264_available

    if not x264_available():
        logger.warning("libx264 unavailable; x264enc falls back to tpuh264enc")
        return _FACTORIES["tpuh264enc"](width=width, height=height, fps=fps, **kw)
    return X264Encoder(width=width, height=height, fps=fps, bitrate_kbps=bitrate_kbps)


@register("tpuav1enc", codec="av1")
def _tpuav1enc(*, width: int, height: int, fps: int = 60, bitrate_kbps: int = 2000, **kw):
    """AV1 row with the framework's capture-delta front-end: unchanged
    frames encode with an all-inactive active map (every block skips from
    reference), changed frames restrict libaom's per-block work to dirty
    tiles (see models/av1/encoder.py). Degrades to the from-scratch TPU
    H.264 encoder only if the libaom ABI probe fails — the reference's
    own policy when an encoder is missing is to fail the pipeline
    (gstwebrtc_app.py:1123-1140); we degrade instead and log."""
    from selkies_tpu.models.libaom_enc import (
        aom_strip_available, libaom_available)
    from selkies_tpu.parallel.codec_mesh import TileColumnAV1Encoder, cols_from_env

    cols = kw.pop("cols", None)
    cols = cols_from_env() if cols is None else max(1, int(cols))
    if cols > 1 and aom_strip_available():
        # SELKIES_TILE_COLS / negotiated carve: the tile-column mesh mode
        # (parallel/codec_mesh.py — per-column strip encoders spliced
        # into one frame). Pinned lossless; the realtime CBR hybrid row
        # below stays the single-column path.
        return TileColumnAV1Encoder(
            width=width, height=height, fps=fps, cols=cols,
            frontend=kw.get("frontend"),
            keyframe_interval=kw.get("keyframe_interval", 0))
    if not libaom_available():
        if aom_strip_available():
            # legacy-ABI libaom (1.0): no realtime usage for the hybrid
            # CBR row, but the lossless tile-column splice works — serve
            # AV1 through the mesh row at cols=1 rather than silently
            # negotiating H.264
            return TileColumnAV1Encoder(
                width=width, height=height, fps=fps, cols=1,
                frontend=kw.get("frontend"),
                keyframe_interval=kw.get("keyframe_interval", 0))
        logger.warning("libaom unavailable; tpuav1enc falls back to tpuh264enc "
                       "— the session will negotiate H.264")
        kw.pop("cpu_used", None)
        kw.pop("frontend", None)
        return _FACTORIES["tpuh264enc"](width=width, height=height, fps=fps, **kw)
    from selkies_tpu.models.av1.encoder import TPUAV1Encoder

    return TPUAV1Encoder(width=width, height=height, fps=fps,
                         bitrate_kbps=bitrate_kbps, **kw)


@register("av1enc", codec="av1")
def _av1enc(*, width: int, height: int, fps: int = 60, bitrate_kbps: int = 2000, **kw):
    """The REAL libaom software row (ctypes, reference tuning —
    gstwebrtc_app.py:741-783); degrades to tpuav1enc's fallback chain
    when the library/ABI probe fails (models/libaom_enc.py)."""
    from selkies_tpu.models.libaom_enc import LibAomEncoder, libaom_available

    if not libaom_available():
        logger.warning("libaom unavailable; av1enc falls back to tpuh264enc")
        kw.pop("cpu_used", None)  # AV1-only knob; TPUH264Encoder rejects it
        return _FACTORIES["tpuh264enc"](width=width, height=height, fps=fps, **kw)
    return LibAomEncoder(width=width, height=height, fps=fps,
                         bitrate_kbps=bitrate_kbps, **kw)


@register("x265enc", codec="h265")
def _x265enc(*, width: int, height: int, fps: int = 60, bitrate_kbps: int = 2000, **kw):
    """The REAL x265 HEVC software row (ctypes libx265, reference tuning —
    gstwebrtc_app.py:667-683); degrades to the TPU encoder when the
    library/ABI probe fails (models/x265enc.py)."""
    from selkies_tpu.models.x265enc import X265Encoder, x265_available

    if not x265_available():
        logger.warning("libx265 unavailable; x265enc falls back to tpuh264enc")
        kw.pop("preset", None)  # x265-only knob; TPUH264Encoder rejects it
        return _FACTORIES["tpuh264enc"](width=width, height=height, fps=fps, **kw)
    return X265Encoder(width=width, height=height, fps=fps,
                       bitrate_kbps=bitrate_kbps,
                       preset=kw.get("preset", "ultrafast"))


# Legacy GStreamer encoder names (reference gstwebrtc_app.py:1133) map to
# the TPU equivalent so existing SELKIES_ENCODER values keep working.
# (x264enc / x265enc / av1enc are REAL rows above, not aliases.)
for _legacy_h264 in ("nvh264enc", "vah264enc", "openh264enc"):
    alias(_legacy_h264, "tpuh264enc")
# H.265 silicon rows (reference gstwebrtc_app.py:369-424,510-542) map to
# the libx265 software row — HEVC's CABAC-only entropy coding can't be
# rebuilt from scratch here (normative context tables), so the library
# the reference's own x265enc wraps carries the codec.
for _legacy_h265 in ("nvh265enc", "vah265enc"):
    alias(_legacy_h265, "x265enc")
alias("vavp9enc", "tpuvp9enc")  # silicon VP9 row maps to the hybrid
# AV1 silicon/alternative-library rows map to the hybrid libaom row
# (av1enc above is the REAL plain-libaom row, not an alias)
for _legacy_av1 in ("nvav1enc", "vaav1enc", "rav1enc"):
    alias(_legacy_av1, "tpuav1enc")


@register("svtav1enc", codec="av1")
def _svtav1enc(*, width: int, height: int, fps: int = 60,
               bitrate_kbps: int = 2000, **kw):
    """REAL SVT-AV1 row when libSvtAv1Enc passes ABI validation
    (models/svt_av1_enc.py — the same library the reference's svtav1enc
    element wraps, gstwebrtc_app.py:724-739); otherwise the hybrid
    libaom row serves the name, as the silicon aliases do."""
    from selkies_tpu.models.svt_av1_enc import SvtAv1Encoder, svt_av1_available

    if svt_av1_available():
        return SvtAv1Encoder(width=width, height=height, fps=fps,
                             bitrate_kbps=int(bitrate_kbps),
                             preset=int(kw.get("preset", 10)))
    return create_encoder("tpuav1enc", width=width, height=height, fps=fps,
                          bitrate_kbps=bitrate_kbps, **kw)
