"""Host-side frame preparation (ctypes binding for native/frameprep.cc).

Converts captured BGRx frames to padded I420 planes on the host CPU and
tracks per-band dirty state vs the previous capture. Rationale: the
host↔device link carries every uploaded byte — uploading I420 is 2.7x
less data than BGRx, and
the dirty-band map feeds the encoder's static-frame fast path today (an
unchanged capture encodes as an all-skip P slice with zero device work;
partial-band uploads are the next step). The reference leans on
ximagesrc's XDamage for the same effect (gstwebrtc_app.py:210-241).

The uplink front-end is FUSED and band-parallel (ISSUE 12): one native
pass per band computes the dirty-tile map, updates the previous-frame
state for dirty tiles only, and emits the tile-cache content hashes —
replacing the serial band_diff + tile_diff + full-frame np.copyto +
tile_hash sequence (three full-frame memory passes). Bands are
independent 16-row stripes, so the scan fans out across a small shared
worker pool (``SELKIES_FRONTEND_WORKERS``); the sharded result is
byte-identical to the serial scan, which remains available as the
oracle behind ``SELKIES_PARALLEL_FRONTEND=0``. Capture layers that know
the damaged region (X11 XDamage, the synthetic traces' dirty boxes) can
pass ``damage`` rect hints: damage rects are authoritative SUPERSETS of
changed pixels, so bands/tiles outside them skip classification and the
previous-frame update entirely — with a forced periodic full scan
(``SELKIES_DAMAGE_FULL_SCAN``) as the safety ratchet against a buggy
hint source.

Contiguity contract: every converter and the scan walk raw BGRx bytes
via ctypes, so frames must arrive C-contiguous. The capture boundary
guarantees this (X11 grabs materialize via np.ascontiguousarray, the
synthetic sources build contiguous arrays); a non-contiguous frame from
a direct caller is copied here defensively — at 3.7 MB/frame (720p)
that copy is exactly the kind of hidden full-frame pass this module
exists to avoid, so keep captures contiguous.

The conversion is bit-exact with the device path (ops/colorspace.py); a
pure-numpy fallback keeps headless test environments working without the
shared library.
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass

import numpy as np

logger = logging.getLogger("models.frameprep")

_NATIVE_DIR = os.environ.get("SELKIES_NATIVE_DIR") or os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))), "native"
)
_LIB_PATH = os.path.join(_NATIVE_DIR, "libframeprep.so")

_lib = None
_lib_tried = False

BAND_ROWS = 16  # dirty-detection granularity = one MB row


def parallel_frontend_enabled() -> bool:
    """SELKIES_PARALLEL_FRONTEND gate (default on): 0 forces the serial
    single-call scan — the byte-identity oracle for the sharded path."""
    return os.environ.get("SELKIES_PARALLEL_FRONTEND", "1") != "0"


def frontend_workers() -> int:
    """Front-end scan/convert pool width. Sized like the h264-pack pool
    (bounded by host cores); SELKIES_FRONTEND_WORKERS overrides. The
    scan shards 16-row bands, so more workers than band-chunks is waste
    — 4 covers the measured knee on desktop geometries."""
    env = os.environ.get("SELKIES_FRONTEND_WORKERS", "")
    if env:
        try:
            return max(1, min(16, int(env)))
        except ValueError:
            logger.warning("SELKIES_FRONTEND_WORKERS=%r not an integer; "
                           "using default", env)
    return max(1, min(os.cpu_count() or 2, 4))


def damage_full_scan_interval() -> int:
    """Every Nth scan ignores damage hints and walks the whole frame —
    the safety ratchet bounding how long a wrong (non-superset) hint
    source could desync the previous-frame state. 0 disables the
    ratchet (trusted hint sources only)."""
    env = os.environ.get("SELKIES_DAMAGE_FULL_SCAN", "")
    if env:
        try:
            return max(0, int(env))
        except ValueError:
            logger.warning("SELKIES_DAMAGE_FULL_SCAN=%r not an integer; "
                           "using default", env)
    return 120


# below this many bands per worker the thread fan-out overhead exceeds
# the memcmp it parallelizes (a 720p frame is 45 bands)
_MIN_BANDS_PER_CHUNK = 8

_fe_pool: ThreadPoolExecutor | None = None
_fe_pool_lock = threading.Lock()


def _frontend_pool() -> ThreadPoolExecutor:
    """Shared process-wide front-end pool (scan shards + band converts).
    One pool serves every encoder in the process: front-end work is
    bursty per frame, and per-encoder pools would oversubscribe a fleet
    host the same way per-session pack pools used to (PERF.md)."""
    global _fe_pool
    with _fe_pool_lock:
        if _fe_pool is None:
            _fe_pool = ThreadPoolExecutor(
                max_workers=frontend_workers(),
                thread_name_prefix="frontend")
        return _fe_pool


def tile_width_for(width: int) -> int:
    """The delta-tile column width tpuh264enc uses for `width`: the
    largest power-of-two tile that divides the padded plane (pad_w
    itself degenerates to full bands). Single definition — the encoder,
    the trace generators (pipeline/elements.py), and the link-byte
    profiler all derive geometry from here."""
    pad_w = (width + 15) // 16 * 16
    return next((t for t in (128, 64, 32, 16) if pad_w % t == 0), pad_w)


def delta_buckets_for(width: int, height: int) -> tuple[int, ...]:
    """tpuh264enc's delta bucket ladder for a geometry: dirty-tile
    counts round up to one of these; frames dirtier than the largest
    bucket take the full-upload path. Single definition (see
    tile_width_for) so tools/tests sizing content to 'fits the delta
    path' cannot drift from the encoder."""
    pad_h = (height + 15) // 16 * 16
    pad_w = (width + 15) // 16 * 16
    ntiles = (pad_h // 16) * (pad_w // tile_width_for(width))
    return tuple(
        b for b in (8, 16, 32, 64, 128, 256, 512) if b <= ntiles // 2
    ) or ((ntiles // 2,) if ntiles >= 2 else ())


def _load() -> ctypes.CDLL | None:
    global _lib, _lib_tried
    if _lib_tried:
        return _lib
    _lib_tried = True
    # always invoke make: it is a no-op when fresh and rebuilds a stale
    # .so after a frameprep.cc change (new exported symbols). A build
    # failure (no toolchain) is not fatal — a prebuilt .so may exist.
    if os.path.exists(os.path.join(_NATIVE_DIR, "Makefile")):
        try:
            subprocess.run(
                ["make", "-C", _NATIVE_DIR, "-s", "libframeprep.so"],
                check=True, capture_output=True, timeout=120,
            )
        except (OSError, subprocess.SubprocessError) as exc:
            logger.warning("could not (re)build libframeprep.so (%s); trying prebuilt", exc)
    try:
        lib = ctypes.CDLL(_LIB_PATH)
    except OSError as exc:
        logger.warning("could not load libframeprep.so (%s); numpy fallback", exc)
        return None
    u8p = ctypes.POINTER(ctypes.c_uint8)
    i32p = ctypes.POINTER(ctypes.c_int32)
    lib.bgrx_to_i420_pad.restype = None
    lib.bgrx_to_i420_pad.argtypes = [u8p, ctypes.c_int, ctypes.c_int, ctypes.c_int,
                                     ctypes.c_int, u8p, u8p, u8p]
    lib.band_diff.restype = ctypes.c_int
    lib.band_diff.argtypes = [u8p, u8p, ctypes.c_int, ctypes.c_int, ctypes.c_int, u8p]
    try:
        lib.tile_diff.restype = ctypes.c_int
        lib.tile_diff.argtypes = [u8p, u8p, ctypes.c_int, ctypes.c_int, ctypes.c_int,
                                  ctypes.c_int, u8p, u8p]
        lib.bgrx_to_i420_tiles.restype = None
        lib.bgrx_to_i420_tiles.argtypes = [u8p, ctypes.c_int, ctypes.c_int, ctypes.c_int,
                                           ctypes.c_int, i32p, ctypes.c_int, u8p, u8p, u8p]
        lib.tile_hash.restype = None
        lib.tile_hash.argtypes = [u8p, ctypes.c_int, ctypes.c_int,
                                  ctypes.POINTER(ctypes.c_uint64)]
        lib.frontend_scan.restype = ctypes.c_int
        lib.frontend_scan.argtypes = [
            u8p, u8p, ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int,
            u8p, ctypes.POINTER(ctypes.c_uint64)]
        lib.gather_tiles.restype = None
        lib.gather_tiles.argtypes = [u8p, ctypes.c_int, ctypes.c_int,
                                     ctypes.c_int, i32p, ctypes.c_int, u8p]
        lib.bgrx_to_i420_pad_rows.restype = None
        lib.bgrx_to_i420_pad_rows.argtypes = [
            u8p, ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.c_int, ctypes.c_int, u8p, u8p, u8p]
        lib.pad_i420_bottom.restype = None
        lib.pad_i420_bottom.argtypes = [
            ctypes.c_int, ctypes.c_int, ctypes.c_int, u8p, u8p, u8p]
    except AttributeError:
        pass  # stale .so without the tile converters; numpy fallback used
    _lib = lib
    return lib


def _u8p(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))


def _numpy_convert_pad(frame: np.ndarray, ph: int, pw: int):
    """Fallback mirror of bgrx_to_i420_pad (and of ops/colorspace.py)."""
    f = frame.astype(np.int32)
    r, g, b = f[..., 2], f[..., 1], f[..., 0]
    y = np.clip(((66 * r + 129 * g + 25 * b + 128) >> 8) + 16, 16, 235)
    u = np.clip(((-38 * r - 74 * g + 112 * b + 128) >> 8) + 128, 16, 240)
    v = np.clip(((112 * r - 94 * g - 18 * b + 128) >> 8) + 128, 16, 240)
    h, w = y.shape

    def sub(p):
        return (p.reshape(h // 2, 2, w // 2, 2).sum(axis=(1, 3)) + 2) >> 2

    u, v = sub(u), sub(v)

    def pad(p, th, tw):
        return np.pad(p, ((0, th - p.shape[0]), (0, tw - p.shape[1])), mode="edge")

    return (
        pad(y, ph, pw).astype(np.uint8),
        pad(u, ph // 2, pw // 2).astype(np.uint8),
        pad(v, ph // 2, pw // 2).astype(np.uint8),
    )


@dataclass
class ScanResult:
    """One fused front-end scan's outputs (FramePrep.scan).

    tiles: (nbands, ntiles) bool dirty map. hashes: (nbands, ntiles)
    uint64 tile-cache content hashes, valid ONLY at dirty cacheable
    tiles (None unless want_hashes). full_scan: True when the whole
    frame was walked (no damage hint, or the periodic ratchet fired)."""

    tiles: np.ndarray
    hashes: np.ndarray | None
    full_scan: bool


class FramePrep:
    """Per-stream host prep state: conversion buffers + previous frame."""

    def __init__(self, width: int, height: int, pad_w: int, pad_h: int, nslots: int = 4):
        self.width, self.height = width, height
        # odd capture geometry (DCI projectors, xrandr panning splits)
        # cannot carry 4:2:0 chroma siting — the 2x2 subsample and the
        # native converter both walk pixel quads. Odd frames are edge-
        # replicated to even dims on the host before conversion; the
        # extra column/row lands inside the encoder's 16-multiple pad
        # region (the capture layer normally pads BEFORE the encoder is
        # built — pipeline/capture.pad_frame_to_even — this is the
        # defensive mirror for direct FramePrep users).
        self._even_w = width + (width & 1)
        self._even_h = height + (height & 1)
        if pad_w < self._even_w or pad_h < self._even_h:
            raise ValueError(
                f"pad {pad_w}x{pad_h} cannot hold the even-padded "
                f"{self._even_w}x{self._even_h} frame")
        self.pad_w, self.pad_h = pad_w, pad_h
        self._lib = _load()
        # rotating output buffers: the encoder pipelines dispatches, and an
        # async h2d transfer may still be reading a plane when the next
        # capture converts — each convert() writes a different slot, so
        # nslots must cover every possibly-in-flight upload plus one
        self._nslots = max(2, int(nslots))
        # conversion slots allocate lazily: change-detection-only users
        # (the VP9 hybrid row) never call convert() and would otherwise
        # carry ~6 MB of dead plane buffers per encoder
        self._bufs: list | None = None
        self._slot = 0
        self._prev: np.ndarray | None = None
        self.nbands = (height + BAND_ROWS - 1) // BAND_ROWS
        self._bands = np.empty(self.nbands, np.uint8)
        # damage-hint safety ratchet (scan): every Nth scan is forced full
        self._scan_count = 0
        self._full_every = damage_full_scan_interval()

    @property
    def native(self) -> bool:
        return self._lib is not None

    def convert(self, frame: np.ndarray):
        """(H, W, 4) BGRx uint8 -> (y, u, v) padded planes.

        Buffers rotate over 4 slots, so up to 4 conversions can be in
        flight (async device uploads) before a slot is overwritten."""
        if frame.shape != (self.height, self.width, 4):
            raise ValueError(f"frame {frame.shape} != {(self.height, self.width, 4)}")
        if (self._even_h, self._even_w) != (self.height, self.width):
            frame = np.pad(frame, ((0, self._even_h - self.height),
                                   (0, self._even_w - self.width), (0, 0)),
                           mode="edge")
        if not frame.flags["C_CONTIGUOUS"]:
            frame = np.ascontiguousarray(frame)
        if self._bufs is None:
            self._bufs = [
                (
                    np.empty((self.pad_h, self.pad_w), np.uint8),
                    np.empty((self.pad_h // 2, self.pad_w // 2), np.uint8),
                    np.empty((self.pad_h // 2, self.pad_w // 2), np.uint8),
                )
                for _ in range(self._nslots)
            ]
        y, u, v = self._bufs[self._slot]
        self._slot = (self._slot + 1) % self._nslots
        if self._lib is not None:
            lib = self._lib
            eh, ew = self._even_h, self._even_w
            workers = (frontend_workers()
                       if parallel_frontend_enabled()
                       and hasattr(lib, "bgrx_to_i420_pad_rows") else 1)
            # band-parallel conversion: workers convert disjoint even-row
            # ranges of the same padded planes (byte-identical to the
            # single-call path); the bottom padding replicates afterwards
            nchunks = min(workers, max(1, eh // (2 * 16 * _MIN_BANDS_PER_CHUNK)))
            if nchunks <= 1:
                lib.bgrx_to_i420_pad(
                    _u8p(frame), eh, ew, self.pad_h,
                    self.pad_w, _u8p(y), _u8p(u), _u8p(v),
                )
            else:
                step = (-(-eh // (2 * nchunks))) * 2  # even row chunks
                futs = [
                    _frontend_pool().submit(
                        lib.bgrx_to_i420_pad_rows,
                        _u8p(frame), eh, ew, self.pad_h, self.pad_w,
                        r0, min(r0 + step, eh), _u8p(y), _u8p(u), _u8p(v))
                    for r0 in range(0, eh, step)
                ]
                for f in futs:
                    f.result()
                lib.pad_i420_bottom(eh, self.pad_h, self.pad_w,
                                    _u8p(y), _u8p(u), _u8p(v))
        else:
            y2, u2, v2 = _numpy_convert_pad(frame, self.pad_h, self.pad_w)
            y[:], u[:], v[:] = y2, u2, v2
        return y, u, v

    def reset(self) -> None:
        """Forget the previous frame: the next dirty_bands() reports
        everything dirty (used by encoder prewarm / stream restart)."""
        self._prev = None

    def convert_tiles(self, frame: np.ndarray, idx: np.ndarray, tile_w: int):
        """Convert only the 16-row x tile_w-col tiles listed in idx
        (int32, band*1024 + tile) to packed I420 tile buffers:
        (k, 16, tile_w) luma and (k, 8, tile_w/2) chroma, bit-exact with
        the same region of a full convert(). tile_w must divide pad_w and
        be a multiple of 16; tile_w == pad_w degenerates to bands."""
        if frame.shape != (self.height, self.width, 4):
            raise ValueError(f"frame {frame.shape} != {(self.height, self.width, 4)}")
        if tile_w % 16 or self.pad_w % tile_w:
            raise ValueError(f"tile_w {tile_w} must be a 16-multiple dividing {self.pad_w}")
        # odd geometry: same even-pad normalization as convert() — the
        # quad-walking converters (native and numpy) must never see an
        # odd plane, whichever entry point a direct FramePrep user hits
        if (self._even_h, self._even_w) != (self.height, self.width):
            frame = np.pad(frame, ((0, self._even_h - self.height),
                                   (0, self._even_w - self.width), (0, 0)),
                           mode="edge")
        if not frame.flags["C_CONTIGUOUS"]:
            frame = np.ascontiguousarray(frame)
        idx = np.ascontiguousarray(idx, np.int32)
        k = len(idx)
        yb = np.empty((k, 16, tile_w), np.uint8)
        ub = np.empty((k, 8, tile_w // 2), np.uint8)
        vb = np.empty((k, 8, tile_w // 2), np.uint8)
        if self._lib is not None and hasattr(self._lib, "bgrx_to_i420_tiles"):
            self._lib.bgrx_to_i420_tiles(
                _u8p(frame), self._even_h, self._even_w, self.pad_w, tile_w,
                idx.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)), k,
                _u8p(yb), _u8p(ub), _u8p(vb),
            )
        else:
            y, u, v = _numpy_convert_pad(frame, self.pad_h, self.pad_w)
            ctw = tile_w // 2
            for i, t in enumerate(idx):
                band, tile = int(t) // 1024, int(t) % 1024
                yb[i] = y[band * 16:band * 16 + 16, tile * tile_w:(tile + 1) * tile_w]
                ub[i] = u[band * 8:band * 8 + 8, tile * ctw:(tile + 1) * ctw]
                vb[i] = v[band * 8:band * 8 + 8, tile * ctw:(tile + 1) * ctw]
        return yb, ub, vb

    # -- fused band-parallel dirty scan (ISSUE 12) ----------------------

    def _damage_box(self, damage, tile_w: int) -> tuple[int, int, int, int]:
        """Damage rects -> inclusive-exclusive (b0, b1, t0, t1) bounding
        box in band/tile units, clipped to the frame. Rects are
        (x, y, w, h) pixel tuples; an empty iterable means "nothing
        changed" (box collapses to zero bands)."""
        ntiles = (self.width + tile_w - 1) // tile_w
        b0, b1, t0, t1 = self.nbands, 0, ntiles, 0
        for (x, y, w, h) in damage:
            if w <= 0 or h <= 0:
                continue
            x0 = max(0, int(x))
            y0 = max(0, int(y))
            x1 = min(self.width, int(x) + int(w))
            y1 = min(self.height, int(y) + int(h))
            if x1 <= x0 or y1 <= y0:
                continue
            b0 = min(b0, y0 // BAND_ROWS)
            b1 = max(b1, (y1 + BAND_ROWS - 1) // BAND_ROWS)
            t0 = min(t0, x0 // tile_w)
            t1 = max(t1, (x1 + tile_w - 1) // tile_w)
        if b1 <= b0 or t1 <= t0:
            return 0, 0, 0, 0
        return b0, b1, t0, t1

    def _scan_chunk_numpy(self, frame: np.ndarray, tile_w: int,
                          b0: int, b1: int, t0: int, t1: int,
                          out: np.ndarray, hashes: np.ndarray | None) -> None:
        """Pure-numpy mirror of native frontend_scan for bands [b0, b1) x
        tiles [t0, t1): vectorized reshape + any-reduction instead of the
        historical O(ntiles) per-tile Python loop, prev updated for dirty
        tiles only, tile_hash_np values for dirty cacheable tiles."""
        h, w = self.height, self.width
        r0, r1 = b0 * BAND_ROWS, min(b1 * BAND_ROWS, h)
        c0, c1 = t0 * tile_w, min(t1 * tile_w, w)
        nb, nt = b1 - b0, t1 - t0
        neq = (frame[r0:r1, c0:c1] != self._prev[r0:r1, c0:c1]).any(axis=2)
        pad = np.zeros((nb * BAND_ROWS, nt * tile_w), bool)
        pad[: r1 - r0, : c1 - c0] = neq
        dirty = pad.reshape(nb, BAND_ROWS, nt, tile_w).any(axis=(1, 3))
        out[b0:b1, t0:t1] = dirty
        band_i, tile_i = np.nonzero(dirty)
        full_bands = h // BAND_ROWS
        full_tiles = w // tile_w
        raws = []
        hash_pos = []
        for bi, ti in zip(band_i + b0, tile_i + t0):
            rr0, rr1 = bi * BAND_ROWS, min((bi + 1) * BAND_ROWS, h)
            cc0, cc1 = ti * tile_w, min((ti + 1) * tile_w, w)
            if hashes is not None and bi < full_bands and ti < full_tiles:
                raws.append(frame[rr0:rr1, cc0:cc1].reshape(-1))
                hash_pos.append((bi, ti))
            self._prev[rr0:rr1, cc0:cc1] = frame[rr0:rr1, cc0:cc1]
        if raws:
            from selkies_tpu.models.tilecache import tile_hash_np

            hs = tile_hash_np(np.stack(raws))
            for (bi, ti), hv in zip(hash_pos, hs):
                hashes[bi, ti] = hv

    def scan(self, frame: np.ndarray, tile_w: int, *, damage=None,
             want_hashes: bool = False) -> "ScanResult | None":
        """Fused front-end scan: dirty-tile map + previous-frame update
        (+ tile-cache content hashes) in one pass over the frame bytes.

        Returns None on the first frame (prev seeded, everything dirty —
        the caller takes the full-upload path). ``damage`` is an optional
        iterable of (x, y, w, h) pixel rects known to be a SUPERSET of
        all changed pixels (XDamage / synthetic-trace dirty boxes): the
        scan is bounded to their band/tile bounding box and everything
        outside reports clean without being read — exact because a
        superset guarantees outside bytes are unchanged. Every
        ``SELKIES_DAMAGE_FULL_SCAN``-th call ignores the hints (safety
        ratchet). ``want_hashes`` additionally emits tile_hash_np-
        compatible content hashes for dirty CACHEABLE tiles (fully
        inside the unpadded capture — the tile cache's rule); other
        entries of the hash array are unspecified.

        Bands shard across the shared front-end pool
        (SELKIES_FRONTEND_WORKERS) unless SELKIES_PARALLEL_FRONTEND=0;
        the sharded output is byte-identical to the serial scan
        (tests/test_frontend_parallel.py)."""
        if frame.shape != (self.height, self.width, 4):
            raise ValueError(f"frame {frame.shape} != {(self.height, self.width, 4)}")
        if not frame.flags["C_CONTIGUOUS"]:
            frame = np.ascontiguousarray(frame)
        ntiles = (self.width + tile_w - 1) // tile_w
        if self._prev is None:
            self._prev = frame.copy()
            return None
        self._scan_count += 1
        full_scan = (
            damage is None
            or (self._full_every > 0
                and self._scan_count % self._full_every == 0))
        if full_scan:
            box = (0, self.nbands, 0, ntiles)
        else:
            box = self._damage_box(damage, tile_w)
        b0, b1, t0, t1 = box
        out = np.zeros((self.nbands, ntiles), np.uint8)
        hashes = np.zeros((self.nbands, ntiles), np.uint64) if want_hashes else None
        if b1 > b0:
            native = self._lib is not None and hasattr(self._lib, "frontend_scan")
            if native:
                hp = (hashes.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64))
                      if hashes is not None else None)
                workers = frontend_workers() if parallel_frontend_enabled() else 1
                nchunks = min(workers, max(1, (b1 - b0) // _MIN_BANDS_PER_CHUNK))
                if nchunks <= 1:
                    self._lib.frontend_scan(
                        _u8p(frame), _u8p(self._prev), self.height, self.width,
                        BAND_ROWS, tile_w, b0, b1, t0, t1, _u8p(out), hp)
                else:
                    # contiguous band chunks; the C call releases the GIL,
                    # and chunks touch disjoint rows of prev/out/hashes
                    step = -(-(b1 - b0) // nchunks)
                    spans = [(b0 + i * step, min(b0 + (i + 1) * step, b1))
                             for i in range(nchunks)]
                    futs = [
                        _frontend_pool().submit(
                            self._lib.frontend_scan,
                            _u8p(frame), _u8p(self._prev), self.height,
                            self.width, BAND_ROWS, tile_w, s0, s1, t0, t1,
                            _u8p(out), hp)
                        for s0, s1 in spans if s1 > s0
                    ]
                    for f in futs:
                        f.result()
            else:
                self._scan_chunk_numpy(frame, tile_w, b0, b1, t0, t1,
                                       out, hashes)
        return ScanResult(tiles=out.astype(bool), hashes=hashes,
                          full_scan=bool(full_scan))

    def dirty_tiles(self, frame: np.ndarray, tile_w: int,
                    damage=None) -> np.ndarray | None:
        """Which 16-row x tile_w-col tiles changed vs the previous call's
        frame: (nbands, ntiles) bool, or None on the first frame. tile_w
        is in LUMA columns; detection compares the 4*tile_w BGRx bytes.
        Advances the previous-frame state for the changed tiles (clean
        tiles are already byte-equal, so the stored previous frame stays
        byte-identical to a full copy)."""
        res = self.scan(frame, tile_w, damage=damage)
        return None if res is None else res.tiles

    def dirty_bands(self, frame: np.ndarray, damage=None) -> np.ndarray | None:
        """Which 16-row bands changed vs the previous call's frame.

        Returns a bool array of shape (nbands,), or None on the first frame
        (everything dirty). Band granularity is the degenerate full-width
        tile of the fused scan."""
        res = self.scan(frame, self.width, damage=damage)
        return None if res is None else res.tiles[:, 0]
