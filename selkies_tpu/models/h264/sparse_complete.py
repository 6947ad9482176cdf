"""Shared per-slice sparse P completion: fused downlink words → slice NAL.

Both host completion paths — the solo pipelined encoder's delta frames
(models/h264/encoder.py) and the band-parallel encoder's per-band slices
(parallel/bands.py) — finish a sparse P downlink the same way:

  1. read the fused prefix's need/row/non-skip counts
     (``p_sparse_*_need``) and feed the hint feedback loop;
  2. refetch the full live content when the hint-sized slice fell short;
  3. fetch the row spill past the fused cap (``fetch_rest``);
  4. hand the wire-format regions straight to the native C packer
     (``p_sparse_wire_views`` + ``pack_slice_p_sparse_native``) when
     it is available, else run the Python dense expansion
     (``unpack_p_sparse_*`` + ``pack_slice_p_fast``) — including the
     ns > nscap dense-header fallback fetch where the caller has one.

With ``device_bits=True`` the fused buffer is the entropy-wrapped
layout (encoder_core.pack_p_sparse_entropy): an 8-int32 meta prefix
whose mode flag says whether the payload is the unchanged sparse coeff
layout (the flow above, applied to the offset view) or the frame's
FINAL slice-data bits packed on device — in which case the host only
splices the slice header around the fetched words (``assemble_p_nal``)
and no coefficient unpack or CAVLC pack runs at all. That bits branch
is what turns a busy delta frame's completion into a near-zero host
tail (ISSUE 7 / PERF.md round 9).

PR 5 duplicated this flow per band; this module is the one definition
(flagged follow-up in CHANGES.md PR 5). The two callers differ only in
slice geometry (full frame vs one band), the ``first_mb`` slice-header
offset, and the LTR slice-header flags — all parameters here. Byte
output is identical to both former inline flows by construction
(tests/test_sparse_native_pack.py, tests/test_band_slices.py,
tests/test_device_entropy_sparse.py).
"""

from __future__ import annotations

import time
from typing import Callable

import numpy as np

from selkies_tpu.models.h264.compact import (
    ENTROPY_META16,
    p_sparse_entropy_meta,
    p_sparse_packed_need,
    p_sparse_var_need,
    p_sparse_wire_views,
    unpack_p_compact,
    unpack_p_sparse_packed,
    unpack_p_sparse_var,
)
from selkies_tpu.models.h264.device_cabac import assemble_p_cabac_nal
from selkies_tpu.models.h264.device_cavlc import assemble_p_nal
from selkies_tpu.models.h264.native import (
    pack_slice_p_fast,
    pack_slice_p_sparse_native,
    sparse_native_available,
)
from selkies_tpu.monitoring.tracing import tracer

__all__ = ["complete_sparse_slice", "fetch_rest"]


def _settle_device_bits(fused, need, note_need, link_bytes, prefix_bytes,
                        full_d, pts=None):
    """Shared mode=1 completion plumbing — hint feedback, downlink-byte
    accounting and the hint-too-small refetch are identical for both
    entropy coders; only the payload parse after this differs. Returns
    the (possibly refetched) fused buffer."""
    if note_need is not None:
        note_need(need)
    if link_bytes is not None and prefix_bytes:
        link_bytes.add("down_bits", prefix_bytes)
    if need > len(fused):  # hint too small: refetch
        # span marks only the EXTRA transfer (tracing.py contract —
        # the main prefix fetch rode the caller's "fetch" span)
        with tracer.span("bits_fetch", pts=pts):
            fused = np.asarray(full_d)
        if link_bytes is not None:
            link_bytes.add("down_bits_refetch", fused.nbytes)
    return fused


def fetch_rest(buf, n: int, base: int = 4096) -> np.ndarray:
    """Overflow path: rows [base, >=n) in power-of-two buckets (base=0
    fetches from the start, bucketed from 4096). Exactly two-ish fetch
    shapes per geometry keep the compile discipline of the prefix
    fetches (encoder.py PFX_SMALL)."""
    total = buf.shape[0]
    bucket = max(base, 4096)
    while bucket < n:
        bucket <<= 1
    if bucket >= total:
        return np.asarray(buf)[base:]
    return np.asarray(buf[base:bucket])


def complete_sparse_slice(
    fused: np.ndarray,
    *,
    mbh: int,
    mbw: int,
    nscap: int,
    cap_rows: int,
    qp: int,
    frame_num: int,
    params,
    packed: bool = False,
    device_bits: bool = False,
    full_d=None,
    buf_d=None,
    dense_d=None,
    link_bytes=None,
    prefix_bytes: int = 0,
    note_need: Callable[[int], None] | None = None,
    first_mb: int = 0,
    ltr_ref: int | None = None,
    mark_ltr: int | None = None,
    mmco_evict: tuple = (),
    entropy_coder: str = "cavlc",
    cabac_init_idc: int = 0,
    pts=None,
) -> tuple[bytes, int, float, str]:
    """One P slice's fused sparse downlink → (nal, skipped_mbs,
    t_unpacked, downlink_mode).

    ``fused`` is the (possibly hint-sized) fetched prefix; ``full_d`` the
    full-length device handle for the shortfall refetch, ``buf_d`` the
    row-spill buffer, ``dense_d`` the dense header for the ns > nscap
    fallback (callers whose nscap equals the slice MB count pass None —
    that branch is structurally unreachable for them). ``t_unpacked`` is
    the unpack→pack boundary timestamp for the caller's stage split.
    ``pts`` tags the tracer spans with the frame's 90 kHz timestamp.

    ``prefix_bytes`` is the caller's already-fetched prefix size: the
    accounting lives here (not at the fetch site) because only the meta
    read knows whether those bytes were coefficient rows (``down_prefix``)
    or device bits (``down_bits``) — bench.py splits the per-frame
    downlink on exactly that stage-name prefix. ``downlink_mode`` is
    "bits" (device-entropy payload), "dense" (ns > nscap dense-header
    fallback) or "coeff" (sparse rows, either layout).
    """
    off = 0
    if device_bits:
        mode, nbits, trailing, nskip, ns = p_sparse_entropy_meta(fused)
        if mode == 1 and entropy_coder == "cabac":
            # device-token payload: interleave skip/terminate bins and
            # run the host arithmetic engine — no unpack, no host
            # binarization (the slice's mb token bodies came binarized
            # and context-indexed from the device)
            ntok = nbits  # the nbits meta slot carries ntok for cabac
            m = mbh * mbw
            sw = (m + 31) // 32
            nw = (ntok + 1) // 2
            base = ENTROPY_META16 + 2 * sw
            need = base + ns + 2 * nw
            fused = _settle_device_bits(fused, need, note_need,
                                        link_bytes, prefix_bytes, full_d,
                                        pts)
            skip_words = (np.ascontiguousarray(
                fused[ENTROPY_META16:base]).view(np.int32)
                .astype(np.int64) & 0xFFFFFFFF)
            skip = (((skip_words[:, None] >> np.arange(32)) & 1)
                    .astype(bool).reshape(-1)[:m].reshape(mbh, mbw))
            counts = fused[base:base + ns].astype(np.int64)
            words = np.ascontiguousarray(
                fused[base + ns:base + ns + 2 * nw]).view(np.uint32)
            t_unpacked = time.perf_counter()
            with tracer.span("pack", pts=pts):
                nal = assemble_p_cabac_nal(
                    words, ntok, counts, skip, params, frame_num, qp,
                    ltr_ref=ltr_ref, mark_ltr=mark_ltr,
                    mmco_evict=mmco_evict, first_mb=first_mb,
                    cabac_init_idc=cabac_init_idc)
            return nal, nskip, t_unpacked, "cabac"
        if mode == 1:
            # device-entropy payload: the words ARE the slice data —
            # splice the header, no unpack, no host CAVLC
            nw = (nbits + 31) // 32
            need = ENTROPY_META16 + 2 * nw
            fused = _settle_device_bits(fused, need, note_need,
                                        link_bytes, prefix_bytes, full_d,
                                        pts)
            words = np.ascontiguousarray(
                fused[ENTROPY_META16:ENTROPY_META16 + 2 * nw]).view(np.uint32)
            t_unpacked = time.perf_counter()
            with tracer.span("pack", pts=pts):
                nal = assemble_p_nal(
                    words, nbits, trailing, params, frame_num, qp,
                    ltr_ref=ltr_ref, mark_ltr=mark_ltr,
                    mmco_evict=mmco_evict, first_mb=first_mb)
            return nal, nskip, t_unpacked, "bits"
        # mode 0: the payload is the unchanged sparse layout at an offset
        off = ENTROPY_META16
        fused = fused[off:]
    if link_bytes is not None and prefix_bytes:
        link_bytes.add("down_prefix", prefix_bytes)
    downlink_mode = "coeff"
    with tracer.span("unpack", pts=pts):
        need_fn = p_sparse_packed_need if packed else p_sparse_var_need
        need, n, ns = need_fn(fused, mbh, mbw, nscap, cap_rows)
        if note_need is not None:
            note_need(need + off)
        if need > len(fused):  # hint too small: refetch the live content
            fused = np.asarray(full_d)[off:]
            if link_bytes is not None:
                link_bytes.add("down_refetch", fused.nbytes)
        extra = None
        if n > cap_rows:  # rows spilled past the fused buffer
            extra = fetch_rest(buf_d, n, cap_rows)
            if link_bytes is not None:
                link_bytes.add("down_spill", extra.nbytes)
        wire = pfc = None
        if (ns <= nscap and entropy_coder == "cavlc"
                and sparse_native_available()):
            wire = p_sparse_wire_views(
                fused, mbh, mbw, nscap, cap_rows, packed, extra)
        if wire is None:
            unpack = unpack_p_sparse_packed if packed else unpack_p_sparse_var
            pfc, rows = unpack(fused, qp, mbh, mbw, nscap, cap_rows, extra)
            if pfc is None:  # ns > nscap: dense-header fallback fetch
                if dense_d is None:
                    raise RuntimeError(
                        "ns > nscap with no dense fallback buffer (caller "
                        "geometry should make this unreachable)")
                dense = np.asarray(dense_d)
                if link_bytes is not None:
                    link_bytes.add("down_spill", dense.nbytes)
                pfc = unpack_p_compact(dense, rows, qp)
                downlink_mode = "dense"
    t_unpacked = time.perf_counter()
    with tracer.span("pack", pts=pts):
        if wire is not None:
            nal = pack_slice_p_sparse_native(
                wire, params, frame_num, qp, ltr_ref=ltr_ref,
                mark_ltr=mark_ltr, mmco_evict=mmco_evict, first_mb=first_mb)
            skipped = mbh * mbw - wire.ns
        elif entropy_coder == "cabac":
            # a Main-profile stream cannot mix in CAVLC slices
            # (entropy_coding_mode_flag is PPS-scoped) — the coefficient
            # fallback packs through the host CABAC coder instead
            from selkies_tpu.models.h264.cabac import pack_slice_p_cabac

            nal = pack_slice_p_cabac(
                pfc, params, frame_num, ltr_ref=ltr_ref,
                mark_ltr=mark_ltr, mmco_evict=mmco_evict,
                first_mb=first_mb, cabac_init_idc=cabac_init_idc)
            skipped = int(pfc.skip.sum())
        else:
            nal = pack_slice_p_fast(
                pfc, params, frame_num=frame_num, ltr_ref=ltr_ref,
                mark_ltr=mark_ltr, mmco_evict=mmco_evict, first_mb=first_mb)
            skipped = int(pfc.skip.sum())
    return nal, skipped, t_unpacked, downlink_mode
