"""Host-side reference mirror of the device sparse-P downlink packers.

`build_p_sparse_wire` produces byte-identical buffers to
encoder_core.pack_p_sparse_var / pack_p_sparse_packed from a host
PFrameCoeffs — the input generator for the sparse-native equivalence
suite (tests/test_sparse_native_pack.py) and for tools/profile_pack.py,
which must exercise the completion path at arbitrary densities and
geometries without a device in the loop. The
mirror is validated against the device packers' unpack contract by the
round-trip tests; it is NOT a production path.

`synth_pfc` generates random-but-consistent P frames: skip MBs carry
zero residual and the 8.4.1.1-derived MV (the invariants encode_frame_p
guarantees), so a wire built from one round-trips exactly.
"""

from __future__ import annotations

import numpy as np

from selkies_tpu.models.h264.native import derive_skip_mvs_fast
from selkies_tpu.models.h264.numpy_ref import PFrameCoeffs

__all__ = ["build_p_sparse_wire", "synth_pfc"]


def _bitpack32(bits: np.ndarray) -> np.ndarray:
    """(M,) bool -> (ceil(M/32),) int32, zero-padded (encoder_core._bitpack32)."""
    pad = (-len(bits)) % 32
    b = np.concatenate([bits.astype(np.int64), np.zeros(pad, np.int64)])
    words = (b.reshape(-1, 32) << np.arange(32, dtype=np.int64)).sum(-1)
    return (words & 0xFFFFFFFF).astype(np.uint32).view(np.int32)


def _p_rows(pfc: PFrameCoeffs):
    """PFrameCoeffs -> (rows (M*26, 16) int16, per-MB flags, mv/info words)
    in the P_ENTRIES row layout (encoder_core._p_components)."""
    mbh, mbw = pfc.skip.shape
    m = mbh * mbw
    rows = np.zeros((m, 26, 16), np.int16)
    rows[:, :16] = np.asarray(pfc.luma_ac).reshape(m, 16, 16)
    rows[:, 16:24] = np.asarray(pfc.chroma_ac).reshape(m, 8, 16)
    rows[:, 24:26, :4] = np.asarray(pfc.chroma_dc).reshape(m, 2, 4)
    flat = rows.reshape(m * 26, 16)
    fl = (flat != 0).any(-1)
    mbinfo = (
        (fl.reshape(m, 26).astype(np.int64) << np.arange(26, dtype=np.int64))
        .sum(-1).astype(np.int32)
    )
    mvs = np.asarray(pfc.mvs, np.int64)
    mv_words = ((mvs[..., 0] & 0xFFFF) | ((mvs[..., 1] << 16) & 0xFFFFFFFF))
    mv_words = mv_words.reshape(-1).astype(np.uint32).view(np.int32)
    return flat, fl, mv_words, mbinfo


def build_p_sparse_wire(pfc: PFrameCoeffs, nscap: int, cap_rows: int,
                        packed: bool = False, density_pct: int = 75):
    """-> (fused int16, dense_header int32, buf (M*26, 16) int16), the
    exact triple the device steps downlink (fused layout per
    pack_p_sparse_var, or pack_p_sparse_packed when `packed`)."""
    mbh, mbw = pfc.skip.shape
    m = mbh * mbw
    sw = (m + 31) // 32
    flat, fl, mv_words, mbinfo = _p_rows(pfc)
    n = int(fl.sum())
    buf = np.zeros((m * 26, 16), np.int16)
    buf[:n] = flat[fl]
    skip_flat = np.asarray(pfc.skip, bool).reshape(-1)
    skip_words = _bitpack32(skip_flat)
    ns = int((~skip_flat).sum())
    mv_c = mv_words[~skip_flat][:nscap]
    info_c = mbinfo[~skip_flat][:nscap]
    pairs16 = np.stack([mv_c, info_c], -1).reshape(-1).view(np.int16)
    held = min(n, cap_rows)

    if packed:
        rows = buf[:cap_rows]  # clamps when the geometry holds fewer rows
        sig = rows != 0
        bitmap16 = (
            (sig.astype(np.int64) << np.arange(16, dtype=np.int64)).sum(-1)
            & 0xFFFF
        ).astype(np.uint16).view(np.int16)
        counts = sig.sum(-1)
        width = 4 * ((counts + 3) // 4)
        off = np.cumsum(width) - width
        nw = int(width.sum())
        vals16 = np.zeros(16 * len(rows) + 1, np.int16)
        rr, cc = np.nonzero(sig)
        if len(rr):
            rank = (np.cumsum(sig, axis=1) - 1)[rr, cc]
            vals16[off[rr] + rank] = rows[rr, cc]
        vals16 = vals16[: 16 * len(rows)]
        dense_flag = int((held + nw) * 100 > (16 * held) * density_pct)
        meta = np.array([n, mbh, mbw, ns, nw, dense_flag], np.int32)
        base = 12 + 2 * sw
        fused = np.zeros(base + 4 * nscap + cap_rows + 16 * cap_rows, np.int16)
        fused[:base] = np.concatenate([meta, skip_words]).view(np.int16)
        fused[base : base + len(pairs16)] = pairs16
        rows_off = base + 4 * min(ns, nscap)
        if dense_flag:
            fused[rows_off : rows_off + 16 * len(rows)] = rows.reshape(-1)
        else:
            fused[rows_off : rows_off + len(rows)] = bitmap16
            fused[rows_off + held : rows_off + held + len(vals16)] = vals16
    else:
        meta = np.array([n, mbh, mbw, ns], np.int32)
        base = 8 + 2 * sw
        fused = np.zeros(base + 4 * nscap + 16 * cap_rows, np.int16)
        fused[:base] = np.concatenate([meta, skip_words]).view(np.int16)
        fused[base : base + len(pairs16)] = pairs16
        rows_off = base + 4 * min(ns, nscap)
        rows = buf[:cap_rows].reshape(-1)  # clamps on tiny geometries
        fused[rows_off : rows_off + len(rows)] = rows

    dense = np.concatenate([
        np.array([n, mbh, mbw, 0], np.int32), mv_words, mbinfo, skip_words,
    ])
    return fused, dense, buf


def synth_pfc(rng: np.random.Generator, mbh: int, mbw: int, *,
              skip_frac: float = 0.9, row_density: float = 0.15,
              lane_density: float = 0.25, big_levels: bool = False,
              qp: int = 30) -> PFrameCoeffs:
    """Random P frame honouring the encoder invariants (skip MBs have
    zero residual and the derived skip MV). `row_density` is the chance
    a coded MB's row is live; `lane_density` the per-lane nonzero chance
    inside a live row; `big_levels` sprinkles escape-coded magnitudes."""
    m = mbh * mbw
    skip = rng.random((mbh, mbw)) < skip_frac
    coded = ~skip.reshape(-1)
    rowmask = (rng.random((m, 26)) < row_density) & coded[:, None]
    lanes = rng.random((m, 26, 16)) < lane_density
    hi = 2400 if big_levels else 30
    vals = rng.integers(-hi, hi + 1, (m, 26, 16))
    rows = np.where(rowmask[..., None] & lanes, vals, 0).astype(np.int16)
    rows[:, 24:26, 4:] = 0  # chroma DC rows carry 4 values only
    mvs = np.zeros((mbh, mbw, 2), np.int32)
    mvs.reshape(-1, 2)[coded] = rng.integers(-32, 33, (int(coded.sum()), 2))
    pfc = PFrameCoeffs(
        mvs=mvs,
        skip=skip,
        luma_ac=rows[:, :16].reshape(mbh, mbw, 4, 4, 4, 4).astype(np.int32),
        chroma_dc=rows[:, 24:26, :4].reshape(mbh, mbw, 2, 2, 2).astype(np.int32),
        chroma_ac=rows[:, 16:24].reshape(mbh, mbw, 2, 2, 2, 4, 4).astype(np.int32),
        qp=qp,
    )
    derive_skip_mvs_fast(pfc.mvs, pfc.skip)
    return pfc
