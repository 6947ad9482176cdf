"""CAVLC table validation harness (dev tool).

Crafts minimal H.264 streams whose residual bits exercise one VLC-table
slot at a time, decodes them with FFmpeg (via cv2), and compares decoded
pixels against the bit-exact expectation from the numpy golden model.
FFmpeg's stderr is captured per probe (os.dup2) to classify failures
("negative number of zero coeffs", "corrupted macroblock", desync in later
MBs, ...).

Usage: `PYTHONPATH=. python tools/cavlc_probe.py` (no jax needed).
"""

from __future__ import annotations

import os
import tempfile

import cv2
import numpy as np

from selkies_tpu.models.h264.bitstream import SLICE_I, StreamParams, write_pps, write_slice_header, write_sps
from selkies_tpu.models.h264.cavlc import nc_context as _nc_ctx, residual_block
from selkies_tpu.models.h264.numpy_ref import (
    _dc_pred_chroma,
    _dc_pred_luma,
    dequant4,
    dequant_chroma_dc,
    dequant_luma_dc,
    idct4,
    merge_blocks,
)
from selkies_tpu.models.h264.tables import ZIGZAG_FLAT, LUMA_BLOCK_ORDER
from selkies_tpu.utils.bits import BitWriter, annexb_nal

QP = 20  # fixed probe QP


def _unscan16(scan: np.ndarray) -> np.ndarray:
    out = np.zeros(16, np.int64)
    out[ZIGZAG_FLAT] = scan
    return out.reshape(4, 4)


def decode_file(path: str):
    """Decode one file, returning (frames, stderr_text)."""
    errfd = tempfile.TemporaryFile()
    saved = os.dup(2)
    os.dup2(errfd.fileno(), 2)
    try:
        cap = cv2.VideoCapture(path)
        frames = []
        while True:
            ok, f = cap.read()
            if not ok:
                break
            frames.append(f)
        cap.release()
    finally:
        os.dup2(saved, 2)
        os.close(saved)
    errfd.seek(0)
    err = errfd.read().decode("utf-8", "replace")
    errfd.close()
    return frames, err


def probe_luma_dc(dc_scan: list[int], tmpdir: str, name: str = "p"):
    """Single-MB frame; luma DC block = dc_scan (zigzag order), no AC/chroma.

    Returns (ok, mae, stderr). ok means decoded pixel block matches the
    golden-model expectation within RGB-conversion tolerance.
    """
    p = StreamParams(width=16, height=16, qp=QP)
    w = BitWriter()
    write_slice_header(w, p, SLICE_I, 0, idr=True)
    w.write_ue(1 + 2 + 0 + 0)  # mb_type: I16x16, DC pred, cbp 0/0
    w.write_ue(0)  # intra_chroma_pred_mode DC
    w.write_se(0)  # mb_qp_delta
    residual_block(w, np.array(dc_scan, np.int64), 16, 0)
    w.rbsp_trailing_bits()
    data = write_sps(p) + write_pps(p) + annexb_nal(3, 5, w.get_bytes())
    path = os.path.join(tmpdir, f"{name}.h264")
    with open(path, "wb") as fh:
        fh.write(data)
    frames, err = decode_file(path)
    if not frames:
        return False, None, err
    # expectation: pred 128 + idct(dequant DC), uniform per 4x4 block
    deq = np.zeros((4, 4, 4, 4), np.int64)
    deq[..., 0, 0] = dequant_luma_dc(_unscan16(np.array(dc_scan, np.int64)), QP)
    recon = np.clip(merge_blocks(idct4(deq)) + 128, 0, 255)
    exp_rgb = np.clip((recon - 16) * 1.164383 + 0.5, 0, 255)
    got = frames[0][..., 1].astype(float)  # G channel; gray content
    mae = float(np.abs(got - exp_rgb).mean())
    return mae < 2.0, mae, err


def probe_luma_dc_and_ac(dc_scan, ac_blocks: dict[int, list[int]], tmpdir, name="p2", mbs=1):
    """One MB with cbp_luma=15: DC block + specified AC blocks (blk->scan15).

    Exercises nC context transitions across the 16 AC blocks.
    """
    p = StreamParams(width=16 * mbs, height=16, qp=QP)
    w = BitWriter()
    write_slice_header(w, p, SLICE_I, 0, idr=True)
    luma_tc = np.zeros((4, 4 * mbs), np.int64)
    deq_all = []
    for mb in range(mbs):
        w.write_ue(1 + 2 + 0 + 12)  # I16x16 DC pred, cbp_luma 15, chroma 0
        w.write_ue(0)
        w.write_se(0)
        # DC block nC
        bx0 = mb * 4
        nc = _nc_ctx(luma_tc, bx0, 0)
        residual_block(w, np.array(dc_scan, np.int64), 16, nc)
        deq = np.zeros((4, 4, 4, 4), np.int64)
        deq[..., 0, 0] = dequant_luma_dc(_unscan16(np.array(dc_scan, np.int64)), QP)
        for blk, (x4, y4) in enumerate(LUMA_BLOCK_ORDER):
            scan15 = np.array(ac_blocks.get(blk, [0] * 15), np.int64)
            nc = _nc_ctx(luma_tc, mb * 4 + x4, y4)
            tc = residual_block(w, scan15, 15, nc)
            luma_tc[y4, mb * 4 + x4] = tc
            full = np.zeros(16, np.int64)
            full[1:] = scan15
            unsc = np.zeros(16, np.int64)
            unsc[ZIGZAG_FLAT] = full
            dq = dequant4(unsc.reshape(4, 4), QP)
            dq[0, 0] = deq[y4, x4, 0, 0]
            deq[y4, x4] = dq
        deq_all.append(deq)
    w.rbsp_trailing_bits()
    data = write_sps(p) + write_pps(p) + annexb_nal(3, 5, w.get_bytes())
    path = os.path.join(tmpdir, f"{name}.h264")
    with open(path, "wb") as fh:
        fh.write(data)
    frames, err = decode_file(path)
    if not frames:
        return False, None, err
    recon = _chain_luma_recon(deq_all)
    exp_rgb = np.clip((recon - 16) * 1.164383 + 0.5, 0, 255)
    got = frames[0][..., 1].astype(float)
    mae = float(np.abs(got - exp_rgb).mean())
    return mae < 2.0, mae, err


def _chain_luma_recon(deq_all):
    """Sequential recon of a row of MBs with DC-from-left prediction."""
    mbs = []
    prev = None
    for d in deq_all:
        left = prev[:, -1] if prev is not None else None
        pred = _dc_pred_luma(None, left)
        prev = np.clip(merge_blocks(idct4(d)) + pred, 0, 255)
        mbs.append(prev)
    return np.concatenate(mbs, axis=1)


def _chain_chroma_recon(deq_all):
    """Sequential recon of a row of chroma 8x8 with DC-from-left prediction."""
    mbs = []
    prev = None
    for d in deq_all:
        left = prev[:, -1] if prev is not None else None
        pred = _dc_pred_chroma(None, left)
        prev = np.clip(merge_blocks(idct4(d)) + pred, 0, 255)
        mbs.append(prev)
    return np.concatenate(mbs, axis=1)



def make_scan(total: int, trailing: int, maxlen: int = 16, gap_pattern: list[int] | None = None):
    """Build a scan-order coeff list with given TotalCoeff/TrailingOnes.

    Non-trailing levels use magnitude 3 (so they are not counted as T1s);
    gap_pattern optionally inserts zeros between coefficients.
    """
    vals = [3] * (total - trailing) + [1] * trailing
    # alternate signs for variety
    vals = [v if i % 2 == 0 else -v for i, v in enumerate(vals)]
    out = []
    gaps = gap_pattern or [0] * total
    for v, g in zip(vals, gaps):
        out.extend([0] * g)
        out.append(v)
    assert len(out) <= maxlen, (total, trailing, gaps)
    out.extend([0] * (maxlen - len(out)))
    return out


def sweep_nc0(tmpdir: str):
    """Validate coeff_token nC<2 + total_zeros + run_before via DC probes."""
    failures = []
    for total in range(0, 17):
        for t1 in range(0, min(3, total) + 1):
            if total == 0 and t1 > 0:
                continue
            scan = make_scan(total, t1) if total else [0] * 16
            ok, mae, err = probe_luma_dc(scan, tmpdir, f"tc{total}t{t1}")
            if not ok:
                failures.append((f"TC={total} T1={t1} tz=0", mae, err.strip().splitlines()[:2]))
    # total_zeros sweep: leading zeros before the run of coeffs
    for total in range(1, 16):
        for tz in range(0, 16 - total + 1):
            scan = [0] * tz + make_scan(total, min(total, 1), maxlen=16 - tz)
            ok, mae, err = probe_luma_dc(scan, tmpdir, f"tz{total}_{tz}")
            if not ok:
                failures.append((f"TC={total} tz={tz}", mae, err.strip().splitlines()[:2]))
    # run_before: distribute zeros between coeffs
    for total in range(2, 8):
        for run in range(1, 15 - total):
            gaps = [0] * (total - 1) + [run]  # gap before last coeff
            if total + run > 16:
                continue
            scan = make_scan(total, 1, gap_pattern=gaps)
            ok, mae, err = probe_luma_dc(scan, tmpdir, f"rb{total}_{run}")
            if not ok:
                failures.append((f"TC={total} run={run}", mae, err.strip().splitlines()[:2]))
    return failures


def probe_chroma(cb_dc_scan, cr_dc_scan, cb_ac: dict[int, list[int]] | None, cr_ac: dict[int, list[int]] | None, tmpdir, name="pc"):
    """Single-MB frame exercising chroma DC (nC=-1) and optionally chroma AC.

    Luma: DC-only zeros. cbp_chroma = 2 if any AC given else 1.
    """
    from selkies_tpu.models.h264.numpy_ref import dequant_chroma_dc
    from selkies_tpu.models.h264.tables import CHROMA_BLOCK_ORDER
    from selkies_tpu.models.h264.numpy_ref import chroma_qp

    cbp_chroma = 2 if (cb_ac or cr_ac) else 1
    p = StreamParams(width=16, height=16, qp=QP)
    qpc = chroma_qp(QP)
    w = BitWriter()
    write_slice_header(w, p, SLICE_I, 0, idr=True)
    w.write_ue(1 + 2 + 4 * cbp_chroma)  # I16 DC pred, cbp_luma 0
    w.write_ue(0)  # chroma DC pred
    w.write_se(0)
    residual_block(w, np.zeros(16, np.int64), 16, 0)  # luma DC empty
    for scan in (cb_dc_scan, cr_dc_scan):
        residual_block(w, np.array(scan, np.int64), 4, -1)
    chroma_tc = {0: np.zeros((2, 2), np.int64), 1: np.zeros((2, 2), np.int64)}
    if cbp_chroma == 2:
        for comp, acs in ((0, cb_ac or {}), (1, cr_ac or {})):
            for blk, (x4, y4) in enumerate(CHROMA_BLOCK_ORDER):
                scan15 = np.array(acs.get(blk, [0] * 15), np.int64)
                cnt = chroma_tc[comp]
                left = cnt[y4, x4 - 1] if x4 > 0 else None
                top = cnt[y4 - 1, x4] if y4 > 0 else None
                nc = ((int(left) + int(top) + 1) >> 1) if (left is not None and top is not None) else int(left if left is not None else (top if top is not None else 0))
                tc = residual_block(w, scan15, 15, nc)
                cnt[y4, x4] = tc
    w.rbsp_trailing_bits()
    data = write_sps(p) + write_pps(p) + annexb_nal(3, 5, w.get_bytes())
    path = os.path.join(tmpdir, f"{name}.h264")
    with open(path, "wb") as fh:
        fh.write(data)
    frames, err = decode_file(path)
    if not frames:
        return False, None, err
    # expected chroma recon per component
    recons = []
    for comp, dc_scan, acs in ((0, cb_dc_scan, cb_ac or {}), (1, cr_dc_scan, cr_ac or {})):
        dc22 = np.array(dc_scan, np.int64).reshape(2, 2)
        deq = np.zeros((2, 2, 4, 4), np.int64)
        for blk, (x4, y4) in enumerate(CHROMA_BLOCK_ORDER):
            full = np.zeros(16, np.int64)
            full[1:] = np.array(acs.get(blk, [0] * 15), np.int64)
            unsc = np.zeros(16, np.int64)
            unsc[ZIGZAG_FLAT] = full
            deq[y4, x4] = dequant4(unsc.reshape(4, 4), qpc)
        deq[..., 0, 0] = dequant_chroma_dc(dc22, qpc)
        recons.append(np.clip(merge_blocks(idct4(deq)) + 128, 0, 255).astype(float))
    u_r, v_r = recons  # 8x8 each
    up = np.repeat(np.repeat(u_r, 2, 0), 2, 1)
    vp = np.repeat(np.repeat(v_r, 2, 0), 2, 1)
    yf = (128.0 - 16) * 1.164383
    exp_b = np.clip(yf + 2.017232 * (up - 128) + 0.5, 0, 255)
    exp_r = np.clip(yf + 1.596027 * (vp - 128) + 0.5, 0, 255)
    got = frames[0].astype(float)
    mae = float(np.abs(got[..., 0] - exp_b).mean() + np.abs(got[..., 2] - exp_r).mean()) / 2
    return mae < 2.0, mae, err


def sweep_higher_nc(tmpdir: str):
    """coeff_token tables for nC in 2..3, 4..7, >=8 via in-MB neighbour control."""
    failures = []
    # blk3's nC = (tc(blk1) + tc(blk2) + 1) >> 1
    for nbr_a, nbr_b, label in ((2, 3, "nC=3"), (2, 2, "nC=2"), (5, 5, "nC=5"), (4, 4, "nC=4"), (7, 7, "nC=7"), (16, 16, "nC>=8... n/a", ), (8, 8, "nC=8"), (15, 15, "nC=15")):
        if nbr_a > 15:
            continue
        for total in range(0, 16):
            for t1 in range(0, min(3, total) + 1):
                ac = {
                    1: make_scan(nbr_a, min(nbr_a, 1), maxlen=15),
                    2: make_scan(nbr_b, min(nbr_b, 1), maxlen=15),
                    3: make_scan(total, t1, maxlen=15) if total else [0] * 15,
                }
                ok, mae, err = probe_luma_dc_and_ac([0] * 16, ac, tmpdir, f"h{nbr_a}_{total}_{t1}")
                if not ok:
                    failures.append((f"{label} TC={total} T1={t1}", mae, (err or "").strip().splitlines()[:1]))
    return failures


def sweep_dc16_high_nc(tmpdir: str):
    """TC=16 rows of tables nC 2..7 via a 2-MB frame: MB1 DC block sees
    left-neighbour TC from MB0's block 5."""
    failures = []
    for nbr in (2, 3, 4, 5, 6, 7):
        for t1 in range(0, 4):
            # MB0: cbp_luma=15; give block 5 (right edge, top row) TC=nbr.
            # MB1: DC block TC=16, T1=t1.
            ok, mae, err = _probe_two_mb_dc(nbr, 16, t1, tmpdir)
            if not ok:
                failures.append((f"nC={nbr} TC=16 T1={t1}", mae, (err or "").strip().splitlines()[:1]))
    return failures


def _probe_two_mb_dc(nbr_tc: int, total: int, t1: int, tmpdir: str):
    p = StreamParams(width=32, height=16, qp=QP)
    w = BitWriter()
    write_slice_header(w, p, SLICE_I, 0, idr=True)
    luma_tc = np.zeros((4, 8), np.int64)
    deq_all = []
    # MB0 with AC blocks: blocks 5 and 7 and 13,15 on right edge get nbr_tc
    ac0 = {5: make_scan(nbr_tc, min(nbr_tc, 1), maxlen=15)}
    for mbi, (cbp_luma_bit, dc_scan, acs) in enumerate(zip([12, 0], [[0] * 16, make_scan(total, t1)], [ac0, {}])):
        w.write_ue(1 + 2 + 0 + cbp_luma_bit)
        w.write_ue(0)
        w.write_se(0)
        bx0 = mbi * 4
        nc = _nc_ctx(luma_tc, bx0, 0)
        residual_block(w, np.array(dc_scan, np.int64), 16, nc)
        deq = np.zeros((4, 4, 4, 4), np.int64)
        deq[..., 0, 0] = dequant_luma_dc(_unscan16(np.array(dc_scan, np.int64)), QP)
        if cbp_luma_bit:
            for blk, (x4, y4) in enumerate(LUMA_BLOCK_ORDER):
                scan15 = np.array(acs.get(blk, [0] * 15), np.int64)
                nc = _nc_ctx(luma_tc, mbi * 4 + x4, y4)
                tc = residual_block(w, scan15, 15, nc)
                luma_tc[y4, mbi * 4 + x4] = tc
                full = np.zeros(16, np.int64)
                full[1:] = scan15
                unsc = np.zeros(16, np.int64)
                unsc[ZIGZAG_FLAT] = full
                dq = dequant4(unsc.reshape(4, 4), QP)
                dq[0, 0] = deq[y4, x4, 0, 0]
                deq[y4, x4] = dq
        deq_all.append(deq)
    w.rbsp_trailing_bits()
    data = write_sps(p) + write_pps(p) + annexb_nal(3, 5, w.get_bytes())
    path = os.path.join(tmpdir, "two_mb.h264")
    with open(path, "wb") as fh:
        fh.write(data)
    frames, err = decode_file(path)
    if not frames:
        return False, None, err
    recon = _chain_luma_recon(deq_all)
    exp_rgb = np.clip((recon - 16) * 1.164383 + 0.5, 0, 255)
    got = frames[0][..., 1].astype(float)
    mae = float(np.abs(got - exp_rgb).mean())
    return mae < 2.0, mae, err


def probe_chroma_strict(cb0_scan, tmpdir, name="pcs", tail_scan=(3, -3, 1, 0)):
    """4-MB frame: MB0 Cb DC under test, MBs 1-3 carry a fixed known pattern.

    Any misparse in MB0 desyncs the remaining MBs (loud failure); recon
    models chroma DC prediction chains.
    """
    from selkies_tpu.models.h264.numpy_ref import chroma_qp

    n = 4
    qpc = chroma_qp(QP)
    p = StreamParams(width=16 * n, height=16, qp=QP)
    w = BitWriter()
    write_slice_header(w, p, SLICE_I, 0, idr=True)
    scans = [np.array(cb0_scan, np.int64)] + [np.array(tail_scan, np.int64)] * (n - 1)
    for i in range(n):
        w.write_ue(1 + 2 + 4)
        w.write_ue(0)
        w.write_se(0)
        residual_block(w, np.zeros(16, np.int64), 16, 0)
        residual_block(w, scans[i], 4, -1)
        residual_block(w, np.zeros(4, np.int64), 4, -1)
    w.rbsp_trailing_bits()
    data = write_sps(p) + write_pps(p) + annexb_nal(3, 5, w.get_bytes())
    path = os.path.join(tmpdir, f"{name}.h264")
    with open(path, "wb") as fh:
        fh.write(data)
    frames, err = decode_file(path)
    if not frames:
        return False, None, err
    deqs = []
    for s in scans:
        deq = np.zeros((2, 2, 4, 4), np.int64)
        deq[..., 0, 0] = dequant_chroma_dc(s.reshape(2, 2), qpc)
        deqs.append(deq)
    u = _chain_chroma_recon(deqs).astype(float)
    up = np.repeat(np.repeat(u, 2, 0), 2, 1)
    exp_b = np.clip(130.41 + 2.017232 * (up - 128) + 0.5, 0, 255)
    mae = float(np.abs(frames[0][..., 0].astype(float) - exp_b).mean())
    return (mae < 1.5 and not err.strip()), mae, err


def sweep_chroma(tmpdir: str):
    failures = []
    # chroma DC coeff_token (nC=-1) + chroma-DC total_zeros
    for total in range(0, 5):
        for t1 in range(0, min(3, total) + 1):
            for tz in range(0, 4 - total + 1):
                if total == 0 and (t1 or tz):
                    continue
                scan = ([0] * tz + make_scan(total, t1, maxlen=4 - tz)) if total else [0] * 4
                ok, mae, err = probe_chroma_strict(scan, tmpdir, "cdc")
                if not ok:
                    failures.append((f"cdc TC={total} T1={t1} tz={tz}", mae, (err or "").strip().splitlines()[:1]))
    # chroma AC spot checks (shares luma tables)
    for total in (1, 4, 9, 15):
        ac = {0: make_scan(total, min(total, 1), maxlen=15), 3: make_scan(min(total, 15), 0, maxlen=15)}
        ok, mae, err = probe_chroma([1, 0, 0, 0], [0] * 4, ac, None, tmpdir, "cac")
        if not ok:
            failures.append((f"cac TC={total}", mae, (err or "").strip().splitlines()[:1]))
    return failures


def sweep_run_before_full(tmpdir: str):
    """Cover (zeros_left, run) combos beyond the diagonal."""
    failures = []
    for zl in range(1, 14):
        for run in range(0, min(zl, 14) + 1):
            # two coeffs: [gap=run before last coeff], rest zeros leading
            lead = zl - run
            if lead < 0 or 2 + zl > 16:
                continue
            scan = [0] * lead + [3] + [0] * run + [1]
            scan += [0] * (16 - len(scan))
            ok, mae, err = probe_luma_dc(scan, tmpdir, f"rbf{zl}_{run}")
            if not ok:
                failures.append((f"rb zl={zl} run={run}", mae, (err or "").strip().splitlines()[:1]))
    return failures


if __name__ == "__main__":
    import sys

    allfail = []
    with tempfile.TemporaryDirectory() as td:
        for name, fn in [
            ("nC<2", sweep_nc0),
            ("run_before full", sweep_run_before_full),
            ("higher nC", sweep_higher_nc),
            ("DC16 high nC", sweep_dc16_high_nc),
            ("chroma", sweep_chroma),
        ]:
            fails = fn(td)
            print(f"{name} sweep: {len(fails)} failures")
            for f in fails[:40]:
                print("  ", f)
            allfail += fails
    sys.exit(1 if allfail else 0)
