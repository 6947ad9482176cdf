"""Pallas fused motion-estimation + motion-compensation kernel.

Replaces encoder_core.hier_me_mc's two lax.scan walks (cost + pred) with
ONE kernel that keeps each MB row's reference window resident in VMEM:

  * grid = (mbh,): one program per 16-pixel MB row;
  * the luma/chroma reference windows for the row are DMA'd HBM->VMEM
    once (the XLA scans re-read the full padded plane from HBM for every
    one of the ~76 candidates — the dominant cost of the device step);
  * per-candidate SAD reduces 16x16 blocks via an MXU matmul against a
    0/1 block-indicator matrix (bf16 halves, exact: SAD*scale + rank
    < 2^23);
  * cost argmin and prediction selection fuse into the same candidate
    loop — a running min with payload blend, so the winner's luma and
    half-pel chroma prediction are produced in the same pass.

Bit-exactness contract: identical outputs to encoder_core.hier_me_mc
(tests/test_pallas_me.py asserts array equality), which mirrors
numpy_ref.hier_search_me + mc_luma/mc_chroma. All integer quantities
stay below 2^23 so the f32 cost path is exact; chroma bilinear runs in
int32 inside the kernel.

The reference's analogue of this file is NVENC silicon
(gstwebrtc_app.py:260-367) — there is nothing to port; this is the
TPU-native design the hardware wants.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from selkies_tpu.models.h264.numpy_ref import MV_PAD

_LANES = 128
_LUMA_WIN = 96  # rows of padded luma ref per program: 16 + 2*MV_PAD = 96
_CHROMA_WIN = 96  # rows of padded chroma ref per program (needs 8+2*(MV_PAD//2+1))
_CAND_GROUP = 8  # candidates per fat row-select matmul (G*16 = 128 MXU rows)


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _me_mc_kernel(cand_ref, cur_ref, ry_ref, ru_ref, rv_ref, m_ref, mt_ref,
                  mct_ref, predy_ref, predu_ref, predv_ref, mvx_ref, mvy_ref,
                  ry_w, ru_w, rv_w, sems):
    h16, w = cur_ref.shape
    cw = predu_ref.shape[1]
    ncand = cand_ref.shape[0]
    i = pl.program_id(0)

    cy_dma = pltpu.make_async_copy(ry_ref.at[pl.ds(i * 16, _LUMA_WIN), :], ry_w, sems.at[0])
    cu_dma = pltpu.make_async_copy(ru_ref.at[pl.ds(i * 8, _CHROMA_WIN), :], ru_w, sems.at[1])
    cv_dma = pltpu.make_async_copy(rv_ref.at[pl.ds(i * 8, _CHROMA_WIN), :], rv_w, sems.at[2])
    cy_dma.start()
    cu_dma.start()
    cv_dma.start()
    cy_dma.wait()
    cu_dma.wait()
    cv_dma.wait()

    cur = cur_ref[:]  # (16, w) f32
    predy_ref[:] = jnp.zeros((16, w), jnp.int32)
    predu_ref[:] = jnp.zeros((8, cw), jnp.int32)
    predv_ref[:] = jnp.zeros((8, cw), jnp.int32)

    wp = ry_w.shape[1]
    cwp = ru_w.shape[1]
    # bf16 windows for the one-hot row-select matmuls: pixel values
    # <= 255 are exact in bf16 and each dot product has exactly one
    # nonzero term, so the f32-accumulated result is exact at 2x MXU rate
    winf = ry_w[:].astype(jnp.float32).astype(jnp.bfloat16)  # (96, wp)
    ruf = ru_w[:].astype(jnp.float32).astype(jnp.bfloat16)
    rvf = rv_w[:].astype(jnp.float32).astype(jnp.bfloat16)

    # scale = next power of two above ncand (static; matches golden model)
    scale = float(1 << int(ncand - 1).bit_length())

    # candidates are processed in groups of G: one fat (G*16, 96) one-hot
    # row-select matmul materializes all G shifted row-sets per step (the
    # MXU is ~idle at 16 rows; 128 rows is its native height), then G
    # cheap vector updates fold each candidate into the running best.
    G = _CAND_GROUP
    n_groups = ncand // G

    m_bf = m_ref[:].astype(jnp.bfloat16)  # 0/1 block-indicator, exact
    row_iota = jax.lax.broadcasted_iota(jnp.int32, (G * 16, _LUMA_WIN), 0)
    col_iota = jax.lax.broadcasted_iota(jnp.int32, (G * 16, _LUMA_WIN), 1)
    row_iota9 = jax.lax.broadcasted_iota(jnp.int32, (9, _CHROMA_WIN), 0)
    col_iota9 = jax.lax.broadcasted_iota(jnp.int32, (9, _CHROMA_WIN), 1)

    def body(g, carry):
        best, mvx, mvy = carry
        c0 = g * G
        dys = [cand_ref[c0 + k, 1] for k in range(G)]
        dxs = [cand_ref[c0 + k, 0] for k in range(G)]
        # win row for stacked row rr = 16k + r is MV_PAD + dy_k + r
        dy_rows = jnp.concatenate(
            [jnp.full((16, 1), d, jnp.int32) for d in dys], axis=0)
        sel = (col_iota == (row_iota % 16) + dy_rows + MV_PAD).astype(jnp.bfloat16)
        rows_g = jnp.dot(sel, winf, preferred_element_type=jnp.float32)  # (G*16, wp)

        shs = []
        rowsums = []
        for k in range(G):
            sh = pltpu.roll(rows_g[16 * k:16 * k + 16, :],
                            wp - MV_PAD - dxs[k], 1)[:, 0:w]
            shs.append(sh)
            rowsums.append(jnp.sum(jnp.abs(cur - sh), axis=0, keepdims=True))
        rs = jnp.concatenate(rowsums, axis=0).astype(jnp.int32)  # (G, w)
        # per-MB SAD = rs x block matrix. The MXU multiplies in bf16,
        # exact only for integers <= 256, and a column sum reaches
        # 16*255: split it into two exact bf16 halves, rs = 256*hi + lo
        hi = jnp.right_shift(rs, 8).astype(jnp.float32).astype(jnp.bfloat16)
        lo = jnp.bitwise_and(rs, 255).astype(jnp.float32).astype(jnp.bfloat16)
        mbsum = 256.0 * jnp.dot(hi, m_bf, preferred_element_type=jnp.float32) \
            + jnp.dot(lo, m_bf, preferred_element_type=jnp.float32)  # (G, 128)

        for k in range(G):
            c = c0 + k
            cost = mbsum[k:k + 1, :] * scale + c.astype(jnp.float32)
            better = cost < best
            best = jnp.where(better, cost, best)
            bf = better.astype(jnp.float32)
            dx, dy = dxs[k], dys[k]
            mvx = jnp.where(better, dx, mvx)
            mvy = jnp.where(better, dy, mvy)
            sh = shs[k]

            # prediction blend only when this candidate actually won some
            # MB: typical rows improve a handful of times over ~76 cands
            @pl.when(jnp.max(bf) > 0.0)
            def _(bf=bf, sh=sh, dx=dx, dy=dy):
                mask_y = jnp.dot(bf, mt_ref[:], preferred_element_type=jnp.float32)
                predy_ref[:] = jnp.where(mask_y > 0.5, sh.astype(jnp.int32), predy_ref[:])

                # chroma half-pel bilinear (8.4.2.2.2); one-hot select is
                # exact in f32 (values <= 255), arithmetic in int32
                cx = jax.lax.shift_right_arithmetic(dx, 1)
                cyy = jax.lax.shift_right_arithmetic(dy, 1)
                xf = 4 * jax.lax.bitwise_and(dx, 1)
                yf = 4 * jax.lax.bitwise_and(dy, 1)
                selc = (col_iota9 == row_iota9 + (MV_PAD + cyy)).astype(jnp.bfloat16)
                mask_c = jnp.dot(bf, mct_ref[:], preferred_element_type=jnp.float32) > 0.5

                def blend(winc):
                    rows9 = jnp.dot(selc, winc, preferred_element_type=jnp.float32)
                    rot = pltpu.roll(rows9, cwp - MV_PAD - cx, 1).astype(jnp.int32)
                    a = rot[0:8, 0:cw]
                    b = rot[0:8, 1:cw + 1]
                    cc = rot[1:9, 0:cw]
                    dd = rot[1:9, 1:cw + 1]
                    return jax.lax.shift_right_arithmetic(
                        (8 - xf) * (8 - yf) * a + xf * (8 - yf) * b
                        + (8 - xf) * yf * cc + xf * yf * dd + 32, 6)

                predu_ref[:] = jnp.where(mask_c, blend(ruf), predu_ref[:])
                predv_ref[:] = jnp.where(mask_c, blend(rvf), predv_ref[:])

        return best, mvx, mvy

    init = (
        jnp.full((1, _LANES), 3.4e38, jnp.float32),
        jnp.zeros((1, _LANES), jnp.int32),
        jnp.zeros((1, _LANES), jnp.int32),
    )
    _, mvx, mvy = jax.lax.fori_loop(0, n_groups, body, init)
    mvx_ref[pl.ds(i, 1), :] = mvx
    mvy_ref[pl.ds(i, 1), :] = mvy


@functools.partial(jax.jit, static_argnames=("interpret",))
def _me_mc_call(cands, cur, ry_pad, ru_pad, rv_pad, interpret=False):
    h, w = cur.shape
    mbh, mbw = h // 16, w // 16
    ch, cw = h // 2, w // 2
    if mbw > _LANES:
        raise ValueError(f"width {w} exceeds the kernel's {_LANES}-MB row limit")
    ncand = cands.shape[0]

    # pad refs so every program's DMA window is in-bounds
    wp = _round_up(w + 2 * MV_PAD, _LANES)
    hp = _round_up(16 * (mbh - 1) + _LUMA_WIN, 32)
    cwp = _round_up(cw + 2 * MV_PAD, _LANES)
    chp = _round_up(8 * (mbh - 1) + _CHROMA_WIN, 32)
    # int32 planes: tpu.DynamicRotate (the in-kernel shift) is 32-bit only
    ry = jnp.pad(ry_pad.astype(jnp.int32),
                 ((0, hp - ry_pad.shape[0]), (0, wp - ry_pad.shape[1])))
    ru = jnp.pad(ru_pad.astype(jnp.int32),
                 ((0, chp - ru_pad.shape[0]), (0, cwp - ru_pad.shape[1])))
    rv = jnp.pad(rv_pad.astype(jnp.int32),
                 ((0, chp - rv_pad.shape[0]), (0, cwp - rv_pad.shape[1])))

    # 0/1 block-indicator mats: M sums 16-pixel groups, Mc masks 8-pixel
    # groups; MT/McT broadcast an MB-lane mask back onto pixels
    cols = np.arange(w) // 16
    m = jnp.asarray((cols[:, None] == np.arange(_LANES)[None, :]).astype(np.float32))
    ccols = np.arange(cw) // 8
    mct = jnp.asarray((np.arange(_LANES)[:, None] == ccols[None, :]).astype(np.float32))

    grid = (mbh,)
    in_specs = [
            pl.BlockSpec(memory_space=pltpu.SMEM),  # cands (ncand, 2)
            pl.BlockSpec((16, w), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.ANY),  # ry (DMA'd manually)
            pl.BlockSpec(memory_space=pltpu.ANY),  # ru
            pl.BlockSpec(memory_space=pltpu.ANY),  # rv
            pl.BlockSpec((w, _LANES), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((_LANES, w), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((_LANES, cw), lambda i: (0, 0), memory_space=pltpu.VMEM),
    ]
    out_specs = [
            pl.BlockSpec((16, w), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((8, cw), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((8, cw), lambda i: (i, 0), memory_space=pltpu.VMEM),
            # mv outputs ride one full-array VMEM block (grid is sequential
            # on TPU); each program writes its own row
            pl.BlockSpec((mbh, _LANES), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((mbh, _LANES), lambda i: (0, 0), memory_space=pltpu.VMEM),
    ]
    predy, predu, predv, mvx, mvy = pl.pallas_call(
        _me_mc_kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=[
            jax.ShapeDtypeStruct((h, w), jnp.int32),
            jax.ShapeDtypeStruct((ch, cw), jnp.int32),
            jax.ShapeDtypeStruct((ch, cw), jnp.int32),
            jax.ShapeDtypeStruct((mbh, _LANES), jnp.int32),
            jax.ShapeDtypeStruct((mbh, _LANES), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((_LUMA_WIN, wp), jnp.int32),
            pltpu.VMEM((_CHROMA_WIN, cwp), jnp.int32),
            pltpu.VMEM((_CHROMA_WIN, cwp), jnp.int32),
            pltpu.SemaphoreType.DMA((3,)),
        ],
        interpret=interpret,
    )(cands, cur.astype(jnp.float32), ry, ru, rv, m, jnp.transpose(m), mct)
    mvs = jnp.stack([mvx[:, :mbw], mvy[:, :mbw]], axis=-1)
    return mvs, predy, predu, predv


def hier_me_mc_pallas(cur, ref_y, ry_pad, ru_pad, rv_pad, *, interpret=None,
                      dy_max=None, dx_max=None, coarse=None):
    """Drop-in replacement for encoder_core.hier_me_mc (same signature,
    bit-identical outputs). Coarse candidate voting stays in XLA (tiny);
    the refine+MC walk runs in the fused kernel.

    dy_max (static int) band-clamps the candidate window for the
    band-sliced step (encoder_core.encode_band_p_planes): with a clamped
    vertical reach every row each program DMAs from the `ry_pad` window
    into VMEM is real reference content from the band's halo slab, so a
    band's kernel never depends on rows resident on another chip. The
    kernel body is unchanged — the clamp lands in the candidate list,
    keeping the rank/tie-break order bit-identical to hier_me_mc.
    dx_max is the horizontal mirror for the 2D tile grid
    (encoder_core.encode_tile_p_planes), and ``coarse`` injects the tile
    grid's row-merged (TOPK, 2) coarse candidate list — both land in the
    candidate list exactly like dy_max; the kernel is untouched."""
    from selkies_tpu.models.h264 import encoder_core as core

    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if coarse is None:
        coarse = core.coarse_vote_candidates_jnp(cur, ref_y)
    cands = core._refine_cands_jnp(coarse, dy_max, dx_max)
    # pad to a multiple of the kernel's candidate group with zero-MV
    # duplicates: same SAD as the rank-0 zero MV but a later rank, so a
    # padded slot can never win (cost = sad*scale + rank is all-distinct)
    pad = (-cands.shape[0]) % _CAND_GROUP
    if pad:
        cands = jnp.concatenate([cands, jnp.zeros((pad, 2), jnp.int32)])
    return _me_mc_call(cands, cur, ry_pad, ru_pad, rv_pad, interpret=interpret)
