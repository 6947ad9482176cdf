"""JAX encode core for tpuh264enc: the jit-compiled per-frame device step.

This is the TPU re-design of the encoder matrix's device work (the
reference delegates it to NVENC/VAAPI silicon, gstwebrtc_app.py:260-783):
intra prediction, forward/inverse 4x4 transforms, Hadamard DC paths, and
quantization — everything except bit-serial entropy coding, which stays on
the host (cavlc.py / native/cavlc_pack.cc).

Parallelisation strategy (the reason the prediction-mode policy exists):
  * rows 1..N use Intra16x16 VERTICAL prediction — each MB depends only on
    the reconstructed row above, so one `lax.scan` step processes an
    entire MB row as a single batched tensor op (120 MBs at 1080p).
  * row 0 uses DC prediction (left-only chain) — a short scan over
    columns, paid once per IDR frame.

TPU mapping: the 4x4 DCT/Hadamard transforms are expressed as explicit
add/shift butterflies over batched int32 tensors — pure VPU element-wise
work that XLA fuses with the quantizer (no integer-matmul lowering, no
float roundoff). All arithmetic is int32: the widest intermediate
(|coeff|·MF + f at QP 0) stays under 2^27. QP is a traced scalar, so
rate-control retunes never recompile.

Bit-exactness contract: every op mirrors numpy_ref.py exactly
(tests/test_encoder_core.py asserts array equality), which in turn is
FFmpeg-conformant (tools/cavlc_probe.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from selkies_tpu.models.h264 import tables

_POS_CLASS = np.array(
    [[0 if (i % 2 == 0 and j % 2 == 0) else 1 if (i % 2 and j % 2) else 2 for j in range(4)] for i in range(4)],
    np.int32,
)
_MF_BY_REM = jnp.asarray(np.asarray(tables.QUANT_MF, np.int32)[:, _POS_CLASS])  # (6, 4, 4)
_V_BY_REM = jnp.asarray(np.asarray(tables.DEQUANT_V, np.int32)[:, _POS_CLASS])  # (6, 4, 4)
_CHROMA_QP = jnp.asarray([tables.chroma_qp(q) for q in range(52)], jnp.int32)


def _last(x, i):
    return x[..., i]


def _fdct1d(x):
    """1-D forward core transform along the last axis of (..., 4)."""
    x0, x1, x2, x3 = _last(x, 0), _last(x, 1), _last(x, 2), _last(x, 3)
    s0, s1 = x0 + x3, x1 + x2
    d0, d1 = x0 - x3, x1 - x2
    return jnp.stack([s0 + s1, 2 * d0 + d1, s0 - s1, d0 - 2 * d1], axis=-1)


def fdct4(blocks):
    """Forward 4x4 core transform over (..., 4, 4) int32 blocks (exact)."""
    b = blocks.astype(jnp.int32)
    b = _fdct1d(b)  # transform columns index (last axis = j)
    b = _fdct1d(b.swapaxes(-1, -2)).swapaxes(-1, -2)  # transform rows
    return b


def _idct1d(x):
    """1-D inverse butterfly along the last axis (8.5.12.2 step)."""
    x0, x1, x2, x3 = _last(x, 0), _last(x, 1), _last(x, 2), _last(x, 3)
    e0, e1 = x0 + x2, x0 - x2
    e2 = jnp.right_shift(x1, 1) - x3
    e3 = x1 + jnp.right_shift(x3, 1)
    return jnp.stack([e0 + e3, e1 + e2, e1 - e2, e0 - e3], axis=-1)


def idct4(coeffs):
    """Bit-exact inverse 4x4 transform: horizontal first, then vertical."""
    d = coeffs.astype(jnp.int32)
    d = _idct1d(d)  # horizontal: mix columns within each row
    d = _idct1d(d.swapaxes(-1, -2)).swapaxes(-1, -2)  # vertical
    return jnp.right_shift(d + 32, 6)


def _had1d(x):
    x0, x1, x2, x3 = _last(x, 0), _last(x, 1), _last(x, 2), _last(x, 3)
    s0, s1 = x0 + x1, x2 + x3
    d0, d1 = x0 - x1, x2 - x3
    return jnp.stack([s0 + s1, s0 - s1, d0 - d1, d0 + d1], axis=-1)


def _had4(x):
    """H4 · X · H4 for (..., 4, 4) (H4 symmetric)."""
    x = _had1d(x.astype(jnp.int32))
    return _had1d(x.swapaxes(-1, -2)).swapaxes(-1, -2)


def _had2(x):
    """H2 · X · H2 for (..., 2, 2)."""
    x = x.astype(jnp.int32)
    a = x[..., 0, 0] + x[..., 0, 1]
    b = x[..., 0, 0] - x[..., 0, 1]
    c = x[..., 1, 0] + x[..., 1, 1]
    d = x[..., 1, 0] - x[..., 1, 1]
    return jnp.stack(
        [jnp.stack([a + c, b + d], axis=-1), jnp.stack([a - c, b - d], axis=-1)], axis=-2
    )


def _qparams(qp, intra: bool = True):
    qbits = 15 + qp // 6
    f = jnp.left_shift(jnp.int32(1), qbits) // (3 if intra else 6)
    return qbits, f


def quant4(coeffs, qp, intra: bool = True):
    qbits, f = _qparams(qp, intra)
    mf = _MF_BY_REM[qp % 6]
    c = coeffs.astype(jnp.int32)
    level = jnp.right_shift(jnp.abs(c) * mf + f, qbits)
    return jnp.where(c < 0, -level, level)


def dequant4(levels, qp):
    return levels.astype(jnp.int32) * _V_BY_REM[qp % 6] * jnp.left_shift(jnp.int32(1), qp // 6)


def quant_luma_dc(dc, qp):
    t = jnp.right_shift(_had4(dc), 1)
    qbits, f = _qparams(qp, True)
    mf00 = _MF_BY_REM[qp % 6, 0, 0]
    level = jnp.right_shift(jnp.abs(t) * mf00 + 2 * f, qbits + 1)
    return jnp.where(t < 0, -level, level)


def dequant_luma_dc(levels, qp):
    f = _had4(levels)
    v00 = _V_BY_REM[qp % 6, 0, 0]
    qp_per = qp // 6
    hi = jnp.left_shift(f * v00, jnp.maximum(qp_per - 2, 0))
    lo = jnp.right_shift(
        f * v00 + jnp.left_shift(jnp.int32(1), jnp.maximum(1 - qp_per, 0)),
        jnp.maximum(2 - qp_per, 0),
    )
    return jnp.where(qp_per >= 2, hi, lo)


def quant_chroma_dc(dc, qp_c, intra: bool = True):
    t = _had2(dc)
    qbits, f = _qparams(qp_c, intra)
    mf00 = _MF_BY_REM[qp_c % 6, 0, 0]
    level = jnp.right_shift(jnp.abs(t) * mf00 + 2 * f, qbits + 1)
    return jnp.where(t < 0, -level, level)


def dequant_chroma_dc(levels, qp_c):
    f = _had2(levels)
    v00 = _V_BY_REM[qp_c % 6, 0, 0]
    return jnp.right_shift(jnp.left_shift(f * v00, qp_c // 6), 1)


def _row_to_blocks(row, n: int):
    """(n*4, W) plane row -> (mbw, n, n, 4, 4) indexed [mb][by][bx][i][j]."""
    h, w = row.shape
    mbw = w // (n * 4)
    return row.reshape(n, 4, mbw, n, 4).transpose(2, 0, 3, 1, 4)


def _blocks_to_row(blocks):
    """Inverse of _row_to_blocks: (mbw, n, n, 4, 4) -> (n*4, mbw*n*4)."""
    mbw, n = blocks.shape[0], blocks.shape[1]
    return blocks.transpose(1, 3, 0, 2, 4).reshape(n * 4, mbw * n * 4)


def _encode_plane_row(row, pred, qp, n: int, luma: bool):
    """Batched encode of one MB row of a plane.

    row, pred: (n*4, W) int32. Returns (dc (mbw,n,n), ac (mbw,n,n,4,4),
    recon (n*4, W))."""
    blocks = _row_to_blocks(row - pred, n)
    w = fdct4(blocks)
    dc = w[..., 0, 0]
    if luma:
        dc_levels = quant_luma_dc(dc, qp)
        dc_deq = dequant_luma_dc(dc_levels, qp)
    else:
        dc_levels = quant_chroma_dc(dc, qp)
        dc_deq = dequant_chroma_dc(dc_levels, qp)
    ac_levels = quant4(w, qp, intra=True)
    deq = dequant4(ac_levels, qp)
    deq = deq.at[..., 0, 0].set(dc_deq)
    recon = jnp.clip(_blocks_to_row(idct4(deq)) + pred, 0, 255)
    return dc_levels, ac_levels, recon


def _dc_pred_luma_jnp(left_col, has_left):
    dc = jnp.where(has_left, jnp.right_shift(left_col.sum() + 8, 4), 128)
    return jnp.broadcast_to(dc, (16, 16))


def _dc_pred_chroma_jnp(left_col, has_left):
    """Chroma DC prediction with top unavailable (8.3.4.1): the two block
    rows use the matching 4-sample left segments; no left -> 128."""
    top = jnp.where(has_left, jnp.right_shift(left_col[:4].sum() + 2, 2), 128)
    bot = jnp.where(has_left, jnp.right_shift(left_col[4:].sum() + 2, 2), 128)
    rows = jnp.concatenate([jnp.broadcast_to(top, (4,)), jnp.broadcast_to(bot, (4,))])
    return jnp.broadcast_to(rows[:, None], (8, 8))


def _encode_row0(y_row, u_row, v_row, qp, qp_c):
    """Row 0: DC prediction, serial scan over MB columns."""
    mbw = y_row.shape[1] // 16
    y_mbs = y_row.reshape(16, mbw, 16).transpose(1, 0, 2)
    u_mbs = u_row.reshape(8, mbw, 8).transpose(1, 0, 2)
    v_mbs = v_row.reshape(8, mbw, 8).transpose(1, 0, 2)

    def step(carry, xs):
        yl, ul, vl, has_left = carry
        y_mb, u_mb, v_mb = xs
        dc_y, ac_y, rec_y = _encode_plane_row(y_mb, _dc_pred_luma_jnp(yl, has_left), qp, 4, True)
        dc_u, ac_u, rec_u = _encode_plane_row(u_mb, _dc_pred_chroma_jnp(ul, has_left), qp_c, 2, False)
        dc_v, ac_v, rec_v = _encode_plane_row(v_mb, _dc_pred_chroma_jnp(vl, has_left), qp_c, 2, False)
        carry = (rec_y[:, -1], rec_u[:, -1], rec_v[:, -1], jnp.bool_(True))
        return carry, (dc_y[0], ac_y[0], dc_u[0], ac_u[0], dc_v[0], ac_v[0], rec_y, rec_u, rec_v)

    init = (
        jnp.zeros(16, jnp.int32),
        jnp.zeros(8, jnp.int32),
        jnp.zeros(8, jnp.int32),
        jnp.bool_(False),
    )
    _, outs = jax.lax.scan(step, init, (y_mbs, u_mbs, v_mbs))
    dc_y, ac_y, dc_u, ac_u, dc_v, ac_v, rec_y, rec_u, rec_v = outs
    rec_y = rec_y.transpose(1, 0, 2).reshape(16, mbw * 16)
    rec_u = rec_u.transpose(1, 0, 2).reshape(8, mbw * 8)
    rec_v = rec_v.transpose(1, 0, 2).reshape(8, mbw * 8)
    return dc_y, ac_y, dc_u, ac_u, dc_v, ac_v, rec_y, rec_u, rec_v


@jax.jit
@jax.named_scope("enc.intra")
def encode_frame_planes(y, u, v, qp):
    """Jitted all-Intra16x16 frame encode on padded planes.

    y: (H, W) uint8/int32, u/v: (H/2, W/2). qp: int32 scalar (traced — no
    recompile on rate-control changes). Returns a dict of FrameCoeffs-layout
    arrays plus recon planes (recon also feeds future P-frame prediction).
    """
    y = y.astype(jnp.int32)
    u = u.astype(jnp.int32)
    v = v.astype(jnp.int32)
    qp = jnp.asarray(qp, jnp.int32)
    qp_c = _CHROMA_QP[qp]
    h, w_ = y.shape
    mbh = h // 16

    r0 = _encode_row0(y[:16], u[:8], v[:8], qp, qp_c)
    dc_y0, ac_y0, dc_u0, ac_u0, dc_v0, ac_v0, rec_y0, rec_u0, rec_v0 = r0

    if mbh > 1:
        nrows = mbh - 1
        y_rows = y[16:].reshape(nrows, 16, w_)
        u_rows = u[8:].reshape(nrows, 8, w_ // 2)
        v_rows = v[8:].reshape(nrows, 8, w_ // 2)

        def step(carry, xs):
            yb, ub, vb = carry
            y_row, u_row, v_row = xs
            dc_y, ac_y, rec_y = _encode_plane_row(
                y_row, jnp.broadcast_to(yb, (16, yb.shape[0])), qp, 4, True
            )
            dc_u, ac_u, rec_u = _encode_plane_row(
                u_row, jnp.broadcast_to(ub, (8, ub.shape[0])), qp_c, 2, False
            )
            dc_v, ac_v, rec_v = _encode_plane_row(
                v_row, jnp.broadcast_to(vb, (8, vb.shape[0])), qp_c, 2, False
            )
            return (rec_y[-1], rec_u[-1], rec_v[-1]), (dc_y, ac_y, dc_u, ac_u, dc_v, ac_v, rec_y, rec_u, rec_v)

        init = (rec_y0[-1], rec_u0[-1], rec_v0[-1])
        _, outs = jax.lax.scan(step, init, (y_rows, u_rows, v_rows))
        dc_yr, ac_yr, dc_ur, ac_ur, dc_vr, ac_vr, rec_yr, rec_ur, rec_vr = outs
        luma_dc = jnp.concatenate([dc_y0[None], dc_yr])
        luma_ac = jnp.concatenate([ac_y0[None], ac_yr])
        cb_dc = jnp.concatenate([dc_u0[None], dc_ur])
        cb_ac = jnp.concatenate([ac_u0[None], ac_ur])
        cr_dc = jnp.concatenate([dc_v0[None], dc_vr])
        cr_ac = jnp.concatenate([ac_v0[None], ac_vr])
        recon_y = jnp.concatenate([rec_y0[None], rec_yr]).reshape(mbh * 16, w_)
        recon_u = jnp.concatenate([rec_u0[None], rec_ur]).reshape(mbh * 8, w_ // 2)
        recon_v = jnp.concatenate([rec_v0[None], rec_vr]).reshape(mbh * 8, w_ // 2)
    else:
        luma_dc, luma_ac = dc_y0[None], ac_y0[None]
        cb_dc, cb_ac = dc_u0[None], ac_u0[None]
        cr_dc, cr_ac = dc_v0[None], ac_v0[None]
        recon_y, recon_u, recon_v = rec_y0, rec_u0, rec_v0

    mbw = luma_dc.shape[1]
    row0 = (jnp.arange(mbh) == 0)[:, None] & jnp.ones((1, mbw), bool)
    return {
        "luma_mode": jnp.where(row0, 2, 0).astype(jnp.int32),  # DC / vertical
        "chroma_mode": jnp.where(row0, 0, 2).astype(jnp.int32),  # DC / vertical
        "luma_dc": luma_dc,
        "luma_ac": luma_ac,
        "chroma_dc": jnp.stack([cb_dc, cr_dc], axis=2),
        "chroma_ac": jnp.stack([cb_ac, cr_ac], axis=2),
        "recon_y": recon_y.astype(jnp.uint8),
        "recon_u": recon_u.astype(jnp.uint8),
        "recon_v": recon_v.astype(jnp.uint8),
    }


# ---------------------------------------------------------------------------
# Inter (P-frame) device path
# ---------------------------------------------------------------------------
#
# Unlike the intra row scan above, P frames have NO spatial prediction
# dependencies (P_Skip / P_L0_16x16 partitions only, prediction comes from
# the previous frame's reconstruction), so everything below is a single
# batched tensor program: full-search motion estimation, gather-based
# motion compensation, transform+quant, and skip-mask derivation all run
# over the whole macroblock grid at once. This is the steady-state hot
# path — a remote-desktop stream is one IDR then P frames forever
# (reference: keyframe_distance=-1 default, __main__.py:473-475).

# single source of truth for the ME geometry (the golden model owns it)
from selkies_tpu.models.h264.numpy_ref import COARSE_DS, COARSE_R, MV_PAD, REFINE_R, TOPK

# JAX clamps out-of-bounds gathers silently (no IndexError like numpy), so
# a reach that outgrows the pad would corrupt bitstreams without erroring.
assert COARSE_DS * COARSE_R + REFINE_R <= MV_PAD, "ME reach exceeds MV_PAD"

_ME_CHUNK = 17


def _me_candidates(search: int) -> tuple[np.ndarray, np.ndarray]:
    """Candidate (dx, dy) list in golden-model order: zero MV first, then
    raster (dy outer) — rank breaks SAD ties identically to numpy_ref."""
    cands = [(dx, dy) for dy in range(-search, search + 1) for dx in range(-search, search + 1)]
    cands.sort(key=lambda c: c != (0, 0))
    arr = np.array(cands, np.int32)
    ranks = np.arange(len(arr), dtype=np.int32)
    pad = (-len(arr)) % _ME_CHUNK
    if pad:
        # padding duplicates the zero MV at ranks beyond every real
        # candidate: same SAD as the real zero but a worse tie-break, so a
        # padded entry can never be selected (and ranks stay small enough
        # that SAD·scale + rank fits int32)
        arr = np.concatenate([arr, np.zeros((pad, 2), np.int32)])
        ranks = np.concatenate([ranks, np.arange(len(ranks), len(ranks) + pad, dtype=np.int32)])
    return arr, ranks


def motion_search(cur, ref_pad, search: int = 8):
    """Exhaustive full-pel SAD search: (H, W) planes -> (mbh, mbw, 2) MVs.

    Cost = SAD·scale + candidate rank (scale = next power of two above the
    candidate count), so ties resolve to the golden model's zero-first
    raster order exactly (tests assert array equality).
    Scanned in chunks of 17 candidates (vmap inside scan) to bound the
    live intermediate to chunk×H×W while keeping dispatch count low.
    """
    if search > MV_PAD:
        raise ValueError(f"search {search} exceeds MV_PAD={MV_PAD}")
    h, w = cur.shape
    mbh, mbw = h // 16, w // 16
    cands, ranks = _me_candidates(search)
    # tie-break scale: next power of two above the candidate count so
    # rank never aliases into SAD units
    scale = 1 << int(ranks.max()).bit_length()
    cand_chunks = jnp.asarray(cands.reshape(-1, _ME_CHUNK, 2))
    rank_chunks = jnp.asarray(ranks.reshape(-1, _ME_CHUNK))
    cur = cur.astype(jnp.int32)

    def sad_one(dxdy):
        sh = jax.lax.dynamic_slice(
            ref_pad, (MV_PAD + dxdy[1], MV_PAD + dxdy[0]), (h, w)
        ).astype(jnp.int32)
        return jnp.abs(cur - sh).reshape(mbh, 16, mbw, 16).sum(axis=(1, 3))

    def step(carry, xs):
        best_cost, best_mv = carry
        cand, rank = xs
        sads = jax.vmap(sad_one)(cand)  # (C, mbh, mbw)
        cost = sads * scale + rank[:, None, None]
        i = jnp.argmin(cost, axis=0)
        c = jnp.take_along_axis(cost, i[None], 0)[0]
        mv = cand[i]
        better = c < best_cost
        return (
            jnp.where(better, c, best_cost),
            jnp.where(better[..., None], mv, best_mv),
        ), None

    init = (
        jnp.full((mbh, mbw), jnp.iinfo(jnp.int32).max, jnp.int32),
        jnp.zeros((mbh, mbw, 2), jnp.int32),
    )
    (best_cost, best_mv), _ = jax.lax.scan(step, init, (cand_chunks, rank_chunks))
    return best_mv


def _downsample4(plane):
    """4x4 box downsample, round-half-up (mirrors numpy_ref.downsample4)."""
    h, w = plane.shape
    s = plane.astype(jnp.int32).reshape(h // 4, 4, w // 4, 4).sum(axis=(1, 3))
    return jnp.right_shift(s + 8, 4)


def coarse_votes_jnp(cur, rd_ext, halo_dcols: int = 0):
    """Per-MB coarse-rank vote histogram: ((2*COARSE_R+1)^2,) int32.

    ``rd_ext`` is the DOWNSAMPLED reference, optionally pre-extended by
    ``halo_dcols`` REAL neighbour columns each side (the 2D tile grid's
    column exchange, parallel/bands.py — in downsampled space, so a
    tile's votes are element-exact with the full-row computation whose
    edge pad also happens after downsampling). halo_dcols=0 with a
    full-width plane is the classic band/frame case. Votes from the
    tiles of one slice row SUM to the row's histogram (psum over the
    ``col`` mesh axis / a host-side add), which is what makes the
    merged candidate list identical to the full-row encoder's."""
    h, w = cur.shape
    mbh, mbw = h // 16, w // 16
    yd = _downsample4(cur)
    rd = rd_ext.astype(jnp.int32)
    hd, wd = yd.shape
    if not 0 <= halo_dcols <= COARSE_R:
        raise ValueError(f"halo_dcols {halo_dcols} not in [0, {COARSE_R}]")
    px = COARSE_R - halo_dcols  # edge-pad the remaining horizontal reach

    cands, ranks = _me_candidates(COARSE_R)
    scale = 1 << int(ranks.max()).bit_length()
    cand_chunks = jnp.asarray(cands.reshape(-1, _ME_CHUNK, 2))
    rank_chunks = jnp.asarray(ranks.reshape(-1, _ME_CHUNK))
    rp = jnp.pad(rd, ((COARSE_R, COARSE_R), (px, px)), mode="edge")

    def sad_one(dxdy):
        sh = jax.lax.dynamic_slice(rp, (COARSE_R + dxdy[1], COARSE_R + dxdy[0]), (hd, wd))
        return jnp.abs(yd - sh).reshape(mbh, 4, mbw, 4).sum(axis=(1, 3))

    def step(carry, xs):
        best_cost, = carry
        cand, rank = xs
        sads = jax.vmap(sad_one)(cand)
        cost = sads * scale + rank[:, None, None]
        c = jnp.min(cost, axis=0)
        better = c < best_cost
        return (jnp.where(better, c, best_cost),), None

    init = (jnp.full((mbh, mbw), jnp.iinfo(jnp.int32).max, jnp.int32),)
    (best_cost,), _ = jax.lax.scan(step, init, (cand_chunks, rank_chunks))
    best_rank = best_cost & (scale - 1)  # cost = sad*scale + rank

    n_real = (2 * COARSE_R + 1) ** 2
    # dense bincount (gather/scatter-free): votes[r] = #{MBs with rank r}
    return (best_rank.reshape(-1, 1) == jnp.arange(n_real)[None, :]).sum(0)


def select_coarse_jnp(votes):
    """Vote histogram -> (TOPK, 2) int32 coarse candidates, in the golden
    model's order (votes desc, then rank asc)."""
    cands, _ = _me_candidates(COARSE_R)
    n_real = (2 * COARSE_R + 1) ** 2
    # top-K by votes desc then rank asc; vote count <= mbh*mbw < 2^22
    score = votes * 512 + (511 - jnp.arange(n_real))
    _, top_idx = jax.lax.top_k(score, TOPK)
    return jnp.asarray(cands[:n_real])[top_idx]  # (TOPK, 2) — tiny gather


def coarse_vote_candidates_jnp(cur, ref):
    """Device mirror of numpy_ref.coarse_vote_candidates: (TOPK, 2) int32
    coarse MVs in downsampled units, element-exact with the golden model.
    (Split into coarse_votes_jnp + select_coarse_jnp so the tile grid can
    psum the vote histograms of one slice row before selection — the
    composition here is graph-identical to the pre-split definition.)"""
    return select_coarse_jnp(coarse_votes_jnp(cur, _downsample4(ref.astype(jnp.int32))))


def _refine_cands_jnp(coarse, dy_max: int | None = None,
                      dx_max: int | None = None):
    """(TOPK, 2) coarse -> (1 + TOPK*(2R+1)^2, 2) full-res shift list,
    zero MV first (mirrors numpy_ref.refine_candidate_list).

    dy_max (static) clamps the VERTICAL component of every refined
    candidate to |dy| <= dy_max — the band-sliced step's candidate
    window (parallel/bands.py): a band's chip holds only its reference
    rows plus a `halo`, so when halo is below the full hierarchical
    reach the coarse votes are clamped such that no refined candidate
    can select prediction rows the slab doesn't really hold (predicting
    from replicated slab-edge rows would diverge from the decoder's MC,
    which reads the true full-frame reference). The clamp is applied to
    the coarse displacement, so the refine grid stays the golden ±R
    raster and candidate ORDER (rank tie-breaks) is preserved.
    dx_max is the HORIZONTAL mirror for the 2D tile grid: a tile's chip
    holds only `halo_cols` neighbour columns, so sub-reach column halos
    clamp the coarse dx the same way."""
    side = 2 * REFINE_R + 1
    if dy_max is not None:
        cmax = max(0, (int(dy_max) - REFINE_R) // COARSE_DS)
        coarse = coarse.at[:, 1].set(jnp.clip(coarse[:, 1], -cmax, cmax))
    if dx_max is not None:
        cmax = max(0, (int(dx_max) - REFINE_R) // COARSE_DS)
        coarse = coarse.at[:, 0].set(jnp.clip(coarse[:, 0], -cmax, cmax))
    d = jnp.stack(
        jnp.meshgrid(
            jnp.arange(-REFINE_R, REFINE_R + 1),
            jnp.arange(-REFINE_R, REFINE_R + 1),
            indexing="ij",
        ),
        axis=-1,
    )  # (side, side, 2) with [..., 0]=dy, [..., 1]=dx
    grid = jnp.stack([d[..., 1], d[..., 0]], axis=-1).reshape(1, -1, 2)  # raster dy-outer
    cands = (coarse[:, None, :] * COARSE_DS + grid).reshape(-1, 2)
    return jnp.concatenate([jnp.zeros((1, 2), jnp.int32), cands.astype(jnp.int32)])


def hier_me_mc(cur, ref_y, ry_pad, ru_pad, rv_pad, dy_max: int | None = None,
               dx_max: int | None = None, coarse=None):
    """Global-candidate ME fused with motion compensation — gather-free.

    Two scans over 1+TOPK*(2R+1)^2 global shifts. The COST scan carries
    only (best_cost,) and does a dynamic slice + dense SAD per step; the
    PRED scan re-walks the shifts carrying the luma/chroma prediction
    planes, selecting where the step's rank equals the decoded winner
    rank — no SAD recompute and no chroma math on losing steps' critical
    path state. Splitting keeps the heavy chroma bilinear + 3 plane
    selects out of the cost loop (~2x over the fused single scan).
    Returns (mvs (mbh,mbw,2) i32, pred_y, pred_u, pred_v i32).
    Element-exact vs numpy_ref.hier_search_me + mc_luma/mc_chroma: the
    chroma bilinear runs on the globally-shifted plane with the same
    frac weights, so selected values match the per-MB gather formulation.
    (Why no gathers: a full-plane gather measured 30 ms on v5e vs
    0.26 ms per global-shift SAD map, on an earlier remote-chip setup.)

    ``coarse`` (a (TOPK, 2) candidate array) overrides the internal
    coarse vote — the 2D tile grid passes the row-merged selection
    (parallel/bands.py) so every tile of a slice row refines the same
    global candidates the full-row encoder would. ``dx_max`` clamps the
    horizontal window for sub-reach column halos (see _refine_cands_jnp).
    """
    h, w = cur.shape
    mbh, mbw = h // 16, w // 16
    ch, cw = h // 2, w // 2
    if coarse is None:
        coarse = coarse_vote_candidates_jnp(cur, ref_y)
    cands = _refine_cands_jnp(coarse, dy_max, dx_max)
    ncand = cands.shape[0]
    ranks = jnp.arange(ncand, dtype=jnp.int32)
    scale = 1 << int(np.int64(ncand - 1)).bit_length()
    # statically unrolled chunks. NOT a vmap: batched dynamic_slice
    # lowers to a gather (~30 ms per full plane on v5e); the unrolled
    # Python loop keeps every shift a cheap DynamicSlice. Measured at
    # 1080p/ncand=76 on an earlier remote-chip setup: chunk=4 ~= chunk=19
    # ~= unchunked within its noise floor (the arithmetic, not step
    # launches, bounds this scan) — 4 is kept for its smaller compiled
    # body.
    chunk = next(c for c in (4, 19, 13, 11, 7, 5, 3, 2, 1) if ncand % c == 0)
    cands_c = cands.reshape(-1, chunk, 2)
    ranks_c = ranks.reshape(-1, chunk)

    def cost_step(best_cost, xs):
        mvs_k, ranks_k = xs
        for k in range(chunk):
            mv = mvs_k[k]
            ys = jax.lax.dynamic_slice(ry_pad, (MV_PAD + mv[1], MV_PAD + mv[0]), (h, w))
            sad = jnp.abs(cur - ys.astype(jnp.int32)).reshape(mbh, 16, mbw, 16).sum(axis=(1, 3))
            best_cost = jnp.minimum(sad * scale + ranks_k[k], best_cost)
        return best_cost, None

    init_cost = jnp.full((mbh, mbw), jnp.iinfo(jnp.int32).max, jnp.int32)
    best_cost, _ = jax.lax.scan(cost_step, init_cost, (cands_c, ranks_c))
    best_rank = best_cost & (scale - 1)  # cost = sad*scale + rank

    def pred_step(carry, xs):
        best_mv, py, pu, pv = carry
        mvs_k, ranks_k = xs
        for k in range(chunk):
            mv, rank = mvs_k[k], ranks_k[k]
            better = best_rank == rank  # exactly one (step, k) wins per MB
            dx, dy = mv[0], mv[1]
            ys = jax.lax.dynamic_slice(ry_pad, (MV_PAD + dy, MV_PAD + dx), (h, w))

            # chroma prediction for this global shift (8.4.2.2.2 on the
            # whole plane): full-pel luma MV -> chroma half-pel bilinear
            cx, cy = jnp.right_shift(dx, 1), jnp.right_shift(dy, 1)
            xf, yf = 4 * (dx & 1), 4 * (dy & 1)

            def chroma_shift(rp):
                s = jax.lax.dynamic_slice(
                    rp, (MV_PAD + cy, MV_PAD + cx), (ch + 1, cw + 1)
                ).astype(jnp.int32)
                a, b = s[:-1, :-1], s[:-1, 1:]
                c, d = s[1:, :-1], s[1:, 1:]
                return jnp.right_shift(
                    (8 - xf) * (8 - yf) * a + xf * (8 - yf) * b
                    + (8 - xf) * yf * c + xf * yf * d + 32,
                    6,
                )

            us, vs = chroma_shift(ru_pad), chroma_shift(rv_pad)
            m16 = jnp.repeat(jnp.repeat(better, 16, 0), 16, 1)
            m8 = jnp.repeat(jnp.repeat(better, 8, 0), 8, 1)
            best_mv = jnp.where(better[..., None], mv, best_mv)
            py = jnp.where(m16, ys.astype(jnp.int32), py)
            pu = jnp.where(m8, us, pu)
            pv = jnp.where(m8, vs, pv)
        return (best_mv, py, pu, pv), None

    init_pred = (
        jnp.zeros((mbh, mbw, 2), jnp.int32),
        jnp.zeros((h, w), jnp.int32),
        jnp.zeros((ch, cw), jnp.int32),
        jnp.zeros((ch, cw), jnp.int32),
    )
    (mvs, py, pu, pv), _ = jax.lax.scan(pred_step, init_pred, (cands_c, ranks_c))
    return mvs, py, pu, pv


def hier_motion_search(cur, ref, ref_pad):
    """MV-only wrapper over hier_me_mc (parity tests / tools). ref_pad is
    the MV_PAD-padded luma; chroma planes are synthesized zeros."""
    h, w = cur.shape
    zero_c = jnp.zeros((h // 2 + 2 * MV_PAD, w // 2 + 2 * MV_PAD), jnp.uint8)
    mvs, _, _, _ = hier_me_mc(cur, jnp.asarray(ref), ref_pad, zero_c, zero_c)
    return mvs


def mc_luma(ref_pad, mvs):
    """Full-pel luma MC: gather the per-MB-shifted reference plane."""
    mbh, mbw = mvs.shape[:2]
    h, w = mbh * 16, mbw * 16
    mvx = jnp.repeat(jnp.repeat(mvs[..., 0], 16, 0), 16, 1)
    mvy = jnp.repeat(jnp.repeat(mvs[..., 1], 16, 0), 16, 1)
    iy = jnp.arange(h)[:, None] + mvy + MV_PAD
    ix = jnp.arange(w)[None, :] + mvx + MV_PAD
    return ref_pad[iy, ix].astype(jnp.int32)


def mc_chroma(ref_pad, mvs):
    """Chroma MC (8.4.2.2.2): full-pel luma MVs land chroma on half-pel;
    bilinear blend of the 4 neighbours with weights from frac ∈ {0, 4}."""
    mbh, mbw = mvs.shape[:2]
    h, w = mbh * 8, mbw * 8
    mvx = jnp.repeat(jnp.repeat(mvs[..., 0], 8, 0), 8, 1)
    mvy = jnp.repeat(jnp.repeat(mvs[..., 1], 8, 0), 8, 1)
    xf = 4 * (mvx & 1)
    yf = 4 * (mvy & 1)
    iy = jnp.arange(h)[:, None] + jnp.right_shift(mvy, 1) + MV_PAD
    ix = jnp.arange(w)[None, :] + jnp.right_shift(mvx, 1) + MV_PAD
    p = ref_pad.astype(jnp.int32)
    a = p[iy, ix]
    b = p[iy, ix + 1]
    c = p[iy + 1, ix]
    d = p[iy + 1, ix + 1]
    return jnp.right_shift(
        (8 - xf) * (8 - yf) * a + xf * (8 - yf) * b + (8 - xf) * yf * c + xf * yf * d + 32, 6
    )


def _plane_to_mb_blocks(plane, n: int):
    """(mbh*n*4, mbw*n*4) -> (mbh, mbw, n, n, 4, 4) [by][bx][i][j]."""
    h, w = plane.shape
    mbh, mbw = h // (n * 4), w // (n * 4)
    return plane.reshape(mbh, n, 4, mbw, n, 4).transpose(0, 3, 1, 4, 2, 5)


def _mb_blocks_to_plane(blocks):
    mbh, mbw, n = blocks.shape[0], blocks.shape[1], blocks.shape[2]
    return blocks.transpose(0, 2, 4, 1, 3, 5).reshape(mbh * n * 4, mbw * n * 4)


def _skip_mask(mvs, resid_zero):
    """Vectorized 8.4.1.1 P_Skip eligibility: residual-free MBs whose MV
    equals the skip-derived MV."""
    mbh, mbw = mvs.shape[:2]
    left = jnp.pad(mvs, ((0, 0), (1, 0), (0, 0)))[:, :-1]
    top = jnp.pad(mvs, ((1, 0), (0, 0), (0, 0)))[:-1]
    # C = top-right, replaced by D = top-left on the last column (both exist
    # whenever the else-branch below is reached: mbx>0 and mby>0).
    tr = jnp.pad(mvs, ((1, 0), (0, 1), (0, 0)))[:-1, 1:]
    tl = jnp.pad(mvs, ((1, 0), (1, 0), (0, 0)))[:-1, :-1]
    last_col = jnp.arange(mbw) == mbw - 1
    cmv = jnp.where(last_col[None, :, None], tl, tr)
    med = left + top + cmv - jnp.maximum(jnp.maximum(left, top), cmv) - jnp.minimum(
        jnp.minimum(left, top), cmv
    )
    edge = (jnp.arange(mbw)[None, :] == 0) | (jnp.arange(mbh)[:, None] == 0)
    left_zero = (left == 0).all(-1)
    top_zero = (top == 0).all(-1)
    zero_cond = edge | left_zero | top_zero
    skipmv = jnp.where(zero_cond[..., None], 0, med)
    return resid_zero & (mvs == skipmv).all(-1)


def _use_pallas_me(width: int) -> bool:
    """Pallas ME dispatch: on by default on real TPU backends (interpret
    mode on CPU is far slower than the XLA scan), off above the kernel's
    128-MB row width, SELKIES_PALLAS_ME=0/1 overrides."""
    import os

    env = os.environ.get("SELKIES_PALLAS_ME")
    if env == "0":
        return False
    if width // 16 > 128:
        return False
    if env == "1":
        return True
    try:
        return jax.default_backend() == "tpu"
    except Exception:
        return False


def encode_frame_p_planes(y, u, v, ref_y, ref_u, ref_v, qp, search: int = 8, me: str = "hier"):
    """Jitted P-frame encode on padded planes against the previous recon.

    me="hier" (default): two-level hierarchical search covering ±32 —
    `search` is ignored on this path; me="full": flat exhaustive ±search
    (the original golden contract). `me`/`search` are Python-level config,
    not traceable values: close over them (functools.partial) when jitting
    with a non-default choice.
    Returns mvs/skip/coefficients (PFrameCoeffs layout) + recon planes.
    One batched program, no scans except the ME candidate loops.
    """
    y = y.astype(jnp.int32)
    u = u.astype(jnp.int32)
    v = v.astype(jnp.int32)
    qp = jnp.asarray(qp, jnp.int32)

    with jax.named_scope("enc.me"):
        ry = jnp.pad(ref_y, MV_PAD, mode="edge")
        ru = jnp.pad(ref_u, MV_PAD, mode="edge")
        rv = jnp.pad(ref_v, MV_PAD, mode="edge")
    mvs, pred_y, pred_u, pred_v = _me_mc_dispatch(
        y, ref_y, ry, ru, rv, search=search, me=me)
    return _p_transform_tail(y, u, v, qp, mvs, pred_y, pred_u, pred_v)


def encode_band_p_planes(y, u, v, slab_y, slab_u, slab_v, qp, halo: int,
                         search: int = 8, me: str = "hier"):
    """Band-sliced P encode: one horizontal band of the frame against a
    halo-extended reference SLAB — the device half of the band-parallel
    slice step (parallel/bands.py).

    y/u/v are the band's source rows (16·band_mbh luma rows). slab_y
    carries the band's reference rows plus `halo` REAL reference rows
    above and below (slab_u/slab_v: halo//2 chroma rows each side); at
    picture edges the halo rows are edge-replicated, which matches both
    jnp.pad(mode="edge") on the full frame and the decoder's
    picture-boundary clamp (8.4.2.2.1). The slab is padded out to the
    full MV_PAD reach with edge replication, and when `halo` is below
    the hierarchical search's vertical reach the candidate list is
    band-clamped (dy_max = halo - 2, see _refine_cands_jnp) so every
    SELECTED prediction row is real reference content — exactly what
    the decoder's MC will read from the full decoded reference. That is
    the whole correctness story of the band split: each band's slice
    depends only on data resident on its chip, yet reconstructs
    identically on any conformant decoder.

    With halo=0 and a slab equal to the FULL reference this is
    graph-identical to encode_frame_p_planes (the SELKIES_BANDS=1
    byte-identity contract) — halo=0 is ONLY valid in that full-slab
    case. For a genuine band slab halo must be >= REFINE_R + 2: the
    refine grid always emits dy = ±REFINE_R around every (clamped)
    coarse candidate and the chroma bilinear reads one row past dy>>1,
    so a smaller halo could select predictions from replicated slab
    edges the decoder's full-frame reference does not contain. halo
    must be even and <= MV_PAD."""
    return encode_tile_p_planes(y, u, v, slab_y, slab_u, slab_v, qp,
                                halo=halo, search=search, me=me)


def encode_tile_p_planes(y, u, v, slab_y, slab_u, slab_v, qp, halo: int,
                         halo_cols: int = 0, search: int = 8, me: str = "hier",
                         coarse=None, defer_skip: bool = False):
    """Tile-sliced P encode: one rows×cols tile of the frame against a
    2D halo-extended reference SLAB — the device half of the 2D
    tile-grid step (parallel/bands.py, SELKIES_TILE_GRID).

    Generalizes encode_band_p_planes to a second (column) halo axis:
    ``slab_y`` carries the tile's reference pixels plus ``halo`` REAL
    rows above/below AND ``halo_cols`` REAL columns left/right (chroma
    slabs carry half of each), edge-replicated at picture boundaries —
    including the diagonal corner blocks, which the column-then-row
    exchange order in parallel/bands.py fills with the diagonal
    neighbour's pixels. ``halo_cols=0`` with a full-width slab is
    exactly the band case (same graph). The validity rule mirrors the
    vertical one: halo_cols must be even and either 0 (full-width slab)
    or in [REFINE_R + 2, MV_PAD]; below the full hierarchical reach + the
    chroma bilinear's one-column lookahead (COARSE_DS*COARSE_R +
    REFINE_R + 2 = 36) the horizontal candidate window is clamped to
    ``halo_cols - 2`` so no SELECTED prediction column is fabricated.

    ``coarse`` injects a precomputed (TOPK, 2) coarse candidate list:
    the tile grid merges the per-tile vote histograms of one slice row
    (psum over the ``col`` mesh axis) and selects ONCE, so every tile
    refines the same global candidates as the full-row band encoder —
    that, plus full-reach halos, is what makes an RxC grid's access
    units byte-identical to the SELKIES_BANDS=R oracle.

    ``defer_skip=True`` returns ``resid_zero`` instead of ``skip``: the
    P_Skip derivation needs the MV of the macroblock to the LEFT, which
    at an interior tile seam lives on the neighbouring chip — the tile
    grid derives skip AFTER the row gather, on the merged full-row MV
    grid, exactly reproducing the full-row semantics."""
    if halo % 2 or not 0 <= halo <= MV_PAD or 0 < halo < REFINE_R + 2:
        raise ValueError(
            f"halo {halo} must be even and 0 (full-reference slab) or in "
            f"[{REFINE_R + 2}, {MV_PAD}]")
    if halo_cols % 2 or not 0 <= halo_cols <= MV_PAD or \
            0 < halo_cols < REFINE_R + 2:
        raise ValueError(
            f"halo_cols {halo_cols} must be even and 0 (full-width slab) or "
            f"in [{REFINE_R + 2}, {MV_PAD}]")
    y = y.astype(jnp.int32)
    u = u.astype(jnp.int32)
    v = v.astype(jnp.int32)
    qp = jnp.asarray(qp, jnp.int32)
    halo_c, halo_cc = halo // 2, halo_cols // 2
    vt, vtc = MV_PAD - halo, MV_PAD - halo_c
    ht, htc = MV_PAD - halo_cols, MV_PAD - halo_cc
    with jax.named_scope("enc.me"):
        ry = jnp.pad(slab_y, ((vt, vt), (ht, ht)), mode="edge")
        ru = jnp.pad(slab_u, ((vtc, vtc), (htc, htc)), mode="edge")
        rv = jnp.pad(slab_v, ((vtc, vtc), (htc, htc)), mode="edge")
    # tile-local reference (coarse candidate voting sees the tile when no
    # merged `coarse` list is injected)
    ref_y = slab_y[halo : slab_y.shape[0] - halo] if halo else slab_y
    if halo_cols:
        ref_y = ref_y[:, halo_cols : ref_y.shape[1] - halo_cols]
    # full reach is COARSE_DS*COARSE_R + REFINE_R = 34 luma rows/cols; the
    # chroma bilinear additionally reads one row/col past d>>1, so a halo
    # of 36+ already covers every candidate and no clamp is applied —
    # and neither is halo=0, where the slab IS the full reference
    full_reach = COARSE_DS * COARSE_R + REFINE_R + 2
    dy_max = None if halo == 0 or halo >= full_reach else halo - 2
    dx_max = (None if halo_cols == 0 or halo_cols >= full_reach
              else halo_cols - 2)
    mvs, pred_y, pred_u, pred_v = _me_mc_dispatch(
        y, ref_y, ry, ru, rv, search=search, me=me, dy_max=dy_max,
        dx_max=dx_max, coarse=coarse)
    return _p_transform_tail(y, u, v, qp, mvs, pred_y, pred_u, pred_v,
                             defer_skip=defer_skip)


@jax.named_scope("enc.me")
def _me_mc_dispatch(y, ref_y, ry, ru, rv, *, search: int, me: str,
                    dy_max: int | None = None, dx_max: int | None = None,
                    coarse=None):
    """ME + MC over MV_PAD-padded reference planes (shared by the
    full-frame, band-sliced, and tile-sliced steps)."""
    if me == "hier":
        # fused gather-free ME+MC: predictions fall out of the same
        # candidate scan that picks the MVs. On TPU the Pallas kernel
        # (pallas_me.py) runs the same search ~3x faster by keeping each
        # MB row's reference window in VMEM; outputs are bit-identical
        # (tests/test_pallas_me.py), so this is purely a speed dispatch.
        if _use_pallas_me(y.shape[1]):
            from selkies_tpu.models.h264.pallas_me import hier_me_mc_pallas

            return hier_me_mc_pallas(y, ref_y, ry, ru, rv, dy_max=dy_max,
                                     dx_max=dx_max, coarse=coarse)
        return hier_me_mc(y, ref_y, ry, ru, rv, dy_max, dx_max, coarse)
    if dy_max is not None or dx_max is not None or coarse is not None:
        raise ValueError("tile-clamped candidate windows require me='hier'")
    mvs = motion_search(y, ry, search)
    return mvs, mc_luma(ry, mvs), mc_chroma(ru, mvs), mc_chroma(rv, mvs)


@jax.named_scope("enc.tq")
def _p_transform_tail(y, u, v, qp, mvs, pred_y, pred_u, pred_v,
                      defer_skip: bool = False):
    """Transform + quant + recon + skip derivation — everything after
    ME/MC, shared bit-exactly by encode_frame_p_planes and
    encode_band_p_planes/encode_tile_p_planes. ``defer_skip`` replaces
    the ``skip`` output with ``resid_zero`` (the residual-free mask) so
    a tile-grid caller can run _skip_mask on the row-merged MV grid."""
    qp_c = _CHROMA_QP[qp]
    # Luma: plain 4x4 transform, all 16 coeffs (no DC Hadamard in inter MBs)
    yb = _plane_to_mb_blocks(y - pred_y, 4)
    wy = fdct4(yb)
    luma_ac = quant4(wy, qp, intra=False)
    rec_y = jnp.clip(_mb_blocks_to_plane(idct4(dequant4(luma_ac, qp))) + pred_y, 0, 255)

    def chroma(plane, pred):
        cb = _plane_to_mb_blocks(plane - pred, 2)
        wc = fdct4(cb)
        dc = quant_chroma_dc(wc[..., 0, 0], qp_c, intra=False)
        ac = quant4(wc, qp_c, intra=False)
        deq = dequant4(ac, qp_c)
        deq = deq.at[..., 0, 0].set(dequant_chroma_dc(dc, qp_c))
        rec = jnp.clip(_mb_blocks_to_plane(idct4(deq)) + pred, 0, 255)
        return dc, ac, rec

    cb_dc, cb_ac, rec_u = chroma(u, pred_u)
    cr_dc, cr_ac, rec_v = chroma(v, pred_v)

    resid_zero = (
        (luma_ac == 0).all((-4, -3, -2, -1))
        & (cb_dc == 0).all((-2, -1))
        & (cr_dc == 0).all((-2, -1))
        & (cb_ac == 0).all((-4, -3, -2, -1))
        & (cr_ac == 0).all((-4, -3, -2, -1))
    )
    skip_kv = ({"resid_zero": resid_zero} if defer_skip
               else {"skip": _skip_mask(mvs, resid_zero)})

    return {
        "mvs": mvs,
        **skip_kv,
        "luma_ac": luma_ac,
        "chroma_dc": jnp.stack([cb_dc, cr_dc], axis=2),
        "chroma_ac": jnp.stack([cb_ac, cr_ac], axis=2),
        "recon_y": rec_y.astype(jnp.uint8),
        "recon_u": rec_u.astype(jnp.uint8),
        "recon_v": rec_v.astype(jnp.uint8),
    }


# ---------------------------------------------------------------------------
# Compact downlink
# ---------------------------------------------------------------------------
#
# The coefficient tensors are the device->host traffic (the reference's
# encoders emit final bitstreams on the GPU; ours entropy-codes on the
# host). Dense P-frame coeffs at 1080p are ~6.4 MB/frame — far more than
# the actual information content (desktop P frames are mostly zero blocks).
# pack_*_compact runs INSIDE the frame jit and emits:
#   * one int32 header: counts + packed MVs + per-MB nonzero-block bitmap
#     + skip bitmask (+ intra modes for IDR) — ~65 KB at 1080p, fixed size;
#   * one int16 data buffer whose first n rows are the nonzero 4x4 blocks
#     in global scan order — the host fetches only that prefix.
# The host scatters rows back into dense arrays (models/h264/compact.py)
# and feeds the unchanged CAVLC packer, so bitstreams are bit-identical to
# the dense path.

# Row-layout constants — the ONLY definition; compact.py (host unpack)
# imports these, so pack and unpack cannot drift apart.
# P frame, per-MB rows: [0:16) luma AC, [16:24) chroma AC, [24:26) chroma DC.
P_ROW_CHROMA = 16
P_ROW_DC = 24
P_ENTRIES = 26
# IDR, per-MB rows: [0] luma DC, [1:17) luma AC, [17:25) chroma AC,
# [25:27) chroma DC.
I_ROW_LUMA = 1
I_ROW_CHROMA = 17
I_ROW_DC_C = 25
I_ENTRIES = 27


def _compact_rows(rows):
    """rows: (M, E, 16) int16 -> (flags (M,E) bool, buf (M*E, 16) int16,
    n int32). buf's first n rows are the nonzero rows in scan order."""
    m, e, _ = rows.shape
    flat = rows.reshape(m * e, 16)
    fl = (flat != 0).any(-1)
    pos = jnp.cumsum(fl) - 1
    dest = jnp.where(fl, pos, m * e)  # sentinel row, dropped below
    buf = jnp.zeros((m * e + 1, 16), jnp.int16).at[dest].set(flat)[: m * e]
    return fl.reshape(m, e), buf, fl.sum().astype(jnp.int32)


def _bitmap_words(flags):
    """(M, E<=32) bool -> (M,) int32 per-MB bitmap."""
    e = flags.shape[1]
    return (flags.astype(jnp.int32) << jnp.arange(e, dtype=jnp.int32)).sum(-1)


def _bitpack32(bits):
    """(M,) bool -> (ceil(M/32),) int32."""
    m = bits.shape[0]
    pad = (-m) % 32
    b = jnp.pad(bits.astype(jnp.int32), (0, pad)).reshape(-1, 32)
    return (b << jnp.arange(32, dtype=jnp.int32)).sum(-1)


def _p_components(out):
    mbh, mbw = out["mvs"].shape[:2]
    m = mbh * mbw
    luma = out["luma_ac"].reshape(m, 16, 16).astype(jnp.int16)
    chroma = out["chroma_ac"].reshape(m, 8, 16).astype(jnp.int16)
    dc = out["chroma_dc"].reshape(m, 2, 4).astype(jnp.int16)
    dc_rows = jnp.pad(dc, ((0, 0), (0, 0), (0, 12)))
    rows = jnp.concatenate([luma, chroma, dc_rows], axis=1)  # (M, 26, 16)
    flags, buf, n = _compact_rows(rows)
    mv = out["mvs"]
    mv_words = (mv[..., 0] & 0xFFFF) | (mv[..., 1] << 16)
    return n, mbh, mbw, mv_words.reshape(-1).astype(jnp.int32), _bitmap_words(flags), buf


@jax.named_scope("enc.downlink")
def pack_p_compact(out):
    """P-frame outputs -> (header int32, data int16 (M*26, 16)).

    Header layout: [n, mbh, mbw, 0] ++ mv_words(M) ++ mbinfo(M) ++
    skip_words(ceil(M/32)); mv_words = (mvx & 0xFFFF) | (mvy << 16)."""
    n, mbh, mbw, mv_words, mbinfo, buf = _p_components(out)
    header = jnp.concatenate([
        jnp.stack([n, jnp.int32(mbh), jnp.int32(mbw), jnp.int32(0)]),
        mv_words,
        mbinfo,
        _bitpack32(out["skip"].reshape(-1)),
    ])
    return header, buf


@jax.named_scope("enc.downlink")
def pack_p_sparse_var(out, nscap: int, cap_rows: int):
    """Skip-aware variable-density P downlink (the delta-upload path):
    ONE int16 buffer whose live content is proportional to frame
    activity, not to the caps.

    Most desktop P frames are almost-all-skip, so only the first `nscap`
    NON-skip MBs carry their mv/mbinfo words (the host reconstructs
    positions from the dense skip bitmap). A fixed-layout prefix would
    still fetch nscap pairs + cap_rows coefficient rows — 165 KB at
    1080p even for a 2-band cursor blink, and every fetched byte is
    d2h traffic. Here the host fetches only a slice sized by
    recent history (encoder._pfx_hint):

      [meta: n, mbh, mbw, ns (4 int32)] ++ skip_words(ceil(M/32) int32)
      ++ (mv, info) int32 pairs for the first ns non-skip MBs
      ++ coefficient rows (n x 16 int16)  -- at dynamic offset 4*ns

    so live content = 8 + 2*ceil(M/32)*2 + 4*ns + 16*n int16 words. The
    pair region is written at its nscap-sized static offset first, then
    the rows overwrite its dead tail via a dynamic slice — content stays
    contiguous without a device-side size branch. Returns
    (fused int16 (p_sparse_var_words(...),), dense_header, buf); dense
    header is the ns > nscap fallback, buf the n > cap_rows spill."""
    n, mbh, mbw, mv_words, mbinfo, buf = _p_components(out)
    m = mbh * mbw
    mask = ~out["skip"].reshape(-1)
    ns = mask.sum().astype(jnp.int32)
    pos = jnp.cumsum(mask) - 1
    dest = jnp.where(mask & (pos < nscap), pos, nscap)  # sentinel dropped
    mv_c = jnp.zeros((nscap + 1,), jnp.int32).at[dest].set(mv_words)[:nscap]
    info_c = jnp.zeros((nscap + 1,), jnp.int32).at[dest].set(mbinfo)[:nscap]
    skip_words = _bitpack32(out["skip"].reshape(-1))
    sw = skip_words.shape[0]
    pairs16 = jax.lax.bitcast_convert_type(
        jnp.stack([mv_c, info_c], -1).reshape(-1), jnp.int16
    ).reshape(-1)  # (4*nscap,)
    head16 = jax.lax.bitcast_convert_type(
        jnp.concatenate([jnp.stack([n, jnp.int32(mbh), jnp.int32(mbw), ns]), skip_words]),
        jnp.int16,
    ).reshape(-1)  # (8 + 2*sw,)
    base = 8 + 2 * sw
    total16 = base + 4 * nscap + 16 * cap_rows
    fused = jnp.zeros((total16,), jnp.int16)
    fused = jax.lax.dynamic_update_slice(fused, head16, (0,))
    fused = jax.lax.dynamic_update_slice(fused, pairs16, (base,))
    rows16 = buf[:cap_rows].reshape(-1)  # (16*cap_rows,) zero past n
    fused = jax.lax.dynamic_update_slice(
        fused, rows16, (base + 4 * jnp.clip(ns, 0, nscap),)
    )
    dense = jnp.concatenate([
        jnp.stack([n, jnp.int32(mbh), jnp.int32(mbw), jnp.int32(0)]),
        mv_words,
        mbinfo,
        skip_words,
    ])
    return fused, dense, buf


@jax.named_scope("enc.downlink")
def pack_p_sparse_packed(out, nscap: int, cap_rows: int, density_pct: int = 75):
    """Bit-packed variant of pack_p_sparse_var: coefficient rows ride as
    a significance bitmap + their nonzero values only.

    A typical desktop-residual 4x4 block has 1-4 nonzero coefficients,
    so shipping all 16 int16 lanes (32 B/row) wastes 3-6x of the
    dominant d2h term. Per nonzero row the packed stream carries:

      * one int16 significance bitmap (bit j = scan-order lane j != 0);
      * the nonzero values, compacted to the front and padded to groups
        of FOUR int16 — one int64 lane per group, so the stream stays
        8-byte aligned and the host can bulk-view it.

    Layout (int16 words):
      [meta: n, mbh, mbw, ns, nw, dense_flag (6 int32 = 12)]
      ++ skip_words(ceil(M/32) int32) ++ (mv, info) pairs for the first
      ns non-skip MBs  -- as in pack_p_sparse_var --
      ++ at dynamic offset base + 4*min(ns, nscap):
           dense_flag=0: bitmaps (held int16) ++ values (nw int16)
           dense_flag=1: rows (16 * held int16, the var layout)

    `nw` = total packed value words (4 * sum of per-row groups). The
    DENSE FALLBACK triggers when the packed stream would exceed
    `density_pct`% of the dense rows — busy frames approach 16 nonzeros
    per row, where bitmap + padding overhead inverts the win and the
    host-side re-expansion is pure loss. Both layouts reconstruct the
    exact same PFrameCoeffs (compact.unpack_p_sparse_packed), so
    bitstreams are byte-identical either way. Returns (fused, dense
    header, buf) with the same fallback contract as pack_p_sparse_var."""
    n, mbh, mbw, mv_words, mbinfo, buf = _p_components(out)
    mask = ~out["skip"].reshape(-1)
    ns = mask.sum().astype(jnp.int32)
    pos = jnp.cumsum(mask) - 1
    dest = jnp.where(mask & (pos < nscap), pos, nscap)
    mv_c = jnp.zeros((nscap + 1,), jnp.int32).at[dest].set(mv_words)[:nscap]
    info_c = jnp.zeros((nscap + 1,), jnp.int32).at[dest].set(mbinfo)[:nscap]
    skip_words = _bitpack32(out["skip"].reshape(-1))
    sw = skip_words.shape[0]
    pairs16 = jax.lax.bitcast_convert_type(
        jnp.stack([mv_c, info_c], -1).reshape(-1), jnp.int16
    ).reshape(-1)

    rows = buf[:cap_rows]  # (cap, 16) int16; zero past row n
    sig = rows != 0
    bitmap16 = (sig.astype(jnp.int32) << jnp.arange(16, dtype=jnp.int32)).sum(-1).astype(jnp.int16)
    counts = sig.sum(-1).astype(jnp.int32)  # per-row nonzeros (>=1 while live)
    width = 4 * ((counts + 3) // 4)  # int16 slots incl group padding
    off = jnp.cumsum(width) - width  # exclusive prefix
    nw = width.sum().astype(jnp.int32)
    lane = jnp.cumsum(sig, axis=-1) - 1  # within-row rank of each nonzero
    vdest = jnp.where(sig, off[:, None] + lane, 16 * cap_rows)  # sentinel dropped
    vals16 = (
        jnp.zeros((16 * cap_rows + 1,), jnp.int16)
        .at[vdest.reshape(-1)]
        .set(rows.reshape(-1))[: 16 * cap_rows]
    )

    held = jnp.minimum(n, cap_rows)
    # fallback when the packed stream stops paying (bitmaps + padding vs
    # the 16-lane rows it replaces)
    dense_flag = (held + nw) * 100 > (16 * held) * density_pct
    meta = jnp.stack([n, jnp.int32(mbh), jnp.int32(mbw), ns, nw,
                      dense_flag.astype(jnp.int32)])
    head16 = jax.lax.bitcast_convert_type(
        jnp.concatenate([meta, skip_words]), jnp.int16
    ).reshape(-1)  # (12 + 2*sw,)
    base = 12 + 2 * sw
    total16 = base + 4 * nscap + cap_rows + 16 * cap_rows
    fused = jnp.zeros((total16,), jnp.int16)
    fused = jax.lax.dynamic_update_slice(fused, head16, (0,))
    fused = jax.lax.dynamic_update_slice(fused, pairs16, (base,))
    rows_off = base + 4 * jnp.clip(ns, 0, nscap)
    rows16 = rows.reshape(-1)

    def write_dense(f):
        return jax.lax.dynamic_update_slice(f, rows16, (rows_off,))

    def write_packed(f):
        # the values overwrite the bitmap array's dead tail (rows past
        # `held` have empty bitmaps), keeping the live content contiguous
        f = jax.lax.dynamic_update_slice(f, bitmap16, (rows_off,))
        return jax.lax.dynamic_update_slice(f, vals16, (rows_off + held,))

    fused = jax.lax.cond(dense_flag, write_dense, write_packed, fused)
    dense = jnp.concatenate([
        jnp.stack([n, jnp.int32(mbh), jnp.int32(mbw), jnp.int32(0)]),
        mv_words,
        mbinfo,
        skip_words,
    ])
    return fused, dense, buf


@jax.named_scope("enc.downlink")
def pack_p_sparse_entropy(out, nscap: int, cap_rows: int,
                          density_pct: int | None, bits_words: int,
                          min_mbs: int, buckets: tuple[int, ...],
                          entropy_coder: str = "cavlc"):
    """Activity-proportional entropy downlink: busy frames ship their
    FINAL slice bits, quiet frames ship sparse coefficients — decided
    per frame ON DEVICE, inside the same jit (so it composes with the
    grouped lax.scan dispatch unchanged).

    Wraps the existing sparse layouts (pack_p_sparse_var /
    pack_p_sparse_packed — byte-for-byte the same payload, so the host
    parses it with the unchanged compact.py machinery) and the
    activity-compacted device CAVLC (device_cavlc.pack_p_slice_bits_
    active). The fused buffer gains an 8-int32 meta prefix:

      [mode, nbits, trailing_skip, nskip, ns, 0, 0, 0]   (16 int16)
      ++ mode=0: the untouched sparse layout (coeff rows)
         mode=1: the slice-data bit words (uint32, bit-cast)

    mode=1 is chosen when the frame is busy enough to pay
    (ns >= min_mbs), codeable (ns <= buckets[-1]) and the bits fit the
    `bits_words` payload cap — otherwise the coefficient path runs
    exactly as before (the word-cap overflow fallback). The sparse pack
    is cheap scatters and the bits pack is activity-proportional, so
    running both costs a quiet frame almost nothing; the decision only
    selects which payload lands in the fused buffer. Returns
    (fused, dense_header, buf) with the same fallback contract as the
    wrapped sparse packers (dense/buf are coeff-mode-only fetches).
    host half: models/h264/sparse_complete.complete_sparse_slice
    (device_bits=True).

    With entropy_coder="cabac" the device half is the token binarizer
    (device_cabac.pack_p_slice_tokens_active) and mode=1 carries the
    16-bit token IR instead of final bits — the host still owns the
    sequential arithmetic engine. Payload layout after the meta prefix
    (meta2 = [1, ntok, 0, nskip, ns, 0, 0, 0]):

      skip bitmap (2*sw int16, the host interleaves per-MB skip flags)
      ++ per-coded-MB token counts (first ns of an A_max block, int16)
      ++ token words at offset 2*sw + ns (the dead counts tail is
         overwritten, keeping the live fetch contiguous — the same
         trick as pack_p_sparse_packed's bitmap/value split)."""
    if entropy_coder == "cabac":
        return _pack_p_sparse_cabac(out, nscap, cap_rows, density_pct,
                                    bits_words, min_mbs, buckets)
    from selkies_tpu.models.h264.device_cavlc import pack_p_slice_bits_active

    if density_pct is None:
        fused, dense, buf = pack_p_sparse_var(out, nscap, cap_rows)
    else:
        fused, dense, buf = pack_p_sparse_packed(out, nscap, cap_rows, density_pct)
    words, nbits, trailing, ns, _counts = pack_p_slice_bits_active(
        out, word_cap=bits_words, buckets=buckets)
    nskip = out["skip"].reshape(-1).sum().astype(jnp.int32)
    use_bits = (
        (ns >= jnp.int32(min_mbs))
        & (ns <= jnp.int32(buckets[-1]))
        & (nbits <= jnp.int32(32 * bits_words))
    )
    meta2 = jnp.stack([
        use_bits.astype(jnp.int32), nbits, trailing, nskip, ns,
        jnp.int32(0), jnp.int32(0), jnp.int32(0)])
    head16 = jax.lax.bitcast_convert_type(meta2, jnp.int16).reshape(-1)
    total16 = 16 + max(int(fused.shape[0]), 2 * bits_words)
    fused2 = jnp.zeros((total16,), jnp.int16)

    def wr_coeff(f):
        return jax.lax.dynamic_update_slice(f, fused, (16,))

    def wr_bits(f):
        w16 = jax.lax.bitcast_convert_type(words, jnp.int16).reshape(-1)
        return jax.lax.dynamic_update_slice(f, w16, (16,))

    fused2 = jax.lax.cond(use_bits, wr_bits, wr_coeff, fused2)
    fused2 = jax.lax.dynamic_update_slice(fused2, head16, (0,))
    return fused2, dense, buf


def _pack_p_sparse_cabac(out, nscap: int, cap_rows: int,
                         density_pct: int | None, bits_words: int,
                         min_mbs: int, buckets: tuple[int, ...]):
    """CABAC arm of pack_p_sparse_entropy (layout documented there)."""
    from selkies_tpu.models.h264.device_cabac import (
        pack_p_slice_tokens_active)

    if density_pct is None:
        fused, dense, buf = pack_p_sparse_var(out, nscap, cap_rows)
    else:
        fused, dense, buf = pack_p_sparse_packed(out, nscap, cap_rows, density_pct)
    words, ntok, counts, ns = pack_p_slice_tokens_active(
        out, word_cap=bits_words, buckets=buckets)
    skip_words = _bitpack32(out["skip"].reshape(-1))
    sw = skip_words.shape[0]
    nskip = out["skip"].reshape(-1).sum().astype(jnp.int32)
    A_max = buckets[-1]
    use_bits = (
        (ns >= jnp.int32(min_mbs))
        & (ns <= jnp.int32(A_max))
        & (ntok <= jnp.int32(2 * bits_words))
    )
    meta2 = jnp.stack([
        use_bits.astype(jnp.int32), ntok, jnp.int32(0), nskip, ns,
        jnp.int32(0), jnp.int32(0), jnp.int32(0)])
    head16 = jax.lax.bitcast_convert_type(meta2, jnp.int16).reshape(-1)
    base = 16 + 2 * sw
    total16 = 16 + max(int(fused.shape[0]),
                       2 * sw + A_max + 2 * bits_words)
    fused2 = jnp.zeros((total16,), jnp.int16)

    def wr_coeff(f):
        return jax.lax.dynamic_update_slice(f, fused, (16,))

    def wr_toks(f):
        sk16 = jax.lax.bitcast_convert_type(skip_words, jnp.int16).reshape(-1)
        f = jax.lax.dynamic_update_slice(f, sk16, (16,))
        f = jax.lax.dynamic_update_slice(
            f, counts.astype(jnp.int16), (base,))
        w16 = jax.lax.bitcast_convert_type(words, jnp.int16).reshape(-1)
        return jax.lax.dynamic_update_slice(
            f, w16, (base + jnp.clip(ns, 0, A_max),))

    fused2 = jax.lax.cond(use_bits, wr_toks, wr_coeff, fused2)
    fused2 = jax.lax.dynamic_update_slice(fused2, head16, (0,))
    return fused2, dense, buf


@jax.named_scope("enc.downlink")
def fuse_downlink(header, buf, cap_rows: int):
    """Fuse header + the first cap_rows data rows into ONE int16 buffer.

    One fetch per frame (the layout dates from a remote chip on which
    each transfer cost ~200 ms; not re-measured on a local one): the
    prefix buffer carries the int32 header bit-cast to int16 pairs
    followed by cap_rows nonzero rows. Frames whose row count exceeds
    cap_rows pay one extra fetch from the full buffer (rare; sized for
    typical P frames)."""
    hdr16 = jax.lax.bitcast_convert_type(header, jnp.int16).reshape(-1)
    prefix = jnp.concatenate([hdr16, buf[:cap_rows].reshape(-1)])
    return prefix


@jax.named_scope("enc.downlink")
def pack_i_compact(out):
    """IDR outputs -> (header int32, data int16 (M*27, 16)).

    Header: [n, mbh, mbw, 0] ++ mbinfo(M) ++ mode_words(M)
    (mode_words = luma_mode | chroma_mode << 8). Per-MB rows: 1 luma DC +
    16 luma AC + 8 chroma AC + 2 chroma DC."""
    mbh, mbw = out["luma_mode"].shape[:2]
    m = mbh * mbw
    luma_dc = out["luma_dc"].reshape(m, 1, 16).astype(jnp.int16)
    luma = out["luma_ac"].reshape(m, 16, 16).astype(jnp.int16)
    chroma = out["chroma_ac"].reshape(m, 8, 16).astype(jnp.int16)
    dc = out["chroma_dc"].reshape(m, 2, 4).astype(jnp.int16)
    dc_rows = jnp.pad(dc, ((0, 0), (0, 0), (0, 12)))
    rows = jnp.concatenate([luma_dc, luma, chroma, dc_rows], axis=1)  # (M, 27, 16)
    flags, buf, n = _compact_rows(rows)
    modes = out["luma_mode"].reshape(-1) | (out["chroma_mode"].reshape(-1) << 8)
    header = jnp.concatenate([
        jnp.stack([n, jnp.int32(mbh), jnp.int32(mbw), jnp.int32(0)]),
        _bitmap_words(flags),
        modes.astype(jnp.int32),
    ])
    return header, buf


# ---------------------------------------------------------------------------
# Delta upload: dirty-band scatter into device-resident source planes
# ---------------------------------------------------------------------------
@jax.named_scope("enc.ingest")
def scatter_tiles(y, u, v, yb, ub, vb, idx, tile_w: int):
    """Scatter uploaded I420 TILES into device-resident planes.

    yb: (k, 16, tile_w) luma, ub/vb: (k, 8, tile_w/2) chroma, idx: (k,)
    int32 encoded band*1024 + tile (duplicates allowed — rewriting a
    tile is idempotent, which lets the host pad k to a static bucket).
    tile_w == plane width degenerates to full-width bands. Column tiling
    shrinks the host->device delta traffic by the width fraction that
    actually changed (a cursor blink is one tile, not a full-width band)."""
    ctw = tile_w // 2

    def body(i, planes):
        py, pu, pv = planes
        band = idx[i] // 1024
        tile = idx[i] % 1024
        py = jax.lax.dynamic_update_slice(py, yb[i], (band * 16, tile * tile_w))
        pu = jax.lax.dynamic_update_slice(pu, ub[i], (band * 8, tile * ctw))
        pv = jax.lax.dynamic_update_slice(pv, vb[i], (band * 8, tile * ctw))
        return py, pu, pv

    return jax.lax.fori_loop(0, yb.shape[0], body, (y, u, v))
