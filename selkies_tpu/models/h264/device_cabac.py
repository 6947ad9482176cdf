"""CABAC binarization + context derivation ON DEVICE (ISSUE 19).

Second entropy backend behind the two-pass device split. The structure
pass is device_cavlc._frame_structure, UNCHANGED — skip map, mv
prediction, cbp, coded-block gating and the coding-order block relayout
are entropy-coder agnostic. Only emission differs: instead of VLC
codewords this module binarizes every syntax element into the 16-bit
token IR of cabac.py (REG/RUN/BYP/TERM) and derives each regular bin's
context index, data-parallel over the activity-compacted coded-MB
prefix. The sequential half of CABAC — arithmetic interval updates and
context-state adaptation — stays on host (native/cabac_pack.cc at
~5 ns/bin), fed one finished token stream per slice.

Emission reuses the CAVLC bit-packing machinery verbatim: every token
is a (value, nbits) slot with nbits ∈ {0, 16}, so _pack_pairs +
_merge_streams concatenate per-segment token runs exactly like VLC
codewords, and the merged bit stream is 16-bit aligned — the host views
the big-endian words as uint16 to recover the token sequence.

Division of labour per P slice:

* device — per coded MB, the "body" tokens (mb_type, mvd, cbp,
  mb_qp_delta, residual blocks) over the compacted prefix, bucket-padded
  like _emit_slice_bits, plus a per-coded-MB token COUNT;
* host — mb_skip_flag tokens (one per MB; CABAC P slices have no skip
  runs) and the per-MB end_of_slice terminate bins, interleaved with
  the device bodies by cumsum/repeat arithmetic (numpy, no Python loop);
* host — the arithmetic engine over the interleaved stream, then header
  splice + emulation prevention (finish_cabac_nal).

Output NALs are byte-identical to cabac.pack_slice_p_cabac
(tests/test_device_cabac_tokens.py). IDR/I slices use the host packer —
intra frames are rare in the streaming steady state and their CABAC
syntax (prefix mb_type, intra pred modes) isn't worth a device path.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from selkies_tpu.models.h264.cabac import (
    _LVL_OFF,
    _SIG_OFF,
    TOK_BYP,
    TOK_REG,
    TOK_RUN,
    TOK_TERM,
)
from selkies_tpu.models.h264.device_cavlc import (
    _CHROMA_ORDER,
    _LUMA_ORDER,
    _clz32,
    _compact_structure,
    _frame_structure,
    _merge_streams,
    _mv_pred_grid,
    _pack_pairs,
    bits_buckets,
)

__all__ = [
    "pack_p_slice_tokens",
    "pack_p_slice_tokens_active",
    "cabac_tok_words",
    "skip_flag_tokens",
    "interleave_p_tokens",
    "assemble_p_cabac_nal",
]


def cabac_tok_words(m: int) -> int:
    """Token-payload capacity in uint32 words for an m-MB slice. Tokens
    are 16-bit, roughly one per 1-2 bins; 64 words/MB (128 tokens) covers
    busy desktop residuals. Overflow falls back to the coefficient
    downlink exactly like the CAVLC bits cap."""
    return min(1 << 18, max(4096, 64 * int(m)))


# ---------------------------------------------------------------- token slots
#
# Every emitter below produces (value, nbits) slot arrays for
# _pack_pairs with nbits ∈ {0, 16}: a slot either contributes one whole
# uint16 token or nothing, which keeps the merged stream token-aligned.


def _ontok(on):
    return jnp.where(on, 16, 0).astype(jnp.int32)


def _byp_pair(v, nb, on):
    """One bypass group of nb (<= 20) bits as two <=10-bit BYP tokens,
    MSB-first. Chunking need not match TokenWriter.bypass_bits — engine
    output depends only on the bin sequence, not its grouping."""
    n_lo = jnp.clip(nb - 10, 0, 10)
    n_hi = jnp.clip(nb - n_lo, 0, 10)
    v_hi = (v >> n_lo) & 0x3FF
    v_lo = v & ((jnp.int32(1) << n_lo) - 1)
    hi_v = TOK_BYP | (n_hi << 2) | (v_hi << 6)
    lo_v = TOK_BYP | (n_lo << 2) | (v_lo << 6)
    return hi_v, _ontok(on & (n_hi > 0)), lo_v, _ontok(on & (n_lo > 0))


def _ueg_slots(v, k0: int, on):
    """UEGk escape binarization (9.3.2.3 suffix): unary prefix of j ones
    + stop 0, then a (k0+j)-bit suffix — as four BYP slots. The prefix
    length has a closed form, j = floor(log2(v/2^k0 + 1)), replacing the
    reference's subtract loop."""
    j = 31 - _clz32((v >> k0) + 1)
    pv = (jnp.int32(1) << (j + 1)) - 2          # j ones then a zero
    sv = jnp.clip(v - ((jnp.int32(1) << (k0 + j)) - (1 << k0)), 0, None)
    ph_v, ph_b, pl_v, pl_b = _byp_pair(pv, j + 1, on)
    sh_v, sh_b, sl_v, sl_b = _byp_pair(sv, k0 + j, on)
    return ph_v, ph_b, pl_v, pl_b, sh_v, sh_b, sl_v, sl_b


def _token_blocks(coeffs, cbf_ctx, cat: int):
    """Tokenize a batch of residual_block_cabac (7.3.5.3.3): (B, L)
    scan-order coefficients + (B,) coded_block_flag contexts ->
    (vals (B, S), bits (B, S)) slot arrays. Mirrors cabac._residual_tokens
    with the two serial-looking pieces vectorized:

    * the significance map is elementwise over scan positions (sig/last
      context increments are functions of the position alone);
    * the level contexts' eq1/gt1 counters are EXCLUSIVE CUMSUMS over
      the reverse-scan nonzero sequence — no recurrence — and the UEG0
      escape prefix/suffix have closed forms (_ueg_slots).

    Slot layout: [cbf][per scan pos i<L-1: sig, last][per level k:
    gt0, ones-run a, ones-run b, stop-zero, esc prefix hi/lo, esc
    suffix hi/lo, sign] = 1 + 2(L-1) + 9L slots."""
    B, L = coeffs.shape
    nz = coeffs != 0
    total = nz.sum(-1).astype(jnp.int32)
    cbf = total > 0
    # reverse-scan nonzero compaction — same one-hot contraction as
    # device_cavlc._encode_blocks (sorts are ~30 ms at frame scale)
    rev = coeffs[:, ::-1]
    nzr = rev != 0
    rank = jnp.cumsum(nzr, -1, dtype=jnp.int32) - 1
    oh = ((rank[:, :, None] == jnp.arange(L, dtype=jnp.int32)[None, None, :])
          & nzr[:, :, None]).astype(jnp.int32)
    val_rev = jnp.einsum("blk,bl->bk", oh, rev)
    pos_of = jnp.broadcast_to(
        (L - 1 - jnp.arange(L, dtype=jnp.int32))[None, :], (B, L))
    pos_rev = jnp.einsum("blk,bl->bk", oh, pos_of)
    last = pos_rev[:, 0]  # scan index of the last nonzero (valid iff cbf)

    cbf_v = (cbf.astype(jnp.int32) << 2) | (cbf_ctx << 3)
    cbf_b = jnp.full((B, 1), 16, jnp.int32)

    # significance map: bins at scan positions 0..min(last, L-2)
    i = jnp.arange(L - 1, dtype=jnp.int32)[None, :]
    inc = jnp.minimum(i, 2) if cat == 3 else i
    soff, loff = 105 + _SIG_OFF[cat], 166 + _SIG_OFF[cat]
    sig = nz[:, : L - 1]
    on = cbf[:, None] & (i <= jnp.minimum(last, L - 2)[:, None])
    sig_v = (sig.astype(jnp.int32) << 2) | ((soff + inc) << 3)
    isl = i == last[:, None]
    last_v = (isl.astype(jnp.int32) << 2) | ((loff + inc) << 3)
    sl_v = jnp.stack([jnp.broadcast_to(sig_v, sig.shape), last_v], -1)
    sl_b = jnp.stack([_ontok(on), _ontok(on & sig)], -1)

    # levels, reverse scan order (k-th slot = k-th nonzero from the end)
    mag = jnp.abs(val_rev)
    kvalid = jnp.arange(L, dtype=jnp.int32)[None, :] < total[:, None]
    m = jnp.clip(jnp.minimum(mag - 1, 14), 0, 14)
    gt1 = ((mag > 1) & kvalid).astype(jnp.int32)
    eq1 = ((mag == 1) & kvalid).astype(jnp.int32)
    gt1c = jnp.cumsum(gt1, -1) - gt1            # exclusive: count before k
    eq1c = jnp.cumsum(eq1, -1) - eq1
    base = 227 + _LVL_OFF[cat]
    c0 = base + jnp.where(gt1c > 0, 0, jnp.minimum(4, 1 + eq1c))
    c1 = base + 5 + jnp.minimum(4 - (1 if cat == 3 else 0), gt1c)
    s0_v = ((m > 0).astype(jnp.int32) << 2) | (c0 << 3)
    n1 = jnp.clip(m - 1, 0, 13)                 # TU ones at c1
    na = jnp.minimum(n1, 7)                     # RUN n field is 3 bits
    nb2 = n1 - na
    ra_v = TOK_RUN | (1 << 2) | (c1 << 3) | (na << 13)
    rb_v = TOK_RUN | (1 << 2) | (c1 << 3) | (nb2 << 13)
    z_v = c1 << 3                               # TU stop zero
    esc_on = kvalid & (mag - 1 >= 14)
    ev = jnp.clip(mag - 1 - 14, 0, None)
    ph_v, ph_b, pl_v, pl_b, sh_v, sh_b, su_v, su_b = _ueg_slots(ev, 0, esc_on)
    sgn_v = TOK_BYP | (1 << 2) | ((val_rev < 0).astype(jnp.int32) << 6)
    lev_v = jnp.stack(
        [s0_v, ra_v, rb_v, z_v, ph_v, pl_v, sh_v, su_v, sgn_v], -1)
    lev_b = jnp.stack(
        [_ontok(kvalid), _ontok(kvalid & (na > 0)), _ontok(kvalid & (nb2 > 0)),
         _ontok(kvalid & (m > 0) & (m < 14)), ph_b, pl_b, sh_b, su_b,
         _ontok(kvalid)], -1)

    vals = jnp.concatenate(
        [cbf_v[:, None], sl_v.reshape(B, 2 * (L - 1)), lev_v.reshape(B, 9 * L)], 1)
    bits = jnp.concatenate(
        [cbf_b, sl_b.reshape(B, 2 * (L - 1)), lev_b.reshape(B, 9 * L)], 1)
    return vals, bits


def _header_slots(s):
    """P macroblock header tokens (mb_type, mvd_l0 x/y, cbp, mb_qp_delta)
    for a (possibly compacted) structure -> (vals (A, 32), bits (A, 32)).
    Mirrors cabac.mb_tokens_p's pre-residual half; the mvd UEG3 prefix
    bins j=0..3 double as the TU terminator when |mvd| < 4 (bin = m > j,
    present iff m >= j), the j>=4 ones collapse into one RUN slot."""
    live = s["coded"]
    A = live.shape[0]
    vs, bs = [], []
    for ctx in (14, 15, 16):  # P_L0_16x16 mb_type: three 0 bins
        vs.append(jnp.full((A,), ctx << 3, jnp.int32))
        bs.append(_ontok(live))
    mvd = s["cb_mvd"]
    ctx0 = s["cb_mvd_ctx"]
    for comp in range(2):
        b = 40 if comp == 0 else 47
        d = mvd[:, comp]
        a = jnp.abs(d)
        m = jnp.minimum(a, 9)
        for j in range(4):
            ctx = ctx0[:, comp] if j == 0 else jnp.full((A,), b + 2 + j, jnp.int32)
            vs.append(((m > j).astype(jnp.int32) << 2) | (ctx << 3))
            bs.append(_ontok(live & (m >= j)))
        n = jnp.clip(m - 4, 0, 5)               # prefix ones at positions 4..8
        vs.append(TOK_RUN | (1 << 2) | ((b + 6) << 3) | (n << 13))
        bs.append(_ontok(live & (n > 0)))
        vs.append(jnp.full((A,), (b + 6) << 3, jnp.int32))  # TU stop for m in 4..8
        bs.append(_ontok(live & (m >= 4) & (m < 9)))
        esc_on = live & (a >= 9)
        ph_v, ph_b, pl_v, pl_b, sh_v, sh_b, su_v, su_b = _ueg_slots(
            jnp.clip(a - 9, 0, None), 3, esc_on)
        vs += [ph_v, pl_v, sh_v, su_v]
        bs += [ph_b, pl_b, sh_b, su_b]
        vs.append(TOK_BYP | (1 << 2) | ((d < 0).astype(jnp.int32) << 6))
        bs.append(_ontok(live & (a > 0)))
    ctx6, bins6 = s["cb_cbp_ctx"], s["cb_cbp_bins"]
    for k in range(6):
        vs.append((bins6[:, k] << 2) | (ctx6[:, k] << 3))
        bs.append(_ontok(live if k < 5 else (live & s["cb_cbp5"])))
    vs.append(jnp.full((A,), 60 << 3, jnp.int32))  # mb_qp_delta = se(0)
    bs.append(_ontok(live & s["cb_qpd"]))
    return jnp.stack(vs, -1), jnp.stack(bs, -1)


# ------------------------------------------------------------ structure extras


def _shift_inc(grid):
    """condTermFlagA + 2*condTermFlagB for every cell of a cbf grid —
    left/top shifted reads with zero edges (9.3.3.1.1.9 inter rules:
    unavailable or skipped neighbours read 0)."""
    left = jnp.pad(grid, ((0, 0), (1, 0)))[:, :-1]
    top = jnp.pad(grid, ((1, 0), (0, 0)))[:-1]
    return left + 2 * top


@jax.named_scope("enc.entropy.structure")
def _cabac_structure(out):
    """_frame_structure + the CABAC context columns, all full-grid
    elementwise work (the cheap pass). New per-MB keys, each compactable
    by the same row scatter as the CAVLC keys:

      cb_mvd (M,2)        quarter-pel mvd
      cb_mvd_ctx (M,2)    first-bin ctx (40/47 + neighbour-|mvd|-sum inc)
      cb_cbp_ctx/bins (M,6), cb_cbp5 (M,)   cbp bin contexts/values
      cb_qpd (M,)         mb_qp_delta present
      cb_cbf_luma (M,16), cb_cbf_cdc (M,2), cb_cbf_cac (M,8)
                          coded_block_flag ctx per block, coding order
    """
    s = _frame_structure(out)
    skip = out["skip"]
    mbh, mbw = skip.shape
    M = mbh * mbw
    coded2 = ~skip
    cbp_l, cbp_c = s["cbp_luma"], s["cbp_chroma"]

    pred = _mv_pred_grid(out["mvs"], skip)
    mvd = 4 * (out["mvs"].astype(jnp.int32) - pred)
    amvd = jnp.where(coded2[..., None], jnp.abs(mvd), 0)
    ssum = (jnp.pad(amvd, ((0, 0), (1, 0), (0, 0)))[:, :-1]
            + jnp.pad(amvd, ((1, 0), (0, 0), (0, 0)))[:-1])
    inc = jnp.where(ssum < 3, 0, jnp.where(ssum > 32, 2, 1))
    s["cb_mvd"] = mvd.reshape(M, 2)
    s["cb_mvd_ctx"] = (jnp.asarray([40, 47], jnp.int32) + inc).reshape(M, 2)

    # cbp bin contexts: neighbour patterns read 15 (luma) / 0 (chroma)
    # when unavailable, 0 at skip MBs (cabac._cbp_tokens)
    clg = jnp.where(coded2, cbp_l, 0)
    ccg = jnp.where(coded2, cbp_c, 0)
    col = jnp.arange(mbw, dtype=jnp.int32)[None, :]
    row = jnp.arange(mbh, dtype=jnp.int32)[:, None]
    cl_left = jnp.where(col > 0, jnp.pad(clg, ((0, 0), (1, 0)))[:, :-1], 15)
    cl_top = jnp.where(row > 0, jnp.pad(clg, ((1, 0), (0, 0)))[:-1], 15)
    cc_left = jnp.where(col > 0, jnp.pad(ccg, ((0, 0), (1, 0)))[:, :-1], 0)
    cc_top = jnp.where(row > 0, jnp.pad(ccg, ((1, 0), (0, 0)))[:-1], 0)
    b0, b1 = cbp_l & 1, (cbp_l >> 1) & 1
    b2, b3 = (cbp_l >> 2) & 1, (cbp_l >> 3) & 1
    ctx6 = jnp.stack([
        73 + (1 - ((cl_left >> 1) & 1)) + 2 * (1 - ((cl_top >> 2) & 1)),
        73 + (1 - b0) + 2 * (1 - ((cl_top >> 3) & 1)),
        73 + (1 - ((cl_left >> 3) & 1)) + 2 * (1 - b0),
        73 + (1 - b2) + 2 * (1 - b1),
        77 + (cc_left > 0).astype(jnp.int32) + 2 * (cc_top > 0).astype(jnp.int32),
        81 + (cc_left == 2).astype(jnp.int32) + 2 * (cc_top == 2).astype(jnp.int32),
    ], -1)
    bins6 = jnp.stack([
        b0, b1, b2, b3,
        (cbp_c > 0).astype(jnp.int32), (cbp_c == 2).astype(jnp.int32)], -1)
    s["cb_cbp_ctx"] = ctx6.reshape(M, 6)
    s["cb_cbp_bins"] = bins6.reshape(M, 6)
    s["cb_cbp5"] = (cbp_c > 0).reshape(M)
    s["cb_qpd"] = ((cbp_l | cbp_c) > 0).reshape(M)

    # coded_block_flag contexts from the gated TotalCoeff grids the
    # structure pass already built (transmitted cbf == TotalCoeff > 0;
    # absent blocks hold 0, exactly condTermFlagN)
    luma_perm = jnp.asarray(
        np.asarray(_LUMA_ORDER)[:, 1] * 4 + np.asarray(_LUMA_ORDER)[:, 0])
    lcbf = (s["luma_tc_flat"] > 0).astype(jnp.int32)
    s["cb_cbf_luma"] = jnp.take(
        (93 + _shift_inc(lcbf)).reshape(mbh, 4, mbw, 4)
        .transpose(0, 2, 1, 3).reshape(M, 16), luma_perm, axis=1)
    ch_perm = jnp.asarray(
        np.asarray(_CHROMA_ORDER)[:, 1] * 2 + np.asarray(_CHROMA_ORDER)[:, 0])
    ccbf = (s["ch_tc_flat"] > 0).astype(jnp.int32)
    s["cb_cbf_cac"] = jnp.take(
        jnp.stack([101 + _shift_inc(ccbf[c]) for c in range(2)])
        .reshape(2, mbh, 2, mbw, 2).transpose(1, 3, 0, 2, 4).reshape(M, 2, 4),
        ch_perm, axis=2).reshape(M, 8)
    cdc = out["chroma_dc"].reshape(mbh, mbw, 2, 4)
    dc_cbf = ((cdc != 0).any(-1)
              & (coded2 & (cbp_c >= 1))[..., None]).astype(jnp.int32)
    s["cb_cbf_cdc"] = jnp.stack(
        [97 + _shift_inc(dc_cbf[..., c]) for c in range(2)], -1).reshape(M, 2)
    return s


# per-MB arrays the CABAC emission path needs compacted ("coded" rides
# along as the live mask: compaction makes it the dense ns-prefix)
CABAC_COMPACT_KEYS = (
    "coded", "luma_blocks", "luma_emit", "cdc_blocks", "cdc_emit",
    "ch_blocks", "ch_emit", "cb_mvd", "cb_mvd_ctx", "cb_cbp_ctx",
    "cb_cbp_bins", "cb_cbp5", "cb_qpd", "cb_cbf_luma", "cb_cbf_cdc",
    "cb_cbf_cac",
)


@jax.named_scope("enc.entropy.emit")
def _emit_slice_tokens(s, word_cap: int):
    """The expensive half over a compacted structure: tokenize every
    block + header, pack each MB's 27 segments (header, 16 luma, 2
    chroma DC, 8 chroma AC — same segment split as _emit_slice_bits) and
    merge into one token-aligned bit stream. Returns (words, ntok,
    counts) with counts the per-slot token count (zero on padded
    slots)."""
    U = s["coded"].shape[0]
    lv, lb = _token_blocks(
        s["luma_blocks"].reshape(U * 16, 16), s["cb_cbf_luma"].reshape(-1), 2)
    lb = jnp.where(s["luma_emit"].reshape(-1)[:, None], lb, 0)
    dv, db = _token_blocks(
        s["cdc_blocks"].reshape(U * 2, 4), s["cb_cbf_cdc"].reshape(-1), 3)
    db = jnp.where(s["cdc_emit"].reshape(-1)[:, None], db, 0)
    cv, cb = _token_blocks(
        s["ch_blocks"].reshape(U * 8, 15), s["cb_cbf_cac"].reshape(-1), 4)
    cb = jnp.where(s["ch_emit"].reshape(-1)[:, None], cb, 0)
    hv, hb = _header_slots(s)

    HW, DW, CW, BW = 16, 22, 82, 88  # ceil(16*S/32) per segment kind
    hdr_w, hdr_n = _pack_pairs(hv, hb, HW)
    luma_w, luma_n = _pack_pairs(lv, lb, BW)
    cdc_w, cdc_n = _pack_pairs(dv, db, DW)
    cac_w, cac_n = _pack_pairs(cv, cb, CW)
    seg_words = jnp.concatenate([
        jnp.pad(hdr_w.reshape(U, 1, HW), ((0, 0), (0, 0), (0, BW - HW))),
        luma_w.reshape(U, 16, BW),
        jnp.pad(cdc_w.reshape(U, 2, DW), ((0, 0), (0, 0), (0, BW - DW))),
        jnp.pad(cac_w.reshape(U, 8, CW), ((0, 0), (0, 0), (0, BW - CW))),
    ], axis=1).reshape(U * 27, BW)
    seg_bits = jnp.concatenate([
        hdr_n.reshape(U, 1), luma_n.reshape(U, 16), cdc_n.reshape(U, 2),
        cac_n.reshape(U, 8)], axis=1).reshape(U * 27)
    words, total = _merge_streams(seg_words, seg_bits, word_cap)
    counts = (hdr_n + luma_n.reshape(U, 16).sum(1) + cdc_n.reshape(U, 2).sum(1)
              + cac_n.reshape(U, 8).sum(1)) >> 4
    return words, total >> 4, counts


def pack_p_slice_tokens(out, word_cap: int | None = None):
    """Full-grid device tokenizer (every MB pays) — the fixed-shape
    oracle for tests and the profiler. Returns (words (word_cap,)
    uint32 big-endian bit order, ntok, counts (M,), ns): the first ns
    entries of counts are the coded MBs' body token counts in raster
    order."""
    s = _cabac_structure(out)
    M = s["coded"].shape[0]
    sc = _compact_structure(s, M, keys=CABAC_COMPACT_KEYS)
    words, ntok, counts = _emit_slice_tokens(
        sc, cabac_tok_words(M) if word_cap is None else word_cap)
    return words, ntok, counts, s["ns"]


def pack_p_slice_tokens_active(out, word_cap: int | None = None,
                               buckets: tuple[int, ...] | None = None):
    """Activity-proportional device CABAC: the emission half runs over a
    bucket-compacted coded-MB prefix selected ON DEVICE via lax.switch —
    the same discipline (and the same buckets) as
    pack_p_slice_bits_active. Unlike the CAVLC path the top bucket also
    compacts: counts must land in a dense prefix for the host
    interleave, and every branch pads them to buckets[-1] so the switch
    arms agree on shapes. Token output is identical for every bucket
    (compaction preserves raster order; padded slots emit zero bits)."""
    s = _cabac_structure(out)
    M = s["coded"].shape[0]
    if word_cap is None:
        word_cap = cabac_tok_words(M)
    if buckets is None:
        buckets = bits_buckets(M)
    A_max = buckets[-1]
    ns = s["ns"]

    def _run(A: int):
        sc = _compact_structure(s, A, keys=CABAC_COMPACT_KEYS)
        words, ntok, counts = _emit_slice_tokens(sc, word_cap)
        return words, ntok, jnp.pad(counts, (0, A_max - A))

    if len(buckets) == 1:
        words, ntok, counts = _run(buckets[0])
    else:
        with jax.named_scope("enc.entropy.emit"):
            idx = jnp.clip(
                jnp.searchsorted(jnp.asarray(buckets, jnp.int32), ns,
                                 side="left"),
                0, len(buckets) - 1)
            words, ntok, counts = jax.lax.switch(
                idx, [(lambda _, A=b: _run(A)) for b in buckets],
                jnp.int32(0))
    return words, ntok, counts, ns


# ---------------------------------------------------------------------------
# Host half: skip/terminate interleave, engine, NAL assembly
# ---------------------------------------------------------------------------


def skip_flag_tokens(skip: np.ndarray) -> np.ndarray:
    """mb_skip_flag REG tokens for every MB of a slice, raster order —
    ctx 11 + (#available-and-not-skipped of {left, top})."""
    sk = np.asarray(skip, bool)
    inc = np.zeros(sk.shape, np.int32)
    inc[:, 1:] += ~sk[:, :-1]
    inc[1:, :] += ~sk[:-1, :]
    return (TOK_REG | (sk.astype(np.int32) << 2)
            | ((11 + inc) << 3)).reshape(-1).astype(np.uint16)


def interleave_p_tokens(body: np.ndarray, counts: np.ndarray,
                        skip: np.ndarray) -> np.ndarray:
    """Splice per-MB streams into slice order without a Python loop:
    for each MB [skip_flag] [body tokens if coded] [end_of_slice], the
    last MB's end_of_slice being the TERM(1) flush. `body` is the device
    stream (coded-MB bodies concatenated in raster order), `counts` the
    per-coded-MB token counts (ns entries)."""
    sk = np.asarray(skip, bool).reshape(-1)
    m = sk.size
    cnt = np.zeros(m, np.int64)
    cnt[~sk] = np.asarray(counts, np.int64)
    stride = cnt + 2                      # skip flag + body + terminate
    starts = np.zeros(m, np.int64)
    np.cumsum(stride[:-1], out=starts[1:])
    out = np.empty(int(stride.sum()), np.uint16)
    out[starts] = skip_flag_tokens(skip)
    out[starts + 1 + cnt] = TOK_TERM
    tot = int(cnt.sum())
    if tot:
        body_counts = cnt[~sk]
        excl = np.cumsum(body_counts) - body_counts
        pos = (np.repeat(starts[~sk] + 1 - excl, body_counts)
               + np.arange(tot, dtype=np.int64))
        out[pos] = body[:tot]
    out[-1] = TOK_TERM | (1 << 2)         # end-of-slice flush
    return out


def tokens_from_words(words: np.ndarray, ntok: int) -> np.ndarray:
    """Recover the uint16 token sequence from device words: every slot
    is 16 bits, so the big-endian word stream IS the token stream."""
    nw = (int(ntok) + 1) // 2
    return (np.ascontiguousarray(words[:nw]).astype(">u4")
            .view(">u2").astype(np.uint16)[: int(ntok)])


def assemble_p_cabac_nal(words: np.ndarray, ntok: int, counts: np.ndarray,
                         skip: np.ndarray, p, frame_num: int, qp: int,
                         ltr_ref: int | None = None,
                         mark_ltr: int | None = None,
                         mmco_evict: tuple = (),
                         first_mb: int = 0,
                         cabac_init_idc: int = 0) -> bytes:
    """Finish a P slice from device tokens: interleave skip/terminate
    bins, run the arithmetic engine, splice after the host-written
    header. Byte-identical to cabac.pack_slice_p_cabac for the same
    inputs; first_mb/cabac_init_idc position a band slice exactly like
    assemble_p_nal does for CAVLC."""
    from selkies_tpu.models.h264.bitstream import (
        NAL_SLICE_NON_IDR, SLICE_P, write_slice_header)
    from selkies_tpu.models.h264.cabac import finish_cabac_nal
    from selkies_tpu.utils.bits import BitWriter

    toks = interleave_p_tokens(tokens_from_words(words, ntok), counts, skip)
    w = BitWriter()
    write_slice_header(w, p, SLICE_P, frame_num, idr=False, slice_qp=qp,
                       ltr_ref=ltr_ref, mark_ltr=mark_ltr,
                       mmco_evict=mmco_evict, first_mb=first_mb,
                       cabac_init_idc=cabac_init_idc)
    return finish_cabac_nal(w, toks, qp, SLICE_P, cabac_init_idc,
                            NAL_SLICE_NON_IDR)
