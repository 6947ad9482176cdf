"""Activity-proportional device entropy (ISSUE 7): the compacted
device coder and the whole ship-bits-or-coefficients downlink must be
byte-identical to the host pack at every density, bucket boundary,
fallback, LTR variant, band offset, and grouped-scan shape."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from selkies_tpu.models.h264.bitstream import StreamParams
from selkies_tpu.models.h264.cavlc import pack_slice_p
from selkies_tpu.models.h264.compact import p_sparse_entropy_meta
from selkies_tpu.models.h264 import device_cavlc
from selkies_tpu.models.h264.device_cavlc import (
    _blocks_bits_bound,
    _concat_rows,
    _encode_blocks,
    _frame_structure,
    _mb_bits_bound,
    assemble_p_nal,
    bits_buckets,
    pack_p_slice_bits,
    pack_p_slice_bits_active,
)
from selkies_tpu.models.h264.encoder_core import pack_p_sparse_entropy
from selkies_tpu.models.h264.native import derive_skip_mvs_fast
from selkies_tpu.models.h264.numpy_ref import PFrameCoeffs
from selkies_tpu.models.h264.sparse_complete import complete_sparse_slice

MBH, MBW = 6, 8
M = MBH * MBW
W, H = MBW * 16, MBH * 16
LADDER = (4, 16, M)  # forced multi-bucket ladder for a tiny grid


def _fc(seed, live, mag=8, mv=8, mbh=MBH, mbw=MBW):
    """Random coefficients with EXACTLY `live` non-skip MBs."""
    rng = np.random.default_rng(seed)
    m = mbh * mbw
    skip = np.ones(m, bool)
    if live:
        skip[rng.choice(m, size=min(live, m), replace=False)] = False
    skip = skip.reshape(mbh, mbw)
    mvs = rng.integers(-mv, mv + 1, (mbh, mbw, 2)).astype(np.int32)

    def coeffs(shape):
        c = rng.integers(-mag, mag + 1, shape).astype(np.int32)
        c[rng.random(shape) < 0.8] = 0
        return c

    luma = coeffs((mbh, mbw, 4, 4, 4, 4))
    cac = coeffs((mbh, mbw, 2, 2, 2, 4, 4))
    cac[..., 0, 0] = 0  # AC blocks: DC position unused
    cdc = coeffs((mbh, mbw, 2, 2, 2))
    luma[skip] = 0
    cac[skip] = 0
    cdc[skip] = 0  # skip MBs carry no residual (encoder invariant)
    # ...and carry the DERIVED skip MV (the sparse wire ships no pairs
    # for skip MBs; the host packer re-derives them) — same invariant
    # synth_pfc honours in tests/test_sparse_native_pack.py
    derive_skip_mvs_fast(mvs, skip)
    return PFrameCoeffs(mvs=mvs, skip=skip, luma_ac=luma, chroma_dc=cdc,
                        chroma_ac=cac, qp=26)


def _out(fc):
    return {k: jnp.asarray(getattr(fc, k))
            for k in ("mvs", "skip", "luma_ac", "chroma_dc", "chroma_ac")}


_active = jax.jit(lambda o: pack_p_slice_bits_active(o, buckets=LADDER))
_full = jax.jit(pack_p_slice_bits)


def _assert_active_matches(fc, active=_active, **hdr):
    p = StreamParams(width=W, height=H, qp=fc.qp)
    ref = pack_slice_p(fc, p, frame_num=1, **hdr)
    words, nbits, trailing, ns, _counts = active(_out(fc))
    assert int(ns) == int((~fc.skip).sum())
    nal = assemble_p_nal(np.asarray(words), int(nbits), int(trailing), p, 1,
                         fc.qp, **hdr)
    assert nal == ref, f"compacted coder diverged at ns={int(ns)}"
    # and the compacted stream IS the full-grid stream
    wf, nf, tf = _full(_out(fc))
    assert int(nf) == int(nbits) and int(tf) == int(trailing)
    assert np.array_equal(np.asarray(wf)[: (int(nf) + 31) // 32],
                          np.asarray(words)[: (int(nbits) + 31) // 32])
    return [int(c) for c in np.asarray(_counts)]


@pytest.mark.parametrize("live", [0, 1, M // 2, M])
def test_density_sweep(live):
    """0% / ~2% (one MB) / 50% / 100% live MBs, each through a bucket."""
    _assert_active_matches(_fc(live * 7 + 1, live))


@pytest.mark.parametrize("live", [3, 4, 5, 15, 16, 17])
def test_bucket_boundaries(live):
    """ns exactly at / around each ladder rung (4, 16): the switch picks
    the right bucket and the padded slots stay silent."""
    _assert_active_matches(_fc(live + 100, live))


def _coded_fc(seed, luma_totals, chroma_totals, luma_scale=1, mbh=MBH,
              mbw=MBW):
    """Every MB coded; luma 4x4 block b (MB-major: (mb, y4, x4)) holds
    luma_totals[b] coefficients (levels up to 3, times luma_scale[b])
    and chroma AC block b ((mb, comp, y, x)) chroma_totals[b] — so the
    frame holds exactly as many coefficient-carrying blocks as there
    are nonzero totals."""
    rng = np.random.default_rng(seed)

    def blocks(totals, n_pos, shape, scale=1):
        c = np.zeros((len(totals), n_pos), np.int32)
        for i, t in enumerate(totals):
            pos = rng.choice(n_pos, size=t, replace=False)
            c[i, pos] = rng.choice([-3, -2, -1, 1, 1, 1, 2, 3], size=t)
        return (c * np.reshape(scale, (-1, 1))).reshape(shape)

    luma = blocks(luma_totals, 16, (mbh, mbw, 4, 4, 4, 4), luma_scale)
    cac = np.zeros((mbh * mbw * 8, 16), np.int32)
    cac[:, 1:] = blocks(chroma_totals, 15, (-1, 15))
    cac = cac.reshape(mbh, mbw, 2, 2, 2, 4, 4)
    cdc = rng.integers(-2, 3, (mbh, mbw, 2, 2, 2)).astype(np.int32)
    mvs = rng.integers(-8, 9, (mbh, mbw, 2)).astype(np.int32)
    return PFrameCoeffs(mvs=mvs, skip=np.zeros((mbh, mbw), bool),
                        luma_ac=luma, chroma_dc=cdc, chroma_ac=cac, qp=26)


def _carrying(seed, n_luma, n_chroma):
    """n_luma luma and n_chroma chroma AC blocks with 1-3 coefficients
    each, at random positions; the rest empty (blocks, MB-major)."""
    rng = np.random.default_rng(seed)
    lt = np.zeros(M * 16, int)
    lt[rng.choice(M * 16, n_luma, replace=False)] = rng.integers(1, 4, n_luma)
    ct = np.zeros(M * 8, int)
    ct[rng.choice(M * 8, n_chroma, replace=False)] = rng.integers(1, 4, n_chroma)
    return lt, ct


def _nc_classes():
    """One block of each 8x8 group (and of each chroma component) holds
    1, 4, 8 or 16 (15) coefficients, cycling over the MBs; its empty
    neighbours then read nC 0-1, 2-3, 4-7 and >= 8."""
    lt = np.zeros((M, 4, 4), int)
    ct = np.zeros((M, 2, 2, 2), int)
    for mb in range(M):
        t = (1, 4, 8, 16)[mb % 4]
        lt[mb, ::2, ::2] = t
        ct[mb, :, 0, 0] = min(t, 15)
    return lt.reshape(-1), ct.reshape(-1)


def _one_loud_mb():
    """Every coefficient in MB 5, whose blocks are full of large levels:
    its bit bound is past MB_WORDS."""
    lt = np.zeros((M, 16), int)
    lt[5] = 16
    scale = np.ones((M, 16), int)
    scale[5] = 700
    return lt.reshape(-1), np.zeros(M * 8, int), scale.reshape(-1)


# rungs of LADDER's top bucket: MBs merged whole, or its segments
_MERGE, _FULL = len(LADDER) - 1, len(LADDER)


@pytest.mark.parametrize("case,totals,slack,rung", [
    ("empty_blocks_every_nc_class", _nc_classes(), None, _MERGE),
    ("every_block_carries", (np.full(M * 16, 2), np.full(M * 8, 2)), None,
     _MERGE),
    ("mb_over_its_word_bound", _one_loud_mb(), None, _FULL),
    ("mb_words_just_hold_the_bound", _carrying(61, 300, 150), 0, _MERGE),
    ("mb_words_one_short_of_it", _carrying(61, 300, 150), -1, _FULL),
])
def test_mb_merge_rung(case, totals, slack, rung, monkeypatch):
    """The MB-merge rung: every MB coded with empty emitted blocks in
    every nC class, a frame where every block carries a coefficient, one
    MB past MB_WORDS (segments merged), and MB_WORDS set to just hold
    the frame's largest MB bound or one word short of it all code the
    oracle's bits, and the rung and counts are the ones expected."""
    fc = _coded_fc(len(case), *totals)
    active = _active
    if slack is not None:
        bound = int(jax.jit(lambda o: _mb_bits_bound(_frame_structure(o)))(
            _out(fc)))
        monkeypatch.setattr(device_cavlc, "MB_WORDS", -(-bound // 32) + slack)
        active = jax.jit(lambda o: pack_p_slice_bits_active(o, buckets=LADDER))
    nl, nc = int(np.count_nonzero(totals[0])), int(np.count_nonzero(totals[1]))
    assert _assert_active_matches(fc, active) == [nl, nc, rung]


@pytest.mark.parametrize("fill", [-1, 0])
def test_concat_rows_fills_its_words(fill):
    """_concat_rows joins each row's segments, zero-length ones included,
    bit for bit, up to a row that fills its words exactly or one bit
    short; an empty row stays empty."""
    rng = np.random.default_rng(7 - fill)
    U, K, W, n = 4, 27, 32, 8
    nbits = np.zeros((U, K), np.int32)
    for u, total in enumerate((32 * n + fill, 100, 1, 0)):
        cuts = np.sort(rng.integers(0, total + 1, K - 1))
        nbits[u] = np.diff(np.concatenate([[0], cuts, [total]]))
    bits = rng.integers(0, 2, (U, K, 32 * W)).astype(np.uint8)
    bits[np.arange(32 * W) >= nbits[..., None]] = 0
    words = np.packbits(bits, -1).view(">u4").astype(np.uint32)
    got_w, got_n = jax.jit(_concat_rows, static_argnums=2)(
        jnp.asarray(words), jnp.asarray(nbits), n)
    for u in range(U):
        row = np.concatenate([bits[u, k, :nbits[u, k]] for k in range(K)])
        want = np.packbits(np.pad(row, (0, 32 * n - len(row)))).view(">u4")
        assert int(got_n[u]) == nbits[u].sum()
        assert np.array_equal(np.asarray(got_w[u]), want.astype(np.uint32))


@pytest.mark.parametrize("length,chroma_dc", [(16, False), (15, False),
                                              (4, True)])
def test_block_bits_bound_holds(length, chroma_dc):
    """The MB-merge rung's bound is an upper bound: every block's bound
    (from its coefficients alone) is at least the bits the coder writes,
    over sparse and dense blocks, trailing ones, escapes and extended
    level prefixes, in every nC class."""
    rng = np.random.default_rng(length)
    n = 4000
    mag = rng.choice([1, 2, 3, 4, 8, 16, 100, 3000, 40000], size=(n, 1))
    c = rng.integers(-1, 2, (n, length)) * rng.integers(1, mag + 1, (n, length))
    c[rng.random((n, length)) < rng.random((n, 1))] = 0
    c = jnp.asarray(c.astype(np.int32))
    nc = (jnp.full((n,), -1, jnp.int32) if chroma_dc
          else jnp.asarray(rng.integers(0, 17, n).astype(np.int32)))
    _v, bits, _t = jax.jit(_encode_blocks, static_argnums=2)(c, nc, chroma_dc)
    exact = np.asarray(bits).sum(-1)
    bound = np.asarray(_blocks_bits_bound(c, jnp.ones((n,), bool), chroma_dc))
    assert (bound >= exact).all(), np.flatnonzero(bound < exact)[:5]


def test_big_levels_through_compaction():
    """Escape + extended-prefix levels survive the compacted path."""
    _assert_active_matches(_fc(13, 5, mag=5000))


def _entropy_fused(fc, bits_words=2048, min_mbs=0, nscap=M, cap_rows=M * 26):
    fn = jax.jit(lambda o: pack_p_sparse_entropy(
        o, nscap, cap_rows, None, bits_words, min_mbs, LADDER))
    return fn(_out(fc))


def _complete(fc, fused_d, buf_d, nscap=M, cap_rows=M * 26, **hdr):
    p = StreamParams(width=W, height=H, qp=fc.qp)
    nal, skipped, _tu, mode = complete_sparse_slice(
        np.asarray(fused_d), mbh=MBH, mbw=MBW, nscap=nscap,
        cap_rows=cap_rows, qp=fc.qp, frame_num=1, params=p,
        device_bits=True, full_d=fused_d, buf_d=buf_d, **hdr)
    return nal, skipped, mode


def test_fused_bits_mode_end_to_end():
    """pack_p_sparse_entropy mode=1 -> the host splice reproduces the
    oracle, and the reported skip count matches."""
    fc = _fc(21, M // 2)
    fused_d, _dense_d, buf_d = _entropy_fused(fc)
    mode, nbits, _t, nskip, ns = p_sparse_entropy_meta(np.asarray(fused_d))
    assert mode == 1 and nbits > 0 and ns == int((~fc.skip).sum())
    nal, skipped, m = _complete(fc, fused_d, buf_d)
    p = StreamParams(width=W, height=H, qp=fc.qp)
    assert m == "bits" and skipped == int(fc.skip.sum()) == nskip
    assert nal == pack_slice_p(fc, p, frame_num=1)


def test_word_cap_overflow_falls_back_to_coeff():
    """bits_words too small for the slice -> the on-device decision
    ships coefficients instead; byte output is unchanged."""
    fc = _fc(22, M)  # dense frame, thousands of bits
    fused_d, _dense_d, buf_d = _entropy_fused(fc, bits_words=4)
    assert p_sparse_entropy_meta(np.asarray(fused_d))[0] == 0
    nal, _skipped, m = _complete(fc, fused_d, buf_d)
    p = StreamParams(width=W, height=H, qp=fc.qp)
    assert m == "coeff"
    assert nal == pack_slice_p(fc, p, frame_num=1)


def test_min_mbs_threshold_keeps_quiet_frames_on_coeff():
    fc = _fc(23, 2)
    fused_d, _dense_d, buf_d = _entropy_fused(fc, min_mbs=10)
    assert p_sparse_entropy_meta(np.asarray(fused_d))[0] == 0
    nal, _s, m = _complete(fc, fused_d, buf_d)
    p = StreamParams(width=W, height=H, qp=fc.qp)
    assert m == "coeff"
    assert nal == pack_slice_p(fc, p, frame_num=1)


@pytest.mark.parametrize("hdr", [
    {"ltr_ref": 1},
    {"mark_ltr": 0},
    {"mark_ltr": 1, "mmco_evict": (0, 2)},
])
def test_ltr_header_variants_on_bits(hdr):
    """LTR slice-header flags live entirely in the host-written header;
    the device bits splice must carry them bit-exactly (the header tail
    shifts the device stream by a different phase per variant)."""
    fc = _fc(31, M // 2)
    fused_d, _dense_d, buf_d = _entropy_fused(fc)
    nal, _s, m = _complete(fc, fused_d, buf_d, **hdr)
    p = StreamParams(width=W, height=H, qp=fc.qp)
    assert m == "bits"
    assert nal == pack_slice_p(fc, p, frame_num=1, **hdr)


def test_banded_slice_nonzero_first_mb():
    """A band's bits splice with first_mb_in_slice > 0 matches the host
    pack of the same band grid (slice-local prediction resets)."""
    fc = _fc(41, 10, mbh=3, mbw=MBW)  # one 3-row band of a 6-row frame
    p = StreamParams(width=W, height=H, qp=fc.qp)
    first_mb = 3 * MBW  # second band
    ref = pack_slice_p(fc, p, frame_num=1, first_mb=first_mb)
    words, nbits, trailing, _ns, _counts = jax.jit(
        lambda o: pack_p_slice_bits_active(o, buckets=bits_buckets(3 * MBW))
    )(_out(fc))
    nal = assemble_p_nal(np.asarray(words), int(nbits), int(trailing), p, 1,
                         fc.qp, first_mb=first_mb)
    assert nal == ref


def test_bits_step_meta_counts_carrying_blocks():
    """The full-P bits step's meta prefix [nbits, trailing, nskip,
    coef_luma, coef_chroma, rung] counts the blocks the host sees hold a
    coefficient, and the encoder surfaces them per frame in FrameStats
    and in the tracer's counters."""
    from selkies_tpu.models.h264 import encoder as enc_mod
    from selkies_tpu.models.h264.encoder_core import encode_frame_p_planes
    from selkies_tpu.monitoring.tracing import tracer

    rng = np.random.default_rng(5)
    w, h = 96, 64
    planes = [jnp.asarray(rng.integers(0, 255, shp, np.uint8))
              for shp in ((h, w), (h // 2, w // 2), (h // 2, w // 2))]
    ref = [jnp.roll(p, 3, axis=1) for p in planes]
    qp = jnp.int32(30)
    prefix = np.asarray(jax.jit(enc_mod._p_bits_step)(*planes, qp, *ref)[0])
    out = jax.jit(encode_frame_p_planes)(*planes, *ref, qp)
    coded = ~np.asarray(out["skip"])[..., None, None]
    luma = (np.asarray(out["luma_ac"]) != 0).any((-2, -1)) & coded
    cac = (np.asarray(out["chroma_ac"]) != 0).any((-2, -1)) & coded[..., None]
    assert luma.sum() > 0 and cac.sum() > 0
    assert list(prefix[3:enc_mod.BITS_META]) == [luma.sum(), cac.sum(), 0]

    frames = [np.ascontiguousarray(rng.integers(0, 255, (h, w, 4), np.uint8))
              for _ in range(3)]
    was = tracer.enabled
    tracer.enable()
    tracer.reset()
    try:
        enc = enc_mod.TPUH264Encoder(w, h, qp=30, frame_batch=1,
                                     device_entropy=True)
        stats = []
        for f in frames:
            enc.encode_frame(f)
            stats.append(enc.last_stats)
        summary = tracer.summary()
    finally:
        tracer.enabled = was
        tracer.reset()
    assert stats[0].bits_rung == -1 and stats[0].coef_blocks_luma == 0  # IDR
    for st in stats[1:]:
        assert st.downlink_mode == "bits" and st.bits_rung == 0
        assert 0 < st.coef_blocks_luma <= 16 * (w // 16) * (h // 16)
    assert summary["coef_blocks_luma"]["count"] == 2
    assert summary["coef_blocks_luma"]["max"] == max(
        st.coef_blocks_luma for st in stats[1:])
    assert summary["bits_rung"]["hist"] == {"0": 2}


def test_banded_encoder_bits_vs_coeff_byte_identity():
    """BandedH264Encoder with per-band device entropy == without, over
    IDR + busy P + static frames (2 bands, nonzero first_mb slices)."""
    from selkies_tpu.parallel.bands import BandedH264Encoder

    rng = np.random.default_rng(3)
    frames = [np.ascontiguousarray(rng.integers(0, 255, (96, 96, 4), np.uint8))
              for _ in range(3)]
    frames.append(frames[-1].copy())  # static tail
    ref_enc = BandedH264Encoder(96, 96, qp=24, bands=2, device_entropy=False)
    ref = [ref_enc.encode_frame(f) for f in frames]
    enc = BandedH264Encoder(96, 96, qp=24, bands=2, device_entropy=True,
                            bits_min_mbs=0)
    got = [enc.encode_frame(f) for f in frames]
    assert got == ref
    assert enc.last_stats.downlink_mode == ""  # static frame: no downlink


def _delta_trace(seed=7, w=96, h=64, n=6):
    rng = np.random.default_rng(seed)
    f0 = np.ascontiguousarray(rng.integers(0, 255, (h, w, 4), np.uint8))
    frames = [f0]
    for i in range(1, n):
        f = frames[-1].copy()
        f[(i * 16) % h:(i * 16) % h + 16, 0:16] ^= (i + 1)
        frames.append(f)
    return frames


def test_grouped_scan_vs_single_frame_oracle():
    """frame_batch>1 grouped lax.scan with forced bits mode == the
    single-frame no-entropy oracle, frame for frame."""
    from selkies_tpu.models.h264.encoder import TPUH264Encoder

    frames = _delta_trace()
    ref_enc = TPUH264Encoder(96, 64, qp=24, frame_batch=1,
                             device_entropy=False)
    ref = [ref_enc.encode_frame(f) for f in frames]
    enc = TPUH264Encoder(96, 64, qp=24, frame_batch=3, pipeline_depth=1,
                         device_entropy=True, bits_min_mbs=0)
    got = []
    for f in frames:
        got += [au for au, _s, _m in enc.submit(f)]
    got += [au for au, _s, _m in enc.flush()]
    assert got == ref


def test_bits_refetch_on_short_hint():
    """A hint-sized fetch shorter than the bits payload refetches from
    the full device handle (the bits_fetch path), accounts the bytes
    under down_bits*, and stays byte-exact."""
    from selkies_tpu.models.h264.compact import ENTROPY_META16
    from selkies_tpu.models.stats import LinkByteCounter

    fc = _fc(51, M // 2)
    fused_d, _dense_d, buf_d = _entropy_fused(fc)
    short = np.asarray(fused_d)[:ENTROPY_META16 + 8]  # meta only
    lb = LinkByteCounter()
    p = StreamParams(width=W, height=H, qp=fc.qp)
    nal, _s, _tu, mode = complete_sparse_slice(
        short, mbh=MBH, mbw=MBW, nscap=M, cap_rows=M * 26, qp=fc.qp,
        frame_num=1, params=p, device_bits=True, full_d=fused_d,
        buf_d=buf_d, link_bytes=lb, prefix_bytes=short.nbytes)
    assert mode == "bits"
    assert nal == pack_slice_p(fc, p, frame_num=1)
    snap = lb.snapshot()
    assert snap.get("down_bits_refetch", 0) > 0
    assert snap.get("down_bits", 0) == short.nbytes
