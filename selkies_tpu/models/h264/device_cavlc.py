"""CAVLC entropy coding ON DEVICE: P-frame slice-data bits from XLA,
with cost proportional to frame ACTIVITY, not frame area.

The compact-coefficient downlink still ships multi-MB tensors for busy
frames (a 1080p full-frame change is ~4.5 MB of nonzero rows — the
dominant cost on a per-byte-priced link, PERF.md). This module moves the
entire §9.2 entropy coder into the frame jit, so what crosses the link
is the final slice-data bitstream (~50-300 KB), exactly like the
reference's NVENC emits finished bitstreams on-GPU.

Two entry points share one implementation:

* ``pack_p_slice_bits`` — the full-grid coder (every MB pays), used as
  the fixed-shape oracle by tests and the profiler;
* ``pack_p_slice_bits_active`` — the production coder: the skip map and
  per-MB TotalCoeff are known before any bit is written, so the coded
  (non-skip) MBs are COMPACTED into a dense prefix and the expensive
  per-block work (VLC one-hot LUT contractions, level suffix chains,
  prefix-sum bit concatenation) runs over a bucketed padded count of
  active MBs — a typing frame with ~200 live MBs pays ~256 MBs of
  entropy-coding work instead of the full 8160-MB grid. Buckets are
  powers-of-two-ish (`bits_buckets`) selected per frame ON DEVICE via
  ``lax.switch`` — one executable, no recompiles (the same discipline
  as the NSCAP dense fallback in encoder_core.pack_p_sparse_packed).
  Compaction preserves raster order and padded slots emit zero bits,
  so the merged stream is bit-identical to the full-grid coder. At the
  ladder's top, each MB's 27 segments are first concatenated and whole
  MBs merged, where every MB's bit bound allows: the merge's cost is per
  unit, and a busy frame at a high QP codes every MB in few bits.

Everything vectorizes: VLC tables become constant-array gathers; the
per-level suffix-length adaptation and run_before chains are unrolled
16-step walks across ALL blocks at once; nC neighbour contexts are plain
shifted-grid reads; the serial-looking bit concatenation is two levels
of prefix-sum offsets + shift/scatter-add (bit-disjoint, so add == or).

The host prepends the slice header (variable length, so the device
stream is bit-shifted to the header tail — ``first_mb_in_slice`` for a
band slice lives in that header, so band bits need no device change),
appends the trailing skip_run + rbsp trailing bits, and runs emulation
prevention (C++). Output is BIT-IDENTICAL to cavlc.pack_slice_p
(tests/test_device_cavlc.py, tests/test_device_entropy_sparse.py).
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from selkies_tpu.models.h264 import tables as T
from selkies_tpu.models.h264.cavlc import INTER_CBP_TO_CODENUM

__all__ = [
    "pack_p_slice_bits",
    "pack_p_slice_bits_active",
    "bits_buckets",
    "device_entropy_default",
    "entropy_coder_default",
    "resolve_entropy",
    "BITS_MIN_MBS_DEFAULT",
    "WORD_CAP_DEFAULT",
]

# A delta/band P slice with at least this many live (non-skip) MBs ships
# its final slice bits; below it the sparse coefficient downlink is
# already small and its host pack near-free. SELKIES_BITS_MIN_MBS
# overrides (the density-threshold knob, docs/device_entropy.md).
BITS_MIN_MBS_DEFAULT = 512


def device_entropy_default(explicit=None) -> bool:
    """Resolve the device-entropy knob: an explicit constructor argument
    wins, then SELKIES_DEVICE_ENTROPY=0/1, then auto — on for real TPU
    backends, off on CPU, where the "device" coder competes with the
    host pack for the same cores and only adds compile time (the
    SELKIES_PALLAS_ME dispatch discipline)."""
    if explicit is not None:
        return bool(explicit)
    import os

    env = os.environ.get("SELKIES_DEVICE_ENTROPY", "")
    if env == "0":
        return False
    if env:
        return True
    try:
        return jax.default_backend() == "tpu"
    except Exception:
        return False


def entropy_coder_default(explicit=None) -> str:
    """Resolve the entropy-coder knob: an explicit constructor argument
    wins, then SELKIES_ENTROPY_CODER=cavlc/cabac/auto, else cavlc (the
    Baseline-profile default every pre-CABAC byte contract was recorded
    against). ``auto`` picks cabac on real TPU backends and cavlc on
    CPU — same dispatch discipline as device_entropy_default: the
    CABAC tail costs a host arithmetic-engine pass per slice, which
    the Main-profile bitrate win pays for on a TPU-fed stream but not
    on a CPU backend already contending for the same cores."""
    coder = explicit
    if coder is None:
        import os

        coder = os.environ.get("SELKIES_ENTROPY_CODER", "") or "cavlc"
    coder = str(coder).lower()
    if coder == "auto":
        try:
            return "cabac" if jax.default_backend() == "tpu" else "cavlc"
        except Exception:
            return "cavlc"
    if coder not in ("cavlc", "cabac"):
        raise ValueError(
            f"entropy_coder must be cavlc|cabac|auto, got {coder!r}")
    return coder


def resolve_entropy(m: int, device_entropy=None, bits_min_mbs=None,
                    entropy_coder=None):
    """One resolver for the device-entropy knobs, shared by the solo and
    banded encoders -> (enabled, min_mbs, bits_words, consts).

    `m` is the slice MB count (full grid, or one band). `consts` is the
    (bits_words, min_mbs, buckets, coder) tuple the jitted
    encoder_core.pack_p_sparse_entropy closes over — None when the
    feature is off. For CAVLC bits_words is the bit-payload cap in
    uint32 words (~16 words/MB covers busy desktop residuals, clamped
    to 256 KB); for CABAC it is the token-word cap
    (device_cabac.cabac_tok_words) since the payload is the 16-bit
    token IR, not final bits."""
    enabled = device_entropy_default(device_entropy)
    coder = entropy_coder_default(entropy_coder)
    if bits_min_mbs is None:
        import os

        try:
            bits_min_mbs = int(os.environ.get("SELKIES_BITS_MIN_MBS", "")
                               or BITS_MIN_MBS_DEFAULT)
        except ValueError:
            bits_min_mbs = BITS_MIN_MBS_DEFAULT
    min_mbs = max(0, int(bits_min_mbs))
    if coder == "cabac":
        from selkies_tpu.models.h264.device_cabac import cabac_tok_words

        bits_words = cabac_tok_words(m)
    else:
        bits_words = min(1 << 16, max(1024, 16 * int(m)))
    consts = ((bits_words, min_mbs, bits_buckets(m), coder)
              if enabled else None)
    return enabled, min_mbs, bits_words, consts

# ---------------------------------------------------------------------------
# VLC tables as dense arrays (generated from the FFmpeg-validated
# functions in tables.py, so the two representations cannot drift)
# ---------------------------------------------------------------------------

# coeff_token: class 0..2 -> nc buckets [0,2) [2,4) [4,8); class 3 = nc>=8
# (computed arithmetically); class 4 = chroma DC (nc == -1).
_CT_VAL = np.zeros((5, 17, 4), np.int32)
_CT_BITS = np.zeros((5, 17, 4), np.int32)
for cls, nc_probe in enumerate((0, 2, 4, 8, -1)):
    for total in range(17):
        for t1 in range(min(total, 3) + 1):
            if nc_probe == -1 and total > 4:
                continue
            v, b = T.coeff_token_code(nc_probe, total, t1)
            _CT_VAL[cls, total, t1] = v
            _CT_BITS[cls, total, t1] = b

_TZ_VAL = np.zeros((17, 16), np.int32)
_TZ_BITS = np.zeros((17, 16), np.int32)
for total in range(1, 16):
    for tz in range(0, 16 - total + 1):
        v, b = T.total_zeros_code(total, tz, chroma_dc=False)
        _TZ_VAL[total, tz] = v
        _TZ_BITS[total, tz] = b
_TZC_VAL = np.zeros((4, 4), np.int32)
_TZC_BITS = np.zeros((4, 4), np.int32)
for total in range(1, 4):
    for tz in range(0, 4 - total + 1):
        v, b = T.total_zeros_code(total, tz, chroma_dc=True)
        _TZC_VAL[total, tz] = v
        _TZC_BITS[total, tz] = b

# run_before: zeros_left clamps at 7 in the spec table; run <= 14
_RB_VAL = np.zeros((15, 15), np.int32)
_RB_BITS = np.zeros((15, 15), np.int32)
for zl in range(1, 15):
    for run in range(0, zl + 1):
        v, b = T.run_before_code(zl, run)
        _RB_VAL[zl, run] = v
        _RB_BITS[zl, run] = b

_ZIGZAG = np.asarray(T.ZIGZAG_FLAT, np.int32)            # (16,)
_CBP_CODENUM = np.asarray(INTER_CBP_TO_CODENUM, np.int32)

# luma 4x4 blocks in coding order -> (x4, y4); block index within MB
_LUMA_ORDER = np.asarray(
    [[x4, y4] for x4, y4 in T.LUMA_BLOCK_ORDER], np.int32
)  # (16, 2)
_CHROMA_ORDER = np.asarray([[x, y] for x, y in T.CHROMA_BLOCK_ORDER], np.int32)

WORD_CAP_DEFAULT = 1 << 17  # 512 KB frame bitstream capacity


def _lut(idx, pair: np.ndarray):
    """(value, bits) VLC lookup via a one-hot f32 matmul.

    pair: (N, 2) np table. Per-element gathers price ~17 ns on v5e — a
    (B, 15) run_before lookup pair costs 30+ ms as a gather and ~1 ms as
    an MXU contraction (measured on an earlier setup). f32 is exact for
    every VLC value (< 2^24)."""
    n = pair.shape[0]
    flat = idx.reshape(-1)
    oh = (flat[:, None] == jnp.arange(n, dtype=jnp.int32)[None, :]).astype(jnp.float32)
    out = jnp.dot(oh, jnp.asarray(pair, jnp.float32),
                  preferred_element_type=jnp.float32)
    return (out[:, 0].reshape(idx.shape).astype(jnp.int32),
            out[:, 1].reshape(idx.shape).astype(jnp.int32))


_RB_PAIR = np.stack([_RB_VAL.reshape(-1), _RB_BITS.reshape(-1)], 1).astype(np.float32)
_TZ_PAIR = np.stack([_TZ_VAL.reshape(-1), _TZ_BITS.reshape(-1)], 1).astype(np.float32)
_TZC_PAIR = np.stack([_TZC_VAL.reshape(-1), _TZC_BITS.reshape(-1)], 1).astype(np.float32)
_CT_PAIR = np.stack([_CT_VAL.reshape(-1), _CT_BITS.reshape(-1)], 1).astype(np.float32)


def _ue_bits(v):
    """Exp-Golomb codeword for v (vectorized): (value, nbits)."""
    v1 = v + 1
    # floor(log2(v1)): count significant bits - 1
    nb = 32 - jnp.clip(_clz32(v1), 0, 31)
    return v1, 2 * nb - 1


def _clz32(x):
    """Count leading zeros of a positive int32 (vectorized)."""
    x = x.astype(jnp.uint32)
    n = jnp.zeros_like(x, jnp.int32)
    for shift in (16, 8, 4, 2, 1):
        big = x >= (1 << shift)
        n = jnp.where(big, n + shift, n)
        x = jnp.where(big, x >> shift, x)
    return 31 - n


def _se_bits(v):
    """Signed Exp-Golomb: map se value -> ue codeword."""
    code = jnp.where(v > 0, 2 * v - 1, -2 * v)
    return _ue_bits(code)


def _level_bits(level_code, suffix_len):
    """Two (value, nbits) pairs — prefix codeword and suffix — for one
    level (9.2.2.1), matching cavlc._write_level exactly. Split keeps
    every emission slot <= 28 bits (a 64-bit pack lane covers any slot
    start within a word).

    Extended prefixes (16+) are solved arithmetically: with
    x = lc_adj - (15 << sl) + 2^12, prefix p covers x in
    [2^(p-3), 2^(p-2)), so p = floor(log2 x) + 3 — a 5-step clz instead
    of a 12-iteration search (this runs on every level of every block)."""
    lc0 = level_code
    lc_adj = jnp.where((suffix_len == 0) & (lc0 >= 30), lc0 - 15, lc0)
    sl = jnp.maximum(suffix_len, 0)
    prefix = lc_adj >> sl
    # regular: prefix zeros + 1, then sl suffix bits
    v1 = jnp.ones_like(lc0)
    b1 = prefix + 1
    v2 = lc_adj & ((jnp.int32(1) << sl) - 1)
    b2 = sl
    # escape: prefix 15 (16-bit '...1'), 12-bit suffix
    esc = lc_adj - (jnp.int32(15) << sl)
    in_esc = (prefix >= 15) & (esc < (1 << 12))
    b1 = jnp.where(in_esc, 16, b1)
    v2 = jnp.where(in_esc, jnp.clip(esc, 0, (1 << 12) - 1), v2)
    b2 = jnp.where(in_esc, 12, b2)
    # extended prefixes 16+
    x = jnp.maximum(esc + (1 << 12), 1)
    nb = 31 - _clz32(x)  # floor(log2 x)
    ext = (prefix >= 15) & ~in_esc
    b1 = jnp.where(ext, nb + 4, b1)          # pfx + 1 = (nb + 3) + 1
    v2 = jnp.where(ext, x - (jnp.int32(1) << nb), v2)
    b2 = jnp.where(ext, nb, b2)              # pfx - 3
    # suffix_len==0 specials
    small = (suffix_len == 0) & (lc0 < 14)
    b1 = jnp.where(small, lc0 + 1, b1)
    v2 = jnp.where(small, 0, v2)
    b2 = jnp.where(small, 0, b2)
    mid = (suffix_len == 0) & (lc0 >= 14) & (lc0 < 30)
    b1 = jnp.where(mid, 15, b1)
    v2 = jnp.where(mid, lc0 - 14, v2)
    b2 = jnp.where(mid, 4, b2)
    return v1, b1, v2, b2


def _encode_blocks(coeffs, nc, chroma_dc: bool):
    """CAVLC-encode a batch of residual blocks.

    coeffs: (B, L) int32 scan-order coefficients (L = 16, 15 or 4);
    nc: (B,) int32 neighbour context (-1 for chroma DC).
    Returns (vals (B, S), bits (B, S), total (B,)) — S emission slots in
    order; bits==0 slots contribute nothing.
    """
    B, L = coeffs.shape
    nz = coeffs != 0
    total = nz.sum(-1).astype(jnp.int32)
    # reverse-scan-order nonzero compaction WITHOUT argsort (sorts are
    # ~30 ms at frame scale on TPU; this one-hot contraction is ~free):
    # walking the reversed block, the k-th nonzero seen is slot k
    rev = coeffs[:, ::-1]
    nzr = rev != 0
    rank = jnp.cumsum(nzr, -1, dtype=jnp.int32) - 1
    oh = ((rank[:, :, None] == jnp.arange(L, dtype=jnp.int32)[None, None, :])
          & nzr[:, :, None]).astype(jnp.int32)
    val_rev = jnp.einsum("blk,bl->bk", oh, rev)
    pos_of = jnp.broadcast_to((L - 1 - jnp.arange(L, dtype=jnp.int32))[None, :], (B, L))
    pos_rev = jnp.einsum("blk,bl->bk", oh, pos_of)
    idx = jnp.arange(L, dtype=jnp.int32)[None, :]
    valid = idx < total[:, None]

    # trailing ones: leading run of |1| in val_rev, capped at 3
    is_one = (jnp.abs(val_rev) == 1) & valid
    run1 = jnp.cumprod(is_one, axis=-1, dtype=jnp.int32)
    t1 = jnp.minimum(run1.sum(-1), 3).astype(jnp.int32)

    # coeff_token
    cls = jnp.where(
        nc < 0, 4, jnp.where(nc < 2, 0, jnp.where(nc < 4, 1, jnp.where(nc < 8, 2, 3)))
    )
    ct_val, ct_bits = _lut(cls * 68 + total * 4 + t1, _CT_PAIR)
    # nc >= 8: arithmetic FLC (class 3 table rows were generated for nc=8;
    # they ARE the FLC — generated from the same function, so no special
    # case needed here)

    # Slot layout (emission order): token, 3 t1 signs, 2L interleaved
    # level (prefix, suffix) pairs, total_zeros, L-1 run_befores. The
    # segments are built separately and CONCATENATED once — strided
    # .at[].set() column writes into a (B, S) buffer relayout the whole
    # array per write on TPU.
    sign_v, sign_b = [], []
    for k in range(3):
        sign = (val_rev[:, k] < 0).astype(jnp.int32)
        use = (k < t1) & (total > 0)
        sign_v.append(jnp.where(use, sign, 0))
        sign_b.append(jnp.where(use, 1, 0))

    # levels after the trailing ones. The suffix-length adaptation is the
    # only sequential dependency (~10 ops/step); the codeword
    # construction (_level_bits with its escape/extended-prefix logic)
    # depends only on (level, suffix_len_before, is_first), so it runs
    # ONCE vectorized over all (L, B) slots. The L-step walk is UNROLLED
    # in Python: a lax.scan at this width pays ~1.5 ms of per-step launch
    # overhead on v5e (measured on an earlier setup) while the unrolled
    # form fuses into a handful of kernels.
    init_sl = jnp.where((total > 10) & (t1 < 3), 1, 0)
    val_t = val_rev.T  # (L, B)
    sls_l, firsts_l, uses_l = [], [], []
    suffix_len = init_sl
    first_done = jnp.zeros((B,), bool)
    for k in range(L):
        level = val_t[k]
        use = (k >= t1) & (k < total)
        is_first = use & ~first_done
        new_sl = jnp.where(suffix_len == 0, 1, suffix_len)
        new_sl = jnp.where(
            (jnp.abs(level) > (3 << jnp.maximum(new_sl - 1, 0))) & (new_sl < 6),
            new_sl + 1,
            new_sl,
        )
        sls_l.append(suffix_len)
        firsts_l.append(is_first)
        uses_l.append(use)
        suffix_len = jnp.where(use, new_sl, suffix_len)
        first_done = first_done | is_first
    sls = jnp.stack(sls_l)
    firsts = jnp.stack(firsts_l)
    uses = jnp.stack(uses_l)
    level_code = jnp.where(val_t > 0, 2 * val_t - 2, -2 * val_t - 1)
    level_code = jnp.where(firsts & (t1[None, :] < 3), level_code - 2, level_code)
    lv1, lb1, lv2, lb2 = _level_bits(level_code, sls)
    lv1 = jnp.where(uses, lv1, 0)
    lb1 = jnp.where(uses, lb1, 0)
    lv2 = jnp.where(uses, lv2, 0)
    lb2 = jnp.where(uses, lb2, 0)
    lev_v = jnp.stack([lv1.T, lv2.T], -1).reshape(B, 2 * L)
    lev_b = jnp.stack([lb1.T, lb2.T], -1).reshape(B, 2 * L)

    # total_zeros
    last_pos = pos_rev[:, 0]
    tz = jnp.where(total > 0, last_pos + 1 - total, 0)
    if chroma_dc:
        tz_val, tz_bits = _lut(jnp.clip(total, 0, 3) * 4 + jnp.clip(tz, 0, 3), _TZC_PAIR)
    else:
        tz_val, tz_bits = _lut(jnp.clip(total, 0, 16) * 16 + jnp.clip(tz, 0, 15), _TZ_PAIR)
    use_tz = (total > 0) & (total < L)
    tz_v = jnp.where(use_tz, tz_val, 0)
    tz_b = jnp.where(use_tz, tz_bits, 0)

    # run_before chain (reverse order). The zeros_left recurrence has a
    # CLOSED FORM (telescoping): zeros_left at step k
    #   = tz - sum_{j<k} run_j = tz - (pos_0 - pos_k - k)
    #   = pos_k + k + 1 - total          (since tz = pos_0 + 1 - total)
    # so the whole chain vectorizes — no scan.
    ks_col = jnp.arange(L - 1, dtype=jnp.int32)[None, :]
    run = pos_rev[:, :-1] - pos_rev[:, 1:] - 1            # (B, L-1)
    zl = pos_rev[:, :-1] + ks_col + 1 - total[:, None]    # zeros_left before step k
    use_r = (ks_col < total[:, None] - 1) & (zl > 0)
    rv, rb = _lut(jnp.clip(zl, 0, 14) * 15 + jnp.clip(run, 0, 14), _RB_PAIR)

    vals = jnp.concatenate(
        [ct_val[:, None], jnp.stack(sign_v, -1), lev_v, tz_v[:, None],
         jnp.where(use_r, rv, 0)], axis=1)
    bits = jnp.concatenate(
        [ct_bits[:, None], jnp.stack(sign_b, -1), lev_b, tz_b[:, None],
         jnp.where(use_r, rb, 0)], axis=1)
    return vals, bits, total


def _split2(val, start_in_word, bits):
    """32-bit-only placement of a codeword (<= 28 bits) whose first bit
    lands at `start_in_word` (0..31) of a word: returns (hi, lo) uint32
    contributions to that word and the next. MSB-first."""
    v = val.astype(jnp.uint32)
    fits = start_in_word + bits <= 32
    sh_hi = jnp.clip(32 - start_in_word - bits, 0, 31)
    hi_fit = v << sh_hi
    over = jnp.clip(start_in_word + bits - 32, 1, 31)  # valid in split case
    hi_split = v >> over
    lo_split = (v & ((jnp.uint32(1) << over) - 1)) << (32 - over)
    hi = jnp.where(fits, hi_fit, hi_split)
    lo = jnp.where(fits, 0, lo_split)
    return hi, lo


def _pack_pairs(vals, bits, nwords: int):
    """Pack (U, S) (value, nbits) emission slots into per-unit bit
    buffers: returns (words (U, nwords) uint32, nbits_total (U,)).
    MSB-first within the stream; word 0 holds the first 32 bits.
    32-bit ops only (jax default has no uint64).

    Formulation: a dense one-hot contraction over the output words.
    Slot word-targets are data-dependent, which invites a scatter-add —
    but TPU scatter runs ~20 ns/update (145 ms/frame at CAVLC scale)
    while this where-sum fuses into ~4 ms. Bits are disjoint by
    construction, so integer add == bitwise or."""
    U, S = vals.shape
    offs = jnp.concatenate(
        [jnp.zeros((U, 1), jnp.int32), jnp.cumsum(bits, -1)], -1
    )  # (U, S+1)
    total_bits = offs[:, -1]
    vmask = jnp.where(bits >= 32, jnp.uint32(0xFFFFFFFF),
                      (jnp.uint32(1) << jnp.clip(bits, 0, 31)) - 1)
    v = vals.astype(jnp.uint32) & vmask
    start = offs[:, :-1]
    w0 = start >> 5
    hi, lo = _split2(v, start & 31, bits)
    use = bits > 0
    hi = jnp.where(use, hi, jnp.uint32(0))
    lo = jnp.where(use, lo, jnp.uint32(0))
    wids = jnp.arange(nwords, dtype=jnp.int32)
    oh_hi = w0[:, :, None] == wids[None, None, :]
    oh_lo = (w0[:, :, None] + 1) == wids[None, None, :]
    words = (
        jnp.where(oh_hi, hi[:, :, None], jnp.uint32(0)).sum(1, dtype=jnp.uint32)
        + jnp.where(oh_lo, lo[:, :, None], jnp.uint32(0)).sum(1, dtype=jnp.uint32)
    )
    return words, total_bits


def _merge_streams(words, nbits, out_words: int):
    """Concatenate U bit-buffers: (U, W) words + (U,) lengths ->
    ((out_words,) uint32, total_bits).

    Scatter-adding every unit word (U*W elements) costs >100 ms/frame on
    TPU, so the scatter is shrunk to the words that actually EXIST:

    1. shift every unit to its final bit phase (elementwise, cheap);
    2. count the output words each unit touches (nwp) and lay the used
       words out compactly via cumsum; recover slot->unit with a marker
       scatter (U unique updates) + prefix sum — no searchsorted (its
       binary-search gathers cost more than the merge itself);
    3. gather each used word and scatter-add into the stream — ~T
       near-unique updates where T ≈ total_bits/32 + #nonempty units,
       an order of magnitude under U*W.

    Slots past T_CAP = 2U + out_words only exist when total_bits
    overflows out_words*32, which the caller already treats as the
    fall-back-to-host case. Adjacent units share at most boundary words
    with disjoint bits, so add == or."""
    U, W = words.shape
    offs = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(nbits)])
    starts = offs[:-1]
    total = offs[-1]
    sh = (starts & 31)[:, None]  # right-shift amount (0..31)
    hi = jnp.where(sh > 0, words >> jnp.clip(sh, 0, 31).astype(jnp.uint32), words)
    lo = jnp.where(
        sh > 0,
        (words & ((jnp.uint32(1) << jnp.clip(sh, 1, 31).astype(jnp.uint32)) - 1))
        << jnp.clip(32 - sh, 1, 31).astype(jnp.uint32),
        jnp.uint32(0),
    )
    shifted = jnp.concatenate([hi, jnp.zeros((U, 1), jnp.uint32)], 1) + jnp.concatenate(
        [jnp.zeros((U, 1), jnp.uint32), lo], 1
    )  # (U, W+1): unit words at final bit phase
    nwp = jnp.where(nbits > 0, (nbits + (starts & 31) + 31) >> 5, 0)  # words touched
    woffs = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(nwp)])
    T_CAP = 2 * U + out_words
    mark = jnp.zeros((T_CAP + 1,), jnp.int32)
    mark = mark.at[jnp.clip(woffs[:-1], 0, T_CAP)].add(1)
    unit = jnp.cumsum(mark[:T_CAP]) - 1  # slot -> unit (empties map to none)
    unitc = jnp.clip(unit, 0, U - 1)
    slots = jnp.arange(T_CAP, dtype=jnp.int32)
    win = slots - woffs[unitc]
    valid = (unit >= 0) & (win >= 0) & (win < nwp[unitc])
    vals = shifted[unitc, jnp.clip(win, 0, W)]
    tgt = jnp.where(valid, (starts[unitc] >> 5) + win, out_words)
    out = jnp.zeros((out_words + 1,), jnp.uint32)
    out = out.at[jnp.clip(tgt, 0, out_words)].add(jnp.where(valid, vals, jnp.uint32(0)))
    return out[:out_words], total


def _mv_pred_grid(mvs, skip_unused):
    """Vectorized 8.4.1.3 prediction for every MB (mirrors
    numpy_ref.mv_pred_16x16 including availability cases)."""
    mbh, mbw = mvs.shape[:2]
    zeros = jnp.zeros_like(mvs)
    left = jnp.pad(mvs, ((0, 0), (1, 0), (0, 0)))[:, :-1]
    top = jnp.pad(mvs, ((1, 0), (0, 0), (0, 0)))[:-1]
    tr = jnp.pad(mvs, ((1, 0), (0, 1), (0, 0)))[:-1, 1:]
    tl = jnp.pad(mvs, ((1, 0), (1, 0), (0, 0)))[:-1, :-1]
    col = jnp.arange(mbw)[None, :, None]
    row = jnp.arange(mbh)[:, None, None]
    a_avail = col > 0
    b_avail = row > 0
    c_avail = (row > 0) & (col + 1 < mbw)
    d_avail = (row > 0) & (col > 0)
    c_sub = jnp.where(c_avail, tr, jnp.where(d_avail, tl, zeros))
    c_eff_avail = c_avail | d_avail
    a = jnp.where(a_avail, left, zeros)
    b = jnp.where(b_avail, top, zeros)
    med = a + b + c_sub - jnp.maximum(jnp.maximum(a, b), c_sub) - jnp.minimum(
        jnp.minimum(a, b), c_sub
    )
    n_avail = (
        a_avail.astype(jnp.int32) + b_avail.astype(jnp.int32) + c_eff_avail.astype(jnp.int32)
    )
    only = jnp.where(a_avail, a, jnp.where(b_avail, b, c_sub))
    pred = jnp.where(n_avail == 1, only, med)
    # 8.4.1.3.1: only A available (B, C, D all unavailable) -> mvA
    pred = jnp.where(a_avail & ~b_avail & ~c_eff_avail, a, pred)
    return pred


def _nc_grid(grid):
    """nC for every block position of a (BH, BW) TotalCoeff grid —
    elementwise shifted reads (9.2.1 availability: left/top within the
    slice), no per-block gather. Used instead of the old flat fancy-index
    reads so the per-MB structure compacts with plain row scatters."""
    bh, bw = grid.shape
    left = jnp.pad(grid, ((0, 0), (1, 0)))[:, :-1]
    top = jnp.pad(grid, ((1, 0), (0, 0)))[:-1]
    has_l = jnp.arange(bw, dtype=jnp.int32)[None, :] > 0
    has_t = jnp.arange(bh, dtype=jnp.int32)[:, None] > 0
    both = (left + top + 1) >> 1
    return jnp.where(
        has_l & has_t, both,
        jnp.where(has_l, left, jnp.where(has_t, top, 0)))


@jax.named_scope("enc.entropy.structure")
def _frame_structure(out):
    """Full-grid per-MB syntax structure — the CHEAP half of the coder.

    Everything here is elementwise work or an O(M) prefix scan over the
    MB grid: skip runs, mv prediction, cbp, TotalCoeff/nC context grids,
    header codewords, and the per-MB residual blocks re-laid into coding
    order. No VLC one-hot contraction or bit packing happens yet, so
    this pass costs the same for a busy and an idle frame — the
    expensive emission half (`_emit_slice_bits`) runs on the (optionally
    activity-compacted) structure it returns. Every per-MB array keys
    into `_COMPACT_KEYS` so `_compact_structure` can gather the coded
    MBs into a dense prefix with one row scatter each.
    """
    mvs = out["mvs"]
    skip = out["skip"]
    mbh, mbw = skip.shape
    M = mbh * mbw
    luma = out["luma_ac"].reshape(mbh, mbw, 4, 4, 16).astype(jnp.int32)
    chroma = out["chroma_ac"].reshape(mbh, mbw, 2, 2, 2, 16).astype(jnp.int32)
    cdc = out["chroma_dc"].reshape(mbh, mbw, 2, 4).astype(jnp.int32)

    zig = jnp.asarray(_ZIGZAG)
    luma_scan = luma[..., zig]                     # (mbh,mbw,4,4,16) scan order
    chroma_scan = chroma[..., zig]

    # ---- frame-wide structure ------------------------------------------
    coded = ~skip
    # cbp per MB
    # 8x8 group b8 = (y4>>1)*2 + (x4>>1): regroup (4,4) block grid into 2x2 of 2x2
    lg = luma_scan.reshape(mbh, mbw, 2, 2, 2, 2, 16).transpose(0, 1, 2, 4, 3, 5, 6)
    # lg[.., y8, x8, y4in, x4in, :]
    grp_nz = (lg != 0).any((-3, -2, -1))           # (mbh, mbw, 2, 2) -> b8 grid
    cbp_luma = (
        grp_nz[..., 0, 0].astype(jnp.int32)
        | (grp_nz[..., 0, 1].astype(jnp.int32) << 1)
        | (grp_nz[..., 1, 0].astype(jnp.int32) << 2)
        | (grp_nz[..., 1, 1].astype(jnp.int32) << 3)
    )
    chroma_ac_nz = (chroma_scan[..., 1:] != 0).any((-4, -3, -2, -1))
    chroma_dc_nz = (cdc != 0).any((-2, -1))
    cbp_chroma = jnp.where(chroma_ac_nz, 2, jnp.where(chroma_dc_nz, 1, 0))
    cbp = cbp_luma | (cbp_chroma << 4)

    # TotalCoeff context grids: block coded iff MB coded & its group in cbp
    luma_total = (luma_scan != 0).sum(-1).astype(jnp.int32)  # (mbh,mbw,4,4) [y4][x4]
    b8_of = (jnp.arange(4)[:, None] // 2) * 2 + (jnp.arange(4)[None, :] // 2)  # [y4][x4]
    luma_gate = (
        coded[..., None, None]
        & ((cbp_luma[..., None, None] >> b8_of[None, None]) & 1).astype(bool)
    )
    luma_tc_grid = jnp.where(luma_gate, luma_total, 0)  # (mbh,mbw,4,4)
    # flat (mbh*4, mbw*4) [by][bx]
    luma_tc_flat = luma_tc_grid.transpose(0, 2, 1, 3).reshape(mbh * 4, mbw * 4)
    ch_total = (chroma_scan[..., 1:] != 0).sum(-1).astype(jnp.int32)  # (mbh,mbw,2,2,2) [c][y][x]
    ch_gate = coded[..., None, None, None] & (cbp_chroma[..., None, None, None] == 2)
    ch_tc_grid = jnp.where(ch_gate, ch_total, 0)
    ch_tc_flat = ch_tc_grid.transpose(2, 0, 3, 1, 4).reshape(2, mbh * 2, mbw * 2)

    # ---- per-block inputs (coding order) -------------------------------
    # luma: MBs x 16 blocks in coding order. Block reorder as a STATIC
    # take over the 16-block axis: the equivalent multi-array fancy
    # gather lowers to a general gather that costs ~200 ms/frame on v5e
    # (measured on an earlier setup); nC likewise comes from the
    # elementwise grid (_nc_grid) statically re-laid into coding order.
    ox, oy = jnp.asarray(_LUMA_ORDER)[:, 0], jnp.asarray(_LUMA_ORDER)[:, 1]
    luma_perm = jnp.asarray(
        np.asarray(_LUMA_ORDER)[:, 1] * 4 + np.asarray(_LUMA_ORDER)[:, 0]
    )
    nc_luma = jnp.take(
        _nc_grid(luma_tc_flat).reshape(mbh, 4, mbw, 4).transpose(0, 2, 1, 3)
        .reshape(M, 16),
        luma_perm, axis=1,
    )  # (M, 16) in coding order
    luma_blocks = jnp.take(
        luma_scan.reshape(mbh, mbw, 16, 16), luma_perm, axis=2
    ).reshape(M, 16, 16)  # (M, 16, 16) in coding order
    # gate: block emitted iff MB coded & its b8 set
    b8_idx = (oy // 2) * 2 + (ox // 2)
    luma_emit = (
        coded[..., None] & ((cbp_luma[..., None] >> b8_idx[None, None]) & 1).astype(bool)
    ).reshape(M, 16)

    # chroma DC: MBs x 2 comps (4-coeff blocks, nc = -1)
    cdc_blocks = cdc.reshape(M, 2, 4)
    cdc_emit = jnp.broadcast_to(
        (coded & (cbp_chroma >= 1))[..., None], (mbh, mbw, 2)
    ).reshape(M, 2)

    # chroma AC: MBs x 2 comps x 4 blocks in coding order, 15 coeffs.
    # nC per component from its OWN grid (a component's row 0 must not
    # read the other component's bottom row).
    ch_perm = jnp.asarray(
        np.asarray(_CHROMA_ORDER)[:, 1] * 2 + np.asarray(_CHROMA_ORDER)[:, 0]
    )
    nc_ch = jnp.take(
        jnp.stack([_nc_grid(ch_tc_flat[c]) for c in range(2)])
        .reshape(2, mbh, 2, mbw, 2).transpose(1, 3, 0, 2, 4).reshape(M, 2, 4),
        ch_perm, axis=2,
    ).reshape(M, 8)
    ch_blocks = jnp.take(
        chroma_scan.reshape(mbh, mbw, 2, 4, 16), ch_perm, axis=3
    ).reshape(M, 8, 16)[..., 1:]  # (M, 8, 15) in coding order
    ch_emit = jnp.broadcast_to(
        (coded & (cbp_chroma == 2))[..., None, None], (mbh, mbw, 2, 4)
    ).reshape(M, 8)

    # ---- MB headers -----------------------------------------------------
    # skip_run before each coded MB: # of consecutive skips immediately
    # before it (raster order)
    skip_flat = skip.reshape(-1).astype(jnp.int32)
    csum_skip = jnp.cumsum(skip_flat)
    coded_flat = 1 - skip_flat
    # skip_run before coded MB i = skips since the previous coded MB:
    # csum_skip[i] - csum_skip[prev_coded(i)], with prev_coded found by a
    # running max over coded positions
    idxs = jnp.arange(M, dtype=jnp.int32)
    coded_pos = jnp.where(coded_flat.astype(bool), idxs, -1)
    prev_coded_pos = jax.lax.associative_scan(jnp.maximum, coded_pos)  # running max incl self
    prev_excl = jnp.concatenate([jnp.full(1, -1, jnp.int32), prev_coded_pos[:-1]])
    csum_at = jnp.concatenate([jnp.zeros(1, jnp.int32), csum_skip])  # csum_at[p+1]=csum incl p
    skip_run = csum_skip - jnp.where(prev_excl >= 0, csum_at[prev_excl + 1], 0)
    # (only meaningful at coded positions)

    pred = _mv_pred_grid(mvs, skip).reshape(-1, 2)
    mvd = 4 * (mvs.reshape(-1, 2) - pred)
    sr_v, sr_b = _ue_bits(skip_run)
    mt_v, mt_b = jnp.ones_like(skip_run), jnp.ones_like(skip_run)  # ue(0) = '1'
    mx_v, mx_b = _se_bits(mvd[:, 0])
    my_v, my_b = _se_bits(mvd[:, 1])
    cbp_flat = cbp.reshape(-1)
    cb_v, cb_b = _ue_bits(jnp.asarray(_CBP_CODENUM)[cbp_flat])
    qd_v = jnp.ones_like(skip_run)
    qd_b = jnp.where(cbp_flat > 0, 1, 0)  # se(0) = '1'
    hdr_vals = jnp.stack([sr_v, mt_v, mx_v, my_v, cb_v, qd_v], -1)
    hdr_bits = jnp.stack([sr_b, mt_b, mx_b, my_b, cb_b, qd_b], -1)
    emit_mb = coded_flat.astype(bool)
    hdr_bits = jnp.where(emit_mb[:, None], hdr_bits, 0)

    # trailing skip run (after the last coded MB)
    last_coded = prev_coded_pos[-1]
    trailing = jnp.where(last_coded >= 0, csum_skip[-1] - csum_at[last_coded + 1], csum_skip[-1])
    return {
        "hdr_vals": hdr_vals, "hdr_bits": hdr_bits,
        "luma_blocks": luma_blocks, "nc_luma": nc_luma, "luma_emit": luma_emit,
        "cdc_blocks": cdc_blocks, "cdc_emit": cdc_emit,
        "ch_blocks": ch_blocks, "nc_ch": nc_ch, "ch_emit": ch_emit,
        "coded": emit_mb, "trailing": trailing,
        "ns": coded_flat.sum().astype(jnp.int32),
        # emitted luma 4x4 / chroma AC blocks that hold a coefficient
        "coef_luma": (luma_tc_grid > 0).sum().astype(jnp.int32),
        "coef_chroma": (ch_tc_grid > 0).sum().astype(jnp.int32),
        # full-grid context grids, consumed by the CABAC emitter
        # (device_cabac.py) for its neighbour ctx derivation — dead (and
        # DCE'd by the jit) on the CAVLC path
        "cbp_luma": cbp_luma, "cbp_chroma": cbp_chroma,
        "luma_tc_flat": luma_tc_flat, "ch_tc_flat": ch_tc_flat,
    }


# per-MB arrays the activity compaction gathers into a dense prefix
_COMPACT_KEYS = (
    "hdr_vals", "hdr_bits", "luma_blocks", "nc_luma", "luma_emit",
    "cdc_blocks", "cdc_emit", "ch_blocks", "nc_ch", "ch_emit",
)


@jax.named_scope("enc.entropy.compact")
def _compact_structure(s, A: int, keys=_COMPACT_KEYS):
    """Gather the coded MBs of a frame structure into a dense prefix of
    `A` padded slots (raster order preserved; slots past the coded count
    stay all-zero, so their segments emit zero bits and vanish in the
    merge). One row scatter per array — M near-unique updates each, the
    same cheap shape as encoder_core's sparse pair compaction. Coded MBs
    past slot A are DROPPED: the caller must only select this path when
    ns <= A (pack_p_slice_bits_active's bucket switch guarantees it).
    ``keys`` selects the per-MB arrays to gather (device_cabac passes
    its own set, which includes the CABAC context columns)."""
    coded = s["coded"]
    pos = jnp.cumsum(coded.astype(jnp.int32)) - 1
    dest = jnp.where(coded & (pos < A), pos, A)  # sentinel row, dropped

    def cp(a):
        buf = jnp.zeros((A + 1,) + a.shape[1:], a.dtype)
        return buf.at[dest].set(a)[:A]

    return {k: cp(s[k]) for k in keys}


def _level_bits_bound(a, small):
    """Upper bound on the CAVLC bits of a level of magnitude `a` (0 for
    a == 0), whatever the suffixLength (9.2.2.1): 2a for a <= 3 in a
    block whose levels are all <= 3 (suffixLength stays <= 1 there),
    else 7 / 14 / 19 bits up to 3 / 7 / 15, and 64 beyond (escape or
    extended prefix)."""
    big = jnp.where(a <= 3, 7, jnp.where(a <= 7, 14, jnp.where(a <= 15, 19, 64)))
    return jnp.where(a == 0, 0, jnp.where(small, 2 * a, big))


def _blocks_bits_bound(blocks, emit, chroma_dc: bool = False):
    """Upper bound on each (..., L) residual block's CAVLC bits, from its
    coefficients alone (no VLC): coeff_token <= 16 (8 for chroma DC),
    each level by _level_bits_bound, total_zeros <= 9 (3), and the
    run_befores <= 3 bits each plus the zeros they skip; an emitted
    block with no coefficient writes a coeff_token of <= 6 bits."""
    a = jnp.abs(blocks)
    t = (a > 0).sum(-1)
    lv = _level_bits_bound(a, (a.max(-1) <= 3)[..., None]).sum(-1)
    if chroma_dc:
        b = 8 + lv + 3 + 6
    else:
        b = jnp.where(t > 0, 16 + lv + 9 + 3 * jnp.maximum(t - 1, 0) + 16, 6)
    return jnp.where(emit, b, 0)


@jax.named_scope("enc.entropy.structure")
def _mb_bits_bound(s):
    """The largest per-MB bound on its slice-data bits in a structure:
    the MB header exactly, its blocks by _blocks_bits_bound."""
    return (s["hdr_bits"].sum(-1)
            + _blocks_bits_bound(s["luma_blocks"], s["luma_emit"]).sum(-1)
            + _blocks_bits_bound(s["cdc_blocks"], s["cdc_emit"], True).sum(-1)
            + _blocks_bits_bound(s["ch_blocks"], s["ch_emit"]).sum(-1)).max()


def _concat_rows(words, nbits, nwords: int):
    """Concatenate each row's K bit buffers: (U, K, W) words + (U, K)
    lengths -> ((U, nwords) words, (U,) lengths). Dense shifts only
    (each segment to its bit phase, then to its word offset in log2
    steps), no gather or scatter. Bits past nwords * 32 are LOST: the
    caller bounds every row's length."""
    U, K, W = words.shape
    starts = jnp.cumsum(nbits, 1) - nbits
    sh = (starts & 31)[..., None].astype(jnp.uint32)
    hi = jnp.where(sh > 0, words >> sh, words)
    lo = jnp.where(sh > 0, words << jnp.clip(32 - sh, 1, 31), jnp.uint32(0))
    zero = jnp.zeros((U, K, 1), jnp.uint32)
    x = (jnp.concatenate([hi, zero], -1) + jnp.concatenate([zero, lo], -1))
    x = jnp.pad(x, ((0, 0), (0, 0), (0, max(0, nwords - W - 1))))[..., :nwords]
    q = starts >> 5
    step = 1
    while step < nwords:
        moved = jnp.pad(x, ((0, 0), (0, 0), (step, 0)))[..., :nwords]
        x = jnp.where(((q & step) > 0)[..., None], moved, x)
        step *= 2
    return x.sum(1, dtype=jnp.uint32), nbits.sum(1)


@jax.named_scope("enc.entropy.emit")
def _emit_slice_bits(s, word_cap: int, mb_fits=None):
    """The EXPENSIVE half: VLC-encode every block of a (possibly
    compacted) per-MB structure, pack each segment's codewords into bit
    buffers, and merge them into one stream. Cost scales with the
    structure's leading axis (U MBs), which is what makes the bucket
    compaction activity-proportional. Returns (words, nbits).

    Given ``mb_fits`` (traced: every MB's bound, _mb_bits_bound, fits
    MB_WORDS), each MB's 27 segments are first concatenated densely into
    MB_WORDS words where it holds, so the merge joins U MBs instead of
    U * 27 segments; the VLC and packing above are shared."""
    U = s["hdr_bits"].shape[0]
    lv, lb, _ = _encode_blocks(
        s["luma_blocks"].reshape(U * 16, 16), s["nc_luma"].reshape(-1),
        chroma_dc=False)
    lb = jnp.where(s["luma_emit"].reshape(-1)[:, None], lb, 0)
    dv, db, _ = _encode_blocks(
        s["cdc_blocks"].reshape(U * 2, 4),
        jnp.full((U * 2,), -1, jnp.int32), chroma_dc=True)
    db = jnp.where(s["cdc_emit"].reshape(-1)[:, None], db, 0)
    cv, cb, _ = _encode_blocks(
        s["ch_blocks"].reshape(U * 8, 15), s["nc_ch"].reshape(-1),
        chroma_dc=False)
    cb = jnp.where(s["ch_emit"].reshape(-1)[:, None], cb, 0)

    # ---- assemble: MB unit = header + 16 luma + 2 cdc + 8 cac ----------
    HW = 4      # header words (6 codewords <= 78 bits)
    BW = 32     # per-block words (hard bound: 16+3+16*52+9+14*11 = 1014 bits)
    hdr_w, hdr_n = _pack_pairs(s["hdr_vals"], s["hdr_bits"], HW)
    luma_w, luma_n = _pack_pairs(lv, lb, BW)
    cdc_w, cdc_n = _pack_pairs(dv, db, BW)
    cac_w, cac_n = _pack_pairs(cv, cb, BW)

    # stitch each MB's 27 segments in syntax order:
    # header, luma blocks 0..15, cdc 0..1, cac 0..7
    seg_words = jnp.concatenate(
        [
            jnp.pad(hdr_w.reshape(U, 1, HW), ((0, 0), (0, 0), (0, BW - HW))),
            luma_w.reshape(U, 16, BW),
            cdc_w.reshape(U, 2, BW),
            cac_w.reshape(U, 8, BW),
        ],
        axis=1,
    ).reshape(U * 27, BW)
    seg_bits = jnp.concatenate(
        [hdr_n.reshape(U, 1), luma_n.reshape(U, 16), cdc_n.reshape(U, 2),
         cac_n.reshape(U, 8)],
        axis=1,
    ).reshape(U * 27)
    if mb_fits is None:
        return _merge_streams(seg_words, seg_bits, word_cap)
    return jax.lax.cond(
        mb_fits,
        lambda: _merge_streams(*_concat_rows(
            seg_words.reshape(U, 27, BW), seg_bits.reshape(U, 27), MB_WORDS),
            word_cap),
        lambda: _merge_streams(seg_words, seg_bits, word_cap))


def pack_p_slice_bits(out, word_cap: int = WORD_CAP_DEFAULT):
    """P-frame encode outputs -> slice-data bitstream on device,
    FULL-GRID (every MB pays the emission cost regardless of activity).

    Returns (words (word_cap,) uint32 big-endian bit order, nbits int32,
    trailing_skip int32). The stream covers everything between the slice
    header and the final skip_run — the host splices it after its own
    header bits and finishes the NAL. Production paths use
    pack_p_slice_bits_active; this fixed-shape form remains the oracle
    for tests. A device trace splits the step's cost by the named
    scopes of the two halves (enc.entropy.structure / .compact / .emit,
    monitoring/tracing.py).
    """
    s = _frame_structure(out)
    words, nbits = _emit_slice_bits(s, word_cap)
    return words, nbits, s["trailing"]


def bits_buckets(m: int, ladder=(256, 1024, 4096)) -> tuple[int, ...]:
    """Activity buckets for a slice of `m` MBs: the power-of-two-ish
    ladder clipped to the grid, always ending at m so every frame has a
    bucket. Tiny slices (tests, bands of small frames) collapse to a
    single full-grid bucket — no switch, no extra compile."""
    m = int(m)
    return tuple(sorted({min(int(b), m) for b in ladder} | {m}))


# The top bucket merges whole MBs of up to this many words (2048 bits)
# when every MB's bound (_mb_bits_bound) fits.
MB_WORDS = 64


def pack_p_slice_bits_active(out, word_cap: int = WORD_CAP_DEFAULT,
                             buckets: tuple[int, ...] | None = None):
    """Activity-proportional device CAVLC: like pack_p_slice_bits, but
    the emission half runs over a compacted padded count of coded MBs.

    The bucket (smallest entry >= the frame's coded-MB count ns) is
    selected ON DEVICE with lax.switch — all buckets compile into the
    one executable, each frame executes only its own, so a typing frame
    pays the 256-slot coder while a scene cut pays the full grid. The
    top bucket of a multi-bucket ladder merges whole MBs when every MB's
    bit bound fits MB_WORDS (known from the structure before any bit is
    written), else its segments.
    Returns (words, nbits, trailing_skip, ns, counts); ns lets the
    caller make its ship-bits-or-coefficients decision in the same jit,
    and counts = [coef_luma, coef_chroma, rung] says how many emitted
    luma / chroma AC blocks hold a coefficient and which rung ran: the
    bucket's index, or len(buckets) for the top bucket merging segments.
    Output is bit-identical to the full-grid coder for every ns
    (compaction preserves raster order; padded slots emit zero bits)."""
    s = _frame_structure(out)
    M = s["coded"].shape[0]
    if buckets is None:
        buckets = bits_buckets(M)
    ns = s["ns"]
    if len(buckets) == 1:
        A = buckets[0]
        words, nbits = _emit_slice_bits(
            s if A >= M else _compact_structure(s, A), word_cap)
        rung = jnp.int32(0)
    else:
        top = len(buckets) - 1
        mb_fits = _mb_bits_bound(s) <= 32 * MB_WORDS

        def _branch(i: int):
            fits = mb_fits if i == top else None
            if buckets[i] >= M:
                return lambda _: _emit_slice_bits(s, word_cap, fits)
            return lambda _: _emit_slice_bits(
                _compact_structure(s, buckets[i]), word_cap, fits)

        # the bucket switch counts as emission; each branch's compaction
        # carries its own scope
        with jax.named_scope("enc.entropy.emit"):
            idx = jnp.clip(
                jnp.searchsorted(jnp.asarray(buckets, jnp.int32), ns, side="left"),
                0, top)
            words, nbits = jax.lax.switch(
                idx, [_branch(i) for i in range(len(buckets))], jnp.int32(0))
        rung = jnp.where((idx == top) & ~mb_fits, top + 1, idx).astype(jnp.int32)
    counts = jnp.stack([s["coef_luma"], s["coef_chroma"], rung])
    return words, nbits, s["trailing"], ns, counts


# ---------------------------------------------------------------------------
# Host half: splice header + device bits + trailing, NAL-wrap
# ---------------------------------------------------------------------------


def _or_bits(out: np.ndarray, src: np.ndarray, bit_off: int, nbits: int) -> None:
    """OR `nbits` MSB-first bits of src into out at bit offset bit_off."""
    if nbits <= 0:
        return
    nbytes = (nbits + 7) // 8
    src = src[:nbytes]
    sh = bit_off & 7
    b0 = bit_off >> 3
    # src may be zero-padded past nbits (whole device words): clamp every
    # write to the output (the spilled-over bytes are zeros anyway)
    n1 = min(len(src), len(out) - b0)
    if sh == 0:
        out[b0 : b0 + n1] |= src[:n1]
        return
    out[b0 : b0 + n1] |= (src >> sh)[:n1]
    spill = ((src.astype(np.uint16) << (8 - sh)) & 0xFF).astype(np.uint8)
    n2 = min(len(spill), len(out) - b0 - 1)
    out[b0 + 1 : b0 + 1 + n2] |= spill[:n2]


def assemble_p_nal(words: np.ndarray, nbits: int, trailing_skip: int,
                   p, frame_num: int, qp: int,
                   ltr_ref: int | None = None,
                   mark_ltr: int | None = None,
                   mmco_evict: tuple = (),
                   first_mb: int = 0) -> bytes:
    """Finish a P slice from device bits: header + stream + trailing
    skip_run + rbsp stop, emulation-prevented and Annex-B wrapped.
    Byte-identical to cavlc.pack_slice_p for the same inputs. first_mb
    positions a band slice of a multi-slice picture (parallel/bands.py)
    — it lives entirely in the host-written header, so the device words
    are the same with or without it."""
    from selkies_tpu.models.h264.bitstream import SLICE_P, NAL_SLICE_NON_IDR, write_slice_header
    from selkies_tpu.utils.bits import BitWriter, annexb_nal

    w = BitWriter()
    write_slice_header(w, p, SLICE_P, frame_num, idr=False, slice_qp=qp,
                       ltr_ref=ltr_ref, mark_ltr=mark_ltr,
                       mmco_evict=mmco_evict, first_mb=first_mb)
    hdr_bytes, hdr_bits = w.get_partial()

    dev_bytes = np.ascontiguousarray(words[: (nbits + 31) // 32]).astype(">u4").view(np.uint8)

    tail = BitWriter()
    if trailing_skip:
        tail.write_ue(int(trailing_skip))
    tail.write_bit(1)  # rbsp_stop_one_bit; byte-align zeros come from sizing
    tail_bytes, tail_bits = tail.get_partial()

    total_bits = hdr_bits + int(nbits) + tail_bits
    out = np.zeros((total_bits + 7) // 8, np.uint8)
    _or_bits(out, np.frombuffer(hdr_bytes, np.uint8), 0, hdr_bits)
    _or_bits(out, dev_bytes, hdr_bits, int(nbits))
    _or_bits(out, np.frombuffer(tail_bytes, np.uint8), hdr_bits + int(nbits), tail_bits)
    return annexb_nal(3, NAL_SLICE_NON_IDR, out.tobytes())
