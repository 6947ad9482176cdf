// frameprep: host-side capture-frame preparation for the TPU encoder.
//
// Two jobs, both on the host CPU because they shrink the host->device
// link traffic:
//   1. bgrx_to_i420_pad: packed BGRx -> padded planar I420, bit-exact
//      with the device path (selkies_tpu/ops/colorspace.py):
//        Y = clip((( 66R + 129G +  25B + 128) >> 8) + 16,  16, 235)
//        U = clip(((-38R -  74G + 112B + 128) >> 8) + 128, 16, 240)
//        V = clip(((112R -  94G -  18B + 128) >> 8) + 128, 16, 240)
//      chroma = 2x2 mean of the clipped full-res plane, (sum + 2) >> 2,
//      then edge-replicated padding to macroblock multiples.
//      Uploading I420 instead of BGRx is 2.7x less data (1.5 vs 4 B/px).
//   2. band_diff: per-16-row-band memcmp of the current vs previous BGRx
//      frame — the dirty-region map that lets the encoder upload only
//      changed bands (typing/cursor workloads touch a few bands; the
//      reference gets the analogous effect from ximagesrc's XDamage).
//
// Reference context: the conversion replaces cudaconvert/vapostproc
// (gstwebrtc_app.py:263-284, 477-487); plain C++ loops, auto-vectorized.

#include <cstdint>
#include <cstring>

namespace {

inline uint8_t clip_u8(int v, int lo, int hi) {
    return static_cast<uint8_t>(v < lo ? lo : (v > hi ? hi : v));
}

// Convert one 2x2 BGRx quad (rows row0/row1, luma cols 2*c2, 2*c2+1) to
// two Y pairs + one averaged U/V sample — the single definition of the
// BT.601 matrix and chroma averaging every converter shares (the tile
// path advertises bit-exactness against the full-plane path; one body
// makes that structural).
inline void quad_to_i420(const uint8_t* row0, const uint8_t* row1, int c2,
                         uint8_t* y0, uint8_t* y1, int yo,
                         uint8_t* ur, uint8_t* vr, int co) {
    int usum = 0, vsum = 0;
    const uint8_t* p[2] = {row0 + 8 * c2, row1 + 8 * c2};
    for (int dy = 0; dy < 2; ++dy) {
        for (int dx = 0; dx < 2; ++dx) {
            const uint8_t* px = p[dy] + 4 * dx;
            const int b = px[0], g = px[1], r = px[2];
            const int yy = ((66 * r + 129 * g + 25 * b + 128) >> 8) + 16;
            const int uu = ((-38 * r - 74 * g + 112 * b + 128) >> 8) + 128;
            const int vv = ((112 * r - 94 * g - 18 * b + 128) >> 8) + 128;
            (dy ? y1 : y0)[yo + dx] = clip_u8(yy, 16, 235);
            usum += uu < 16 ? 16 : (uu > 240 ? 240 : uu);
            vsum += vv < 16 ? 16 : (vv > 240 ? 240 : vv);
        }
    }
    ur[co] = static_cast<uint8_t>((usum + 2) >> 2);
    vr[co] = static_cast<uint8_t>((vsum + 2) >> 2);
}

}  // namespace

extern "C" {

// src: (h, w, 4) BGRx rows contiguous. y: (ph, pw); u, v: (ph/2, pw/2).
// h, w must be even; ph >= h, pw >= w, both multiples of 16.
void bgrx_to_i420_pad(const uint8_t* src, int h, int w, int ph, int pw,
                      uint8_t* y, uint8_t* u, uint8_t* v) {
    const int cw = w / 2, ch = h / 2;
    const int cpw = pw / 2, cph = ph / 2;
    // process two source rows at a time: emit two Y rows + one U/V row
    for (int r2 = 0; r2 < ch; ++r2) {
        const uint8_t* row0 = src + static_cast<size_t>(2 * r2) * w * 4;
        const uint8_t* row1 = row0 + static_cast<size_t>(w) * 4;
        uint8_t* y0 = y + static_cast<size_t>(2 * r2) * pw;
        uint8_t* y1 = y0 + pw;
        uint8_t* ur = u + static_cast<size_t>(r2) * cpw;
        uint8_t* vr = v + static_cast<size_t>(r2) * cpw;
        for (int c2 = 0; c2 < cw; ++c2)
            quad_to_i420(row0, row1, c2, y0, y1, 2 * c2, ur, vr, c2);
        // edge-replicate horizontal padding
        for (int c = w; c < pw; ++c) {
            y0[c] = y0[w - 1];
            y1[c] = y1[w - 1];
        }
        for (int c = cw; c < cpw; ++c) {
            ur[c] = ur[cw - 1];
            vr[c] = vr[cw - 1];
        }
    }
    // edge-replicate vertical padding
    for (int r = h; r < ph; ++r)
        std::memcpy(y + static_cast<size_t>(r) * pw, y + static_cast<size_t>(h - 1) * pw, pw);
    for (int r = ch; r < cph; ++r) {
        std::memcpy(u + static_cast<size_t>(r) * cpw, u + static_cast<size_t>(ch - 1) * cpw, cpw);
        std::memcpy(v + static_cast<size_t>(r) * cpw, v + static_cast<size_t>(ch - 1) * cpw, cpw);
    }
}

// Compare cur vs prev (both (h, w, 4) BGRx) in bands of `band` rows.
// out[i] = 1 if band i differs. Returns the number of changed bands.
int band_diff(const uint8_t* cur, const uint8_t* prev, int h, int w, int band,
              uint8_t* out) {
    const size_t row_bytes = static_cast<size_t>(w) * 4;
    const int nbands = (h + band - 1) / band;
    int changed = 0;
    for (int i = 0; i < nbands; ++i) {
        const int r0 = i * band;
        const int rows = (r0 + band <= h) ? band : (h - r0);
        const size_t off = static_cast<size_t>(r0) * row_bytes;
        const int diff =
            std::memcmp(cur + off, prev + off, static_cast<size_t>(rows) * row_bytes) != 0;
        out[i] = static_cast<uint8_t>(diff);
        changed += diff;
    }
    return changed;
}

// Refine a dirty-band map to dirty TILES of tile_px columns: for band i
// with band_dirty[i], out[i*ntiles + t] = 1 iff any BGRx byte in the
// 16-row x tile_px-col region changed. Tiles shrink the delta upload by
// the width fraction that actually changed (a cursor blink is one tile,
// not a full-width band). Returns the changed-tile count.
int tile_diff(const uint8_t* cur, const uint8_t* prev, int h, int w,
              int band, int tile_px, const uint8_t* band_dirty, uint8_t* out) {
    const size_t row_bytes = static_cast<size_t>(w) * 4;
    const int nbands = (h + band - 1) / band;
    const int ntiles = (w + tile_px - 1) / tile_px;
    int changed = 0;
    for (int i = 0; i < nbands; ++i) {
        uint8_t* orow = out + static_cast<size_t>(i) * ntiles;
        if (!band_dirty[i]) {
            std::memset(orow, 0, ntiles);
            continue;
        }
        const int r0 = i * band;
        const int rows = (r0 + band <= h) ? band : (h - r0);
        for (int t = 0; t < ntiles; ++t) {
            const int c0 = t * tile_px;
            const size_t seg = static_cast<size_t>(
                ((c0 + tile_px <= w) ? tile_px : (w - c0))) * 4;
            int diff = 0;
            for (int r = r0; r < r0 + rows && !diff; ++r) {
                const size_t off = static_cast<size_t>(r) * row_bytes + static_cast<size_t>(c0) * 4;
                diff = std::memcmp(cur + off, prev + off, seg) != 0;
            }
            orow[t] = static_cast<uint8_t>(diff);
            changed += diff;
        }
    }
    return changed;
}

// Convert k 16-row x tw-col tiles of src to packed I420 tile buffers:
// yb (k, 16, tw), ub/vb (k, 8, tw/2). idx[i] = band*1024 + tile selects
// luma rows 16*band.. and cols tw*tile.. of the PADDED plane; tw must
// divide pw and be a multiple of 16. Bit-exact with the same region of
// bgrx_to_i420_pad, including replicated right/bottom padding.
void bgrx_to_i420_tiles(const uint8_t* src, int h, int w, int pw, int tw,
                        const int32_t* idx, int k,
                        uint8_t* yb, uint8_t* ub, uint8_t* vb) {
    const int ch = h / 2;
    const int ctw = tw / 2;
    for (int b = 0; b < k; ++b) {
        const int band = idx[b] / 1024;
        const int tile = idx[b] % 1024;
        const int g0 = band * 16;      // first luma row
        const int c0 = tile * tw;      // first luma col
        uint8_t* ybb = yb + static_cast<size_t>(b) * 16 * tw;
        uint8_t* ubb = ub + static_cast<size_t>(b) * 8 * ctw;
        uint8_t* vbb = vb + static_cast<size_t>(b) * 8 * ctw;
        const int content_cols2 = (c0 + tw <= w ? tw : (w > c0 ? w - c0 : 0)) / 2;
        for (int p = 0; p < 8; ++p) {  // row pair: luma g0+2p, g0+2p+1
            const int r = g0 + 2 * p;
            uint8_t* y0 = ybb + static_cast<size_t>(2 * p) * tw;
            uint8_t* y1 = y0 + tw;
            uint8_t* ur = ubb + static_cast<size_t>(p) * ctw;
            uint8_t* vr = vbb + static_cast<size_t>(p) * ctw;
            if (r < h) {
                const uint8_t* row0 = src + static_cast<size_t>(r) * w * 4;
                const uint8_t* row1 = row0 + static_cast<size_t>(w) * 4;
                for (int c2 = 0; c2 < content_cols2; ++c2)
                    quad_to_i420(row0, row1, (c0 / 2) + c2, y0, y1, 2 * c2, ur, vr, c2);
                // horizontal padding: replicate col w-1 (always inside
                // this tile when padding cols exist here: pw - w < 16 <= tw)
                for (int c = 2 * content_cols2; c < tw; ++c) {
                    y0[c] = y0[2 * content_cols2 - 1];
                    y1[c] = y1[2 * content_cols2 - 1];
                }
                for (int c = content_cols2; c < ctw; ++c) {
                    ur[c] = ur[content_cols2 - 1];
                    vr[c] = vr[content_cols2 - 1];
                }
            } else {
                // bottom padding pair: replicate the last content rows,
                // which live earlier in THIS tile (pad - h < 16)
                const uint8_t* ylast = ybb + static_cast<size_t>(h - 1 - g0) * tw;
                std::memcpy(y0, ylast, tw);
                std::memcpy(y1, ylast, tw);
                const uint8_t* ulast = ubb + static_cast<size_t>(ch - 1 - g0 / 2) * ctw;
                const uint8_t* vlast = vbb + static_cast<size_t>(ch - 1 - g0 / 2) * ctw;
                std::memcpy(ur, ulast, ctw);
                std::memcpy(vr, vlast, ctw);
            }
        }
    }
}

// Row-range variant of bgrx_to_i420_pad for the band-parallel front-end
// pool: converts source rows [r0, r1) (both even) into the SAME padded
// planes, including the horizontal padding of those rows but NOT the
// vertical bottom padding (the caller runs pad_i420_bottom once after
// every band worker finished). Workers write disjoint row ranges, so
// concurrent calls over a partition of [0, h) are safe and the result
// is byte-identical to one bgrx_to_i420_pad call.
void bgrx_to_i420_pad_rows(const uint8_t* src, int h, int w, int ph, int pw,
                           int r0, int r1, uint8_t* y, uint8_t* u, uint8_t* v) {
    (void)h; (void)ph;
    const int cw = w / 2;
    const int cpw = pw / 2;
    for (int r2 = r0 / 2; r2 < r1 / 2; ++r2) {
        const uint8_t* row0 = src + static_cast<size_t>(2 * r2) * w * 4;
        const uint8_t* row1 = row0 + static_cast<size_t>(w) * 4;
        uint8_t* y0 = y + static_cast<size_t>(2 * r2) * pw;
        uint8_t* y1 = y0 + pw;
        uint8_t* ur = u + static_cast<size_t>(r2) * cpw;
        uint8_t* vr = v + static_cast<size_t>(r2) * cpw;
        for (int c2 = 0; c2 < cw; ++c2)
            quad_to_i420(row0, row1, c2, y0, y1, 2 * c2, ur, vr, c2);
        for (int c = w; c < pw; ++c) {
            y0[c] = y0[w - 1];
            y1[c] = y1[w - 1];
        }
        for (int c = cw; c < cpw; ++c) {
            ur[c] = ur[cw - 1];
            vr[c] = vr[cw - 1];
        }
    }
}

// The bottom-padding tail bgrx_to_i420_pad_rows leaves out: replicate
// source row h-1 (and chroma row h/2-1) down to the padded heights.
void pad_i420_bottom(int h, int ph, int pw, uint8_t* y, uint8_t* u, uint8_t* v) {
    const int ch = h / 2, cph = ph / 2, cpw = pw / 2;
    for (int r = h; r < ph; ++r)
        std::memcpy(y + static_cast<size_t>(r) * pw, y + static_cast<size_t>(h - 1) * pw, pw);
    for (int r = ch; r < cph; ++r) {
        std::memcpy(u + static_cast<size_t>(r) * cpw, u + static_cast<size_t>(ch - 1) * cpw, cpw);
        std::memcpy(v + static_cast<size_t>(r) * cpw, v + static_cast<size_t>(ch - 1) * cpw, cpw);
    }
}

}  // extern "C"

namespace {

// splitmix64 mix — must match tilecache.py _splitmix64 exactly (the
// numpy fallback and this path feed the same host-side hash index).
inline uint64_t splitmix64(uint64_t x) {
    x += 0x9E3779B97F4A7C15ULL;
    x ^= x >> 30;
    x *= 0xBF58476D1CE4E5B9ULL;
    x ^= x >> 27;
    x *= 0x94D049BB133111EBULL;
    return x ^ (x >> 31);
}

}  // namespace

extern "C" {

// Content hash of k contiguous tile byte rows (nbytes each, a multiple
// of 8) for the uplink tile cache: XOR-fold of each 8-byte lane times a
// per-position splitmix64-derived odd multiplier, then a splitmix64
// avalanche. Identical values to tilecache.tile_hash_np (tests compare
// the two); the hash only nominates a pool slot — the cache verifies
// with a full memcmp before emitting a remap.
void tile_hash(const uint8_t* data, int k, int nbytes, uint64_t* out) {
    const int nwords = nbytes / 8;
    for (int i = 0; i < k; ++i) {
        const uint8_t* p = data + static_cast<size_t>(i) * nbytes;
        uint64_t h = 0;
        for (int w = 0; w < nwords; ++w) {
            uint64_t word;
            std::memcpy(&word, p + 8 * w, 8);
            h ^= word * (splitmix64(static_cast<uint64_t>(w)) | 1ULL);
        }
        out[i] = splitmix64(h);
    }
}

// Gather k 16-row x tile_px-col BGRx tile regions of src ((h, w, 4)
// row-major) into out (k, 16*tile_px*4), flattened row-major per tile —
// the byte layout tile_hash / TileCache verification use. idx[i] =
// band*1024 + tile; every tile must lie fully inside the frame (the
// cacheable rule). memcpy per tile row: ~10x the throughput of numpy's
// element-wise fancy-index gather on these shapes.
void gather_tiles(const uint8_t* src, int h, int w, int tile_px,
                  const int32_t* idx, int k, uint8_t* out) {
    (void)h;
    const size_t row_bytes = static_cast<size_t>(w) * 4;
    const size_t seg = static_cast<size_t>(tile_px) * 4;
    for (int i = 0; i < k; ++i) {
        const int band = idx[i] / 1024;
        const int tile = idx[i] % 1024;
        const uint8_t* p = src + static_cast<size_t>(band) * 16 * row_bytes
                           + static_cast<size_t>(tile) * seg;
        uint8_t* o = out + static_cast<size_t>(i) * 16 * seg;
        for (int r = 0; r < 16; ++r)
            std::memcpy(o + r * seg, p + static_cast<size_t>(r) * row_bytes, seg);
    }
}

// Fused uplink front-end scan — ONE pass over the frame bytes instead of
// three (band_diff + tile_diff reading cur+prev, np.copyto re-writing
// prev, tile_hash re-reading the dirty tiles):
//   * per-tile dirty detection: memcmp of the 16-row x tile_px-col BGRx
//     region against prev, band-gated exactly like band_diff+tile_diff;
//   * prev update: a DIRTY tile's bytes are copied cur->prev in the same
//     pass (clean tiles are already byte-equal, so skipping them leaves
//     prev byte-identical to a full copy);
//   * content hash: when `hashes` is non-null, each dirty FULL tile
//     (band*bnd+bnd <= h and (t+1)*tile_px <= w — the tile-cache's
//     cacheable rule) gets the tile_hash value of its flattened BGRx
//     bytes written to hashes[i*ntiles + t] (others left untouched).
// Scans only bands [b0, b1) and tile columns [t0, t1) — the caller's
// damage-hint bounding box; regions outside must be known-unchanged
// (XDamage supersets) and their out[] entries are NOT written.
// Returns the changed-tile count. Byte-identical outputs to the serial
// three-pass flow on the scanned region (tests/test_frontend_parallel.py).
int frontend_scan(const uint8_t* cur, uint8_t* prev, int h, int w, int bnd,
                  int tile_px, int b0, int b1, int t0, int t1,
                  uint8_t* out, uint64_t* hashes) {
    const size_t row_bytes = static_cast<size_t>(w) * 4;
    const int ntiles = (w + tile_px - 1) / tile_px;
    const int words_per_row = tile_px / 2;  // tile_px*4 bytes / 8
    int changed = 0;
    for (int i = b0; i < b1; ++i) {
        uint8_t* orow = out + static_cast<size_t>(i) * ntiles;
        const int r0 = i * bnd;
        const int rows = (r0 + bnd <= h) ? bnd : (h - r0);
        for (int t = t0; t < t1 && t < ntiles; ++t) {
            const int c0 = t * tile_px;
            const size_t seg = static_cast<size_t>(
                ((c0 + tile_px <= w) ? tile_px : (w - c0))) * 4;
            int diff = 0;
            for (int r = r0; r < r0 + rows && !diff; ++r) {
                const size_t off = static_cast<size_t>(r) * row_bytes + static_cast<size_t>(c0) * 4;
                diff = std::memcmp(cur + off, prev + off, seg) != 0;
            }
            orow[t] = static_cast<uint8_t>(diff);
            if (!diff)
                continue;
            changed += 1;
            const int full = (r0 + bnd <= h) && (c0 + tile_px <= w);
            uint64_t hsh = 0;
            for (int r = r0; r < r0 + rows; ++r) {
                const size_t off = static_cast<size_t>(r) * row_bytes + static_cast<size_t>(c0) * 4;
                if (hashes != nullptr && full) {
                    const uint8_t* p = cur + off;
                    const uint64_t wbase = static_cast<uint64_t>(r - r0) * words_per_row;
                    for (int j = 0; j < words_per_row; ++j) {
                        uint64_t word;
                        std::memcpy(&word, p + 8 * j, 8);
                        hsh ^= word * (splitmix64(wbase + j) | 1ULL);
                    }
                }
                std::memcpy(prev + off, cur + off, seg);
            }
            if (hashes != nullptr && full)
                hashes[static_cast<size_t>(i) * ntiles + t] = splitmix64(hsh);
        }
    }
    return changed;
}

}  // extern "C"
