"""Uplink tile cache (CopyRect remaps) + packed sparse downlink.

The contract under test is bit-exactness: with the cache and the packed
coefficient downlink enabled, the emitted Annex-B stream must be
byte-identical to the uncached/unpacked encoder on every workload —
remaps and packing change WHAT crosses the link, never what the decoder
sees. Byte-reduction assertions ride along on the traces the
optimizations were built for (scroll, window move)."""

import numpy as np
import pytest

from selkies_tpu.models import frameprep
from selkies_tpu.models.h264.encoder import TPUH264Encoder
from selkies_tpu.models.tilecache import TileCache, tile_hash_np
from selkies_tpu.pipeline.elements import scroll_trace, window_move_trace

W, H = 320, 192  # 12 bands, tile_w 64 -> 5 tiles/band, buckets (8, 16, 32)


def _stream(enc, frames):
    return b"".join(enc.encode_frame(f) for f in frames)


def _pair(frames, **kw):
    """(cached+packed stream, plain stream, cached encoder) — both
    encoders see identical inputs; ltr off unless a test opts in (full
    frames then carry MMCO bits whose equivalence is test_h264_ltr's
    business, not this file's)."""
    kw.setdefault("ltr_scenes", False)
    w, h = frames[0].shape[1], frames[0].shape[0]
    enc_c = TPUH264Encoder(w, h, qp=26, tile_cache=kw.pop("slots", 512),
                           packed_downlink=True, **kw)
    enc_p = TPUH264Encoder(w, h, qp=26, tile_cache=0, packed_downlink=False, **kw)
    return _stream(enc_c, frames), _stream(enc_p, frames), enc_c


def test_hash_native_numpy_parity_and_sensitivity():
    rng = np.random.default_rng(3)
    tiles = rng.integers(0, 256, (5, 16 * 64 * 4), np.uint8)
    native = frameprep._load() is not None
    h1 = tile_hash_np(tiles)
    saved = frameprep._lib
    try:
        frameprep._lib = None  # force the numpy fold
        h2 = tile_hash_np(tiles)
    finally:
        frameprep._lib = saved
    if native:
        assert np.array_equal(h1, h2), "native and numpy hashes diverge"
    flip = tiles.copy()
    flip[0, 1000] ^= 1
    assert tile_hash_np(flip)[0] != h1[0]
    # permuting two 8-byte lanes must change the hash (position-dependent
    # multipliers; a plain XOR fold would collide)
    perm = tiles.copy()
    perm[0, :8], perm[0, 8:16] = tiles[0, 8:16].copy(), tiles[0, :8].copy()
    assert tile_hash_np(perm)[0] != h1[0]


def test_split_verifies_and_excludes_edges():
    """Copy pairs only for verified interior content; edge tiles and
    same-call duplicates always upload; hash collisions memcmp out."""
    w, h, tw = 250, 100, 64  # 100/16 -> 6 full bands + remainder, 250/64 partial last tile
    cache = TileCache(h, w, tw, slots=8)
    rng = np.random.default_rng(0)
    frame = rng.integers(0, 256, (h, w, 4), np.uint8)
    interior = np.int32(0 * 1024 + 1)
    edge_col = np.int32(0 * 1024 + 3)   # cols 192..250: partial
    edge_row = np.int32(6 * 1024 + 0)   # rows 96..100: partial
    up, dst, pairs = cache.split(frame, np.array([interior, edge_col, edge_row], np.int32))
    assert len(up) == 3 and len(pairs) == 0
    assert dst[0] != cache.slots          # interior tile kept in a pool slot
    assert dst[1] == dst[2] == cache.slots  # edge tiles -> scratch, never cached
    # same content again -> remap for the interior tile only
    up2, dst2, pairs2 = cache.split(frame, np.array([interior, edge_col], np.int32))
    assert list(up2) == [int(edge_col)]
    assert pairs2.tolist() == [[int(dst[0]), int(interior)]]
    # duplicate content FIRST seen twice in one call: both upload (the
    # device applies pool inserts after copies within one step)
    f2 = frame.copy()
    f2[16:32, :128] = frame[:16, :128]  # band 1 tiles 0,1 == band 0 tiles 0,1
    up3, dst3, pairs3 = cache.split(
        f2, np.array([1 * 1024 + 0, 1 * 1024 + 1], np.int32))
    assert len(pairs3) == 1  # tile (0,0..63) content was cached above; (64..127) was not
    up4, dst4, pairs4 = cache.split(
        f2, np.array([2 * 1024 + 0], np.int32))
    assert len(up4) == 1  # fresh content uploads


def test_scroll_trace_bitexact_and_2x_fewer_uplink_bytes(tmp_path):
    # taller frame: the 5-band scroll region (25 dirty tiles/frame) must
    # fit the delta buckets or the full-upload path hides the cache
    frames = scroll_trace(W, 256, 10, bands=5)
    sc, sp, enc_c = _pair(frames)
    assert sc == sp, "tile cache altered the bitstream on the scroll trace"
    assert enc_c._tcache.hits > 0
    up_c = sum(v for k, v in enc_c.link_bytes.snapshot().items()
               if k == "up_delta")
    # plain arm re-runs to count its delta bytes
    enc_p = TPUH264Encoder(W, 256, qp=26, tile_cache=0, packed_downlink=False,
                           ltr_scenes=False)
    _stream(enc_p, frames)
    up_p = sum(v for k, v in enc_p.link_bytes.snapshot().items()
               if k == "up_delta")
    assert up_c * 2 <= up_p, f"scroll uplink {up_c} not 2x under {up_p}"


def test_window_move_trace_bitexact(tmp_path):
    frames = window_move_trace(W, H, 10)
    sc, sp, enc_c = _pair(frames)
    assert sc == sp, "tile cache altered the bitstream on the window-move trace"
    assert enc_c._tcache.hits > 0


def test_tiny_pool_eviction_and_slot_reuse():
    """A 2-slot pool cycling 4 distinct contents at one position must
    evict constantly and still be bit-exact (slot reuse scatters the new
    content over the evicted tile's pool row)."""
    rng = np.random.default_rng(7)
    base = np.full((H, W, 4), 200, np.uint8)
    tiles = [rng.integers(0, 256, (16, 64, 4), np.uint8) for _ in range(4)]
    frames = [base.copy()]
    for rep in range(3):
        for t in tiles:
            f = frames[-1].copy()
            f[32:48, 64:128] = t  # same interior tile position, cycling content
            frames.append(f)
    sc, sp, enc_c = _pair(frames, slots=2)
    assert sc == sp, "eviction/slot reuse altered the bitstream"
    assert enc_c._tcache.evictions > 0, "tiny pool never evicted"
    assert enc_c._tcache.hits == 0  # 4 contents through 2 slots: all evicted before reuse


def test_tiny_pool_hits_when_content_fits():
    """Two contents alternating through a 2-slot pool stay resident: the
    second visit of each content is a remap, not an upload."""
    rng = np.random.default_rng(8)
    base = np.full((H, W, 4), 200, np.uint8)
    t0 = rng.integers(0, 256, (16, 64, 4), np.uint8)
    t1 = rng.integers(0, 256, (16, 64, 4), np.uint8)
    frames = [base.copy()]
    for t in (t0, t1, t0, t1, t0):
        f = frames[-1].copy()
        f[32:48, 64:128] = t
        frames.append(f)
    sc, sp, enc_c = _pair(frames, slots=2)
    assert sc == sp
    assert enc_c._tcache.hits >= 3
    assert enc_c._tcache.evictions == 0


def test_grouped_dispatch_with_cache_bitexact():
    """frame_batch>1 routes remaps through the lax.scan step (pool in the
    carry); the stream must match the unbatched uncached encoder."""
    frames = scroll_trace(W, 256, 9, bands=5)
    enc_b = TPUH264Encoder(W, 256, qp=26, frame_batch=4, pipeline_depth=2,
                           tile_cache=512, packed_downlink=True, ltr_scenes=False)
    outs = []
    for f in frames:
        outs.extend(enc_b.submit(f))
    outs.extend(enc_b.flush())
    stream_b = b"".join(au for au, _, _ in outs)
    enc_s = TPUH264Encoder(W, 256, qp=26, frame_batch=1, tile_cache=0,
                           packed_downlink=False, ltr_scenes=False)
    stream_s = _stream(enc_s, frames)
    assert stream_b == stream_s, "grouped cache dispatch altered the bitstream"
    assert enc_b._tcache.hits > 0, "group scan never saw a remap"


def test_ltr_restore_with_cache_bitexact(tmp_path):
    """Window switches served from the LTR scene cache must accept
    remapped tiles: cached and uncached encoders produce identical
    streams, and restores actually happen in both."""
    cv2 = pytest.importorskip("cv2")
    rng = np.random.default_rng(11)
    desk_a = rng.integers(0, 256, (H, W, 4), np.uint8)
    desk_b = rng.integers(0, 256, (H, W, 4), np.uint8)
    frames = []
    for which in (0, 1, 0, 1, 0):
        f = (desk_b if which else desk_a).copy()
        frames.append(f.copy())
        f2 = f.copy()
        f2[32:48, 64:128] = rng.integers(0, 256, (16, 64, 4), np.uint8)
        frames.append(f2)
    enc_c = TPUH264Encoder(W, H, qp=26, tile_cache=512, packed_downlink=True,
                           ltr_scenes=True)
    enc_p = TPUH264Encoder(W, H, qp=26, tile_cache=0, packed_downlink=False,
                           ltr_scenes=True)
    sc = _stream(enc_c, frames)
    sp = _stream(enc_p, frames)
    assert sc == sp, "cache altered the bitstream through LTR restores"
    assert enc_c.ltr_restores > 0 and enc_c.ltr_restores == enc_p.ltr_restores
    path = tmp_path / "ltr_cache.h264"
    path.write_bytes(sc)
    cap = cv2.VideoCapture(str(path))
    n = 0
    while cap.read()[0]:
        n += 1
    cap.release()
    assert n == len(frames)


def test_packed_downlink_bitexact_including_dense_fallback():
    """Delta frames spanning sparse (smooth fill) and dense (noise)
    residuals: the packed downlink must match the 16-lane layout's
    stream bit for bit, and the density fallback must engage on noise."""
    rng = np.random.default_rng(13)
    base = np.full((H, W, 4), 180, np.uint8)
    frames = [base]
    f = base.copy()
    f[32:48, :] = (90, 120, 150, 0)  # smooth: sparse residual rows
    frames.append(f)
    f2 = f.copy()
    f2[64:96, :] = rng.integers(0, 256, (32, W, 4), np.uint8)  # noise: dense
    frames.append(f2)
    f3 = f2.copy()
    f3[64:96, :] = rng.integers(0, 256, (32, W, 4), np.uint8)
    frames.append(f3)
    enc_k = TPUH264Encoder(W, H, qp=26, tile_cache=0, packed_downlink=True,
                           ltr_scenes=False)
    enc_v = TPUH264Encoder(W, H, qp=26, tile_cache=0, packed_downlink=False,
                           ltr_scenes=False)
    assert _stream(enc_k, frames) == _stream(enc_v, frames)


def test_cache_disabled_by_env(monkeypatch):
    monkeypatch.setenv("SELKIES_TILE_CACHE", "0")
    monkeypatch.setenv("SELKIES_PACK_DENSITY", "0")
    enc = TPUH264Encoder(W, H, qp=26)
    assert enc._tcache is None and enc._density is None


def test_prewarm_resets_cache_state():
    frames = scroll_trace(W, H, 4, bands=2)
    enc = TPUH264Encoder(W, H, qp=26, tile_cache=64, packed_downlink=True,
                         ltr_scenes=False)
    _stream(enc, frames)
    assert enc._tcache._hash2slot  # populated
    enc.prewarm()
    assert not enc._tcache._hash2slot and enc._pool_d is None


def test_over_budget_scroll_stays_on_delta_path():
    """A scroll region dirtier than the delta buckets (the maximized-
    window case) must still take the delta path once its tiles are
    pool-resident: the gate is the POST-REMAP upload count, and the
    transactional split falls back to full upload — without corrupting
    cache state — only while the content is genuinely new."""
    w, h = 320, 256  # delta buckets (8, 16, 32), try-cap 80
    frames = scroll_trace(w, h, 8, bands=8)  # 40 dirty tiles/frame > 32
    sc, sp, enc_c = _pair(frames)
    assert sc == sp, "over-budget delta remapping altered the bitstream"
    assert enc_c._tcache.hits > 0
    snap = enc_c.link_bytes.snapshot()
    # after the first (genuinely new, full-upload) scroll frame, the
    # remaining frames fit the delta path: ~5 upload tiles + remaps
    # instead of a full plane upload each
    assert snap.get("up_delta", 0) > 0, "cache never routed an over-budget frame to delta"
    enc_p = TPUH264Encoder(w, h, qp=26, tile_cache=0, packed_downlink=False,
                           ltr_scenes=False)
    _stream(enc_p, frames)
    snap_p = enc_p.link_bytes.snapshot()
    assert snap_p.get("up_delta", 0) == 0  # plain encoder full-uploads ALL of them
    assert snap["up_full"] < snap_p["up_full"], "no full uploads were saved"


@pytest.mark.parametrize("frame_batch,device_entropy",
                         [(1, False), (4, False), (4, True)])
def test_steps_built_off_the_stream_bitexact(frame_batch, device_entropy):
    """Where tile-cache steps build off the stream (a TPU, where each
    compiles for minutes), the deltas that need an unbuilt step take the
    full-frame path while it builds on a background thread, and the
    remaps resume once it is built; the stream is byte-identical to the
    same encoder compiling in the stream, LTR marking included."""
    import time

    frames = scroll_trace(W, 256, 16, bands=5)

    def built(enc):
        t0 = time.monotonic()
        while enc._step2_building and time.monotonic() - t0 < 300:
            time.sleep(0.05)

    def encode(off_stream):
        enc = TPUH264Encoder(W, 256, qp=26, frame_batch=frame_batch,
                             pipeline_depth=2, tile_cache=512,
                             packed_downlink=True,
                             device_entropy=device_entropy)
        enc._step2_off_stream = off_stream
        outs = []
        for i, f in enumerate(frames):
            if i == len(frames) // 2:
                built(enc)
            outs.extend(enc.submit(f))
        outs.extend(enc.flush())
        built(enc)  # no build left running past the test
        return outs, enc

    ref, _ = encode(False)
    outs, enc = encode(True)
    assert b"".join(a for a, _, _ in outs) == b"".join(a for a, _, _ in ref)
    kinds = [st.upload_kind for _, st, _ in outs]
    half = len(frames) // 2
    assert "full" in kinds[2:half] and "delta" not in kinds[1:half], kinds
    assert "delta" in kinds[half:], kinds
    assert enc._step2_built and enc._tcache.hits > 0
