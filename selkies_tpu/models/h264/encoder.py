"""tpuh264enc — the TPU-native H.264 encoder element.

Replaces the reference's nvh264enc/vah264enc/x264enc/openh264enc rows of
the encoder matrix (gstwebrtc_app.py:260-367,475-508,609-665). The device
half (colorspace, prediction, transforms, quantization) is one jitted XLA
program per resolution (encoder_core.py); the host half is the C++ CAVLC
packer (native/cavlc_pack.cc). QP is a traced argument, so the GCC
congestion-control loop can retune bitrate every frame without
recompilation (reference: set_video_bitrate, gstwebrtc_app.py:1296).

Latency design: the device step returns int16 coefficient tensors (half
the PCIe traffic of int32); reconstruction planes stay on device for the
future P-frame path. Double-buffering (dispatch frame N+1 while N packs on
host) happens naturally because JAX dispatch is async — encode_frame
blocks only on the coefficient device→host copy.
"""

from __future__ import annotations

import os
import threading
import time
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from functools import partial
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from selkies_tpu.models.h264.numpy_ref import PFrameCoeffs

from selkies_tpu.models.frameprep import FramePrep, delta_buckets_for, tile_width_for
from selkies_tpu.monitoring.telemetry import telemetry
from selkies_tpu.resilience.faultinject import get_injector
from selkies_tpu.monitoring.tracing import tracer
from selkies_tpu.models.stats import FrameStats as _FrameStats
from selkies_tpu.models.h264.bitstream import StreamParams, write_pps, write_sps
from selkies_tpu.models.h264.compact import (
    i_header_words,
    p_header_words,
    p_sparse_entropy_words,
    p_sparse_packed_words,
    p_sparse_var_words,
    split_prefix,
    unpack_i_compact,
    unpack_p_compact,
)
from selkies_tpu.models.h264.cabac import pack_slice_cabac, pack_slice_p_cabac
from selkies_tpu.models.h264.device_cabac import (
    assemble_p_cabac_nal,
    pack_p_slice_tokens_active,
)
from selkies_tpu.models.h264.device_cavlc import (
    WORD_CAP_DEFAULT as BITS_WORD_CAP,
    assemble_p_nal,
    entropy_coder_default,
    pack_p_slice_bits_active,
    resolve_entropy,
)
from selkies_tpu.models.h264.encoder_core import (
    _bitpack32,
    encode_frame_p_planes,
    encode_frame_planes,
    fuse_downlink,
    pack_i_compact,
    pack_p_compact,
    pack_p_sparse_entropy,
    pack_p_sparse_packed,
    pack_p_sparse_var,
    scatter_tiles,
)
from selkies_tpu.models.h264.sparse_complete import (
    complete_sparse_slice,
    fetch_rest,
)
from selkies_tpu.models.stats import LinkByteCounter
from selkies_tpu.models.tilecache import TileCache
from selkies_tpu.models.h264.native import (
    pack_slice_fast,
    pack_slice_p_fast,
)
from selkies_tpu.ops.colorspace import bgrx_to_i420, rgb_to_i420

__all__ = ["TPUH264Encoder", "make_frame_step"]


@jax.named_scope("enc.ingest")
def _convert_pad(frame, *, pad_h: int, pad_w: int, channels: int):
    """Packed frame -> padded I420 planes (device)."""
    if channels == 4:
        y, u, v = bgrx_to_i420(frame)
    else:
        y, u, v = rgb_to_i420(frame)
    h, w = y.shape
    if (pad_h, pad_w) != (h, w):
        y = jnp.pad(y, ((0, pad_h - h), (0, pad_w - w)), mode="edge")
        u = jnp.pad(u, ((0, (pad_h - h) // 2), (0, (pad_w - w) // 2)), mode="edge")
        v = jnp.pad(v, ((0, (pad_h - h) // 2), (0, (pad_w - w) // 2)), mode="edge")
    return y, u, v


# Data rows carried in the single-fetch prefix buffer: typical frames
# complete in ONE fetch; frames with more nonzero rows pay a second fetch.
# (Sized when every transfer was a ~200 ms remote operation; not
# re-measured on a locally attached chip.)
CAP_ROWS = 4096
# Delta frames use the variable-packed sparse downlink
# (encoder_core.pack_p_sparse_var): live fetch bytes track frame activity
# (~11 KB for a typing update, ~60-130 KB through the decay tail that
# follows a full-frame change — measured on the bench trace). NSCAP and
# the row cap only bound the device buffer; they are sized so the decay
# tail (ns up to ~3k, n up to ~3.5k) never triggers the fallback fetches.
CAP_ROWS_DELTA = 4096
NSCAP = 4096
# Device-entropy downlink (full P frames): the slice-data BITSTREAM is
# produced on device (device_cavlc.py) and fetched instead of multi-MB
# coefficient tensors. The prefix fetch carries BITS_META = [nbits,
# trailing, nskip, coef_luma, coef_chroma, rung] + the first
# BITS_PREFIX_WORDS words; bigger frames spill one extra fetch; frames
# overflowing the word cap fall back to the dense path.
BITS_META = 6
BITS_PREFIX_WORDS = 1 << 16  # 256 KB: covers typical full-P slices in ONE fetch
# Delta frames run the same device entropy coder activity-proportionally
# (pack_p_sparse_entropy); the live-MB threshold and the rest of the
# knob resolution live in device_cavlc.resolve_entropy, shared with the
# banded encoder.


def _device_step(frame, qp, *, pad_h: int, pad_w: int, channels: int):
    """Full IDR device path: packed frame -> padded planes -> compacted
    coefficient downlink (header, nonzero rows) + device-resident recon."""
    y, u, v = _convert_pad(frame, pad_h=pad_h, pad_w=pad_w, channels=channels)
    return _i_planes_step(y, u, v, qp)


def _i_planes_step(y, u, v, qp):
    out = encode_frame_planes(y, u, v, qp)
    header, buf = pack_i_compact(out)
    prefix = fuse_downlink(header, buf, CAP_ROWS)
    return prefix, buf, out["recon_y"], out["recon_u"], out["recon_v"]


def _device_step_p(frame, qp, ref_y, ref_u, ref_v, *, pad_h: int, pad_w: int, channels: int):
    """P-frame device path: convert, hierarchical motion search (±32)
    against the previous reconstruction (which never leaves the device),
    encode inter residuals, compact the downlink."""
    y, u, v = _convert_pad(frame, pad_h=pad_h, pad_w=pad_w, channels=channels)
    return _p_planes_step(y, u, v, qp, ref_y, ref_u, ref_v)


def _p_planes_step(y, u, v, qp, ref_y, ref_u, ref_v):
    out = encode_frame_p_planes(y, u, v, ref_y, ref_u, ref_v, qp)
    header, buf = pack_p_compact(out)
    prefix = fuse_downlink(header, buf, CAP_ROWS)
    return prefix, buf, out["recon_y"], out["recon_u"], out["recon_v"]


def _p_bits_step(y, u, v, qp, ref_y, ref_u, ref_v):
    """Full-P with ON-DEVICE entropy coding: what crosses the link is the
    slice bitstream itself. The activity-proportional coder picks its
    bucket per frame (a moderately-busy scene cut pays for its live MBs,
    not the grid). Dense header/buf ride along device-side only, as the
    overflow fallback (fetched on the rare nbits > cap frame)."""
    out = encode_frame_p_planes(y, u, v, ref_y, ref_u, ref_v, qp)
    words, nbits, trailing, _ns, counts = pack_p_slice_bits_active(
        out, BITS_WORD_CAP)
    with jax.named_scope("enc.downlink"):
        nskip = out["skip"].sum().astype(jnp.int32)
        meta = jnp.concatenate(
            [jnp.stack([nbits, trailing, nskip]), counts]).astype(jnp.uint32)
        prefix = jnp.concatenate([meta, words[:BITS_PREFIX_WORDS]])
        header, buf = pack_p_compact(out)
    return prefix, words, header, buf, out["recon_y"], out["recon_u"], out["recon_v"]


# CABAC full-P token downlink: tokens are 16-bit IR slots (two per
# word), so the cap and prefix double relative to the CAVLC bit path to
# cover the same slice activity.
TOK_WORD_CAP = 1 << 18
TOK_PREFIX_WORDS = 1 << 17


def _p_toks_step(y, u, v, qp, ref_y, ref_u, ref_v):
    """Full-P with ON-DEVICE CABAC binarization (device_cabac.py): the
    downlink is the 16-bit token IR plus what the host interleave needs
    — the skip bitmap and the coded MBs' token counts — packed into one
    uint32 prefix [ntok, ns, nskip] ++ skip_words ++ count pairs ++
    token words. The sequential arithmetic engine stays on the host
    (native/cabac_pack.cc)."""
    out = encode_frame_p_planes(y, u, v, ref_y, ref_u, ref_v, qp)
    words, ntok, counts, ns = pack_p_slice_tokens_active(out, TOK_WORD_CAP)
    with jax.named_scope("enc.downlink"):
        skip = out["skip"].reshape(-1)
        nskip = skip.sum().astype(jnp.int32)
        skip_words = _bitpack32(skip)
        m = counts.shape[0]
        cnt16 = jnp.pad(counts.astype(jnp.int16), (0, m & 1))
        cnt_words = jax.lax.bitcast_convert_type(
            cnt16.reshape(-1, 2), jnp.int32).reshape(-1)
        meta = jnp.stack([ntok, ns, nskip])
        prefix = jnp.concatenate([
            meta.astype(jnp.uint32), skip_words.astype(jnp.uint32),
            cnt_words.astype(jnp.uint32), words[:TOK_PREFIX_WORDS]])
        header, buf = pack_p_compact(out)
    return prefix, words, header, buf, out["recon_y"], out["recon_u"], out["recon_v"]


# Full-frame uploads ride in Y_CHUNKS+2 concurrent device_puts (chosen
# when h2d transfers overlapped ~2.5x across Python threads on a remote
# chip; not re-measured on a locally attached one). The chunked steps
# re-join the planes on device and return them so they stay resident as
# the delta base.
Y_CHUNKS = 4


@jax.named_scope("enc.ingest")
def _join_y(y0, y1, y2, y3):
    return jnp.concatenate([y0, y1, y2, y3], 0)


def _i_planes_step_chunked(y0, y1, y2, y3, u, v, qp):
    y = _join_y(y0, y1, y2, y3)
    return (*_i_planes_step(y, u, v, qp), y, u, v)


def _p_bits_step_chunked(y0, y1, y2, y3, u, v, qp, ref_y, ref_u, ref_v):
    y = _join_y(y0, y1, y2, y3)
    return (*_p_bits_step(y, u, v, qp, ref_y, ref_u, ref_v), y, u, v)


def _p_toks_step_chunked(y0, y1, y2, y3, u, v, qp, ref_y, ref_u, ref_v):
    y = _join_y(y0, y1, y2, y3)
    return (*_p_toks_step(y, u, v, qp, ref_y, ref_u, ref_v), y, u, v)


def _p_planes_step_chunked(y0, y1, y2, y3, u, v, qp, ref_y, ref_u, ref_v):
    y = _join_y(y0, y1, y2, y3)
    return (*_p_planes_step(y, u, v, qp, ref_y, ref_u, ref_v), y, u, v)


# Delta steps: only the dirty bands cross the link; the full frame is
# assembled on device by scattering them into the resident source planes
# (donated -> in-place). Each returns the updated source planes so the
# encoder can keep them resident for the next frame's delta. The bands +
# indices ride in ONE packed uint8 buffer: one upload instead of four
# host->device operations.


@jax.named_scope("enc.ingest")
def _unpack_delta(packed, w):
    """packed: [idx int32 LE bytes (k,4)] ++ yb ++ ub ++ vb, k inferred.
    w is the TILE width in luma columns (== plane width for full bands)."""
    per_band = 4 + 24 * w  # 4 idx bytes + 16*w luma + 2*(8*(w//2)) chroma
    k = packed.shape[0] // per_band
    idx = jax.lax.bitcast_convert_type(packed[: 4 * k].reshape(k, 4), jnp.int32)
    off = 4 * k
    yb = jax.lax.dynamic_slice_in_dim(packed, off, k * 16 * w).reshape(k, 16, w)
    off += k * 16 * w
    ub = jax.lax.dynamic_slice_in_dim(packed, off, k * 8 * (w // 2)).reshape(k, 8, w // 2)
    off += k * 8 * (w // 2)
    vb = jax.lax.dynamic_slice_in_dim(packed, off, k * 8 * (w // 2)).reshape(k, 8, w // 2)
    return yb, ub, vb, idx


def _pack_sparse_p(out, nscap, cap, density, entropy=None):
    """Delta-P downlink packer: density=None keeps the 16-lane row
    layout (pack_p_sparse_var); an int percent enables the bit-packed
    rows with that dense-fallback cap (pack_p_sparse_packed). entropy
    (bits_words, min_mbs, buckets) wraps either layout in the
    activity-proportional device-entropy decision (pack_p_sparse_
    entropy): busy frames then ship final slice bits (CAVLC) or the
    binarized token IR (CABAC), quiet frames the sparse rows — same
    fused-buffer fetch either way."""
    if entropy is not None:
        bits_words, min_mbs, buckets, coder = entropy
        return pack_p_sparse_entropy(out, nscap, cap, density,
                                     bits_words, min_mbs, buckets,
                                     entropy_coder=coder)
    if density is None:
        return pack_p_sparse_var(out, nscap, cap)
    return pack_p_sparse_packed(out, nscap, cap, density)


def _p_scatter_step(packed, qp, sy, su, sv, ref_y, ref_u, ref_v, *, nscap, cap, tile_w,
                    density=None, entropy=None):
    yb, ub, vb, idx = _unpack_delta(packed, tile_w)
    y, u, v = scatter_tiles(sy, su, sv, yb, ub, vb, idx, tile_w)
    out = encode_frame_p_planes(y, u, v, ref_y, ref_u, ref_v, qp)
    prefix, dense, buf = _pack_sparse_p(out, nscap, cap, density, entropy)
    return prefix, dense, buf, out["recon_y"], out["recon_u"], out["recon_v"], y, u, v


def _i_scatter_step(packed, qp, sy, su, sv, *, tile_w):
    yb, ub, vb, idx = _unpack_delta(packed, tile_w)
    y, u, v = scatter_tiles(sy, su, sv, yb, ub, vb, idx, tile_w)
    out = encode_frame_planes(y, u, v, qp)
    header, buf = pack_i_compact(out)
    prefix = fuse_downlink(header, buf, CAP_ROWS)
    return prefix, buf, out["recon_y"], out["recon_u"], out["recon_v"], y, u, v


def _p_scatter_multi_step(packed_a, packed_b, qps, sy, su, sv, ref_y, ref_u, ref_v,
                          *, nscap, cap, tile_w, density=None, entropy=None):
    """K delta frames in ONE device round trip.

    packed_a/packed_b: two (K/2, F) uint8 halves of the K frames' tile
    payloads (same bucket), uploaded CONCURRENTLY and re-joined here;
    qps: (K,) int32 per-frame QP. The scan chains recon: frame k's
    motion estimation references frame k-1's reconstruction, exactly as
    K single steps would. One execute + one prefix fetch instead of 2K
    host<->device operations."""
    with jax.named_scope("enc.ingest"):
        packed = jnp.concatenate([packed_a, packed_b], 0)

    def body(carry, xs):
        pk, qp = xs
        cy, cu, cv, ry, ru, rv = carry
        yb, ub, vb, idx = _unpack_delta(pk, tile_w)
        y, u, v = scatter_tiles(cy, cu, cv, yb, ub, vb, idx, tile_w)
        out = encode_frame_p_planes(y, u, v, ry, ru, rv, qp)
        prefix, dense, buf = _pack_sparse_p(out, nscap, cap, density, entropy)
        return (
            (y, u, v, out["recon_y"], out["recon_u"], out["recon_v"]),
            (prefix, dense, buf),
        )

    carry, (prefixes, denses, bufs) = jax.lax.scan(
        body, (sy, su, sv, ref_y, ref_u, ref_v), (packed, qps)
    )
    y, u, v, ry, ru, rv = carry
    return prefixes, denses, bufs, ry, ru, rv, y, u, v


def _i_resident_step(qp, sy, su, sv):
    # IDR over unchanged content (e.g. PLI-forced keyframe on an idle
    # desktop): zero upload, encode straight from the resident planes
    out = encode_frame_planes(sy, su, sv, qp)
    header, buf = pack_i_compact(out)
    prefix = fuse_downlink(header, buf, CAP_ROWS)
    return prefix, buf, out["recon_y"], out["recon_u"], out["recon_v"]


# Tile-cache delta steps (the CopyRect analogue, models/tilecache.py):
# the packed upload carries [upload idx (bucket int32, -1 pads)] ++
# [pool slot each upload is kept in (bucket int32, scratch = last pool
# row)] ++ [(src_slot, dst_idx) copy pairs (cbucket x 2 int32, src=-1
# pads)] ++ the uploaded pixel tiles. Copy pairs remap tiles already
# resident in the device slot pool into their new positions WITHOUT any
# pixel upload — a scrolled or window-moved tile costs 8 bytes instead
# of ~3 KB. bucket/cbucket are static (one executable per combination,
# same ladder discipline as the delta buckets); padding entries write a
# tile's own current content back (reading it first), which keeps every
# lane shape static without a device-side branch.


def _unpack_delta2(packed, w, bucket, cbucket):
    k = bucket
    up_idx = jax.lax.bitcast_convert_type(packed[: 4 * k].reshape(k, 4), jnp.int32)
    off = 4 * k
    pool_dst = jax.lax.bitcast_convert_type(
        packed[off : off + 4 * k].reshape(k, 4), jnp.int32
    )
    off += 4 * k
    pairs = jax.lax.bitcast_convert_type(
        packed[off : off + 8 * cbucket].reshape(2 * cbucket, 4), jnp.int32
    ).reshape(cbucket, 2)
    off += 8 * cbucket
    yb = packed[off : off + k * 16 * w].reshape(k, 16, w)
    off += k * 16 * w
    ub = packed[off : off + k * 8 * (w // 2)].reshape(k, 8, w // 2)
    off += k * 8 * (w // 2)
    vb = packed[off : off + k * 8 * (w // 2)].reshape(k, 8, w // 2)
    return up_idx, pool_dst, pairs, yb, ub, vb


@jax.named_scope("enc.ingest")
def _apply_tiles2(sy, su, sv, py, pu, pv, packed, *, tile_w, bucket, cbucket):
    """Copy remaps (pool -> planes), then pixel uploads (-> planes AND
    their pool slots). Copies run first so a same-step upload can land
    on a position a remap also wrote (the upload is the newer content);
    the host never emits a remap from a slot inserted in the same call."""
    up_idx, pool_dst, pairs, yb, ub, vb = _unpack_delta2(packed, tile_w, bucket, cbucket)
    ctw = tile_w // 2

    def copy_body(i, planes):
        y, u, v = planes
        valid = pairs[i, 0] >= 0
        slot = jnp.maximum(pairs[i, 0], 0)
        d = jnp.maximum(pairs[i, 1], 0)
        band, tile = d // 1024, d % 1024
        ty = jax.lax.dynamic_slice(py, (slot, 0, 0), (1, 16, tile_w))[0]
        tu = jax.lax.dynamic_slice(pu, (slot, 0, 0), (1, 8, ctw))[0]
        tv = jax.lax.dynamic_slice(pv, (slot, 0, 0), (1, 8, ctw))[0]
        cy = jax.lax.dynamic_slice(y, (band * 16, tile * tile_w), (16, tile_w))
        cu = jax.lax.dynamic_slice(u, (band * 8, tile * ctw), (8, ctw))
        cv = jax.lax.dynamic_slice(v, (band * 8, tile * ctw), (8, ctw))
        y = jax.lax.dynamic_update_slice(
            y, jnp.where(valid, ty, cy), (band * 16, tile * tile_w))
        u = jax.lax.dynamic_update_slice(
            u, jnp.where(valid, tu, cu), (band * 8, tile * ctw))
        v = jax.lax.dynamic_update_slice(
            v, jnp.where(valid, tv, cv), (band * 8, tile * ctw))
        return y, u, v

    if cbucket:
        sy, su, sv = jax.lax.fori_loop(0, cbucket, copy_body, (sy, su, sv))
    if not bucket:  # pure-remap frame (scroll/window steady state)
        return sy, su, sv, py, pu, pv

    def up_body(i, state):
        y, u, v, qy, qu, qv = state
        valid = up_idx[i] >= 0
        d = jnp.maximum(up_idx[i], 0)
        band, tile = d // 1024, d % 1024
        cy = jax.lax.dynamic_slice(y, (band * 16, tile * tile_w), (16, tile_w))
        cu = jax.lax.dynamic_slice(u, (band * 8, tile * ctw), (8, ctw))
        cv = jax.lax.dynamic_slice(v, (band * 8, tile * ctw), (8, ctw))
        y = jax.lax.dynamic_update_slice(
            y, jnp.where(valid, yb[i], cy), (band * 16, tile * tile_w))
        u = jax.lax.dynamic_update_slice(
            u, jnp.where(valid, ub[i], cu), (band * 8, tile * ctw))
        v = jax.lax.dynamic_update_slice(
            v, jnp.where(valid, vb[i], cv), (band * 8, tile * ctw))
        # keep the uploaded tile in its assigned pool slot (padding and
        # not-kept uploads target the scratch row)
        qy = jax.lax.dynamic_update_slice(qy, yb[i][None], (pool_dst[i], 0, 0))
        qu = jax.lax.dynamic_update_slice(qu, ub[i][None], (pool_dst[i], 0, 0))
        qv = jax.lax.dynamic_update_slice(qv, vb[i][None], (pool_dst[i], 0, 0))
        return y, u, v, qy, qu, qv

    return jax.lax.fori_loop(0, bucket, up_body, (sy, su, sv, py, pu, pv))


@jax.named_scope("enc.ingest")
def _pool_seed_step(pairs, sy, su, sv, py, pu, pv, *, tile_w, sbucket):
    """Seed pool slots by GATHERING tiles from the resident source
    planes — no pixel upload at all (only the (slot, idx) list crosses).
    Runs after a full-frame upload whose dirty set was over the delta
    budget: the next frame of a sustained scroll then remaps instead of
    aborting to another full upload. pairs: (sbucket, 2) int32
    (slot, dst_idx); padding rows target the scratch slot."""
    ctw = tile_w // 2

    def body(i, pool):
        qy, qu, qv = pool
        slot = pairs[i, 0]
        d = jnp.maximum(pairs[i, 1], 0)
        band, tile = d // 1024, d % 1024
        ty = jax.lax.dynamic_slice(sy, (band * 16, tile * tile_w), (16, tile_w))
        tu = jax.lax.dynamic_slice(su, (band * 8, tile * ctw), (8, ctw))
        tv = jax.lax.dynamic_slice(sv, (band * 8, tile * ctw), (8, ctw))
        qy = jax.lax.dynamic_update_slice(qy, ty[None], (slot, 0, 0))
        qu = jax.lax.dynamic_update_slice(qu, tu[None], (slot, 0, 0))
        qv = jax.lax.dynamic_update_slice(qv, tv[None], (slot, 0, 0))
        return qy, qu, qv

    return jax.lax.fori_loop(0, sbucket, body, (py, pu, pv))


def _p_scatter_step2(packed, qp, sy, su, sv, py, pu, pv, ref_y, ref_u, ref_v,
                     *, nscap, cap, tile_w, bucket, cbucket, density, entropy=None):
    y, u, v, qy, qu, qv = _apply_tiles2(
        sy, su, sv, py, pu, pv, packed, tile_w=tile_w, bucket=bucket, cbucket=cbucket)
    out = encode_frame_p_planes(y, u, v, ref_y, ref_u, ref_v, qp)
    prefix, dense, buf = _pack_sparse_p(out, nscap, cap, density, entropy)
    return (prefix, dense, buf, out["recon_y"], out["recon_u"], out["recon_v"],
            y, u, v, qy, qu, qv)


def _i_scatter_step2(packed, qp, sy, su, sv, py, pu, pv, *, tile_w, bucket, cbucket):
    y, u, v, qy, qu, qv = _apply_tiles2(
        sy, su, sv, py, pu, pv, packed, tile_w=tile_w, bucket=bucket, cbucket=cbucket)
    out = encode_frame_planes(y, u, v, qp)
    header, buf = pack_i_compact(out)
    prefix = fuse_downlink(header, buf, CAP_ROWS)
    return (prefix, buf, out["recon_y"], out["recon_u"], out["recon_v"],
            y, u, v, qy, qu, qv)


def _p_scatter_multi_step2(packed_a, packed_b, qps, sy, su, sv, py, pu, pv,
                           ref_y, ref_u, ref_v,
                           *, nscap, cap, tile_w, bucket, cbucket, density,
                           entropy=None):
    """Grouped (lax.scan) variant of _p_scatter_step2: the slot pool
    rides in the carry, so frame k's copy remaps may reference slots
    frame k-1's uploads inserted — matching the host cache's sequential
    split() order exactly."""
    with jax.named_scope("enc.ingest"):
        packed = jnp.concatenate([packed_a, packed_b], 0)

    def body(carry, xs):
        pk, qp = xs
        cy, cu, cv, qy, qu, qv, ry, ru, rv = carry
        y, u, v, qy, qu, qv = _apply_tiles2(
            cy, cu, cv, qy, qu, qv, pk, tile_w=tile_w, bucket=bucket, cbucket=cbucket)
        out = encode_frame_p_planes(y, u, v, ry, ru, rv, qp)
        prefix, dense, buf = _pack_sparse_p(out, nscap, cap, density, entropy)
        return (
            (y, u, v, qy, qu, qv, out["recon_y"], out["recon_u"], out["recon_v"]),
            (prefix, dense, buf),
        )

    carry, (prefixes, denses, bufs) = jax.lax.scan(
        body, (sy, su, sv, py, pu, pv, ref_y, ref_u, ref_v), (packed, qps)
    )
    y, u, v, qy, qu, qv, ry, ru, rv = carry
    return prefixes, denses, bufs, ry, ru, rv, y, u, v, qy, qu, qv


# shared with the band-parallel completion path (sparse_complete.py owns
# the implementation; the 4096 default there IS CAP_ROWS)
_fetch_rest = fetch_rest


def _lowering_shape(x):
    """What a jitted call sees of argument x, for lowering ahead of it: its
    shape and dtype, and its sharding where the array is committed."""
    sharding = x.sharding if getattr(x, "committed", False) else None
    return jax.ShapeDtypeStruct(np.shape(x), x.dtype, sharding=sharding)


FrameStats = _FrameStats  # shared definition (models/stats.py)


@dataclass
class _Pending:
    """One in-flight frame in the encode pipeline."""

    kind: str  # "static" | "i" | "p" | "pd" (sparse delta P) | "pb" (device-entropy P)
    frame_index: int
    qp: int
    frame_num: int
    idr_pic_id: int
    t0: float
    t1: float
    meta: object = None
    au: bytes | None = None  # static only
    prefix_d: object = None
    pfx_slice_d: object = None  # pd: hint-sized slice, dispatched with the step
    buf_d: object = None
    hdr_d: object = None  # pd/pb: dense header for the fallback fetch
    words_d: object = None  # pb only: full bit-word buffer (spill fetch)
    future: object = None  # completion future (threaded fetch+unpack+pack)
    batch_slot: int = -1  # >=0: index into a shared batch future's result list
    # device-stage attribution (FrameStats upload/step/fetch split):
    # up_ms is the HOST front-end cost of this frame — classify (fused
    # dirty scan + tile-cache hash/split) + convert (BGRx->I420 of the
    # upload payload) + h2d (transfer enqueue) + packing glue — split
    # out in classify_ms/convert_ms/h2d_ms. t_disp is the wall clock
    # just BEFORE the device-step dispatch call: workers measure
    # step_ms = outputs-ready - t_disp, so a dispatch call that blocks
    # (CPU backend contention, full dispatch queue) counts as device
    # step time, not as upload — the round-11 bench misread exactly
    # this (PERF.md round 12)
    up_ms: float = 0.0
    classify_ms: float = 0.0
    convert_ms: float = 0.0
    h2d_ms: float = 0.0
    t_disp: float = 0.0
    scene_cut: bool = False  # full-frame change transition (rate control)
    # dirty-tile accounting for the scenario policy signals
    # (FrameStats.upload_kind/dirty_frac/remap_frac): pixel-upload tiles
    # and tile-cache remap pairs of a delta frame
    n_up: int = 0
    n_remap: int = 0
    # LTR scene cache slice-header flags (bitstream.write_slice_header):
    ltr_ref: int | None = None   # predict from long-term reference j
    mark_ltr: int | None = None  # mark the previous frame as LT index k
    mmco_evict: tuple = ()       # MMCO 1 diffs for stale short-terms
    # device-entropy full-P: the meta prefix's coefficient-carrying
    # (luma, chroma AC) block counts and emission rung
    coef_blocks: tuple = (0, 0)
    bits_rung: int = -1


class TPUH264Encoder:
    """Stateful per-stream encoder: frame in, Annex-B access unit out.

    `codec` identifies the bitstream for client decoder configuration
    (media.js maps it to a WebCodecs codec string).

    GOP policy mirrors the reference default (keyframe_distance=-1,
    __main__.py:473-475): one IDR, then P frames forever; new IDRs only on
    force_keyframe() (client PLI / stream restart) or an explicit
    keyframe_interval. The previous frame's reconstruction stays on the
    TPU between frames — only quantized coefficients cross PCIe.
    """

    codec = "h264"
    # the submit()/encode paths take capture-layer damage-rect hints
    # (FramePrep.scan superset contract); the pipeline only forwards
    # hints to encoders that declare this
    accepts_damage = True

    def __init__(
        self,
        width: int,
        height: int,
        qp: int = 28,
        fps: int = 60,
        channels: int = 4,
        keyframe_interval: int = 0,
        host_convert: bool = True,
        pipeline_depth: int = 2,
        frame_batch: int = 4,
        scene_qp_boost: int = 0,
        device_entropy: bool | None = None,
        bits_min_mbs: int | None = None,
        entropy_coder: str | None = None,
        ltr_scenes: bool = True,
        tile_cache: int | None = None,
        packed_downlink: bool | None = None,
        pack_density: int | None = None,
        bands: int | None = None,
    ):
        self.width = width
        self.height = height
        self.fps = fps
        # bands: intra-frame slice parallelism lives in the band-parallel
        # encoder (parallel/bands.py; the registry routes SELKIES_BANDS>1
        # there) — here the knob only sizes the pack pool, so a caller
        # that wraps this encoder per band fans its slices out correctly
        if bands is None:
            # lazy: parallel.bands imports this module
            from selkies_tpu.parallel.bands import bands_from_env

            bands = bands_from_env()
        self.bands = int(bands)
        self._nscap = NSCAP
        self._cap_delta = CAP_ROWS_DELTA
        # packed delta downlink: coefficient rows cross the link as a
        # significance bitmap + nonzeros (encoder_core.pack_p_sparse_
        # packed) — 3-6x fewer live bytes on desktop residuals, falling
        # back to dense rows above the density cap. SELKIES_PACK_DENSITY
        # overrides: "0" disables, an integer sets the cap percent.
        # env is the DEFAULT only: explicit constructor arguments win
        # (same precedence as the tile_cache knob); a malformed env
        # value falls back rather than failing construction
        dens_env = os.environ.get("SELKIES_PACK_DENSITY", "")
        if packed_downlink is None:
            packed_downlink = dens_env != "0"
        if pack_density is None:
            try:
                pack_density = int(dens_env) if dens_env not in ("", "0") else 75
            except ValueError:
                pack_density = 75
        self._density = int(pack_density) if packed_downlink else None
        self.set_qp(qp)
        self.channels = channels
        self.keyframe_interval = int(keyframe_interval)  # 0 = infinite GOP
        # entropy_coder: cavlc (Baseline, the byte-contract default) or
        # cabac (Main profile). PPS-scoped, so every slice of the stream
        # uses the same coder; SELKIES_ENTROPY_CODER is the env default,
        # explicit constructor arguments win.
        self._coder = entropy_coder_default(entropy_coder)
        self.params = StreamParams(width=width, height=height, qp=self.qp,
                                   fps=fps, entropy_coder=self._coder)
        self._headers = write_sps(self.params) + write_pps(self.params)
        self._pad_h = (height + 15) // 16 * 16
        self._pad_w = (width + 15) // 16 * 16
        # host_convert: BGRx->I420 on the host CPU (native/frameprep.cc) so
        # the upload is 1.5 B/px instead of 4 — the link is the bottleneck
        # (tools/profile_link.py). host_convert=False keeps conversion on
        # device (better when the device is PCIe-local and link-rich).
        self.pipeline_depth = max(0, int(pipeline_depth))
        # delta granularity: 16-row x _tile_w-col tiles. Column tiling
        # shrinks the upload by the width fraction that changed (a cursor
        # blink is one ~3 KB tile, not a ~46 KB full-width band); the
        # largest power-of-two width that divides pad_w keeps device
        # shapes static (pad_w itself => full bands, the old behavior).
        self._tile_w = tile_width_for(width)
        self._prep: FramePrep | None = None
        if host_convert and channels == 4:
            # one conversion slot per possibly-in-flight async upload plus
            # one being written: depth+1 frames can be pipelined before
            # submit() blocks on the oldest completion
            self._prep = FramePrep(
                width, height, self._pad_w, self._pad_h,
                nslots=self.pipeline_depth + 2,
            )
        # device_entropy: P frames emit their slice BITSTREAM on device
        # (device_cavlc.py) — the downlink is the final bits, not
        # coefficient tensors. Full-P frames always ship bits when this
        # is on; delta frames decide per frame ON DEVICE (busy frames —
        # >= bits_min_mbs live MBs — ship bits, quiet frames keep the
        # sparse coeff downlink whose host pack is already near-free).
        # Requires host conversion mode (the only production path);
        # byte-identical either way. Default is AUTO — on for real TPU
        # backends, off on CPU; SELKIES_DEVICE_ENTROPY=0/1 forces,
        # SELKIES_BITS_MIN_MBS moves the decision threshold; explicit
        # constructor arguments win (tile_cache precedence rules). The
        # resolved consts (_entropy) are what the jitted delta steps
        # close over: bits payload cap, live-MB threshold, bucket ladder.
        (self.device_entropy, self.bits_min_mbs, self._bits_words,
         self._entropy) = resolve_entropy(
            (self._pad_h // 16) * (self._pad_w // 16),
            device_entropy, bits_min_mbs, entropy_coder=self._coder)
        if self._prep is None:  # device conversion mode: host path only
            self.device_entropy = False
            self._entropy = None
        if self._prep is not None:
            self._step = jax.jit(_i_planes_step_chunked)
            self._step_p = jax.jit(_p_planes_step_chunked, donate_argnums=(7, 8, 9))
            self._step_pb = jax.jit(
                _p_toks_step_chunked if self._coder == "cabac"
                else _p_bits_step_chunked,
                donate_argnums=(7, 8, 9))
            # delta-upload steps: source planes are donated (scatter is
            # in-place) and returned updated; refs donated as usual
            # nscap/cap ride in a partial (not read from module globals
            # inside the traced body): jax's trace cache is keyed on the
            # function object, so a global read would leak one encoder's
            # constants into another's executable.
            _consts = dict(nscap=self._nscap, cap=self._cap_delta, tile_w=self._tile_w,
                           density=self._density, entropy=self._entropy)
            self._step_scatter_p = jax.jit(
                partial(_p_scatter_step, **_consts), donate_argnums=(2, 3, 4, 5, 6, 7)
            )
            self._step_scatter_pk = jax.jit(
                partial(_p_scatter_multi_step, **_consts), donate_argnums=(3, 4, 5, 6, 7, 8)
            )
            self._step_scatter_i = jax.jit(
                partial(_i_scatter_step, tile_w=self._tile_w), donate_argnums=(2, 3, 4)
            )
            self._step_resident_i = jax.jit(_i_resident_step)
            # LTR scene restore: same scatter+encode step but NON-donating
            # — the long-term slot's planes must survive the step (they
            # are the stash, not the working chain)
            self._step_scatter_ltr = jax.jit(partial(_p_scatter_step, **_consts))
            # device-side plane snapshot for the scene stash (six ~1 MB
            # HBM copies, dispatched once per scene cut)
            self._copy_planes = jax.jit(
                lambda *arrs: tuple(jnp.copy(a) for a in arrs))
        else:
            self._step = jax.jit(
                lambda frame, qp: _device_step(
                    frame, qp, pad_h=self._pad_h, pad_w=self._pad_w, channels=channels
                )
            )
            self._step_p = jax.jit(
                lambda frame, qp, ry, ru, rv: _device_step_p(
                    frame, qp, ry, ru, rv,
                    pad_h=self._pad_h, pad_w=self._pad_w, channels=channels,
                ),
                donate_argnums=(2, 3, 4),
            )
        self._ref = None  # (recon_y, recon_u, recon_v) device arrays
        self._src = None  # device-resident source planes (delta-upload base)
        # frame_batch > 1: consecutive delta frames are grouped into one
        # scan-over-frames device step (one upload/execute/fetch per
        # GROUP). Trades up to frame_batch-1 frame-times of latency for
        # K-fold fewer host<->device round trips.
        # feed-forward scene-cut rate control: a full-frame change encoded
        # at the steady-state QP blows the VBV budget (reference holds VBV
        # at 1.5 frame-times); boost QP for that one frame — the decay
        # frames after it re-sharpen within a few hundred ms. 0 = off
        # (keeps delta-vs-full bit-exactness tests meaningful).
        self.scene_qp_boost = int(scene_qp_boost)
        self._prev_kind = "full"  # first frame is not a "scene cut"
        self.frame_batch = max(1, int(frame_batch))
        # scan executables compile for these group sizes only (greedy
        # grouping in _flush_batch); a half group beats singles when a
        # flush catches the accumulator mid-fill
        self._batch_sizes = tuple(
            sorted({self.frame_batch, max(2, self.frame_batch // 2)}, reverse=True)
        ) if self.frame_batch > 1 else ()
        # live policy cap on the effective group size (set_batch_cap):
        # <= frame_batch, snapped to a compiled scan size; the default
        # (== frame_batch) is byte- and behavior-identical to the
        # pre-policy encoder
        self._batch_cap = self.frame_batch
        self._batch_pend: list = []  # (rec, yb, ub, vb, idx) to group-dispatch
        ntx = self._pad_w // self._tile_w
        # total delta tiles in the frame (policy dirty_frac denominator)
        self._ntiles = (self._pad_h // 16) * ntx
        # delta bucket sizes: dirty-tile counts round up to one of these so
        # each resolution compiles a handful of scatter executables; frames
        # dirtier than the largest bucket use the full-upload path (the
        # delta would save little and each bucket costs a compile)
        self._delta_buckets = delta_buckets_for(width, height)
        # grouped-dispatch buckets: small sparse-update group, then the
        # area equivalents of the old 4- and 16-band group limits
        self.BATCH_BUCKETS = tuple(sorted({16, 4 * ntx, 16 * ntx} | (
            {self._delta_buckets[0]} if self._delta_buckets else set())))
        # uplink tile cache (CopyRect remaps, models/tilecache.py): dirty
        # tiles whose content already sits in the device slot pool become
        # 8-byte (slot -> position) remaps instead of pixel uploads.
        # SELKIES_TILE_CACHE sets the slot count ("0" disables; on
        # PCIe-local hosts the hash/memcmp cost can exceed the cheap
        # upload it saves — see docs/link_bytes.md).
        if tile_cache is None:
            tile_cache = int(os.environ.get("SELKIES_TILE_CACHE", "1024") or "0")
        self.tile_cache_slots = (
            int(tile_cache)
            if (self._prep is not None and self._delta_buckets) else 0
        )
        self._tcache = (
            TileCache(height, width, self._tile_w, self.tile_cache_slots)
            if self.tile_cache_slots > 0 else None
        )
        self._pool_d = None  # device slot-pool planes, allocated lazily
        # copy-pair bucket ladder: tiny (typing: no remaps) or full-delta
        # (scroll: most dirty tiles are remaps); every (bucket, cbucket)
        # combination is one compiled executable, so the ladder stays at 2.
        # Upload buckets gain a 0 rung: a pure-remap frame (scroll/window
        # steady state) ships ONLY the pair list — no pixel payload at all
        # over-budget delta attempts (maximized-window scrolls): dirty
        # counts up to 4x the delta cap still try the cache — remaps
        # don't upload pixels, so such frames usually fit after the
        # split; the bound keeps full-frame video off the hashing path
        ntiles_all = (self._pad_h // 16) * ntx
        self._tc_try_cap = (
            min(4 * self._delta_buckets[-1], ntiles_all) if self._delta_buckets else 0
        )
        self._copy_buckets = (
            tuple(sorted({16, self._delta_buckets[-1], self._tc_try_cap}))
            if self._delta_buckets else ()
        )
        self._up_buckets = (0,) + self._delta_buckets
        self._up_batch_buckets = (0,) + self.BATCH_BUCKETS
        self._step2_cache: dict = {}
        # a tile-cache step compiles for minutes on a TPU: there the first
        # frame that needs one starts its build on a background thread
        # and takes the full-frame path until it is built (same bytes)
        self._step2_off_stream = jax.default_backend() == "tpu"
        self._step2_built: dict = {}  # (kind, bucket, cbucket, take) -> fn
        self._step2_building: set = set()
        self._step2_lock = threading.Lock()
        self._ltr_probe: object = ()  # per-frame memo, see _classify
        self.link_bytes = LinkByteCounter()
        # last-seen tile-cache totals, for per-frame telemetry deltas
        self._tc_seen = (0, 0, 0)
        self._prev_frame: np.ndarray | None = None  # device-convert mode only
        # per-dispatch front-end stage scratch (submit-thread only):
        # convert/h2d accumulate inside the dispatch helpers, _t_disp0
        # records the wall clock immediately before the jitted step call
        # (the step/upload attribution boundary — see _Pending)
        self._t_conv_ms = 0.0
        self._t_h2d_ms = 0.0
        self._t_disp0 = 0.0
        self._inflight: deque = deque()
        self._pool = ThreadPoolExecutor(
            max_workers=max(2, self.pipeline_depth + 1),
            thread_name_prefix="h264-complete",
        )
        # Per-slot CAVLC fan-out pool: a delta GROUP's frames are
        # independent (separate slice NALs; the native packer releases
        # the GIL and its scratch is thread-local), so the group
        # completion spreads across cores instead of packing K frames
        # serially on one worker. Sized to cover every frame that can be
        # in flight at once — min(cores, bands x frame_batch x
        # pipeline_depth), the bands factor covering per-band slice
        # fan-out when this instance packs one band of a split frame —
        # SELKIES_PACK_WORKERS overrides.
        # Kept SEPARATE from self._pool: group coordinators block on
        # slot futures, and coordinators + leaves sharing one executor
        # can deadlock with every worker stuck coordinating.
        pack_workers = int(os.environ.get("SELKIES_PACK_WORKERS", "0") or 0)
        if pack_workers <= 0:
            pack_workers = min(
                os.cpu_count() or 4,
                max(2, self.bands * self.frame_batch * max(1, self.pipeline_depth)),
            )
        self._pack_pool = (
            ThreadPoolExecutor(max_workers=pack_workers,
                               thread_name_prefix="h264-pack")
            if self.frame_batch > 1 else None
        )
        self._upload_pool = ThreadPoolExecutor(
            max_workers=Y_CHUNKS + 2, thread_name_prefix="h264-upload",
        )
        mbh, mbw = self._pad_h // 16, self._pad_w // 16
        self._hdr_words_i = i_header_words(mbh, mbw)
        self._hdr_words_p = p_header_words(mbh, mbw)
        self._mbh, self._mbw = mbh, mbw
        # adaptive delta-downlink fetch: full var-buffer length and the
        # live slice hint (int16 words), grown/shrunk from recent frames.
        # The packed layout shrinks the live content 2-6x, which keeps
        # busy frames UNDER the small-fetch threshold where the 16-lane
        # layout escalates to a full-buffer fetch (measured on the bench
        # trace: typing needs 17.6k -> 8.9k words, i.e. a 164 KB full
        # fetch becomes the 32 KB small one). Still exactly TWO fetch
        # shapes (see PFX_SMALL).
        if self._entropy is not None:
            self._pfx_total = p_sparse_entropy_words(
                mbh, mbw, self._nscap, self._cap_delta,
                self._density is not None, self._bits_words,
                entropy_coder=self._coder)
        elif self._density is not None:
            self._pfx_total = p_sparse_packed_words(mbh, mbw, self._nscap, self._cap_delta)
        else:
            self._pfx_total = p_sparse_var_words(mbh, mbw, self._nscap, self._cap_delta)
        self._pfx_small = self.PFX_SMALL
        self._pfx_hint = min(self._pfx_small, self._pfx_total)
        self._pfx_recent: deque = deque(maxlen=8)
        # appended by completion workers and the submit thread; iterating
        # a deque during a concurrent append raises RuntimeError
        self._pfx_lock = threading.Lock()
        self._allskip: PFrameCoeffs | None = None
        # LTR scene cache (the alt-tab optimization): window switches
        # back to a remembered scene encode as a tiny delta against that
        # scene's long-term reference instead of a full-frame upload +
        # encode round trip — on this deployment's link that is the
        # difference between ~30 ms and ~400 ms for the switch frame.
        # Two slots, LRU replacement; each holds device copies of a
        # scene-cut frame's source+recon planes plus the host capture
        # for match detection. H.264 side: SPS max_num_ref_frames=3
        # (1 short-term + 2 long-term), the frame AFTER a scene cut
        # marks the cut frame long-term (MMCO 3 — it is still resident
        # short-term then, so no ref-list games are needed in between),
        # and restore frames select the slot via ref_pic_list
        # modification (write_slice_header ltr_ref/mark_ltr).
        self.ltr_scenes = bool(ltr_scenes) and self._prep is not None
        self._ltr_slots: list[dict | None] = [None, None]
        # MRU protection: new-scene candidates always target the slot
        # that was NOT most recently matched/stashed-by-restore, so
        # sustained full-frame motion (every frame becomes a candidate)
        # thrashes one slot and can never evict the last restored scene
        self._ltr_mru = 1
        self._ltr_candidate: dict | None = None
        # consecutive-full-frame run length: window switches arrive as
        # runs of 1-2 full frames, sustained motion (video playback) as
        # long runs — stash candidates only for the first two frames of
        # a run, so motion doesn't pay per-frame plane/capture copies or
        # thrash a slot with scenes that can never be restored mid-run
        self._full_run = 0
        self.ltr_restores = 0  # stats: scene switches served from cache
        # decoder-DPB mirror (short-term ref frame_nums, decode order):
        # slices that carry MMCO marking replace the sliding window
        # (8.2.5), so they must explicitly evict any short-terms that
        # accumulated while the DPB had slack — this list is what the
        # decoder's ST set contains, letting submit() compute the MMCO 1
        # diffs (see write_slice_header mmco_evict)
        self._dpb_st: list[int] = []
        self.frame_index = 0
        self._frames_since_idr = 0
        self._idr_pic_id = 0
        self._force_idr = True
        self.last_stats: FrameStats | None = None

    # -- live retune API (parity: set_video_bitrate path ends here) --

    def set_qp(self, qp: int) -> None:
        if not 0 <= qp <= 51:
            raise ValueError(f"qp {qp} out of range")
        self.qp = int(qp)

    def force_keyframe(self) -> None:
        self._force_idr = True

    @property
    def entropy_coder(self) -> str:
        """Active entropy backend ("cavlc"/"cabac") — telemetry stamps
        this onto every frame event (frame_done)."""
        return self._coder

    @property
    def h264_profile(self) -> str:
        """Profile the SPS declares ("baseline"/"main") — the WebRTC
        plane's fmtp profile-level-id must match it (sdp.py)."""
        return "main" if self._coder == "cabac" else "baseline"

    # -- policy actuation (selkies_tpu/policy): runtime-safe retunes ---

    def set_tile_cache(self, enabled: bool) -> bool:
        """Runtime uplink tile-cache toggle (policy actuation); returns
        True when the state changed. Byte-safe at any frame boundary:
        a remap reproduces the exact pixels an upload would (PR 1's
        bit-exactness contract), so the encoded stream is identical
        with the cache on or off. Only togglable when the cache
        machinery was built (slots > 0 at construction — the compiled
        scatter ladder and device pool shapes are sized then).
        Re-enabling starts from an EMPTY cache: while classification
        bypassed it the device pool went stale, and a stale host entry
        would remap garbage pixels."""
        enabled = bool(enabled)
        if self.tile_cache_slots <= 0 or not self._delta_buckets:
            return False
        if enabled == (self._tcache is not None):
            return False
        # pending group payloads were split for the OLD mode (with the
        # cache the tuple carries pool_dst/pairs): dispatch them first
        self._flush_batch()
        self._tcache = (
            TileCache(self.height, self.width, self._tile_w,
                      self.tile_cache_slots)
            if enabled else None)
        self._pool_d = None
        return True

    def set_batch_cap(self, cap: int) -> bool:
        """Cap the effective grouped-dispatch size (policy actuation);
        returns True when it changed. The cap snaps DOWN to an
        already-compiled scan size (1, frame_batch//2, frame_batch —
        _flush_batch's greedy ladder), so no policy flap can trigger a
        group-scan compile. Byte-safe at any frame boundary: grouped
        and single delta dispatches are byte-identical
        (tests/test_sparse_native_pack.py). Cap 1 dispatches every
        delta immediately — the latency posture: a frame never waits
        for group members that are whole capture intervals away."""
        cap = max(1, min(int(cap), self.frame_batch))
        sizes = (1,) + tuple(self._batch_sizes)
        cap = max(s for s in sizes if s <= cap)
        if cap == self._batch_cap:
            return False
        self._batch_cap = cap
        if len(self._batch_pend) >= cap:
            self._flush_batch()
        return True

    def retune_entropy(self, device_entropy: bool | None = None,
                       bits_min_mbs: int | None = None,
                       entropy_coder: str | None = None) -> bool:
        """Re-resolve the device-entropy downlink decision at runtime
        (policy actuation); returns True when anything changed. Bytes
        are identical either way (tests/test_device_entropy_sparse.py)
        — what changes is the DOWNLINK: busy frames ship final slice
        bits instead of multi-MB coefficient rows (PR 7). Expensive:
        the delta-scatter partials close over the entropy consts, so
        they are rebuilt and recompile on next use — the policy
        engine's dwell is what keeps this off the flap path. The
        caller must have NO frames in flight (the in-flight frames'
        completion reads the downlink sizing being replaced); the
        policy actuator drains the pipeline first.

        entropy_coder="cavlc"/"cabac" additionally switches the stream's
        entropy backend. Unlike the downlink knobs this changes the
        BITSTREAM (entropy_coding_mode_flag is PPS-scoped): new SPS/PPS
        are emitted and an IDR is forced so the decoder reconfigures at
        a clean boundary."""
        if self._prep is None:  # device-convert mode has no entropy path
            return False
        coder = self._coder if entropy_coder is None else (
            entropy_coder_default(entropy_coder))
        de, bm, bw, ent = resolve_entropy(
            self._mbh * self._mbw, device_entropy, bits_min_mbs,
            entropy_coder=coder)
        if (de == self.device_entropy and bm == self.bits_min_mbs
                and coder == self._coder):
            return False
        if coder != self._coder:
            if self._inflight or self._batch_pend:
                raise RuntimeError(
                    "retune_entropy with frames in flight; flush first")
            from selkies_tpu.monitoring import jitprof

            jitprof.mark("actuation", "entropy-coder-switch")
            self._coder = coder
            self.params = StreamParams(
                width=self.width, height=self.height, qp=self.qp,
                fps=self.fps, entropy_coder=coder)
            self._headers = write_sps(self.params) + write_pps(self.params)
            self._step_pb = jax.jit(
                _p_toks_step_chunked if coder == "cabac"
                else _p_bits_step_chunked,
                donate_argnums=(7, 8, 9))
            self.device_entropy, self.bits_min_mbs = de, bm
            self._bits_words, self._entropy = bw, ent
            self._rebuild_entropy_partials()
            # decoder must see the new PPS before any slice that uses
            # the other coder: restart the GOP
            self.force_keyframe()
            return True
        if ent == self._entropy and bw == self._bits_words:
            # threshold bookkeeping with the device coder disabled (or
            # consts unchanged): no jitted partial closes over it, so
            # nothing to rebuild and no flush needed
            self.device_entropy, self.bits_min_mbs = de, bm
            return True
        if self._inflight or self._batch_pend:
            raise RuntimeError(
                "retune_entropy with frames in flight; flush first")
        # recompile sentinel (monitoring/jitprof.py): the partials below
        # recompile lazily on their next call — attribute those compiles
        # to this actuation, wherever/whenever they land
        from selkies_tpu.monitoring import jitprof

        jitprof.mark("actuation", "entropy-retune")
        self.device_entropy, self.bits_min_mbs = de, bm
        self._bits_words, self._entropy = bw, ent
        self._rebuild_entropy_partials()
        return True

    def _rebuild_entropy_partials(self) -> None:
        """Rebuild the jitted delta-step partials and the downlink
        sizing after the entropy consts changed (retune_entropy, both
        the downlink-knob and the coder-switch paths)."""
        _consts = dict(nscap=self._nscap, cap=self._cap_delta,
                       tile_w=self._tile_w, density=self._density,
                       entropy=self._entropy)
        self._step_scatter_p = jax.jit(
            partial(_p_scatter_step, **_consts),
            donate_argnums=(2, 3, 4, 5, 6, 7))
        self._step_scatter_pk = jax.jit(
            partial(_p_scatter_multi_step, **_consts),
            donate_argnums=(3, 4, 5, 6, 7, 8))
        self._step_scatter_ltr = jax.jit(partial(_p_scatter_step, **_consts))
        self._step2_cache.clear()
        # downlink sizing tracks the fused-buffer layout
        if self._entropy is not None:
            self._pfx_total = p_sparse_entropy_words(
                self._mbh, self._mbw, self._nscap, self._cap_delta,
                self._density is not None, self._bits_words,
                entropy_coder=self._coder)
        elif self._density is not None:
            self._pfx_total = p_sparse_packed_words(
                self._mbh, self._mbw, self._nscap, self._cap_delta)
        else:
            self._pfx_total = p_sparse_var_words(
                self._mbh, self._mbw, self._nscap, self._cap_delta)
        with self._pfx_lock:
            self._pfx_recent.clear()
            self._pfx_hint = min(self._pfx_small, self._pfx_total)

    # -- frame classification (static / delta / full upload) -----------

    def _classify(self, frame: np.ndarray, damage=None):
        """-> ("static" | "delta" | "full", payload).

        Compares against the previous capture (FramePrep's per-tile
        memcmp when host conversion is on; tiles are 16 rows x _tile_w
        cols). "static": byte-identical — the dominant remote-desktop
        case, zero device work. "delta": few dirty tiles and the device
        holds resident source planes — upload only the changed tiles.
        "full": everything else. The previous-frame state advances on
        every call; that is safe because any encode failure nulls
        self._ref/_src, forcing a full-upload IDR that bypasses the
        static and delta paths.

        Sets _ltr_probe when the over-budget branch already ran the
        (expensive) scene-cache match, so submit() reuses it instead of
        recomputing; () means "not computed this frame".

        payload: dirty indices (band*1024 + tile, int32) without the
        tile cache, or the cache's (up_idx, pool_dst, pairs) split with
        it. With the cache the dirty-count gate moves to the POST-REMAP
        upload count: a maximized-window scroll dirties far more tiles
        than the delta buckets hold, but nearly all of them are
        pool-resident, so the frame still fits after remapping (split
        aborts without state change when it doesn't — the big win the
        cache exists for). The attempt is bounded at _tc_try_cap dirty
        tiles so sustained full-frame video skips the hashing.

        ``damage``: optional capture-layer dirty-rect hints (superset
        contract — see FramePrep.scan): the fused scan is bounded to
        their band/tile box, so an idle/typing frame stops paying a
        full-frame memcmp for a cursor blink. The scan also emits the
        tile-cache content hashes in the same pass (want_hashes), which
        probe/split consume instead of re-reading the dirty tiles."""
        self._ltr_probe = ()
        if self._prep is None:
            if self._prev_frame is None or self._prev_frame.shape != frame.shape:
                self._prev_frame = frame.copy()
                return "full", None
            if np.array_equal(self._prev_frame, frame):
                return "static", None
            np.copyto(self._prev_frame, frame)
            return "full", None
        res = self._prep.scan(frame, self._tile_w, damage=damage,
                              want_hashes=self._tcache is not None)
        if res is None:
            return "full", None
        tiles = res.tiles
        if not tiles.any():
            return "static", None
        if self._src is None or not self._delta_buckets:
            return "full", None
        band_i, tile_i = np.nonzero(tiles)
        cap = self._delta_buckets[-1]
        if len(band_i) > (self._tc_try_cap if self._tcache is not None else cap):
            return "full", None
        idx = (band_i * 1024 + tile_i).astype(np.int32)
        if self._tcache is None:
            return "delta", idx
        if len(band_i) > cap:
            if self.ltr_scenes:
                # a remembered scene covering this frame: the LTR
                # restore emits ~50x fewer slice bits than a remap-delta
                # predicting from the previous (entirely different)
                # frame — let submit()'s scene-cache path take it. The
                # probe is memoized for submit (it is a full per-tile
                # compare).
                self._ltr_probe = self._ltr_match(frame)
                if self._ltr_probe is not None:
                    return "full", None
            # sampled membership probe: scrolled content is pool-
            # resident after its seed frame, video content never is —
            # skip the full hash/split attempt when it cannot pay
            # (sustained motion then reads ~8 precomputed hashes per
            # frame, and the seed hook is additionally bounded by
            # _full_run)
            if self._tcache.probe(frame, idx, hashes=res.hashes) < 0.5:
                return "full", ("seed", idx, res.hashes)
        payload = self._tcache.split(frame, idx, max_up=cap, hashes=res.hashes)
        if payload is None:
            # too many genuinely-new tiles: full upload — but remember
            # the dirty set (and its fused-scan hashes) so submit() can
            # seed the pool from the freshly-resident planes without
            # re-reading the tiles (a sustained over-budget scroll then
            # fits from its second frame on)
            return "full", ("seed", idx, res.hashes)
        return "delta", payload

    def _emit_classify_telemetry(self, kind: str, payload) -> None:
        """Fold one frame's classification into the telemetry bus: per-tile
        cache hit/miss/evict deltas and the frame's upload class (a delta
        whose upload list is empty is a pure-remap frame — the tile
        cache's headline outcome). Called only when telemetry is enabled;
        the frame id rides the ContextVar set by the pipeline's span."""
        tc = self._tcache
        if tc is not None:
            hits, misses, evs = tc.hits, tc.misses, tc.evictions
            dh, dm, de = (hits - self._tc_seen[0], misses - self._tc_seen[1],
                          evs - self._tc_seen[2])
            self._tc_seen = (hits, misses, evs)
            if dh:
                telemetry.count("selkies_tile_cache_tiles_total", dh,
                                result="hit")
            if dm:
                telemetry.count("selkies_tile_cache_tiles_total", dm,
                                result="miss")
            if de:
                telemetry.count("selkies_tile_cache_tiles_total", de,
                                result="evict")
            if (kind == "delta" and isinstance(payload, tuple)
                    and len(payload[0]) == 0):
                kind = "remap_only"
        telemetry.count("selkies_tile_cache_frames_total", kind=kind)

    def _allskip_slice(self, frame_num: int, mark_ltr: int | None = None,
                       mmco_evict: tuple = ()) -> bytes:
        """P slice with every MB P_Skip: recon == ref exactly (zero MV,
        full-pel, no residual), so the device reference stays valid."""
        if self._allskip is None:
            mbh, mbw = self._pad_h // 16, self._pad_w // 16
            self._allskip = PFrameCoeffs(
                mvs=np.zeros((mbh, mbw, 2), np.int32),
                skip=np.ones((mbh, mbw), bool),
                luma_ac=np.zeros((mbh, mbw, 4, 4, 4, 4), np.int32),
                chroma_dc=np.zeros((mbh, mbw, 2, 2, 2), np.int32),
                chroma_ac=np.zeros((mbh, mbw, 2, 2, 2, 4, 4), np.int32),
                qp=self.qp,
            )
        self._allskip.qp = self.qp
        if self._coder == "cabac":
            # the PPS pins entropy_coding_mode_flag for the whole stream
            return pack_slice_p_cabac(self._allskip, self.params, frame_num,
                                      mark_ltr=mark_ltr, mmco_evict=mmco_evict)
        return pack_slice_p_fast(self._allskip, self.params, frame_num=frame_num,
                                 mark_ltr=mark_ltr, mmco_evict=mmco_evict)

    # -- encoding --

    def _put_chunked(self, y, u, v):
        """Full-frame upload as Y_CHUNKS+2 concurrent transfers.
        Explicit device_put (not passing numpy into the jit) keeps each
        transfer an async enqueue instead of a synchronous copy inside
        the dispatch."""
        rows = y.shape[0] // Y_CHUNKS
        parts = [y[i * rows : (i + 1) * rows] if i < Y_CHUNKS - 1
                 else y[(Y_CHUNKS - 1) * rows :] for i in range(Y_CHUNKS)]
        parts += [u, v]
        self.link_bytes.add("up_full", sum(p.nbytes for p in parts))
        t0 = time.perf_counter()
        with tracer.span("h2d"):
            out = list(self._upload_pool.map(jax.device_put, parts))
        self._t_h2d_ms += (time.perf_counter() - t0) * 1e3
        return out

    def _convert_timed(self, frame: np.ndarray):
        t0 = time.perf_counter()
        with tracer.span("convert"):
            planes = self._prep.convert(frame)
        self._t_conv_ms += (time.perf_counter() - t0) * 1e3
        return planes

    def _convert_tiles_timed(self, frame: np.ndarray, idx, tile_w: int):
        t0 = time.perf_counter()
        with tracer.span("convert"):
            out = self._prep.convert_tiles(frame, idx, tile_w)
        self._t_conv_ms += (time.perf_counter() - t0) * 1e3
        return out

    def _put_timed(self, arr):
        t0 = time.perf_counter()
        with tracer.span("h2d"):
            out = jax.device_put(arr)
        self._t_h2d_ms += (time.perf_counter() - t0) * 1e3
        return out

    def _run_step_i(self, frame: np.ndarray):
        if self._prep is not None:
            parts = self._put_chunked(*self._convert_timed(frame))
            self._t_disp0 = time.perf_counter()
            *out, y, u, v = self._step(*parts, np.int32(self.qp))
            # keep the joined planes resident: they are the delta base
            # for the next frame (the I step does not donate them)
            self._src = (y, u, v)
            return out
        self.link_bytes.add("up_full", frame.nbytes)
        parts = self._put_timed(frame)
        self._t_disp0 = time.perf_counter()
        return self._step(parts, np.int32(self.qp))

    def _run_step_p(self, frame: np.ndarray):
        if self._prep is not None:
            parts = self._put_chunked(*self._convert_timed(frame))
            self._t_disp0 = time.perf_counter()
            if self.device_entropy:
                prefix_d, words_d, hdr_d, buf_d, ry, ru, rv, y, u, v = self._step_pb(
                    *parts, np.int32(self.qp), *self._ref
                )
                self._src = (y, u, v)
                return ("pb", prefix_d, words_d, hdr_d, buf_d, ry, ru, rv)
            out = self._step_p(*parts, np.int32(self.qp), *self._ref)
            self._src = (out[5], out[6], out[7])
            # (kind, prefix, words, hdr, buf, recon_y, recon_u, recon_v)
            return ("p", out[0], None, None, out[1], out[2], out[3], out[4])
        self.link_bytes.add("up_full", frame.nbytes)
        parts = self._put_timed(frame)
        self._t_disp0 = time.perf_counter()
        out = self._step_p(parts, np.int32(self.qp), *self._ref)
        return ("p", out[0], None, None, out[1], out[2], out[3], out[4])

    @staticmethod
    def _pack_tiles(yb, ub, vb, idx, bucket: int) -> np.ndarray:
        """Pad to `bucket` tiles (repeating the last tile — rewriting a
        tile is idempotent) and pack into one upload buffer:
        [idx int32 bytes (band*1024 + tile)] ++ yb ++ ub ++ vb
        (see _unpack_delta; element width is _tile_w luma cols)."""
        k = len(idx)
        if k < bucket:
            reps = bucket - k
            yb = np.concatenate([yb, np.repeat(yb[-1:], reps, 0)])
            ub = np.concatenate([ub, np.repeat(ub[-1:], reps, 0)])
            vb = np.concatenate([vb, np.repeat(vb[-1:], reps, 0)])
            idx = np.concatenate([idx, np.full(reps, idx[-1], np.int32)])
        return np.concatenate([idx.view(np.uint8), yb.ravel(), ub.ravel(), vb.ravel()])

    # -- tile cache (CopyRect remaps) -----------------------------------

    def _get_pool(self):
        """Device tile slot pool (slots + 1 rows; last row is scratch)."""
        if self._pool_d is None:
            s, tw = self.tile_cache_slots, self._tile_w
            self._pool_d = (
                jnp.zeros((s + 1, 16, tw), jnp.uint8),
                jnp.zeros((s + 1, 8, tw // 2), jnp.uint8),
                jnp.zeros((s + 1, 8, tw // 2), jnp.uint8),
            )
        return self._pool_d

    def _reset_tile_cache(self) -> None:
        """Host index and device pool must drop together: after a failed
        or abandoned dispatch the pool contents are unknowable, and a
        stale host entry would remap garbage pixels."""
        if self._tcache is not None:
            self._tcache.reset()
        self._pool_d = None

    def _get_step2(self, kind: str, bucket: int, cbucket: int):
        """Compiled tile-cache scatter step for one (bucket, cbucket)
        combination. kinds: "p"/"i" donate src+pool(+refs); "ltr" donates
        only the pool (the stash planes must survive); "pk" is the
        grouped scan."""
        key = (kind, bucket, cbucket)
        fn = self._step2_cache.get(key)
        if fn is None:
            consts = dict(tile_w=self._tile_w, bucket=bucket, cbucket=cbucket)
            pconsts = dict(nscap=self._nscap, cap=self._cap_delta,
                           density=self._density, entropy=self._entropy,
                           **consts)
            if kind == "p":
                fn = jax.jit(partial(_p_scatter_step2, **pconsts),
                             donate_argnums=(2, 3, 4, 5, 6, 7, 8, 9, 10))
            elif kind == "ltr":
                fn = jax.jit(partial(_p_scatter_step2, **pconsts),
                             donate_argnums=(5, 6, 7))
            elif kind == "i":
                fn = jax.jit(partial(_i_scatter_step2, **consts),
                             donate_argnums=(2, 3, 4, 5, 6, 7))
            elif kind == "seed":
                # gathers from the resident planes (NOT donated — they
                # are the next frame's delta base); pool donated
                fn = jax.jit(
                    partial(_pool_seed_step, tile_w=self._tile_w, sbucket=cbucket),
                    donate_argnums=(4, 5, 6))
            else:  # "pk": grouped scan
                fn = jax.jit(partial(_p_scatter_multi_step2, **pconsts),
                             donate_argnums=(3, 4, 5, 6, 7, 8, 9, 10, 11))
            self._step2_cache[key] = fn
        return fn

    def _step2_ready(self, kind: str, bucket: int, cbucket: int, args,
                     take: int = 0) -> bool:
        """Whether the tile-cache step for this key runs without compiling
        in the stream. Where steps build off the stream and this one is
        not built yet, start its build (once) from the shapes of the
        arguments `args()` returns, and answer False: the caller takes a
        path that is already built."""
        fn = self._get_step2(kind, bucket, cbucket)
        key = (kind, bucket, cbucket, take)
        with self._step2_lock:
            if self._step2_built.get(key) is fn:
                return True
            if key in self._step2_building:
                return False
            self._step2_building.add(key)
        specs = [_lowering_shape(a) for a in args()]

        def build() -> None:
            fn.lower(*specs).compile()
            with self._step2_lock:
                self._step2_built[key] = fn
                self._step2_building.discard(key)

        # a failed build stays "building": its frames keep the full path
        threading.Thread(target=build, name=f"step2-build-{kind}",
                         daemon=True).start()
        return False

    def _packed2_shape(self, bucket: int, cbucket: int) -> tuple:
        """Shape of _pack_tiles2's buffer for one (bucket, cbucket)."""
        tw, e = self._tile_w, np.zeros(0, np.int32)
        return self._pack_tiles2(
            np.zeros((0, 16, tw), np.uint8), np.zeros((0, 8, tw // 2), np.uint8),
            np.zeros((0, 8, tw // 2), np.uint8), e, e, np.zeros((0, 2), np.int32),
            bucket, cbucket).shape

    def _delta_step_ready(self, payload, idr: bool) -> bool:
        """_step2_ready for the step a tile-cache delta frame dispatches
        alone (_run_step_delta, or a group of one in _flush_batch)."""
        if not self._step2_off_stream:
            return True
        up_idx, _pool_dst, pairs = payload
        bucket = next(b for b in self._up_buckets if b >= len(up_idx))
        cbucket = next(cb for cb in self._copy_buckets if cb >= len(pairs))

        def args():
            return (np.zeros(self._packed2_shape(bucket, cbucket), np.uint8),
                    np.int32(self.qp), *self._src, *self._get_pool(),
                    *(() if idr else self._ref))

        return self._step2_ready("i" if idr else "p", bucket, cbucket, args)

    def _group_step_ready(self, group) -> bool:
        """_step2_ready for _flush_batch's grouped scan over `group`."""
        if not self._step2_off_stream:
            return True
        bucket = next(b for b in self._up_batch_buckets
                      if b >= max(len(g[4]) for g in group))
        cbucket = next(cb for cb in self._copy_buckets
                       if cb >= max(len(g[6]) for g in group))

        def args():
            n, half = self._packed2_shape(bucket, cbucket)[0], len(group) // 2
            return (np.zeros((half, n), np.uint8),
                    np.zeros((len(group) - half, n), np.uint8),
                    np.zeros(len(group), np.int32),
                    *self._src, *self._get_pool(), *self._ref)

        return self._step2_ready("pk", bucket, cbucket, args, take=len(group))

    def _seed_pool(self, frame: np.ndarray, idx: np.ndarray,
                   hashes: np.ndarray | None = None) -> None:
        """After an over-budget full upload: commit the dirty tiles to
        the host cache and fill their pool slots device-side by
        gathering from the freshly-resident source planes — only the
        (slot, idx) list crosses the link. `hashes` is the fused scan's
        content-hash array for this frame's dirty tiles (the classify
        pass already computed them — re-hashing here would repeat the
        exact redundant read the fused front-end removed)."""
        up_idx, pool_dst, _pairs = self._tcache.split(frame, idx, hashes=hashes)
        self._fill_pool(pool_dst, up_idx)

    def _fill_pool(self, pool_dst: np.ndarray, up_idx: np.ndarray,
                   sbucket: int | None = None) -> None:
        """Pool slots `pool_dst` <- tiles `up_idx` of the resident source
        planes, gathered device-side (padded to `sbucket` pairs, by
        default the smallest copy bucket that holds them)."""
        if not len(up_idx):
            return
        if sbucket is None:
            sbucket = next(cb for cb in self._copy_buckets if cb >= len(up_idx))
        pr = np.zeros((sbucket, 2), np.int32)
        pr[:, 0] = self.tile_cache_slots  # scratch padding
        pr[: len(up_idx), 0] = pool_dst
        pr[: len(up_idx), 1] = up_idx
        self.link_bytes.add("up_seed", pr.nbytes)
        pool2 = self._get_step2("seed", 0, sbucket)(
            jax.device_put(pr), *self._src, *self._get_pool())
        self._pool_d = tuple(pool2)

    def _pack_tiles2(self, yb, ub, vb, up_idx, pool_dst, pairs,
                     bucket: int, cbucket: int) -> np.ndarray:
        """Tile-cache upload buffer (see _unpack_delta2): uploads pad
        with idx -1 (identity writes) targeting the scratch pool row;
        copy pairs pad with src -1."""
        tw = self._tile_w
        k = len(up_idx)
        pad = bucket - k
        idxp = np.concatenate([up_idx, np.full(pad, -1, np.int32)])
        dstp = np.concatenate([pool_dst, np.full(pad, self.tile_cache_slots, np.int32)])
        if pad:
            zy = np.zeros((pad, 16, tw), np.uint8)
            zc = np.zeros((pad, 8, tw // 2), np.uint8)
            yb = np.concatenate([yb, zy]) if k else zy
            ub = np.concatenate([ub, zc]) if k else zc
            vb = np.concatenate([vb, zc]) if k else zc
        pr = np.full((cbucket, 2), -1, np.int32)
        pr[:, 1] = 0
        if len(pairs):
            pr[: len(pairs)] = pairs
        return np.concatenate([
            idxp.view(np.uint8), dstp.view(np.uint8), pr.reshape(-1).view(np.uint8),
            yb.ravel(), ub.ravel(), vb.ravel(),
        ])

    def _pack_payload2(self, frame: np.ndarray, payload):
        """Cache split result -> (packed, bucket, cbucket): convert the
        upload tiles and build the packed buffer (split itself already
        ran — in _classify for delta frames, or at the call site for
        LTR restores)."""
        up_idx, pool_dst, pairs = payload
        bucket = next(b for b in self._up_buckets if b >= len(up_idx))
        cbucket = next(cb for cb in self._copy_buckets if cb >= len(pairs))
        yb, ub, vb = self._convert_tiles_timed(frame, up_idx, self._tile_w)
        packed = self._pack_tiles2(yb, ub, vb, up_idx, pool_dst, pairs, bucket, cbucket)
        return packed, bucket, cbucket

    def _run_step_delta(self, frame: np.ndarray, idx, idr: bool):
        """Single-frame delta: upload only the dirty tiles (remapping
        cache-resident ones); scatter+encode on device. `idx` is the
        _classify payload: dirty indices, or the cache's split triple.
        Returns (prefix_d, hdr_d, buf_d, recon triple)."""
        qp = np.int32(self.qp)
        if self._tcache is not None:
            packed, bucket, cbucket = self._pack_payload2(frame, idx)
            self.link_bytes.add("up_delta", packed.nbytes)
            packed_d = self._put_timed(packed)
            pool = self._get_pool()
            self._t_disp0 = time.perf_counter()
            if idr:
                prefix_d, buf_d, ry, ru, rv, sy, su, sv, *pool2 = self._get_step2(
                    "i", bucket, cbucket)(packed_d, qp, *self._src, *pool)
                hdr_d = None
            else:
                prefix_d, hdr_d, buf_d, ry, ru, rv, sy, su, sv, *pool2 = self._get_step2(
                    "p", bucket, cbucket)(packed_d, qp, *self._src, *pool, *self._ref)
            self._pool_d = tuple(pool2)
            self._src = (sy, su, sv)
            return prefix_d, hdr_d, buf_d, ry, ru, rv
        bucket = next(b for b in self._delta_buckets if b >= len(idx))
        yb, ub, vb = self._convert_tiles_timed(frame, idx, self._tile_w)
        packed = self._pack_tiles(yb, ub, vb, idx, bucket)
        self.link_bytes.add("up_delta", packed.nbytes)
        packed_d = self._put_timed(packed)
        self._t_disp0 = time.perf_counter()
        if idr:
            prefix_d, buf_d, ry, ru, rv, sy, su, sv = self._step_scatter_i(
                packed_d, qp, *self._src
            )
            hdr_d = None
        else:
            prefix_d, hdr_d, buf_d, ry, ru, rv, sy, su, sv = self._step_scatter_p(
                packed_d, qp, *self._src, *self._ref
            )
        # reassign IMMEDIATELY: the old src (and refs on P) were donated
        self._src = (sy, su, sv)
        return prefix_d, hdr_d, buf_d, ry, ru, rv

    # -- LTR scene cache (alt-tab restore) ------------------------------

    def _dirty_vs(self, frame: np.ndarray, cap: np.ndarray) -> np.ndarray:
        """Per-tile inequality of two captures in FramePrep's geometry
        (16-row bands x _tile_w luma cols). Runs only on scene cuts."""
        d = (frame != cap).any(axis=2)
        h, w = d.shape
        pb = np.zeros((self._pad_h, self._pad_w), bool)
        pb[:h, :w] = d
        nb, nt = self._pad_h // 16, self._pad_w // self._tile_w
        return pb.reshape(nb, 16, nt, self._tile_w).any(axis=(1, 3))

    @staticmethod
    def _ltr_quick_reject(frame: np.ndarray, cap: np.ndarray) -> bool:
        """Sampled pre-filter (~8 K pixels) so sustained full-frame motion
        (video playback) rejects candidate scenes in microseconds instead
        of paying the full per-tile compare every frame. A genuine scene
        restore differs only in its dirty region (bounded by the delta
        buckets at ~25% of tiles), so a >35% sampled mismatch can never
        be a match."""
        s1, s2 = frame[8::48, 16::128], cap[8::48, 16::128]
        return float((s1 != s2).any(axis=-1).mean()) > 0.35

    def _ltr_match(self, frame: np.ndarray):
        """-> (slot, dirty_idx) of the best-matching remembered scene, or
        None when no slot matches within the delta-bucket budget."""
        if not self._delta_buckets:
            return None
        best = None
        for j, s in enumerate(self._ltr_slots):
            if s is None or s["cap"].shape != frame.shape:
                continue
            if self._ltr_quick_reject(frame, s["cap"]):
                continue
            tiles = self._dirty_vs(frame, s["cap"])
            band_i, tile_i = np.nonzero(tiles)
            if len(band_i) > self._delta_buckets[-1]:
                continue
            if best is None or len(band_i) < len(best[1]):
                best = (j, (band_i * 1024 + tile_i).astype(np.int32))
        if best is None:
            return None
        j, idx = best
        if len(idx) == 0:
            # capture identical to the stash: rewrite tile 0 with its own
            # content (idempotent) so the scatter step has a real input
            idx = np.zeros(1, np.int32)
        return j, idx

    def _run_step_ltr(self, frame: np.ndarray, idx: np.ndarray, stash: dict):
        """Scene restore: scatter the (few) tiles that differ from the
        remembered scene into a fresh copy of its source planes and
        encode against its recon — the stash planes survive untouched.
        Restore tiles hit the tile cache like any delta (the content a
        window switch re-exposes is often pool-resident)."""
        if self._tcache is not None:
            packed, bucket, cbucket = self._pack_payload2(
                frame, self._tcache.split(frame, idx))
            self.link_bytes.add("up_ltr", packed.nbytes)
            packed_d = self._put_timed(packed)
            pool = self._get_pool()
            self._t_disp0 = time.perf_counter()
            prefix_d, hdr_d, buf_d, ry, ru, rv, sy, su, sv, *pool2 = self._get_step2(
                "ltr", bucket, cbucket)(
                    packed_d, np.int32(self.qp), *stash["src"], *pool,
                    *stash["ref"])
            self._pool_d = tuple(pool2)
            self._src = (sy, su, sv)
            return prefix_d, hdr_d, buf_d, ry, ru, rv
        bucket = next(b for b in self._delta_buckets if b >= len(idx))
        yb, ub, vb = self._convert_tiles_timed(frame, idx, self._tile_w)
        packed = self._pack_tiles(yb, ub, vb, idx, bucket)
        self.link_bytes.add("up_ltr", packed.nbytes)
        packed_d = self._put_timed(packed)
        self._t_disp0 = time.perf_counter()
        prefix_d, hdr_d, buf_d, ry, ru, rv, sy, su, sv = self._step_scatter_ltr(
            packed_d, np.int32(self.qp), *stash["src"], *stash["ref"]
        )
        self._src = (sy, su, sv)
        return prefix_d, hdr_d, buf_d, ry, ru, rv

    def _stash_candidate(self, frame: np.ndarray, slot: int) -> None:
        """Snapshot this scene-cut frame as the pending LTR candidate.
        Device copies are dispatched NOW (before any later step donates
        the planes); the slot commits when the next frame emits MMCO 3."""
        if self._src is None or self._ref is None:
            return
        copies = self._copy_planes(*self._src, *self._ref)
        self._ltr_candidate = {
            "slot": int(slot),
            "src": tuple(copies[:3]),
            "ref": tuple(copies[3:]),
            "cap": np.array(frame, copy=True),
        }

    # -- grouped delta dispatch (frame_batch > 1) -----------------------


    def _flush_batch(self) -> None:
        """Dispatch the pending delta frames (if any) as device steps.

        Greedy grouping: full groups of frame_batch, then a half group,
        then singles — only those scan sizes ever compile. Must run
        before any other dispatch so device-side src/ref state advances
        in frame order."""
        pend = self._batch_pend
        if not pend:
            return
        self._batch_pend = []
        tc = self._tcache is not None
        try:
            i = 0
            while i < len(pend):
                t_d0 = time.perf_counter()
                take = next((s for s in self._batch_sizes if len(pend) - i >= s), 1)
                if take > 1 and tc and not self._group_step_ready(pend[i : i + take]):
                    take = 1  # each member's single step is built
                group = pend[i : i + take]
                i += take
                if take == 1:
                    rec, yb, ub, vb, idx, pool_dst, pairs = group[0]
                    self._t_h2d_ms = 0.0
                    if tc:
                        bucket = next(b for b in self._up_buckets if b >= len(idx))
                        cbucket = next(cb for cb in self._copy_buckets if cb >= len(pairs))
                        packed = self._pack_tiles2(yb, ub, vb, idx, pool_dst, pairs,
                                                   bucket, cbucket)
                        self.link_bytes.add("up_delta", packed.nbytes)
                        packed_d = self._put_timed(packed)
                        pool = self._get_pool()
                        self._t_disp0 = time.perf_counter()
                        (prefix_d, hdr_d, buf_d, ry, ru, rv, sy, su, sv,
                         *pool2) = self._get_step2("p", bucket, cbucket)(
                            packed_d, np.int32(rec.qp),
                            *self._src, *pool, *self._ref)
                        self._pool_d = tuple(pool2)
                    else:
                        bucket = next(b for b in self._delta_buckets if b >= len(idx))
                        packed = self._pack_tiles(yb, ub, vb, idx, bucket)
                        self.link_bytes.add("up_delta", packed.nbytes)
                        packed_d = self._put_timed(packed)
                        self._t_disp0 = time.perf_counter()
                        prefix_d, hdr_d, buf_d, ry, ru, rv, sy, su, sv = self._step_scatter_p(
                            packed_d, np.int32(rec.qp), *self._src, *self._ref
                        )
                    self._src, self._ref = (sy, su, sv), (ry, ru, rv)
                    rec.prefix_d, rec.hdr_d, rec.buf_d = prefix_d, hdr_d, buf_d
                    rec.pfx_slice_d = self._pfx_slice(prefix_d)
                    rec.batch_slot = -1
                    # upload/step boundary: t_disp is the instant BEFORE the
                    # step dispatch call, so a blocking dispatch reads as
                    # device step time (see _Pending); up_ms is the host
                    # front-end (classify + convert at submit, h2d + pack
                    # glue here)
                    rec.t_disp = self._t_disp0
                    rec.h2d_ms += self._t_h2d_ms
                    rec.up_ms = (rec.classify_ms + rec.convert_ms
                                 + (rec.t_disp - t_d0) * 1e3)
                    rec.future = self._pool.submit(self._complete_work, rec)
                    continue
                qps = np.array([g[0].qp for g in group], np.int32)
                if tc:
                    bucket = next(
                        b for b in self._up_batch_buckets
                        if b >= max(len(g[4]) for g in group)
                    )
                    cbucket = next(
                        cb for cb in self._copy_buckets
                        if cb >= max(len(g[6]) for g in group)
                    )
                    packed = np.stack([
                        self._pack_tiles2(yb, ub, vb, idx, pool_dst, pairs,
                                          bucket, cbucket)
                        for _, yb, ub, vb, idx, pool_dst, pairs in group
                    ])
                else:
                    bucket = next(
                        b for b in self.BATCH_BUCKETS
                        if b >= max(len(g[4]) for g in group)
                    )
                    packed = np.stack([
                        self._pack_tiles(yb, ub, vb, idx, bucket)
                        for _, yb, ub, vb, idx, _pd, _pr in group
                    ])
                self.link_bytes.add("up_delta", packed.nbytes)
                # two concurrent half uploads (h2d overlaps across threads)
                half = take // 2
                t_h0 = time.perf_counter()
                pa, pb = self._upload_pool.map(
                    jax.device_put, (packed[:half], packed[half:])
                )
                qps_d = jax.device_put(qps)
                h2d_ms = (time.perf_counter() - t_h0) * 1e3
                self._t_disp0 = time.perf_counter()
                if tc:
                    (prefixes_d, denses_d, bufs_d, ry, ru, rv, sy, su, sv,
                     *pool2) = self._get_step2("pk", bucket, cbucket)(
                        pa, pb, qps_d,
                        *self._src, *self._get_pool(), *self._ref)
                    self._pool_d = tuple(pool2)
                else:
                    prefixes_d, denses_d, bufs_d, ry, ru, rv, sy, su, sv = self._step_scatter_pk(
                        pa, pb, qps_d, *self._src, *self._ref
                    )
                self._src, self._ref = (sy, su, sv), (ry, ru, rv)
                recs = [g[0] for g in group]
                # per-slot full-row handles, dispatched NOW so a worker
                # shortfall refetch is a pure transfer (no queued slice)
                rows_d = [prefixes_d[i] for i in range(take)]
                # group-wide host front-end time (pack + h2d enqueue,
                # everything before the step dispatch call) stamped on
                # every member, plus each frame's own classify/convert
                # from submit time — the step/upload boundary is
                # t_disp = pre-dispatch (see _Pending)
                t_disp = self._t_disp0
                grp_ms = (t_disp - t_d0) * 1e3
                for rec in recs:
                    rec.t_disp = t_disp
                    rec.h2d_ms += h2d_ms
                    rec.up_ms = rec.classify_ms + rec.convert_ms + grp_ms
                shared = self._pool.submit(
                    self._complete_batch, recs, self._pfx_slice(prefixes_d),
                    rows_d, denses_d, bufs_d,
                )
                for slot, rec in enumerate(recs):
                    rec.future = shared
                    rec.batch_slot = slot
        except Exception:
            # dispatch failed: frames not yet dispatched never produced
            # AUs. Drop their queued records (the frame_num gap is healed
            # by the forced IDR that the nulled ref causes next frame);
            # already-dispatched groups stay deliverable.
            dropped = {id(g[0]) for g in pend if g[0].future is None}
            self._inflight = deque(r for r in self._inflight if id(r) not in dropped)
            self._ref = None
            self._src = None
            self._reset_tile_cache()
            raise

    # Small-slice length for the delta downlink fetch (int16 words =
    # 32 KB): covers typical desktop deltas (~11 K live content). Exactly
    # TWO fetch sizes exist — this and the full buffer — because every
    # distinct slice shape is a fresh executable and this deployment
    # compiles via a remote service (seconds, occasionally flaky); a
    # finer-grained adaptive ladder stalls the steady state on compiles.
    PFX_SMALL = 1 << 14

    def _update_pfx_hint(self) -> None:
        """Recompute the delta-downlink fetch length from recent frames.

        Runs on completion workers AND the submit thread; the compute and
        the `_pfx_hint` store both happen under `_pfx_lock` — the hint
        used to be assigned from pool workers with no lock while
        `_pfx_slice` read it on the main thread (a torn read can't happen
        for an int, but a stale one mis-sized the next fetch and the
        deque iteration raced appends)."""
        with self._pfx_lock:
            want = max([2048] + [n * 3 // 2 for n in self._pfx_recent])
            self._pfx_hint = (
                self._pfx_small if want <= self._pfx_small else self._pfx_total
            )

    def _pfx_slice(self, prefix_d):
        """Hint-sized view of a fused delta downlink, dispatched from the
        MAIN thread right behind the step that produced it. Slicing is a
        device op: doing it in the completion worker would enqueue it
        after later groups' scans and stall the fetch behind them."""
        with self._pfx_lock:
            L = self._pfx_hint
        if prefix_d.ndim == 1:
            return prefix_d[:L] if L < self._pfx_total else prefix_d
        return prefix_d[:, :L] if L < self._pfx_total else prefix_d

    def _note_need(self, need: int) -> None:
        """Record one slice's live word count for the fetch-hint loop
        (the hint itself recomputes in _update_pfx_hint)."""
        with self._pfx_lock:
            self._pfx_recent.append(need)

    def _complete_sparse_p(self, fused, fused_d, dense_d, buf_d, rec):
        """One delta frame's fused slice -> finished slice NAL: spliced
        straight from device bits when the frame shipped them, sparse
        end-to-end otherwise (native packer when available).

        The shared per-slice flow (sparse_complete.complete_sparse_slice)
        reads the entropy meta (when enabled) and handles the bits
        splice, slice shortfall, row spill past the cap, and the
        ns > nscap dense-header fallback, for either sparse layout
        (bit-packed when self._density is set). fused_d is a per-frame
        FULL-row handle created at dispatch time: the shortfall refetch
        is then a pure transfer — slicing here (a device op) would queue
        behind scans dispatched since.
        Returns (au, skipped_mbs, t_start, t_unpacked, t_done, mode)."""
        t1 = time.perf_counter()
        au, skipped, tu, mode = complete_sparse_slice(
            fused, mbh=self._mbh, mbw=self._mbw, nscap=self._nscap,
            cap_rows=self._cap_delta, qp=rec.qp, frame_num=rec.frame_num,
            params=self.params, packed=self._density is not None,
            device_bits=self._entropy is not None,
            full_d=fused_d, buf_d=buf_d, dense_d=dense_d,
            link_bytes=self.link_bytes, prefix_bytes=fused.nbytes,
            note_need=self._note_need,
            ltr_ref=rec.ltr_ref, mark_ltr=rec.mark_ltr,
            mmco_evict=rec.mmco_evict, entropy_coder=self._coder,
            pts=rec.meta)
        return au, skipped, t1, tu, time.perf_counter(), mode

    def _complete_batch(self, recs, pfx_slice_d, pfx_rows_d, denses_d, bufs_d):
        """Worker half for a delta group: ONE transfer of the pre-sliced
        prefix stack, then per-frame unpack + CAVLC pack FANNED OUT
        per-slot across the pack pool — frames in a group are
        independent slices and the native packer releases the GIL, so a
        12-frame group completes in ~one frame's pack time instead of
        twelve. Results come back indexed by batch_slot (submission
        order is preserved by the ordered gather)."""
        step_ms, t_ready = self._wait_step(recs[0], pfx_slice_d)
        prefixes = np.asarray(pfx_slice_d)
        # the group shares ONE transfer: step/fetch attribution is the
        # group's, stamped onto every member frame
        fetch_ms = (time.perf_counter() - t_ready) * 1e3
        # down_prefix/down_bits accounting happens per slot inside
        # complete_sparse_slice (only the meta read knows the mode)
        if self._pack_pool is not None and len(recs) > 1:
            futs = [
                self._pack_pool.submit(
                    self._complete_sparse_p, prefixes[slot], pfx_rows_d[slot],
                    denses_d[slot], bufs_d[slot], rec)
                for slot, rec in enumerate(recs)
            ]
            results = [f.result() for f in futs]
        else:
            results = [
                self._complete_sparse_p(prefixes[slot], pfx_rows_d[slot],
                                        denses_d[slot], bufs_d[slot], rec)
                for slot, rec in enumerate(recs)
            ]
        self._update_pfx_hint()
        return [(*r, step_ms, fetch_ms) for r in results]

    def submit(self, frame: np.ndarray, qp: int | None = None, meta=None,
               damage=None) -> list:
        """Dispatch one frame into the encode pipeline.

        Returns completed (au, stats, meta) tuples, oldest first — empty
        while the pipeline (depth `pipeline_depth`) is filling. Device
        dispatch is async, so frame N+1's host front-end (the fused
        classify/hash/convert scan) overlaps frame N's device step,
        downlink fetch and host CAVLC pack: the round-trip latency of
        the host↔device link is hidden at steady state.

        ``damage``: optional capture-layer dirty-rect hints ((x, y, w, h)
        pixel tuples, superset contract — FramePrep.scan) bounding the
        classification scan. None = full scan; hints never change the
        encoded bytes, only how much of the frame the classifier reads.
        """
        if qp is not None:
            self.set_qp(qp)
        idr = (
            self._force_idr
            or self.frame_index == 0
            or self._ref is None
            or (self.keyframe_interval > 0 and self._frames_since_idr >= self.keyframe_interval)
        )
        t0 = time.perf_counter()
        fi = get_injector()
        if fi is not None:
            # "frontend" chaos site: a fault in the classify/hash/convert
            # stage must surface like any encode failure (submit raises,
            # the next frame self-heals as a full-upload IDR) and must
            # never strand the frames already in flight
            fi.check("frontend")
        # classify on every frame (advances the previous-frame state even
        # across IDRs) but only short-circuit on P frames
        with tracer.span("classify", pts=meta):
            kind, dirty_idx = self._classify(frame, damage)
        classify_ms = (time.perf_counter() - t0) * 1e3
        if telemetry.enabled:
            self._emit_classify_telemetry(kind, dirty_idx)
        # a tile-cache delta whose step is still building goes whole: the
        # full-frame path codes the same frame, and the pool slots its
        # split assigned are filled from the resident planes after it
        whole = (kind == "delta" and self._tcache is not None
                 and not self._delta_step_ready(dirty_idx, idr))
        batch_full = False
        orig_qp = self.qp
        # a scene CUT is the transition into a full-frame change; during
        # sustained full-frame motion (video playback, scrolling) the
        # rate controller owns QP and the boost must stay out of the loop
        scene_cut = kind == "full" and self._src is not None and self._prev_kind != "full"
        self._prev_kind = kind
        self._full_run = self._full_run + 1 if kind == "full" else 0
        # LTR scene cache: look for a remembered scene on ANY full frame
        # (window-switch pairs arrive back-to-back, so the second switch
        # is not a `scene_cut` transition; the sampled quick-reject keeps
        # this out of the sustained-motion hot path). Match against the
        # CURRENT slot table — the pending candidate commits below, which
        # matches the decoder applying this slice's MMCO only after
        # decoding it.
        ltr_hit = None
        ltr_stash = None
        if self.ltr_scenes and not idr and kind == "full" and self._src is not None:
            # reuse _classify's probe when it already ran the match this
            # frame (slot table unchanged in between: the pending
            # candidate commits only below)
            hit = (self._ltr_probe if self._ltr_probe != ()
                   else self._ltr_match(frame))
            if hit is not None:
                ltr_hit = hit
                ltr_stash = self._ltr_slots[hit[0]]
        # commit the pending scene candidate: this slice emits the MMCO 3
        # that marks the previous full frame as long-term
        mark_ltr = None
        if self.ltr_scenes and not idr and self._ltr_candidate is not None:
            cand = self._ltr_candidate
            self._ltr_candidate = None
            self._ltr_slots[cand["slot"]] = cand
            mark_ltr = cand["slot"]
        # decoder-DPB mirror: marking slices bypass the sliding window,
        # so they must evict stale short-terms themselves (MMCO 1) or the
        # DPB would exceed max_num_ref_frames=3
        mmco_evict: tuple = ()
        if idr:
            self._dpb_st = [0]
        else:
            cur_fn = self._frames_since_idr % 256
            if mark_ltr is not None:
                prev_fn = (cur_fn - 1) % 256
                if prev_fn in self._dpb_st:
                    self._dpb_st.remove(prev_fn)  # it becomes long-term
                mmco_evict = tuple(sorted(
                    ((cur_fn - s) % 256) - 1 for s in self._dpb_st))
                self._dpb_st = [cur_fn]
            else:
                lt_count = sum(1 for s in self._ltr_slots if s is not None)
                if len(self._dpb_st) + lt_count >= 3:  # sliding window
                    self._dpb_st.pop(0)
                self._dpb_st.append(cur_fn)
        if scene_cut and self.scene_qp_boost and ltr_hit is None:
            self.qp = min(51, self.qp + self.scene_qp_boost)
        if kind == "static" and not idr:
            # unchanged capture: all-skip P slice host-side — no upload,
            # no device step, no downlink (idle-desktop steady state).
            # The screen just went idle, so stop waiting for more group
            # members: dispatch any pending deltas now.
            self._flush_batch()
            slice_nal = self._allskip_slice(self._frames_since_idr % 256,
                                            mark_ltr=mark_ltr,
                                            mmco_evict=mmco_evict)
            rec = _Pending(
                kind="static", frame_index=self.frame_index, qp=self.qp,
                frame_num=self._frames_since_idr % 256, idr_pic_id=0,
                t0=t0, t1=time.perf_counter(), meta=meta, au=slice_nal,
                mark_ltr=mark_ltr, mmco_evict=mmco_evict,
                classify_ms=classify_ms, up_ms=classify_ms,
            )
        elif (
            not idr
            and kind == "delta"
            and not whole
            and self.frame_batch > 1
            and (len(dirty_idx[0]) if self._tcache is not None else len(dirty_idx))
            <= self.BATCH_BUCKETS[-1]
        ):
            # group candidate: convert the (post-remap) upload tiles NOW
            # — the capture buffer may be reused before dispatch, and
            # the cache split already ran in _classify in frame order —
            # then dispatch when the group fills or a non-groupable
            # frame arrives
            self._t_conv_ms = 0.0
            if self._tcache is not None:
                up_idx, pool_dst, pairs = dirty_idx
                yb, ub, vb = self._convert_tiles_timed(frame, up_idx, self._tile_w)
            else:
                up_idx, pool_dst, pairs = dirty_idx, None, None
                yb, ub, vb = self._convert_tiles_timed(frame, dirty_idx, self._tile_w)
            rec = _Pending(
                kind="pd", frame_index=self.frame_index, qp=self.qp,
                frame_num=self._frames_since_idr % 256, idr_pic_id=0,
                t0=t0, t1=0.0, meta=meta, mark_ltr=mark_ltr,
                mmco_evict=mmco_evict,
                n_up=len(up_idx),
                n_remap=len(pairs) if pairs is not None else 0,
                classify_ms=classify_ms, convert_ms=self._t_conv_ms,
            )
            self._batch_pend.append((rec, yb, ub, vb, up_idx, pool_dst, pairs))
            # the policy batch cap (set_batch_cap) bounds the group; its
            # default is frame_batch, the pre-policy behavior
            batch_full = len(self._batch_pend) >= self._batch_cap
        else:
            try:
                # dispatch order must match frame order: drain any pending
                # delta group before this frame touches device state
                self._flush_batch()
                t_d0 = time.perf_counter()
                self._t_conv_ms = 0.0
                self._t_h2d_ms = 0.0
                self._t_disp0 = 0.0
                hdr_d = None
                if idr:
                    if kind == "delta" and not whole:
                        prefix_d, hdr_d, buf_d, ry, ru, rv = self._run_step_delta(
                            frame, dirty_idx, idr=True
                        )
                    elif kind == "static" and self._src is not None:
                        # forced IDR over unchanged content: zero upload
                        self._t_disp0 = time.perf_counter()
                        prefix_d, buf_d, ry, ru, rv = self._step_resident_i(
                            np.int32(self.qp), *self._src
                        )
                    else:
                        prefix_d, buf_d, ry, ru, rv = self._run_step_i(frame)
                    # recon never leaves the device: it is the P-frame
                    # reference (donated into the next P step)
                    self._ref = (ry, ru, rv)
                    rec = _Pending(
                        kind="i", frame_index=self.frame_index, qp=self.qp,
                        frame_num=0, idr_pic_id=self._idr_pic_id,
                        t0=t0, t1=0.0, meta=meta,
                        prefix_d=prefix_d, buf_d=buf_d,
                    )
                    self._frames_since_idr = 0
                    self._idr_pic_id = (self._idr_pic_id + 1) % 2
                    self._force_idr = False
                else:
                    ltr_ref = None
                    n_up = n_remap = 0
                    if ltr_hit is not None:
                        # scene restore: a few tiles against the slot's
                        # long-term reference instead of a full-frame
                        # upload + encode
                        prefix_d, hdr_d, buf_d, ry, ru, rv = self._run_step_ltr(
                            frame, ltr_hit[1], ltr_stash
                        )
                        pk, words_d = "pd", None
                        ltr_ref = ltr_hit[0]
                        n_up = len(ltr_hit[1])
                        self.ltr_restores += 1
                    elif kind == "delta" and not whole:
                        prefix_d, hdr_d, buf_d, ry, ru, rv = self._run_step_delta(
                            frame, dirty_idx, idr=False
                        )
                        pk, words_d = "pd", None
                        if isinstance(dirty_idx, tuple):  # tile-cache split
                            n_up, n_remap = len(dirty_idx[0]), len(dirty_idx[2])
                        else:
                            n_up = len(dirty_idx)
                    else:
                        (pk, prefix_d, words_d, hdr_d, buf_d, ry, ru, rv) = (
                            self._run_step_p(frame)
                        )
                    # reassign IMMEDIATELY: _step_p donated the old buffers
                    self._ref = (ry, ru, rv)
                    rec = _Pending(
                        kind=pk,
                        frame_index=self.frame_index, qp=self.qp,
                        frame_num=self._frames_since_idr % 256, idr_pic_id=0,
                        t0=t0, t1=0.0, meta=meta,
                        prefix_d=prefix_d, buf_d=buf_d, hdr_d=hdr_d,
                        words_d=words_d, scene_cut=scene_cut,
                        n_up=n_up, n_remap=n_remap,
                        ltr_ref=ltr_ref, mark_ltr=mark_ltr,
                        mmco_evict=mmco_evict,
                    )
                    if pk == "pd":
                        rec.pfx_slice_d = self._pfx_slice(prefix_d)
                # upload/step attribution boundary: everything since
                # flush UP TO the step dispatch call (conversion, tile
                # packing, h2d enqueue) is the host front-end cost of
                # THIS frame; the dispatch call itself counts as step
                # time (it blocks exactly when the device is the
                # bottleneck — see _Pending)
                rec.t_disp = self._t_disp0 or time.perf_counter()
                rec.classify_ms = classify_ms
                rec.convert_ms = self._t_conv_ms
                rec.h2d_ms = self._t_h2d_ms
                rec.up_ms = classify_ms + (rec.t_disp - t_d0) * 1e3
                if whole:
                    # one gather size for every such frame: the one a
                    # full-frame seed of the whole grid already built
                    self._fill_pool(dirty_idx[1], dirty_idx[0],
                                    self._copy_buckets[-1])
                # over-budget delta that fell back to full: seed the tile
                # pool from the now-resident planes so the NEXT frame of
                # a sustained scroll fits the delta path via remaps.
                # Only the first frames of a full-frame run seed (same
                # policy as the LTR stash): video playback would pay a
                # full hash + commit + device gather per frame for
                # content that never repeats
                if (
                    self._tcache is not None
                    and kind == "full"
                    and isinstance(dirty_idx, tuple)
                    and self._src is not None
                    and self._full_run <= 2
                ):
                    self._seed_pool(frame, dirty_idx[1], dirty_idx[2])
                # scene-stash bookkeeping: every full frame (IDR, full-P,
                # or restore) becomes the pending LTR candidate — window
                # switches arrive back-to-back, so mid-run frames are
                # boundaries too; the next slice's MMCO 3 commits it.
                # Restores refresh their own slot and become MRU; new
                # scenes go to the unprotected slot.
                if self.ltr_scenes:
                    if idr:
                        # DPB reset: the decoder dropped every reference
                        self._ltr_slots = [None, None]
                        self._ltr_candidate = None
                        self._ltr_mru = 0  # protect the IDR's scene slot
                        self._stash_candidate(frame, 0)
                    elif ltr_hit is not None:
                        self._ltr_mru = ltr_hit[0]
                        self._stash_candidate(frame, ltr_hit[0])
                    elif kind == "full" and self._full_run <= 2:
                        self._stash_candidate(frame, 1 - self._ltr_mru)
                if kind == "full" and ltr_hit is None:
                    # decay feed-forward: the frames after a full-frame
                    # change carry a frame-wide quantization-error tail,
                    # so the next delta fetches will be large — grow the
                    # hint NOW instead of stalling on shortfall refetches
                    with self._pfx_lock:
                        self._pfx_recent.append(self._pfx_total // 2)
                    self._update_pfx_hint()
                # start the downlink fetch + entropy pack on a worker NOW
                # so it overlaps the next frame's front-end
                rec.future = self._pool.submit(self._complete_work, rec)
            except Exception:
                # device failure after donation: the old reference (and
                # possibly source) planes are gone. Null both so the next
                # frame self-heals as a full-upload IDR instead of
                # desyncing the decoder. Older frames already in flight
                # stay queued — they were dispatched against an intact
                # chain and remain deliverable.
                self._ref = None
                self._src = None
                self._ltr_candidate = None  # forced IDR will clear slots
                self._reset_tile_cache()
                self.qp = orig_qp
                raise
        self.qp = orig_qp
        self.frame_index += 1
        self._frames_since_idr += 1
        self._inflight.append(rec)
        if batch_full:
            self._flush_batch()
        out = []
        # emit completions in submission order; block only when the
        # dispatched (device-side) pipeline is deeper than pipeline_depth
        while self._inflight:
            head = self._inflight[0]
            if head.au is not None or (head.future is not None and head.future.done()):
                out.append(self._emit(self._inflight.popleft()))
                continue
            # depth counts device ROUND TRIPS (distinct futures), not
            # frames: a grouped dispatch of K frames is one round trip
            dispatched = len({
                id(r.future)
                for r in self._inflight
                if r.future is not None and not r.future.done()
            })
            if dispatched > self.pipeline_depth:
                out.append(self._emit(self._inflight.popleft()))  # blocking wait
                continue
            # frame-count backstop: pipeline_depth ROUND TRIPS of grouped
            # dispatches plus the group being accumulated (with
            # frame_batch=1 this is the old depth+1 frame bound)
            if len(self._inflight) > (self.pipeline_depth + 1) * self.frame_batch:
                if head.future is None:
                    self._flush_batch()  # give the stalled head a future
                else:
                    out.append(self._emit(self._inflight.popleft()))
                continue
            break
        return out

    def flush(self) -> list:
        """Complete every in-flight frame (oldest first)."""
        self._flush_batch()
        out = []
        while self._inflight:
            out.append(self._emit(self._inflight.popleft()))
        return out

    def _emit(self, rec: "_Pending"):
        """Resolve one pending frame (waiting on its worker if needed)."""
        if rec.kind == "static":
            au = rec.au
            stats = FrameStats(
                frame_index=rec.frame_index, idr=False, qp=rec.qp,
                bytes=len(au), device_ms=(rec.t1 - rec.t0) * 1e3,
                pack_ms=0.0,
                skipped_mbs=(self._pad_h // 16) * (self._pad_w // 16),
                upload_kind="static",
                upload_ms=rec.up_ms, classify_ms=rec.classify_ms,
            )
            self.last_stats = stats
            return au, stats, rec.meta
        # A fetch/pack failure means the client never receives this frame:
        # encoding successors against its recon would silently desync the
        # decoder, so null the ref (forces IDR) and drop the pipeline.
        try:
            if rec.batch_slot >= 0:
                au, skipped, t1, tu, t2, mode, step_ms, fetch_ms = (
                    rec.future.result()[rec.batch_slot])
            else:
                au, skipped, t1, tu, t2, mode, step_ms, fetch_ms = rec.future.result()
            handoff_ms = (time.perf_counter() - t2) * 1e3
        except Exception:
            self._ref = None
            self._src = None
            self._inflight.clear()
            self._batch_pend.clear()
            self._reset_tile_cache()
            raise
        # upload classification signals for the policy engine: "pd" was
        # a tile delta (dirty = uploads + remaps), everything else that
        # reached the device was a full-frame upload
        dirty = rec.n_up + rec.n_remap
        stats = FrameStats(
            frame_index=rec.frame_index, idr=rec.kind == "i", qp=rec.qp,
            bytes=len(au), device_ms=(t1 - rec.t0) * 1e3,
            pack_ms=(t2 - t1) * 1e3, skipped_mbs=skipped,
            scene_cut=rec.scene_cut,
            unpack_ms=(tu - t1) * 1e3, cavlc_ms=(t2 - tu) * 1e3,
            upload_ms=rec.up_ms, step_ms=step_ms, fetch_ms=fetch_ms,
            classify_ms=rec.classify_ms, convert_ms=rec.convert_ms,
            h2d_ms=rec.h2d_ms,
            downlink_mode=mode,
            handoff_wait_ms=handoff_ms,
            coef_blocks_luma=rec.coef_blocks[0],
            coef_blocks_chroma=rec.coef_blocks[1],
            bits_rung=rec.bits_rung,
            upload_kind="delta" if rec.kind == "pd" else "full",
            dirty_frac=(min(1.0, dirty / self._ntiles)
                        if rec.kind == "pd" else 1.0),
            remap_frac=(rec.n_remap / dirty
                        if rec.kind == "pd" and dirty else 0.0),
        )
        self.last_stats = stats
        return au, stats, rec.meta

    def _wait_step(self, rec: "_Pending", handle) -> tuple[float, float]:
        """Block until the frame's downlink buffer is ready on device and
        return (step_ms, t_ready). Worker-side only — the main thread
        never waits — so the upload/step/fetch attribution costs one
        block_until_ready per frame, not a pipeline stall."""
        with tracer.span("step", pts=rec.meta):
            jax.block_until_ready(handle)
        t_ready = time.perf_counter()
        t_disp = rec.t_disp or rec.t0
        return (t_ready - t_disp) * 1e3, t_ready

    def _complete_work(self, rec: "_Pending"):
        """Worker-thread half: single-fetch downlink + unpack/assemble.
        Returns (au, skipped_mbs, t_start, t_unpacked, t_done, step_ms,
        fetch_ms) — the unpack/cavlc and upload/step/fetch splits feed
        the stage attribution in FrameStats."""
        if rec.kind == "pb":
            if self._coder == "cabac":
                return self._complete_toks(rec)
            return self._complete_bits(rec)
        if rec.kind == "pd":
            step_ms, t_ready = self._wait_step(rec, rec.pfx_slice_d)
            with tracer.span("fetch", pts=rec.meta):
                fused = np.asarray(rec.pfx_slice_d)
            fetch_ms = (time.perf_counter() - t_ready) * 1e3
            out = self._complete_sparse_p(fused, rec.prefix_d, rec.hdr_d,
                                          rec.buf_d, rec)
            self._update_pfx_hint()
            return (*out, step_ms, fetch_ms)
        hdr_words = self._hdr_words_i if rec.kind == "i" else self._hdr_words_p
        cap = CAP_ROWS
        step_ms, t_ready = self._wait_step(rec, rec.prefix_d)
        with tracer.span("fetch", pts=rec.meta):
            prefix = np.asarray(rec.prefix_d)
        fetch_ms = (time.perf_counter() - t_ready) * 1e3
        self.link_bytes.add("down_prefix", prefix.nbytes)
        header, data, n = split_prefix(prefix, hdr_words)
        if n > cap:  # rare: heavy frame spilled past the prefix
            rest = _fetch_rest(rec.buf_d, n, cap)
            self.link_bytes.add("down_spill", rest.nbytes)
            data = np.concatenate([data, rest])
        t1 = time.perf_counter()
        skipped = 0
        if rec.kind == "i":
            with tracer.span("unpack", pts=rec.meta):
                fc = unpack_i_compact(header, data, rec.qp)
            tu = time.perf_counter()
            # frame_num counts from the last IDR (7.4.3: gaps are
            # disallowed by our SPS)
            with tracer.span("pack", pts=rec.meta):
                if self._coder == "cabac":
                    slice_nal = pack_slice_cabac(
                        fc, self.params, frame_num=0, idr=True,
                        idr_pic_id=rec.idr_pic_id)
                else:
                    slice_nal = pack_slice_fast(
                        fc, self.params, frame_num=0, idr=True,
                        idr_pic_id=rec.idr_pic_id)
            au = self._headers + slice_nal
        else:
            with tracer.span("unpack", pts=rec.meta):
                pfc = unpack_p_compact(header, data, rec.qp)
            tu = time.perf_counter()
            skipped = int(pfc.skip.sum())
            with tracer.span("pack", pts=rec.meta):
                if self._coder == "cabac":
                    au = pack_slice_p_cabac(
                        pfc, self.params, rec.frame_num,
                        ltr_ref=rec.ltr_ref, mark_ltr=rec.mark_ltr,
                        mmco_evict=rec.mmco_evict)
                else:
                    au = pack_slice_p_fast(
                        pfc, self.params, frame_num=rec.frame_num,
                        ltr_ref=rec.ltr_ref, mark_ltr=rec.mark_ltr,
                        mmco_evict=rec.mmco_evict)
        # downlink_mode is a P-frame label ("" on the IDR row — keyframes
        # can never ship device bits, so they must not count as "coeff")
        mode = "coeff" if rec.kind != "i" else ""
        return au, skipped, t1, tu, time.perf_counter(), mode, step_ms, fetch_ms

    def _complete_bits(self, rec: "_Pending"):
        """Device-entropy P frame: fetch [meta ++ bit words], splice the
        slice header, done — no coefficient unpack, no host CAVLC."""
        step_ms, t_ready = self._wait_step(rec, rec.prefix_d)
        with tracer.span("fetch", pts=rec.meta):
            arr = np.asarray(rec.prefix_d)  # uint32: BITS_META, words...
        fetch_ms = (time.perf_counter() - t_ready) * 1e3
        self.link_bytes.add("down_bits", arr.nbytes)
        nbits, trailing, skipped = int(arr[0]), int(arr[1]), int(arr[2])
        rec.coef_blocks = (int(arr[3]), int(arr[4]))
        rec.bits_rung = int(arr[5])
        tracer.value("coef_blocks_luma", rec.coef_blocks[0])
        tracer.value("coef_blocks_chroma", rec.coef_blocks[1])
        tracer.value("bits_rung", rec.bits_rung)
        if nbits > BITS_WORD_CAP * 32:
            # pathological frame overflowed the bit buffer: dense fallback
            header = np.asarray(rec.hdr_d)
            data = _fetch_rest(rec.buf_d, int(header[0]), 0)
            self.link_bytes.add("down_spill", header.nbytes + data.nbytes)
            t1 = time.perf_counter()
            pfc = unpack_p_compact(header, data, rec.qp)
            tu = time.perf_counter()
            au = pack_slice_p_fast(pfc, self.params, frame_num=rec.frame_num,
                                   ltr_ref=rec.ltr_ref, mark_ltr=rec.mark_ltr,
                                   mmco_evict=rec.mmco_evict)
            return (au, int(pfc.skip.sum()), t1, tu, time.perf_counter(),
                    "dense", step_ms, fetch_ms)
        need = (nbits + 31) // 32
        words = arr[BITS_META : BITS_META + min(need, BITS_PREFIX_WORDS)]
        if need > BITS_PREFIX_WORDS:  # spill: one extra fetch
            with tracer.span("bits_fetch", pts=rec.meta):
                rest = _fetch_rest(rec.words_d, need, BITS_PREFIX_WORDS)
            self.link_bytes.add("down_bits_spill", rest.nbytes)
            words = np.concatenate([words, rest])
        t1 = time.perf_counter()
        with tracer.span("pack", pts=rec.meta):
            au = assemble_p_nal(words, nbits, trailing, self.params,
                                rec.frame_num, rec.qp, ltr_ref=rec.ltr_ref,
                                mark_ltr=rec.mark_ltr,
                                mmco_evict=rec.mmco_evict)
        return au, skipped, t1, t1, time.perf_counter(), "bits", step_ms, fetch_ms

    def _complete_toks(self, rec: "_Pending"):
        """Device-CABAC P frame: fetch [meta ++ skip bitmap ++ counts ++
        token words], interleave the skip/terminate bins and run the
        host arithmetic engine — no coefficient unpack, no host
        binarization."""
        step_ms, t_ready = self._wait_step(rec, rec.prefix_d)
        with tracer.span("fetch", pts=rec.meta):
            arr = np.asarray(rec.prefix_d)  # uint32: ntok, ns, nskip, ...
        fetch_ms = (time.perf_counter() - t_ready) * 1e3
        self.link_bytes.add("down_bits", arr.nbytes)
        ntok, ns, skipped = int(arr[0]), int(arr[1]), int(arr[2])
        if ntok > 2 * TOK_WORD_CAP:
            # pathological frame overflowed the token buffer: dense
            # fallback — still through the host CABAC coder (the PPS
            # pins entropy_coding_mode_flag for the whole stream)
            header = np.asarray(rec.hdr_d)
            data = _fetch_rest(rec.buf_d, int(header[0]), 0)
            self.link_bytes.add("down_spill", header.nbytes + data.nbytes)
            t1 = time.perf_counter()
            pfc = unpack_p_compact(header, data, rec.qp)
            tu = time.perf_counter()
            au = pack_slice_p_cabac(pfc, self.params, rec.frame_num,
                                    ltr_ref=rec.ltr_ref,
                                    mark_ltr=rec.mark_ltr,
                                    mmco_evict=rec.mmco_evict)
            return (au, int(pfc.skip.sum()), t1, tu, time.perf_counter(),
                    "dense", step_ms, fetch_ms)
        m = self._mbh * self._mbw
        sw = (m + 31) // 32
        cw = (m + 1) // 2
        skip_words = arr[3:3 + sw].astype(np.int64)
        skip = (((skip_words[:, None] >> np.arange(32)) & 1)
                .astype(bool).reshape(-1)[:m].reshape(self._mbh, self._mbw))
        counts = (np.ascontiguousarray(arr[3 + sw:3 + sw + cw])
                  .view(np.int16)[:ns].astype(np.int64))
        base = 3 + sw + cw
        need = (ntok + 1) // 2
        words = arr[base:base + min(need, TOK_PREFIX_WORDS)]
        if need > TOK_PREFIX_WORDS:  # spill: one extra fetch
            with tracer.span("bits_fetch", pts=rec.meta):
                rest = _fetch_rest(rec.words_d, need, TOK_PREFIX_WORDS)
            self.link_bytes.add("down_bits_spill", rest.nbytes)
            words = np.concatenate([words, rest])
        t1 = time.perf_counter()
        with tracer.span("pack", pts=rec.meta):
            au = assemble_p_cabac_nal(words, ntok, counts, skip, self.params,
                                      rec.frame_num, rec.qp,
                                      ltr_ref=rec.ltr_ref,
                                      mark_ltr=rec.mark_ltr,
                                      mmco_evict=rec.mmco_evict)
        return (au, skipped, t1, t1, time.perf_counter(), "cabac", step_ms,
                fetch_ms)

    def encode_frame(self, frame: np.ndarray, qp: int | None = None) -> bytes:
        """Synchronous encode ((H, W, 4) BGRx or (H, W, 3) RGB uint8 in,
        complete Annex-B access unit out; SPS/PPS prepended on IDR).
        Equivalent to submit() + flush() — no pipelining."""
        if self._inflight:
            # mixing submit() and encode_frame() would silently drop the
            # in-flight frames' access units (only this frame's AU is
            # returned) — a decoder-visible frame_num gap. Refuse.
            raise RuntimeError("encode_frame() called with frames in flight; use flush() first")
        outs = self.submit(frame, qp)
        outs.extend(self.flush())
        return outs[-1][0]

    def prewarm(self) -> None:
        """Compile the hot executables (IDR full, P full) before the live
        loop starts. The device-entropy P program in particular is a
        large XLA build (~tens of seconds cold); paying it at session
        start instead of on the first real frame keeps the stream from
        stalling. Leaves the encoder in a fresh-GOP state."""
        rng = np.random.default_rng(0)
        shape = (self.height, self.width, self.channels)
        f0 = rng.integers(0, 255, shape, np.uint8)
        f1 = rng.integers(0, 255, shape, np.uint8)
        self.encode_frame(f0)  # IDR full
        self.encode_frame(f1)  # P full (device-entropy path)
        # reset stream state: the next real frame starts a clean GOP
        self._force_idr = True
        self._ref = None
        self._src = None
        self._reset_tile_cache()
        if self._prep is not None:
            self._prep.reset()
        self._prev_frame = None
        self.frame_index = 0
        self._frames_since_idr = 0
        self._idr_pic_id = 0
        self._prev_kind = "full"

    def close(self) -> None:
        """Discard in-flight frames and stop the completion workers."""
        self._inflight.clear()
        self._batch_pend.clear()
        self._pool.shutdown(wait=False, cancel_futures=True)
        if self._pack_pool is not None:
            self._pack_pool.shutdown(wait=False, cancel_futures=True)
        self._upload_pool.shutdown(wait=False, cancel_futures=True)

    def recon_planes(self, frame: np.ndarray):
        """Debug helper: (recon_y, recon_u, recon_v) for a frame."""
        _, _, ry, ru, rv = self._run_step_i(frame)
        return (np.asarray(ry), np.asarray(ru), np.asarray(rv))


def make_frame_step(width: int, height: int, qp: int = 28):
    """(jittable fn, example args) for the driver's compile check: the
    steady-state P-frame step (ME + MC + transform), the flagship path."""
    pad_h = (height + 15) // 16 * 16
    pad_w = (width + 15) // 16 * 16

    def fn(frame, qp_arr, ry, ru, rv):
        return _device_step_p(
            frame, qp_arr, ry, ru, rv, pad_h=pad_h, pad_w=pad_w, channels=4
        )

    rng = np.random.default_rng(0)
    frame = rng.integers(0, 256, size=(height, width, 4), dtype=np.uint8)
    ry = rng.integers(0, 256, size=(pad_h, pad_w), dtype=np.uint8)
    ru = rng.integers(0, 256, size=(pad_h // 2, pad_w // 2), dtype=np.uint8)
    rv = rng.integers(0, 256, size=(pad_h // 2, pad_w // 2), dtype=np.uint8)
    return fn, (frame, np.int32(qp), ry, ru, rv)
