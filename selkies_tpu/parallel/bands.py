"""Intra-frame band parallelism: one frame sharded across the chip mesh
as independent H.264 slices.

The FIFO-serialized device step is the last per-frame term on a
PCIe-local host (~10-14 ms/frame at 1080p, PERF.md round-7), capping a
single chip at ~50-60 fps and putting 4K@60 out of reach. The classic
encoder answer is slice parallelism (x264's sliced-threads; AV1/VP9 tile
columns) — and H.264 multi-slice pictures are first-class syntax our
slice headers already parameterize (`first_mb_in_slice`). This module
splits each frame into `SELKIES_BANDS` horizontal macroblock-row bands
and encodes each band as an INDEPENDENT slice on its own chip:

  * device half — a `shard_map` over a ``band`` mesh axis runs
    encoder_core.encode_band_p_planes per chip; each band's motion
    estimation is constrained to its own reference rows plus a ``halo``
    of neighbour rows exchanged on-mesh with ``jax.lax.ppermute``, so a
    band's slice depends ONLY on data resident on its chip (and the
    selected predictions are always real reference content, matching
    the decoder's full-frame MC exactly — see encode_band_p_planes);
  * link half — each band emits its own variable-packed sparse downlink
    (encoder_core.pack_p_sparse_var), landing as N smaller fetches that
    overlap on the link;
  * host half — per-band unpack + CAVLC pack fan out across the
    h264-pack pool (sized min(cores, bands × frame_batch ×
    pipeline_depth)); the host concatenates the N slice NALs into one
    access unit in band order.

Correctness contract: each band's slice is byte-identical to a
single-chip encode of the same band with the same ME constraint (the
per-band oracle — the mesh and fallback paths run the same per-band
graph), and ``SELKIES_BANDS=1`` reproduces the solo encoder's
single-slice bytes exactly (tests/test_band_slices.py).

Placement composes with the ``session`` axis: a v5e-8 can serve
8 sessions × 1 band (parallel/sessions.py), 2 sessions × 4 bands, or
1 session × 8 bands — ``partition_devices`` carves the chip list into
per-session band rows for the fleet (serving.BandedFleetService).

2D tile grid (``SELKIES_TILE_GRID=RxC``): rows alone stop paying at 4K —
a horizontal band of a 4K frame is ~4x the MB area of its 1080p
counterpart, and bands below 3 MB rows break the adjacent-halo
invariant — so the band axis extends to a two-axis ``(band, col)`` chip
mesh where each chip encodes ONE tile:

  * compute (ME/MC, transform, quant) is per-tile independent; vertical
    reference halos ride the existing ``band``-axis ppermute and NEW
    horizontal halo columns ride a ``col``-axis ppermute (columns first,
    then rows, so the diagonal corner blocks carry the diagonal
    neighbour's real pixels);
  * the coarse ME vote histograms of one slice row are psum-merged over
    ``col`` before candidate selection, and P_Skip derivation runs on
    the row-gathered MV grid (the post-ME neighbour-MV exchange), so
    MV prediction at tile seams matches the full-row encoder exactly;
  * the bitstream stays valid H.264 by keeping SLICES per band-row: each
    row's C tile payloads are all-gathered along ``col``, merged into
    the full-row coefficient layout (or handed to the PR 7 active
    entropy coder, run per row), and completed by the unchanged
    per-slice host flow (sparse_complete.py).

``RxC`` with ``C=1`` is byte-identical to ``SELKIES_BANDS=R`` (same code
path), ``1x1`` to the solo encoder, and — with the default full-reach
halos — an RxC access unit is byte-identical to the SELKIES_BANDS=R
oracle (tests/test_tile_grid.py).
"""

from __future__ import annotations

import logging
import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from selkies_tpu.models.h264.bitstream import StreamParams, write_pps, write_sps
from selkies_tpu.models.h264.compact import (
    i_header_words,
    p_sparse_entropy_words,
    p_sparse_var_words,
    split_prefix,
    unpack_i_compact,
)
from selkies_tpu.models.h264.cabac import pack_slice_cabac, pack_slice_p_cabac
from selkies_tpu.models.h264.device_cavlc import (
    entropy_coder_default,
    resolve_entropy,
)
from selkies_tpu.models.h264.encoder_core import (
    _downsample4,
    _skip_mask,
    coarse_votes_jnp,
    encode_band_p_planes,
    encode_frame_planes,
    encode_tile_p_planes,
    fuse_downlink,
    pack_i_compact,
    pack_p_sparse_entropy,
    pack_p_sparse_var,
    select_coarse_jnp,
)
from selkies_tpu.models.h264.native import (
    pack_slice_fast,
    pack_slice_p_fast,
)
from selkies_tpu.models.h264.numpy_ref import COARSE_R, MV_PAD, PFrameCoeffs
from selkies_tpu.models.stats import FrameStats, LinkByteCounter
from selkies_tpu.monitoring.telemetry import telemetry
from selkies_tpu.monitoring.tracing import tracer
from selkies_tpu.resilience.devhealth import (
    check_device_faults,
    get_device_pool,
)

logger = logging.getLogger("parallel.bands")

__all__ = [
    "BAND_HALO",
    "BandedH264Encoder",
    "band_mesh",
    "band_spans",
    "bands_from_env",
    "grid_from_env",
    "halo_from_env",
    "partition_devices",
    "tile_halo_from_env",
    "tile_mesh",
    "usable_bands",
    "usable_cols",
]

# Default halo: the full hierarchical-ME reach (34 luma rows) plus the
# chroma bilinear's one-row lookahead rounds up to MV_PAD, so every
# candidate the search can select reads REAL reference rows from the
# slab and no candidate clamping is needed. Smaller halos (see
# SELKIES_BAND_HALO) trade neighbour-row exchange bytes for a clamped
# vertical search window (encode_band_p_planes dy_max).
BAND_HALO = MV_PAD
# A band must be tall enough that its neighbour's halo comes from THIS
# band alone (ppermute exchanges adjacent bands only): 16·3 = 48 luma /
# 24 chroma rows covers the 40/20-row default halo.
MIN_BAND_MB_ROWS = 3
# The column mirror: a tile must be wide enough that its neighbour's
# column halo comes from THIS tile alone — 16·3 = 48 luma columns covers
# the 40/20-column default halo AND the coarse vote's downsampled
# COARSE_R-column exchange (8 <= 48/4 = 12 downsampled columns).
MIN_TILE_MB_COLS = 3


def grid_from_env() -> tuple[int, int] | None:
    """SELKIES_TILE_GRID=RxC -> (rows, cols), or None when unset/invalid.
    Set, it owns the carve: R band-rows × C tile columns per frame
    (SELKIES_BANDS is ignored — RxC with C=1 IS the band carve)."""
    env = os.environ.get("SELKIES_TILE_GRID", "")
    if not env:
        return None
    try:
        r_s, c_s = env.lower().replace("×", "x").split("x")
        return max(1, int(r_s)), max(1, int(c_s))
    except ValueError:
        logger.warning("SELKIES_TILE_GRID=%r is not RxC; ignoring", env)
        return None


def bands_from_env() -> int:
    env = os.environ.get("SELKIES_BANDS", "")
    if not env:
        return 1
    try:
        return max(1, int(env))
    except ValueError:
        logger.warning("SELKIES_BANDS=%r is not an integer; using 1", env)
        return 1


def halo_from_env() -> int:
    env = os.environ.get("SELKIES_BAND_HALO", "")
    if not env:
        return BAND_HALO
    try:
        halo = int(env)
    except ValueError:
        logger.warning("SELKIES_BAND_HALO=%r is not an integer; using %d",
                       env, BAND_HALO)
        return BAND_HALO
    halo = max(4, min(BAND_HALO, halo))
    return halo - halo % 2  # even: chroma slabs carry halo//2 rows


def tile_halo_from_env() -> int:
    """Horizontal halo COLUMNS exchanged along the ``col`` axis
    (SELKIES_TILE_HALO; default = the full hierarchical reach, like the
    row halo — below 36 the horizontal candidate window clamps to
    halo-2 and the byte-oracle vs SELKIES_BANDS=R no longer holds)."""
    env = os.environ.get("SELKIES_TILE_HALO", "")
    if not env:
        return BAND_HALO
    try:
        halo = int(env)
    except ValueError:
        logger.warning("SELKIES_TILE_HALO=%r is not an integer; using %d",
                       env, BAND_HALO)
        return BAND_HALO
    halo = max(4, min(BAND_HALO, halo))
    return halo - halo % 2  # even: chroma slabs carry halo//2 columns


def usable_cols(mb_width: int, requested: int) -> int:
    """Largest tile-column count <= `requested` that splits `mb_width` MB
    columns into EQUAL tiles of at least MIN_TILE_MB_COLS (the column
    mirror of usable_bands)."""
    requested = max(1, int(requested))
    for cols in range(min(requested, mb_width // MIN_TILE_MB_COLS), 1, -1):
        if mb_width % cols == 0:
            return cols
    return 1


def usable_bands(mb_height: int, requested: int) -> int:
    """Largest band count <= `requested` that splits `mb_height` MB rows
    into EQUAL bands of at least MIN_BAND_MB_ROWS (equal shards are what
    shard_map places; unequal tails would force padded encodes)."""
    requested = max(1, int(requested))
    for bands in range(min(requested, mb_height // MIN_BAND_MB_ROWS), 1, -1):
        if mb_height % bands == 0:
            return bands
    return 1


def band_spans(mb_height: int, bands: int) -> list[tuple[int, int]]:
    """(first_mb_row, mb_rows) per band, top to bottom (equal split)."""
    if mb_height % bands:
        raise ValueError(f"{bands} bands do not divide {mb_height} MB rows")
    rows = mb_height // bands
    return [(b * rows, rows) for b in range(bands)]


def band_mesh(bands: int, devices=None) -> Mesh:
    """One-axis ``band`` mesh over the first `bands` devices (the
    DevicePool's healthy view when none are given — a quarantined chip
    never lands in a fresh mesh)."""
    devs = np.array(devices if devices is not None
                    else get_device_pool().healthy_devices())
    if len(devs) < bands:
        raise ValueError(f"need {bands} devices for the band mesh, have {len(devs)}")
    return Mesh(devs[:bands], axis_names=("band",))


def tile_mesh(rows: int, cols: int, devices=None) -> Mesh:
    """Two-axis ``(band, col)`` mesh over the first rows*cols devices:
    chip (r, c) encodes the tile at band-row r, tile-column c."""
    devs = np.array(devices if devices is not None
                    else get_device_pool().healthy_devices())
    if len(devs) < rows * cols:
        raise ValueError(
            f"need {rows * cols} devices for the {rows}x{cols} tile mesh, "
            f"have {len(devs)}")
    return Mesh(devs[: rows * cols].reshape(rows, cols),
                axis_names=("band", "col"))


def partition_devices(n_sessions: int, bands: int, devices=None) -> list[list]:
    """Carve the chip list into per-session band rows — the fleet's
    chips-per-session vs sessions-per-slice trade. Returns n_sessions
    rows of `bands` devices; raises when the slice is too small (the
    caller decides whether to drop bands or sessions)."""
    devs = list(devices if devices is not None
                else get_device_pool().healthy_devices())
    need = n_sessions * bands
    if len(devs) < need:
        raise ValueError(
            f"{n_sessions} sessions x {bands} bands needs {need} devices, "
            f"have {len(devs)}")
    return [devs[k * bands : (k + 1) * bands] for k in range(n_sessions)]


# ---------------------------------------------------------------------------
# Device steps
# ---------------------------------------------------------------------------
#
# Per-band body shared by BOTH execution modes: the mesh path runs it
# once per chip inside shard_map, the fallback path runs it per band
# inside one single-device jit (a Python loop over a static band count,
# NOT a vmap — identical per-band graphs are what makes the per-band
# oracle a byte-identity statement rather than an approximation).


def _band_i_body(y, u, v, qp, cap_rows: int):
    out = encode_frame_planes(y, u, v, qp)
    header, buf = pack_i_compact(out)
    prefix = fuse_downlink(header, buf, cap_rows)
    return prefix, buf, out["recon_y"], out["recon_u"], out["recon_v"]


def _pack_fused(out, nscap: int, cap_rows: int, entropy):
    """One band-row's P outputs -> (fused, buf) downlink pair — the
    pack dispatch shared by the 1D band body and the tile grid's
    post-merge row pack. nscap == the row's MB count, so the ns > nscap
    dense fallback is structurally unreachable — every row completes
    from its fused buffer (+ the rare row spill from `buf`)."""
    if entropy is not None:
        # activity-proportional device entropy per row: a busy row
        # ships its own bit-shifted slice payload (first_mb lives in the
        # host-written header) or, under CABAC, its binarized token IR —
        # a quiet row keeps the sparse rows — decided per row per frame,
        # inside the shard_map body
        bits_words, min_mbs, buckets, coder = entropy
        fused, _dense, buf = pack_p_sparse_entropy(
            out, nscap, cap_rows, None, bits_words, min_mbs, buckets,
            entropy_coder=coder)
    else:
        fused, _dense, buf = pack_p_sparse_var(out, nscap, cap_rows)
    return fused, buf


def _band_p_body(y, u, v, qp, slab_y, slab_u, slab_v, *, halo: int,
                 nscap: int, cap_rows: int, entropy=None):
    out = encode_band_p_planes(y, u, v, slab_y, slab_u, slab_v, qp, halo=halo)
    fused, buf = _pack_fused(out, nscap, cap_rows, entropy)
    return fused, buf, out["recon_y"], out["recon_u"], out["recon_v"]


def _slab_indices(bands: int, rows: int, halo: int) -> np.ndarray:
    """(bands, rows + 2*halo) row gather indices into the stacked
    (bands*rows) plane, clipped at the picture edges (clip == the
    decoder's boundary replication == jnp.pad mode='edge')."""
    base = rows * np.arange(bands)[:, None]
    span = np.arange(-halo, rows + halo)[None, :]
    return np.clip(base + span, 0, bands * rows - 1)


def _stacked_slabs(ref, halo: int):
    """Fallback-mode slab build: (B, rows, W) stacked ref -> halo-extended
    (B, rows + 2*halo, W) slabs via one static gather."""
    b, rows, w = ref.shape
    idx = jnp.asarray(_slab_indices(b, rows, halo))
    return ref.reshape(b * rows, w)[idx]


def _ppermute_slab(r0, halo: int, bands: int, axis: str):
    """Mesh-mode slab build: exchange `halo` boundary rows with the
    adjacent bands over the mesh (band 0 / band B-1 edge-replicate,
    matching the fallback clip and the decoder's picture clamp)."""
    if halo == 0 or bands == 1:
        return r0
    w = r0.shape[1]
    from_above = jax.lax.ppermute(
        r0[-halo:], axis, [(b, b + 1) for b in range(bands - 1)])
    from_below = jax.lax.ppermute(
        r0[:halo], axis, [(b + 1, b) for b in range(bands - 1)])
    i = jax.lax.axis_index(axis)
    top = jnp.where(i == 0, jnp.broadcast_to(r0[:1], (halo, w)), from_above)
    bot = jnp.where(i == bands - 1, jnp.broadcast_to(r0[-1:], (halo, w)), from_below)
    return jnp.concatenate([top, r0, bot], axis=0)


def _ppermute_cols(r0, halo: int, cols: int, axis: str):
    """Column mirror of _ppermute_slab: exchange `halo` boundary COLUMNS
    with the adjacent tiles over the mesh (tile 0 / tile C-1
    edge-replicate, matching the full-row encoder's horizontal edge pad
    and the decoder's picture clamp). Run BEFORE the row exchange so the
    vertically-exchanged rows already carry their horizontal halos — the
    diagonal corner blocks then hold the diagonal neighbour's pixels."""
    if halo == 0 or cols == 1:
        return r0
    h = r0.shape[0]
    from_left = jax.lax.ppermute(
        r0[:, -halo:], axis, [(c, c + 1) for c in range(cols - 1)])
    from_right = jax.lax.ppermute(
        r0[:, :halo], axis, [(c + 1, c) for c in range(cols - 1)])
    i = jax.lax.axis_index(axis)
    left = jnp.where(i == 0, jnp.broadcast_to(r0[:, :1], (h, halo)), from_left)
    right = jnp.where(i == cols - 1, jnp.broadcast_to(r0[:, -1:], (h, halo)),
                      from_right)
    return jnp.concatenate([left, r0, right], axis=1)


# keys merged tile->row before the per-row pack: everything the sparse
# packers read, in MB-grid layout (axis 1 = MB column). recon stays
# per-tile — it is next frame's per-chip reference.
_ROW_MERGE_KEYS = ("mvs", "resid_zero", "luma_ac", "chroma_dc", "chroma_ac")


def _row_pack(row, nscap: int, cap_rows: int, entropy):
    """Full-row out dict (post-merge) -> (fused, buf): P_Skip derivation
    on the merged MV grid, then the unchanged per-row pack dispatch
    (_pack_fused — sparse rows or the PR 7 entropy wrap, per row)."""
    row["skip"] = _skip_mask(row["mvs"], row.pop("resid_zero"))
    return _pack_fused(row, nscap, cap_rows, entropy)


def _mesh_tile_p_body(y, u, v, qp, ry, ru, rv, *, bands: int, cols: int,
                      halo: int, halo_cols: int, nscap: int, cap_rows: int,
                      entropy=None):
    """Per-chip tile body (shard_map over the 2D (band, col) mesh):
    column-then-row halo exchange, row-merged coarse votes, independent
    tile encode, then the ``col``-axis row gather + per-row pack. The
    gathered inputs are identical on every chip of a row, so the row's
    fused payload is computed replicated along ``col`` (the host fetches
    the col-0 copy) — the pack is cheap scatters; ME/MC/transform, the
    actual per-chip budget, stays fully tile-split."""
    cur, cu, cv = y[0, 0], u[0, 0], v[0, 0]
    r0, u0, v0 = ry[0, 0], ru[0, 0], rv[0, 0]
    hy = _ppermute_cols(r0, halo_cols, cols, "col")
    hu = _ppermute_cols(u0, halo_cols // 2, cols, "col")
    hv = _ppermute_cols(v0, halo_cols // 2, cols, "col")
    sy = _ppermute_slab(hy, halo, bands, "band")
    su = _ppermute_slab(hu, halo // 2, bands, "band")
    sv = _ppermute_slab(hv, halo // 2, bands, "band")
    # coarse votes with a REAL-column downsampled halo (exchanged in
    # downsampled space so picture-edge replication matches the full-row
    # encoder's post-downsample edge pad), psum-merged over the row:
    # every tile refines the same candidates the full-row encoder picks
    rd_ext = _ppermute_cols(_downsample4(r0), COARSE_R, cols, "col")
    votes = jax.lax.psum(coarse_votes_jnp(cur, rd_ext, COARSE_R), "col")
    coarse = select_coarse_jnp(votes)
    out = encode_tile_p_planes(cur, cu, cv, sy, su, sv, qp, halo=halo,
                               halo_cols=halo_cols, coarse=coarse,
                               defer_skip=True)
    # row gather: each row's C tile outputs merge into the full-row MB
    # grid (axis 1 = MB/pixel column) — the post-ME neighbour exchange
    # that makes seam P_Skip/mvd context identical to the full-row coder
    row = {k: jax.lax.all_gather(out[k], "col", axis=1, tiled=True)
           for k in _ROW_MERGE_KEYS}
    fused, buf = _row_pack(row, nscap, cap_rows, entropy)
    return (fused[None, None], buf[None, None], out["recon_y"][None, None],
            out["recon_u"][None, None], out["recon_v"][None, None])


def _mesh_tile_i_body(y, u, v, qp, *, cols: int, cap_rows: int, tile_w: int):
    """IDR tile body: row 0 of an I slice is a serial DC-prediction chain
    across the FULL row (left-neighbour recon), so the row's source tiles
    are all-gathered and every chip of the row runs the identical
    full-row I encode (IDRs are one-per-GOP — redundant compute on C
    chips beats serializing the chain through one). Each chip keeps its
    own tile's recon crop as the P-step reference."""
    gy = jax.lax.all_gather(y[0, 0], "col", axis=1, tiled=True)
    gu = jax.lax.all_gather(u[0, 0], "col", axis=1, tiled=True)
    gv = jax.lax.all_gather(v[0, 0], "col", axis=1, tiled=True)
    prefix, buf, ry_, ru_, rv_ = _band_i_body(gy, gu, gv, qp, cap_rows)
    c = jax.lax.axis_index("col")
    ty = jax.lax.dynamic_slice(ry_, (0, c * tile_w), (ry_.shape[0], tile_w))
    tu = jax.lax.dynamic_slice(
        ru_, (0, c * (tile_w // 2)), (ru_.shape[0], tile_w // 2))
    tv = jax.lax.dynamic_slice(
        rv_, (0, c * (tile_w // 2)), (rv_.shape[0], tile_w // 2))
    return (prefix[None, None], buf[None, None], ty[None, None],
            tu[None, None], tv[None, None])


def _stacked_tile_p_step(ys, us, vs, qp, rys, rus, rvs, *, bands: int,
                         cols: int, halo: int, halo_cols: int, nscap: int,
                         cap_rows: int, entropy=None):
    """Single-device fallback of the tile-grid P step: identical per-tile
    graphs run in a static Python loop (the per-tile oracle stays a
    byte-identity statement), slabs/votes built from the reassembled
    full planes with the same edge semantics as the mesh exchanges."""
    b, c, th, tw = rys.shape
    cth, ctw = th // 2, tw // 2
    hc, hcc = halo_cols, halo_cols // 2
    fy = rys.transpose(0, 2, 1, 3).reshape(b * th, c * tw)
    fu = rus.transpose(0, 2, 1, 3).reshape(b * cth, c * ctw)
    fv = rvs.transpose(0, 2, 1, 3).reshape(b * cth, c * ctw)
    py = jnp.pad(fy, ((halo, halo), (hc, hc)), mode="edge")
    pu = jnp.pad(fu, ((halo // 2, halo // 2), (hcc, hcc)), mode="edge")
    pv = jnp.pad(fv, ((halo // 2, halo // 2), (hcc, hcc)), mode="edge")
    twd = tw // 4  # downsampled tile width (coarse vote geometry)
    fused_rows, buf_rows = [], []
    recon = [[None] * c for _ in range(b)]
    for r in range(b):
        # merged coarse votes of the row (the psum's serial analogue)
        rd = jnp.pad(_downsample4(fy[r * th:(r + 1) * th]),
                     ((0, 0), (COARSE_R, COARSE_R)), mode="edge")
        votes = sum(
            coarse_votes_jnp(
                ys[r, k], rd[:, k * twd : (k + 1) * twd + 2 * COARSE_R],
                COARSE_R)
            for k in range(c))
        coarse = select_coarse_jnp(votes)
        touts = []
        for k in range(c):
            sy = py[r * th : (r + 1) * th + 2 * halo,
                    k * tw : (k + 1) * tw + 2 * hc]
            su = pu[r * cth : (r + 1) * cth + halo,
                    k * ctw : (k + 1) * ctw + 2 * hcc]
            sv = pv[r * cth : (r + 1) * cth + halo,
                    k * ctw : (k + 1) * ctw + 2 * hcc]
            out = encode_tile_p_planes(
                ys[r, k], us[r, k], vs[r, k], sy, su, sv, qp, halo=halo,
                halo_cols=hc, coarse=coarse, defer_skip=True)
            touts.append(out)
            recon[r][k] = (out["recon_y"], out["recon_u"], out["recon_v"])
        row = {key: jnp.concatenate([t[key] for t in touts], axis=1)
               for key in _ROW_MERGE_KEYS}
        fused, buf = _row_pack(row, nscap, cap_rows, entropy)
        fused_rows.append(fused)
        buf_rows.append(buf)
    # fused/buf gain a unit col axis so the host-side handle logic is
    # shape-uniform with the mesh path's (bands, cols, ...) outputs
    return (
        jnp.stack(fused_rows)[:, None],
        jnp.stack(buf_rows)[:, None],
        jnp.stack([jnp.stack([recon[r][k][0] for k in range(c)])
                   for r in range(b)]),
        jnp.stack([jnp.stack([recon[r][k][1] for k in range(c)])
                   for r in range(b)]),
        jnp.stack([jnp.stack([recon[r][k][2] for k in range(c)])
                   for r in range(b)]),
    )


def _stacked_tile_i_step(ys, us, vs, qp, *, bands: int, cols: int,
                         cap_rows: int):
    b, c, th, tw = ys.shape
    prefixes, bufs = [], []
    ry, ru, rv = [], [], []
    for r in range(b):
        gy = ys[r].transpose(1, 0, 2).reshape(th, c * tw)
        gu = us[r].transpose(1, 0, 2).reshape(th // 2, c * tw // 2)
        gv = vs[r].transpose(1, 0, 2).reshape(th // 2, c * tw // 2)
        prefix, buf, ry_, ru_, rv_ = _band_i_body(gy, gu, gv, qp, cap_rows)
        prefixes.append(prefix)
        bufs.append(buf)
        ry.append(jnp.stack([ry_[:, k * tw:(k + 1) * tw] for k in range(c)]))
        ru.append(jnp.stack(
            [ru_[:, k * (tw // 2):(k + 1) * (tw // 2)] for k in range(c)]))
        rv.append(jnp.stack(
            [rv_[:, k * (tw // 2):(k + 1) * (tw // 2)] for k in range(c)]))
    return (jnp.stack(prefixes)[:, None], jnp.stack(bufs)[:, None],
            jnp.stack(ry), jnp.stack(ru), jnp.stack(rv))


def _stacked_i_step(ys, us, vs, qp, *, bands: int, cap_rows: int):
    outs = [_band_i_body(ys[b], us[b], vs[b], qp, cap_rows) for b in range(bands)]
    return tuple(jnp.stack([o[k] for o in outs]) for k in range(5))


def _stacked_p_step(ys, us, vs, qp, rys, rus, rvs, *, bands: int, halo: int,
                    nscap: int, cap_rows: int, entropy=None):
    sy = _stacked_slabs(rys, halo)
    su = _stacked_slabs(rus, halo // 2)
    sv = _stacked_slabs(rvs, halo // 2)
    outs = [
        _band_p_body(ys[b], us[b], vs[b], qp, sy[b], su[b], sv[b],
                     halo=halo, nscap=nscap, cap_rows=cap_rows,
                     entropy=entropy)
        for b in range(bands)
    ]
    return tuple(jnp.stack([o[k] for o in outs]) for k in range(5))


def _mesh_i_body(y, u, v, qp, *, cap_rows: int):
    outs = _band_i_body(y[0], u[0], v[0], qp, cap_rows)
    return tuple(o[None] for o in outs)


def _mesh_p_body(y, u, v, qp, ry, ru, rv, *, bands: int, halo: int,
                 nscap: int, cap_rows: int, entropy=None):
    sy = _ppermute_slab(ry[0], halo, bands, "band")
    su = _ppermute_slab(ru[0], halo // 2, bands, "band")
    sv = _ppermute_slab(rv[0], halo // 2, bands, "band")
    outs = _band_p_body(y[0], u[0], v[0], qp, sy, su, sv,
                        halo=halo, nscap=nscap, cap_rows=cap_rows,
                        entropy=entropy)
    return tuple(o[None] for o in outs)


# row spill past the fused cap: the solo encoder's overflow fetch (same
# bucketing discipline, one definition — drift between the two fetch
# paths would mean different compiled fetch shapes for the same spill)
from selkies_tpu.models.h264.sparse_complete import (
    complete_sparse_slice,
    fetch_rest as _fetch_rest,
)


class _PendingFrame:
    """In-flight state between ``dispatch_frame`` and ``complete_frame``:
    the per-band device handles of a dispatched (unfetched) step plus the
    GOP/QP snapshots the completion must pack against. A static frame
    short-circuits at dispatch and carries its host-built AU here."""

    __slots__ = ("idr", "static_au", "static_stats", "qp", "frame_num",
                 "idr_pic_id", "pfx_h", "full_h", "buf_h", "t0", "t_up",
                 "classify_ms", "convert_ms", "h2d_ms")

    def __init__(self, *, idr: bool, static_au: bytes | None = None):
        self.idr = idr
        self.static_au = static_au


class BandedH264Encoder:
    """Full-frame band/tile-parallel H.264 encoder: frame in, multi-slice
    Annex-B access unit out.

    One IDR then P frames forever (keyframe_interval / force_keyframe as
    in TPUH264Encoder); every picture is `bands` slices, one per chip
    when a band mesh is available, falling back to a single-device
    band-sliced encode (identical bytes, no parallelism) when the mesh
    is smaller than the band count. This is the full-motion / 4K path —
    the delta-upload and tile-cache machinery of the solo encoder is
    intentionally absent (those frames are not device-step-bound); an
    unchanged capture still short-circuits to host-built all-skip
    slices.

    With ``cols > 1`` (SELKIES_TILE_GRID=RxC) each band-row additionally
    splits into C tiles across a 2D ``(band, col)`` chip mesh — compute
    is per-tile, slices (and the whole host completion path) stay per
    band-row via the on-mesh row gather. ``cols=1`` takes the 1D band
    code path unchanged.
    """

    codec = "h264"
    # encode_frame/submit take capture-layer damage-rect hints
    # (FramePrep.scan superset contract)
    accepts_damage = True

    def __init__(self, width: int, height: int, qp: int = 28, fps: int = 60,
                 channels: int = 4, keyframe_interval: int = 0,
                 bands: int | None = None, halo: int | None = None,
                 cols: int | None = None, halo_cols: int | None = None,
                 devices=None, frame_batch: int = 1, pipeline_depth: int = 1,
                 pack_workers: int | None = None,
                 device_entropy: bool | None = None,
                 bits_min_mbs: int | None = None,
                 entropy_coder: str | None = None):
        if channels != 4:
            raise ValueError("band-parallel encode expects BGRx capture (channels=4)")
        self.width = width
        self.height = height
        self.fps = fps
        self.set_qp(qp)
        self.keyframe_interval = int(keyframe_interval)
        self._pad_h = (height + 15) // 16 * 16
        self._pad_w = (width + 15) // 16 * 16
        self._mbh, self._mbw = self._pad_h // 16, self._pad_w // 16
        if bands is None and cols is None:
            grid = grid_from_env()
            if grid is not None:
                bands, cols = grid
        requested = bands if bands is not None else bands_from_env()
        cols_req = 1 if cols is None else max(1, int(cols))
        # device carve: explicit lists are the caller's contract; the
        # default enumerates through the health plane (resilience/
        # devhealth.py) so a rebuild after a chip quarantine lands on
        # the SURVIVING chips — and, when quarantines shrank the slice
        # below the requested carve, on a SHRUNK mesh (fewer bands;
        # grid carves shrink in whole band-rows of `cols` chips) rather
        # than piling the full band count onto one fallback device. A
        # machine that simply has fewer chips than bands (no quarantine)
        # keeps the classic identical-bytes single-device fallback.
        if devices is not None:
            devs = list(devices)
        else:
            pool = get_device_pool()
            devs = pool.healthy_devices()
            if pool.has_quarantined():
                cap = max(1, len(devs) // cols_req)
                if cap < requested:
                    logger.warning(
                        "%dx%d: %d bands requested but only %d healthy "
                        "chips (quarantine active) — shrinking the carve "
                        "to %d bands", width, height, requested,
                        len(devs), cap)
                    requested = cap
        self.bands = usable_bands(self._mbh, requested)
        if self.bands != requested:
            logger.info(
                "%dx%d: %d bands requested, using %d (%d MB rows must split "
                "into equal bands of >= %d rows)", width, height, requested,
                self.bands, self._mbh, MIN_BAND_MB_ROWS)
        self.cols = usable_cols(self._mbw, cols_req)
        if self.cols != cols_req:
            logger.info(
                "%dx%d: %d tile columns requested, using %d (%d MB columns "
                "must split into equal tiles of >= %d columns)", width,
                height, cols_req, self.cols, self._mbw, MIN_TILE_MB_COLS)
        halo = halo_from_env() if halo is None else int(halo)
        # a real band slab (bands > 1) needs at least the refine grid's
        # reach + the chroma bilinear lookahead in REAL rows — see
        # encode_band_p_planes; below that, a single band's slab IS the
        # full reference and halo collapses to the 0 identity case
        self.halo = max(0, min(BAND_HALO, halo - halo % 2))
        if self.halo < 4:
            self.halo = 0 if self.bands == 1 else 4
        if self.halo != halo:
            logger.info("band halo %d adjusted to %d", halo, self.halo)
        # column halo: 0 (full-width slab) in band mode, else the same
        # adjustment rules as the row halo. NOTE: below 36 the horizontal
        # candidate window clamps and the RxC == SELKIES_BANDS=R byte
        # oracle no longer holds (still a valid, decodable stream).
        halo_cols = (tile_halo_from_env() if halo_cols is None
                     else int(halo_cols))
        if self.cols == 1:
            self.halo_cols = 0
        else:
            self.halo_cols = max(4, min(BAND_HALO, halo_cols - halo_cols % 2))
            if self.halo_cols != halo_cols:
                logger.info("tile column halo %d adjusted to %d", halo_cols,
                            self.halo_cols)
            if self.bands == 1:
                # a single band-row spans the full frame height: the
                # band-axis ppermute exchanges nothing, so the tile slab
                # IS the full-height reference (halo=0 identity case)
                self.halo = 0
        self.spans = band_spans(self._mbh, self.bands)
        self._band_mbh = self._mbh // self.bands
        self._band_h = 16 * self._band_mbh
        self._tile_mbw = self._mbw // self.cols
        self._tile_w = 16 * self._tile_mbw
        # per-ROW downlink geometry: slices stay one-per-band-row in tile
        # mode (the col axis gathers before the pack), so every cap/fetch
        # shape below is identical to the same-R band encoder's
        m_band = self._band_mbh * self._mbw
        # per-band downlink caps: nscap = the band's MB count makes the
        # dense-header fallback unreachable; the row cap matches the solo
        # encoder's per-frame prefix budget so bands=1 fetches the exact
        # same shapes
        self._nscap = m_band
        self._cap_p = min(26 * m_band, 4096)
        self._cap_i = min(27 * m_band, 4096)
        self._hdr_words_i = i_header_words(self._band_mbh, self._mbw)
        # per-band activity-proportional device entropy (the solo
        # encoder's knobs resolved at per-slice geometry — one shared
        # resolver, device_cavlc.resolve_entropy): a busy band downlinks
        # its final slice bits instead of coefficient rows
        # PPS-scoped entropy backend: every band slice of the stream
        # uses the same coder (SELKIES_ENTROPY_CODER; explicit wins)
        self._coder = entropy_coder_default(entropy_coder)
        (self.device_entropy, self.bits_min_mbs, self._bits_words,
         self._entropy) = resolve_entropy(m_band, device_entropy,
                                          bits_min_mbs,
                                          entropy_coder=self._coder)
        if self._entropy is not None:
            self._pfx_total = p_sparse_entropy_words(
                self._band_mbh, self._mbw, self._nscap, self._cap_p,
                False, self._bits_words, entropy_coder=self._coder)
        else:
            self._pfx_total = p_sparse_var_words(
                self._band_mbh, self._mbw, self._nscap, self._cap_p)
        # two fetch shapes only (compile discipline, encoder.py PFX_SMALL)
        self._pfx_small = min(1 << 14, self._pfx_total)
        self._pfx_hint = self._pfx_small
        self._pfx_recent: list[int] = []
        self._pfx_lock = threading.Lock()

        chips = self.bands * self.cols
        self.mesh_enabled = chips > 1 and len(devs) >= chips
        self.params = StreamParams(width=width, height=height, qp=self.qp,
                                   fps=fps, entropy_coder=self._coder)
        self._headers = write_sps(self.params) + write_pps(self.params)
        from selkies_tpu.models.frameprep import FramePrep

        self._prep = FramePrep(width, height, self._pad_w, self._pad_h, nslots=2)
        # 1D band-step constants (unused by the cols > 1 tile branch,
        # but built once so the mesh and fallback band paths can never
        # compile against different constants)
        iconsts = dict(cap_rows=self._cap_i)
        pconsts = dict(bands=self.bands, halo=self.halo, nscap=self._nscap,
                       cap_rows=self._cap_p, entropy=self._entropy)
        if self.cols > 1:
            ticonsts = dict(cols=self.cols, cap_rows=self._cap_i,
                            tile_w=self._tile_w)
            tpconsts = dict(bands=self.bands, cols=self.cols, halo=self.halo,
                            halo_cols=self.halo_cols, nscap=self._nscap,
                            cap_rows=self._cap_p, entropy=self._entropy)
            if self.mesh_enabled:
                self.mesh = tile_mesh(self.bands, self.cols, devs)
                self._shard = NamedSharding(self.mesh, P("band", "col"))
                spec = P("band", "col")
                self._step_i = jax.jit(jax.shard_map(
                    partial(_mesh_tile_i_body, **ticonsts), mesh=self.mesh,
                    in_specs=(spec, spec, spec, P()), out_specs=spec,
                    check_vma=False))
                self._step_p = jax.jit(
                    jax.shard_map(
                        partial(_mesh_tile_p_body, **tpconsts), mesh=self.mesh,
                        in_specs=(spec, spec, spec, P(), spec, spec, spec),
                        out_specs=spec, check_vma=False),
                    donate_argnums=(4, 5, 6))
            else:
                logger.info(
                    "tile mesh unavailable (%d devices < %dx%d grid): "
                    "running the tile-sliced step on one device (identical "
                    "bytes, no intra-frame parallelism)", len(devs),
                    self.bands, self.cols)
                self.mesh = None
                self._shard = None
                self._fallback_dev = devs[0] if devs else None
                self._step_i = jax.jit(partial(
                    _stacked_tile_i_step, bands=self.bands, cols=self.cols,
                    cap_rows=self._cap_i))
                self._step_p = jax.jit(partial(_stacked_tile_p_step,
                                               **tpconsts),
                                       donate_argnums=(4, 5, 6))
        elif self.mesh_enabled:
            self.mesh = band_mesh(self.bands, devs)
            self._shard = NamedSharding(self.mesh, P("band"))
            spec = P("band")
            self._step_i = jax.jit(jax.shard_map(
                partial(_mesh_i_body, **iconsts), mesh=self.mesh,
                in_specs=(spec, spec, spec, P()), out_specs=spec,
                check_vma=False))
            self._step_p = jax.jit(
                jax.shard_map(
                    partial(_mesh_p_body, **pconsts), mesh=self.mesh,
                    in_specs=(spec, spec, spec, P(), spec, spec, spec),
                    out_specs=spec, check_vma=False),
                donate_argnums=(4, 5, 6))
        else:
            if self.bands > 1:
                logger.info(
                    "band mesh unavailable (%d devices < %d bands): running "
                    "the band-sliced step on one device (identical bytes, "
                    "no intra-frame parallelism)", len(devs), self.bands)
            self.mesh = None
            self._shard = None
            # honor the assigned device (a fleet round-robins fallback
            # sessions across chips); None = the process default
            self._fallback_dev = devs[0] if devs else None
            self._step_i = jax.jit(partial(_stacked_i_step, bands=self.bands,
                                           **iconsts))
            self._step_p = jax.jit(partial(_stacked_p_step, **pconsts),
                                   donate_argnums=(4, 5, 6))
        # the chips this encoder actually dispatches to: the device
        # fault site checks exactly these each frame, and the health
        # plane's restart regression asserts a rebuilt encoder's carve
        # against them
        self.devices = (list(devs[:chips]) if self.mesh_enabled
                        else ([getattr(self, "_fallback_dev", None)]
                              if getattr(self, "_fallback_dev", None)
                              is not None else []))
        # per-band completion fan-out over the h264-pack pool, sized for
        # every slice that can be in flight at once (the solo formula
        # gains the bands factor — see encoder.py)
        if pack_workers is None:
            pack_workers = min(
                os.cpu_count() or 4,
                max(2, self.bands * max(1, frame_batch) * max(1, pipeline_depth)),
            )
        self._pack_pool = ThreadPoolExecutor(
            max_workers=pack_workers, thread_name_prefix="h264-pack")
        self.link_bytes = LinkByteCounter()
        self._ref = None  # stacked (bands, band_h, W) recon triple
        self._allskip: PFrameCoeffs | None = None
        self.frame_index = 0
        self._frames_since_idr = 0
        self._idr_pic_id = 0
        self._force_idr = True
        self.last_stats: FrameStats | None = None
        # dispatch/complete split guard (occupancy scheduler): at most
        # one frame in flight — self._ref advances at dispatch
        self._inflight = False

    # -- live retune API ------------------------------------------------

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

    # -- device dispatch ------------------------------------------------

    def _put_band_planes(self, y: np.ndarray, u: np.ndarray, v: np.ndarray):
        """Stack converted planes on a leading band axis — (bands, cols)
        leading axes in tile-grid mode — and upload, sharded one band
        (tile) per chip on the mesh (each chip receives only its own
        pixels), plain on the fallback device."""
        b, bh = self.bands, self._band_h
        if self.cols > 1:
            c, tw = self.cols, self._tile_w
            ys = np.ascontiguousarray(
                np.asarray(y).reshape(b, bh, c, tw).transpose(0, 2, 1, 3))
            us = np.ascontiguousarray(
                np.asarray(u).reshape(b, bh // 2, c, tw // 2)
                .transpose(0, 2, 1, 3))
            vs = np.ascontiguousarray(
                np.asarray(v).reshape(b, bh // 2, c, tw // 2)
                .transpose(0, 2, 1, 3))
        else:
            ys = np.asarray(y).reshape(b, bh, self._pad_w)
            us = np.asarray(u).reshape(b, bh // 2, self._pad_w // 2)
            vs = np.asarray(v).reshape(b, bh // 2, self._pad_w // 2)
        self.link_bytes.add("up_full", ys.nbytes + us.nbytes + vs.nbytes)
        dst = self._shard if self._shard is not None else self._fallback_dev
        return (jax.device_put(ys, dst), jax.device_put(us, dst),
                jax.device_put(vs, dst))

    def _band_handles(self, arr):
        """Per-band-row device handles of a stacked (bands, ...) output,
        in band order. On the mesh these are the per-chip shards (so a
        fetch pulls only from that band's chip); on the fallback device
        they are row slices of the same array. In tile-grid mode the
        per-row downlink payloads are (bands, cols, ...) with identical
        copies along ``col`` (every chip of a row computed the gathered
        row pack) — the fetch pulls the col-0 chip's copy."""
        if self.cols > 1:
            if self._shard is None:  # fallback: unit col axis
                return [arr[b, 0] for b in range(self.bands)]
            handles = [None] * self.bands
            for sh in arr.addressable_shards:
                # a size-1 mesh axis leaves its dim unpartitioned, so the
                # shard index is slice(None) there — start None means 0
                if (sh.index[1].start or 0) == 0:
                    handles[sh.index[0].start or 0] = sh.data[0, 0]
            if any(h is None for h in handles):  # non-addressable topology
                return [arr[b, 0] for b in range(self.bands)]
            return handles
        if self._shard is None or self.bands == 1:
            return [arr[b] for b in range(self.bands)]
        handles = [None] * self.bands
        for sh in arr.addressable_shards:
            # drop the unit band axis on the owning chip (a view-level
            # slice, enqueued behind the step like any other device op)
            handles[sh.index[0].start] = sh.data[0]
        if any(h is None for h in handles):  # non-addressable topology
            return [arr[b] for b in range(self.bands)]
        return handles

    def _pfx_slice_len(self) -> int:
        with self._pfx_lock:
            return self._pfx_hint

    def _note_need(self, need: int) -> None:
        with self._pfx_lock:
            self._pfx_recent.append(need)
            del self._pfx_recent[:-8]
            want = max([2048] + [n * 3 // 2 for n in self._pfx_recent])
            self._pfx_hint = (
                self._pfx_small if want <= self._pfx_small else self._pfx_total)

    # -- host completion (per band, on the pack pool) -------------------

    def _complete_band_i(self, band: int, pfx_d, buf_d, idr_pic_id: int):
        jax.block_until_ready(pfx_d)  # keep fetch_ms a pure-transfer time
        t0 = time.perf_counter()
        with tracer.span("fetch"):
            prefix = np.asarray(pfx_d)
        t_f = time.perf_counter()
        self.link_bytes.add("down_prefix", prefix.nbytes)
        header, data, n = split_prefix(prefix, self._hdr_words_i)
        if n > self._cap_i:
            rest = _fetch_rest(buf_d, n, self._cap_i)
            self.link_bytes.add("down_spill", rest.nbytes)
            data = np.concatenate([data, rest])
        with tracer.span("unpack"):
            fc = unpack_i_compact(header, data, self.qp)
        t_u = time.perf_counter()
        with tracer.span("pack"):
            if self._coder == "cabac":
                nal = pack_slice_cabac(
                    fc, self.params, frame_num=0, idr=True,
                    idr_pic_id=idr_pic_id,
                    first_mb=self.spans[band][0] * self._mbw)
            else:
                nal = pack_slice_fast(
                    fc, self.params, frame_num=0, idr=True,
                    idr_pic_id=idr_pic_id,
                    first_mb=self.spans[band][0] * self._mbw)
        return (nal, 0, t_f - t0, t_u - t_f, time.perf_counter() - t_u, t_f,
                "")  # downlink_mode is a P-frame label — "" on IDR rows

    def _complete_band_p(self, band: int, pfx_d, full_d, buf_d, frame_num: int,
                         qp: int):
        jax.block_until_ready(pfx_d)  # keep fetch_ms a pure-transfer time
        t0 = time.perf_counter()
        with tracer.span("fetch"):
            fused = np.asarray(pfx_d)
        t_f = time.perf_counter()
        # shared per-slice flow (models/h264/sparse_complete.py): entropy
        # meta (bits splice vs coeff rows), need + hint feedback,
        # shortfall refetch, row spill, native wire pack vs Python dense
        # fallback — one band IS one slice, so the solo delta-frame
        # completion applies verbatim with this band's geometry and
        # first_mb offset (dense_d omitted: nscap equals the band's MB
        # count, the dense-header fallback is unreachable; down_prefix/
        # down_bits accounting happens inside, where the mode is known)
        nal, skipped, t_u, mode = complete_sparse_slice(
            fused, mbh=self._band_mbh, mbw=self._mbw, nscap=self._nscap,
            cap_rows=self._cap_p, qp=qp, frame_num=frame_num,
            params=self.params, device_bits=self._entropy is not None,
            full_d=full_d, buf_d=buf_d,
            link_bytes=self.link_bytes, prefix_bytes=fused.nbytes,
            note_need=self._note_need,
            first_mb=self.spans[band][0] * self._mbw,
            entropy_coder=self._coder)
        return (nal, skipped, t_f - t0, t_u - t_f,
                time.perf_counter() - t_u, t_f, mode)

    # -- static short-circuit -------------------------------------------

    def _allskip_au(self, frame_num: int) -> bytes:
        """Unchanged capture: every band becomes an all-skip P slice,
        built host-side — no upload, no device step, no downlink (the
        decoder's recon stays exactly the device reference)."""
        if self._allskip is None:
            bm, mw = self._band_mbh, self._mbw
            self._allskip = PFrameCoeffs(
                mvs=np.zeros((bm, mw, 2), np.int32),
                skip=np.ones((bm, mw), bool),
                luma_ac=np.zeros((bm, mw, 4, 4, 4, 4), np.int32),
                chroma_dc=np.zeros((bm, mw, 2, 2, 2), np.int32),
                chroma_ac=np.zeros((bm, mw, 2, 2, 2, 4, 4), np.int32),
                qp=self.qp,
            )
        self._allskip.qp = self.qp
        if self._coder == "cabac":
            return b"".join(
                pack_slice_p_cabac(self._allskip, self.params, frame_num,
                                   first_mb=mb0 * self._mbw)
                for mb0, _ in self.spans
            )
        return b"".join(
            pack_slice_p_fast(self._allskip, self.params, frame_num=frame_num,
                              first_mb=mb0 * self._mbw)
            for mb0, _ in self.spans
        )

    # -- encoding -------------------------------------------------------

    def encode_frame(self, frame: np.ndarray, qp: int | None = None,
                     damage=None) -> bytes:
        """Synchronous encode: (H, W, 4) BGRx uint8 in, complete multi-
        slice Annex-B access unit out (SPS/PPS prepended on IDR).

        ``damage``: optional capture-layer dirty-rect hints (superset
        contract, FramePrep.scan) bounding the static-detection scan —
        an idle tick with a tight hint stops reading the whole frame.

        Composed of :meth:`dispatch_frame` + :meth:`complete_frame` —
        the occupancy scheduler's split (parallel/occupancy.py) — so the
        overlapped path is byte-identical to this one by construction."""
        return self.complete_frame(self.dispatch_frame(frame, qp,
                                                       damage=damage))

    def dispatch_frame(self, frame: np.ndarray, qp: int | None = None,
                       damage=None) -> "_PendingFrame":
        """Front half of :meth:`encode_frame`: host front-end (fused
        dirty scan, BGRx->I420 conversion, h2d upload) plus the ASYNC
        device step dispatch. Returns a pending token whose downlink has
        been enqueued on the chips but not fetched — the caller's thread
        is free while the device steps (jax dispatch returns before the
        chips finish). Exactly one frame may be in flight per encoder:
        the reference-plane donation chain (``self._ref``) advances at
        dispatch, so a second dispatch before ``complete_frame`` would
        step against a recon the client never received."""
        if self._inflight:
            raise RuntimeError(
                "dispatch_frame while a frame is in flight — "
                "complete_frame the previous token first")
        if qp is not None:
            self.set_qp(qp)
        # deterministic device chaos (resilience/devhealth.py): a
        # scheduled device:<chip> fault kills (DeviceFault), wedges
        # (delay) or flaps this encoder's chips exactly where hardware
        # would — BEFORE the scan mutates any previous-frame state, so
        # a killed tick leaves the front-end consistent and the next
        # frame self-heals cleanly. One injector read when unset.
        check_device_faults(self.devices)
        t0 = time.perf_counter()
        idr = (
            self._force_idr
            or self._ref is None
            or (self.keyframe_interval > 0
                and self._frames_since_idr >= self.keyframe_interval)
        )
        # fused band-granular scan (ISSUE 12): dirty detection + the
        # previous-frame update for dirty bands only, sharded across the
        # front-end pool — replacing the strided probe + full-frame
        # array_equal + full-frame copyto triple read/write
        scan = self._prep.scan(frame, self.width, damage=damage)
        static = not idr and scan is not None and not scan.tiles.any()
        classify_ms = (time.perf_counter() - t0) * 1e3
        if static:
            au = self._allskip_au(self._frames_since_idr % 256)
            stats = FrameStats(
                frame_index=self.frame_index, idr=False, qp=self.qp,
                bytes=len(au), device_ms=(time.perf_counter() - t0) * 1e3,
                pack_ms=0.0, skipped_mbs=self._mbh * self._mbw,
                bands=self.bands, cols=self.cols,
                upload_ms=classify_ms, classify_ms=classify_ms,
                upload_kind="static",
            )
            pending = _PendingFrame(idr=False, static_au=au)
            pending.static_stats = stats
            self._inflight = True
            return pending
        t_c0 = time.perf_counter()
        y, u, v = self._prep.convert(frame)
        t_h0 = time.perf_counter()
        parts = self._put_band_planes(y, u, v)
        t_up = time.perf_counter()
        convert_ms = (t_h0 - t_c0) * 1e3
        h2d_ms = (t_up - t_h0) * 1e3
        qp32 = np.int32(self.qp)
        try:
            if idr:
                prefix_d, buf_d, ry, ru, rv = self._step_i(*parts, qp32)
            else:
                prefix_d, buf_d, ry, ru, rv = self._step_p(*parts, qp32, *self._ref)
            self._ref = (ry, ru, rv)
        except Exception:
            # a failed/aborted step may have consumed the donated refs:
            # null them so the next frame self-heals as an IDR
            self._ref = None
            self._prep.reset()
            raise
        # hint-sized fused slices, dispatched from the submit thread
        # right behind the step (a later slice op would queue behind
        # other work); per-band handles so each fetch pulls one chip
        if idr:
            pfx = prefix_d
        else:
            hint = self._pfx_slice_len()
            if hint >= self._pfx_total:
                pfx = prefix_d
            elif self.cols > 1:
                pfx = prefix_d[:, :, :hint]
            else:
                pfx = prefix_d[:, :hint]
        pending = _PendingFrame(idr=idr)
        pending.pfx_h = self._band_handles(pfx)
        pending.full_h = self._band_handles(prefix_d)
        pending.buf_h = self._band_handles(buf_d)
        # GOP/QP snapshots: complete_frame must pack against the state
        # this frame was DISPATCHED under, even if a policy set_qp or a
        # force_keyframe lands between the halves on the scheduler
        pending.qp = self.qp
        pending.frame_num = self._frames_since_idr % 256
        pending.idr_pic_id = self._idr_pic_id
        pending.t0, pending.t_up = t0, t_up
        pending.classify_ms = classify_ms
        pending.convert_ms, pending.h2d_ms = convert_ms, h2d_ms
        self._inflight = True
        return pending

    def complete_frame(self, pending: "_PendingFrame") -> bytes:
        """Back half of :meth:`encode_frame`: per-band downlink fetch +
        host unpack/CAVLC pack fan-out, stats assembly, and the GOP
        state advance. Blocks until the dispatched step's outputs are
        ready — this is where the device wait lives, so the occupancy
        scheduler runs it on a completion worker while the caller's
        thread dispatches the next session."""
        self._inflight = False
        if pending.static_au is not None:
            self.last_stats = pending.static_stats
            self.frame_index += 1
            self._frames_since_idr += 1
            return pending.static_au
        idr = pending.idr
        pfx_h, full_h, buf_h = pending.pfx_h, pending.full_h, pending.buf_h
        t0, t_up = pending.t0, pending.t_up
        classify_ms = pending.classify_ms
        convert_ms, h2d_ms = pending.convert_ms, pending.h2d_ms

        def _one(b: int):
            if idr:
                return self._complete_band_i(b, pfx_h[b], buf_h[b],
                                             pending.idr_pic_id)
            return self._complete_band_p(b, pfx_h[b], full_h[b], buf_h[b],
                                         pending.frame_num, pending.qp)

        # per-band step timing: ready time of each band's downlink on its
        # chip (the profile tool and bench read band_step_ms off stats).
        # Measured on the MAIN thread, in band order, while completions
        # run on the pack pool — a pool smaller than the band count would
        # otherwise queue later bands behind earlier bands' host packs
        # and report that host time as device step latency.
        t_ready = [0.0] * self.bands
        # span vocabulary: "row_gather" is the tile-grid fan-out (per-ROW
        # payloads off a 2D mesh — each already col-merged on device),
        # "band_gather" the classic 1D band fan-out (tracing.py)
        gather_stage = "row_gather" if self.cols > 1 else "band_gather"
        try:
            with tracer.span(gather_stage):
                futs = [self._pack_pool.submit(_one, b)
                        for b in range(self.bands)]
                for b in range(self.bands):
                    with tracer.span("step"):
                        jax.block_until_ready(pfx_h[b])
                    t_ready[b] = time.perf_counter()
                results = [f.result() for f in futs]
        except Exception:
            # a failed band fetch/pack means the client never receives
            # this frame, but self._ref already advanced to its recon:
            # null the chain so the next frame self-heals as a full IDR
            # instead of silently desyncing the decoder
            self._ref = None
            self._prep.reset()
            raise
        t_done = time.perf_counter()
        nals = [r[0] for r in results]
        au = (self._headers + b"".join(nals)) if idr else b"".join(nals)
        skipped = sum(r[1] for r in results)
        # wall-clock attribution matching the solo encoder's device_ms
        # (dispatch -> downlink fetched): the overlapped per-band d2h
        # transfers contribute their slowest tail, so fetch_ms is the
        # max band fetch and device_ms runs to the LAST band's fetch
        # end; unpack/cavlc stay per-band sums (host pool work)
        fetch_ms = max(r[2] for r in results) * 1e3
        t_fetched = max(r[5] for r in results)
        unpack_ms = sum(r[3] for r in results) * 1e3
        cavlc_ms = sum(r[4] for r in results) * 1e3
        # per-band payload modes fold into one frame-level label: "bits"
        # only when EVERY slice shipped device bits ("dense" never occurs
        # here — band nscap equals the band MB count)
        modes = {r[6] for r in results}
        downlink_mode = ("dense" if "dense" in modes
                         else "bits" if modes == {"bits"}
                         else "cabac" if modes == {"cabac"}
                         else "coeff" if "coeff" in modes else "")
        band_step = tuple(round((t - t_up) * 1e3, 3) for t in t_ready)
        step_ms = (max(t_ready) - t_up) * 1e3
        if telemetry.enabled:
            telemetry.stage_ms(gather_stage, (t_done - t_up) * 1e3)
            for ms in band_step:
                telemetry.stage_ms("step", ms)
        stats = FrameStats(
            frame_index=self.frame_index, idr=idr, qp=pending.qp,
            bytes=len(au), device_ms=(t_fetched - t0) * 1e3,
            pack_ms=unpack_ms + cavlc_ms, skipped_mbs=skipped,
            unpack_ms=unpack_ms, cavlc_ms=cavlc_ms,
            # upload_ms spans the whole host front-end (fused dirty
            # scan, BGRx->I420 conversion, h2d enqueue) — the same
            # boundary as the solo sync path, so a bands-vs-solo A/B
            # attributes conversion time identically on both rows; the
            # classify/convert/h2d split is the ISSUE 12 contract
            upload_ms=(t_up - t0) * 1e3, step_ms=step_ms,
            fetch_ms=fetch_ms, bands=self.bands, cols=self.cols,
            classify_ms=classify_ms, convert_ms=convert_ms, h2d_ms=h2d_ms,
            band_step_ms=band_step, downlink_mode=downlink_mode,
        )
        self.last_stats = stats
        if idr:
            self._frames_since_idr = 0
            self._idr_pic_id = (self._idr_pic_id + 1) % 2
            self._force_idr = False
        self.frame_index += 1
        self._frames_since_idr += 1
        return au

    def submit(self, frame: np.ndarray, qp: int | None = None, meta=None,
               damage=None) -> list:
        """Pipelined-API adapter (encoder.py submit/flush contract): the
        band encoder overlaps WITHIN the frame (N chips + the pack pool)
        rather than across frames, so submit completes synchronously and
        returns its one (au, stats, meta) triple immediately. Lets
        bench.py and the VideoPipeline drive either encoder unchanged."""
        au = self.encode_frame(frame, qp, damage=damage)
        return [(au, self.last_stats, meta)]

    def flush(self) -> list:
        return []  # synchronous encoder: nothing ever in flight

    def prewarm(self) -> None:
        """Compile the IDR and P executables before the live loop."""
        rng = np.random.default_rng(0)
        shape = (self.height, self.width, 4)
        self.encode_frame(rng.integers(0, 255, shape, np.uint8))
        self.encode_frame(rng.integers(0, 255, shape, np.uint8))
        self._force_idr = True
        self._ref = None
        self._prep.reset()
        self.frame_index = 0
        self._frames_since_idr = 0
        self._idr_pic_id = 0

    def close(self) -> None:
        self._pack_pool.shutdown(wait=False, cancel_futures=True)
