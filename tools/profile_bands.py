#!/usr/bin/env python3
"""Microbenchmark of the band/tile-parallel device step (parallel/
bands.py): per-band step latency, downlink gather, and multi-slice
assembly overhead vs band count — and, with --grid, 2D tile-grid sweeps
(grid shape × dedicated-chip projection per TILE) for the 4K/8K
split-frame path.

Runs anywhere: with no real TPU it forces an 8-device CPU host mesh
(the same trick tests/conftest.py uses), so band scaling is measurable
in CI containers; on hardware it measures the chips JAX finds. Prints one human line per shape plus
bench.py-shaped JSON lines (the same shape tools/profile_pack.py's
summary feeds the PERF record with):

    JAX_PLATFORMS=cpu python tools/profile_bands.py [--frames N] [--bands 1,2,4]
    JAX_PLATFORMS=cpu python tools/profile_bands.py --width 3840 --height 2160 \\
        --grid 1x1,2x1,2x2 --frames 6

The dedicated-chip projection divides the one-device serial run of the
same R×C-tile program by the tile count — what a chip per tile delivers
when host cores stop being the bound (the PERF.md round-8 methodology;
the concurrent-mesh row is reported alongside). For grids it slightly
under-counts per-chip work: on a real mesh every chip of a row
recomputes the cheap row pack after the gather (the serial program runs
it once per row) — the separately-timed `col_halo`/`row_gather` probe
bounds that term.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

# must land before jax import: an 8-device host mesh on CPU-only boxes
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    ).strip()

import numpy as np  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

from selkies_tpu.monitoring.tracing import tracer  # noqa: E402
from selkies_tpu.parallel.bands import (  # noqa: E402
    BandedH264Encoder,
    usable_bands,
    usable_cols,
)


def _motion_frames(w: int, h: int, n: int) -> list[np.ndarray]:
    """Full-motion trace (the band path's target workload): a textured
    frame scrolling diagonally, every frame a full-frame change."""
    rng = np.random.default_rng(11)
    base = rng.integers(0, 256, (h, w, 4), np.uint8)
    return [np.roll(np.roll(base, 4 * i, 0), 7 * i, 1).copy() for i in range(n)]


def profile_halo_gather(enc, iters: int = 32) -> dict:
    """Time the tile grid's collective terms in isolation: `col_halo`
    (column+row halo slab construction from the stacked reference — the
    serial analogue of the two ppermute exchanges) and `row_gather` (the
    per-row merge of the per-tile coefficient tensors — the serial
    analogue of the col-axis all_gather). On a real mesh both are ICI
    collectives; this bounds the term the dedicated-chip projection
    amortizes. Emitted under the matching tracer span names so trace
    summaries carry them (monitoring/tracing.py vocabulary)."""
    import jax.numpy as jnp

    b, c = enc.bands, enc.cols
    th, tw = enc._band_h, enc._tile_w
    halo, hc = enc.halo, enc.halo_cols
    rng = np.random.default_rng(3)
    ry = jnp.asarray(rng.integers(0, 256, (b, c, th, tw), np.uint8))

    @jax.jit
    def halo_probe(r):
        f = r.transpose(0, 2, 1, 3).reshape(b * th, c * tw)
        p = jnp.pad(f, ((halo, halo), (hc, hc)), mode="edge")
        return jnp.stack([
            jax.lax.dynamic_slice(
                p, (r_ * th, k * tw), (th + 2 * halo, tw + 2 * hc))
            for r_ in range(b) for k in range(c)])

    tiles = jnp.asarray(
        rng.integers(-8, 8, (b, c, th // 16, tw // 16, 4, 4, 4, 4), np.int32))

    @jax.jit
    def gather_probe(t):
        return jnp.stack([
            jnp.concatenate([t[r_, k] for k in range(c)], axis=1)
            for r_ in range(b)])

    out = {}
    for name, probe, arg in (("col_halo", halo_probe, ry),
                             ("row_gather", gather_probe, tiles)):
        jax.block_until_ready(probe(arg))
        t0 = time.perf_counter()
        for _ in range(iters):
            with tracer.span(name):
                res = probe(arg)
        jax.block_until_ready(res)
        out[f"{name}_ms"] = (time.perf_counter() - t0) / iters * 1e3
    return out


def profile_bands(bands: int, w: int, h: int, frames: list[np.ndarray],
                  qp: int = 28, force_serial: bool = False,
                  cols: int = 1) -> dict:
    devices = jax.devices()[:1] if force_serial else None
    enc = BandedH264Encoder(w, h, qp=qp, bands=bands, cols=cols,
                            devices=devices)
    try:
        enc.encode_frame(frames[0])      # compile IDR
        enc.encode_frame(frames[1])      # compile P
        enc.encode_frame(frames[2])      # steady
        sums = {"wall_ms": 0.0, "step_ms": 0.0, "fetch_ms": 0.0,
                "pack_ms": 0.0, "upload_ms": 0.0}
        band_step = np.zeros(enc.bands)
        n = 0
        au = b""
        for f in frames[3:]:
            t0 = time.perf_counter()
            au = enc.encode_frame(f)
            sums["wall_ms"] += (time.perf_counter() - t0) * 1e3
            s = enc.last_stats
            sums["step_ms"] += s.step_ms
            sums["fetch_ms"] += s.fetch_ms
            sums["pack_ms"] += s.pack_ms
            sums["upload_ms"] += s.upload_ms
            band_step += np.asarray(s.band_step_ms)
            n += 1
        # assembly overhead: re-join the last AU's slice NALs (what the
        # encoder does after the per-band fan-out) — amortized cost of
        # the multi-slice access unit itself
        nals = [b"\x00\x00\x00\x01" + p
                for p in au.split(b"\x00\x00\x00\x01")[1:]]
        t0 = time.perf_counter()
        for _ in range(256):
            b"".join(nals)
        asm_ms = (time.perf_counter() - t0) / 256 * 1e3
        out = {k: v / n for k, v in sums.items()}
        out["assemble_ms"] = asm_ms
        out["band_step_ms"] = [round(x / n, 2) for x in band_step]
        out["bands"] = enc.bands
        out["cols"] = enc.cols
        out["mesh"] = enc.mesh_enabled
        out["au_bytes"] = len(au)
        if enc.cols > 1:
            out.update(profile_halo_gather(enc))
        return out
    finally:
        enc.close()


def _grid_sweep(args, mbh: int, mbw: int, frames: list[np.ndarray]) -> int:
    """RxC tile-grid sweep: per-shape wall/step/gather rows plus the
    dedicated-chip projection per TILE (the PERF.md round-8 methodology
    extended to two axes: the same R×C-tile program run serially on ONE
    device, divided by the tile count — what a chip per tile delivers
    when host cores stop being the bound). The 1x1 row is the projection
    baseline; the concurrent-mesh row is always reported alongside."""
    shapes = []
    for token in args.grid.split(","):
        token = token.strip().lower().replace("×", "x")
        if not token:
            continue
        r_s, _, c_s = token.partition("x")
        r, c = usable_bands(mbh, int(r_s)), usable_cols(mbw, int(c_s or 1))
        if (r, c) not in shapes:
            shapes.append((r, c))
    results = {}
    for r, c in shapes:
        out = profile_bands(r, args.width, args.height, frames, args.qp,
                            cols=c)
        if r * c > 1:
            serial = profile_bands(r, args.width, args.height, frames,
                                   args.qp, force_serial=True, cols=c)
            out["per_tile_isolated_ms"] = serial["step_ms"] / (r * c)
        results[(r, c)] = out
        extra = "".join(
            f"  {k.split('_ms')[0]} {out[k]:5.2f}" for k in
            ("col_halo_ms", "row_gather_ms") if k in out)
        print(f"grid={r}x{c} (mesh={out['mesh']}): "
              f"wall {out['wall_ms']:7.1f} ms  step {out['step_ms']:7.1f}  "
              f"fetch {out['fetch_ms']:5.2f}  pack {out['pack_ms']:5.1f}"
              + extra)
        doc = {
            "metric": f"tile grid step latency ({r}x{c}, "
                      f"{args.width}x{args.height})",
            "value": round(out["step_ms"], 2), "unit": "ms/frame",
            "wall_ms": round(out["wall_ms"], 2),
            "fetch_ms": round(out["fetch_ms"], 3),
            "pack_ms": round(out["pack_ms"], 2),
            "assemble_ms": round(out["assemble_ms"], 4),
            "band_step_ms": out["band_step_ms"],
            "bands": r, "cols": c, "mesh": out["mesh"],
            "au_bytes": out["au_bytes"],
        }
        for k in ("per_tile_isolated_ms", "col_halo_ms", "row_gather_ms"):
            if k in out:
                doc[k] = round(out[k], 3)
        print(json.dumps(doc))

    base = results.get((1, 1))
    if base is not None:
        for (r, c), out in results.items():
            if (r, c) == (1, 1):
                continue
            doc = {
                "metric": f"tile step speedup ({r}x{c} vs 1x1, "
                          f"{args.width}x{args.height})",
                "value": round(base["step_ms"] / out["step_ms"], 2),
                "unit": "x",
            }
            if "per_tile_isolated_ms" in out:
                # dedicated-chip projection: per-tile step cost with a
                # chip per tile vs the 1-band/1-chip frame
                doc["dedicated_chip_speedup"] = round(
                    base["step_ms"] / out["per_tile_isolated_ms"], 2)
            print(json.dumps(doc))
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--width", type=int, default=1920)
    ap.add_argument("--height", type=int, default=1080)
    ap.add_argument("--frames", type=int, default=12)
    ap.add_argument("--bands", default="1,2,4",
                    help="comma-separated band counts to sweep")
    ap.add_argument("--grid", default="",
                    help="comma-separated RxC tile-grid shapes to sweep "
                         "(e.g. 1x1,2x1,2x2) — replaces the --bands sweep")
    ap.add_argument("--qp", type=int, default=28)
    args = ap.parse_args()

    mbh = (args.height + 15) // 16
    mbw = (args.width + 15) // 16
    ndev = len(jax.devices())
    print(f"devices: {ndev} ({jax.default_backend()}), "
          f"{args.width}x{args.height} ({mbh} MB rows), "
          f"{args.frames} timed P frames")
    frames = _motion_frames(args.width, args.height, args.frames + 3)

    if args.grid:
        return _grid_sweep(args, mbh, mbw, frames)

    results = {}
    for req in (int(b) for b in args.bands.split(",")):
        b = usable_bands(mbh, req)
        if b in results:
            continue
        r = profile_bands(b, args.width, args.height, frames, args.qp)
        if b > 1:
            # the same b-band program on ONE device runs the bands
            # serially: total/b is each band's program latency free of
            # host-core contention — i.e. what a DEDICATED chip per band
            # delivers. On starved CPU hosts (2-core CI containers) the
            # concurrent mesh number under-reports the hardware scaling;
            # both are printed.
            serial = profile_bands(b, args.width, args.height, frames,
                                   args.qp, force_serial=True)
            r["per_band_isolated_ms"] = serial["step_ms"] / b
        results[b] = r
        per_band = ("  [" + " ".join(f"{x:6.1f}" for x in r["band_step_ms"]) + "]"
                    if b > 1 else "")
        print(f"bands={b} (mesh={r['mesh']}): wall {r['wall_ms']:7.1f} ms  "
              f"step {r['step_ms']:7.1f}  fetch {r['fetch_ms']:5.2f}  "
              f"pack {r['pack_ms']:5.1f}  assemble {r['assemble_ms']:.3f} ms"
              + per_band)
        doc = {
            "metric": f"band device step latency ({b} bands, "
                      f"{args.width}x{args.height})",
            "value": round(r["step_ms"], 2), "unit": "ms/frame",
            "wall_ms": round(r["wall_ms"], 2),
            "fetch_ms": round(r["fetch_ms"], 3),
            "pack_ms": round(r["pack_ms"], 2),
            "assemble_ms": round(r["assemble_ms"], 4),
            "band_step_ms": r["band_step_ms"],
            "bands": b, "mesh": r["mesh"], "au_bytes": r["au_bytes"],
        }
        if "per_band_isolated_ms" in r:
            doc["per_band_isolated_ms"] = round(r["per_band_isolated_ms"], 2)
        print(json.dumps(doc))

    if 1 in results:
        base = results[1]["step_ms"]
        for b, r in sorted(results.items()):
            if b == 1:
                continue
            doc = {
                "metric": f"band step speedup ({b} vs 1 bands, "
                          f"{args.width}x{args.height})",
                "value": round(base / r["step_ms"], 2), "unit": "x",
                "assemble_ms": round(r["assemble_ms"], 4),
            }
            if "per_band_isolated_ms" in r:
                # dedicated-chip projection: what the mesh delivers when
                # each band really has its own chip (host cores stop
                # being the bound)
                doc["dedicated_chip_speedup"] = round(
                    base / r["per_band_isolated_ms"], 2)
            print(json.dumps(doc))
    return 0


if __name__ == "__main__":
    sys.exit(main())
