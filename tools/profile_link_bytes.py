#!/usr/bin/env python3
"""Per-stage host<->device link-byte profile of tpuh264enc.

Runs the synthetic scroll / window-move traces (pipeline/elements.py)
and the bench desktop trace through the encoder with the tile cache and
packed downlink ON vs OFF, and reports bytes/frame per stage
(up_full / up_delta / up_ltr, down_prefix / down_refetch / down_spill,
plus down_bits / down_bits_refetch / down_bits_spill when device
entropy ships final slice bits — docs/device_entropy.md)
plus the reduction ratios. This is the measurement backing the ISSUE-1
acceptance criteria (>=2x uplink cut on scroll, >=2x prefix-fetch cut
on desktop).

Usage:
  JAX_PLATFORMS=cpu python tools/profile_link_bytes.py [--width W]
      [--height H] [--frames N] [--traces scroll,window,desktop]

Byte counts are deterministic (they measure layout, not the link), so
the CPU backend gives the same numbers the chip would.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _run(frames, *, tile_cache, packed, frame_batch=1, warm=2):
    from selkies_tpu.models.h264.encoder import TPUH264Encoder

    h, w = frames[0].shape[:2]
    enc = TPUH264Encoder(w, h, qp=28, frame_batch=frame_batch,
                         tile_cache=tile_cache,
                         packed_downlink=packed, ltr_scenes=True)
    for f in frames[:warm]:  # IDR + first delta stay out of the count
        enc.encode_frame(f)
    base = enc.link_bytes.snapshot()
    n = 0
    for f in frames[warm:]:
        for _ in enc.submit(f):
            n += 1
    for _ in enc.flush():
        n += 1
    snap = enc.link_bytes.snapshot()
    stages = {k: (v - base.get(k, 0)) / max(n, 1) for k, v in snap.items()}
    out = {
        "frames": n,
        "per_stage_bytes_per_frame": {k: round(v, 1) for k, v in sorted(stages.items())},
        "bytes_up_per_frame": round(sum(v for k, v in stages.items() if k.startswith("up_")), 1),
        "bytes_down_per_frame": round(sum(v for k, v in stages.items() if k.startswith("down_")), 1),
    }
    if enc._tcache is not None:
        out["tile_cache"] = {"hits": enc._tcache.hits, "misses": enc._tcache.misses,
                             "evictions": enc._tcache.evictions}
    enc.close()
    return out


def _desktop_like(w: int, h: int, n: int):
    """bench._desktop_trace's shape (static desktop + terminal text lines
    + cursor blink + window switch every 15 frames) scaled to any
    geometry — the bench trace itself hardcodes 1080p coordinates."""
    import numpy as np

    rng = np.random.default_rng(42)

    def wallpaper(seed):
        r = np.random.default_rng(seed)
        base = r.integers(40, 200, size=(h // 8, w // 8, 4), dtype=np.uint8)
        return np.ascontiguousarray(np.kron(base, np.ones((8, 8, 1), np.uint8))[:h, :w])

    desk_a, desk_b = wallpaper(1), wallpaper(2)
    for d in (desk_a, desk_b):
        d[h // 4 : 3 * h // 4, w // 6 : 5 * w // 6] = (248, 248, 248, 0)
    frames, cur, which = [], desk_a.copy(), 0
    trow = h // 4 + 16
    for i in range(n):
        if i % 15 == 14:
            which ^= 1
            cur = (desk_b if which else desk_a).copy()
        else:
            row = trow + ((i * 16) % 64)
            glyphs = rng.integers(0, 2, size=(12, w // 2), dtype=np.uint8) * 255
            cur[row : row + 12, w // 6 : w // 6 + w // 2, :3] = glyphs[..., None]
            cur[trow + 96 : trow + 108, w // 6 : w // 6 + 12] = (
                (0, 0, 0, 0) if i % 2 else (248, 248, 248, 0))
        frames.append(cur.copy())
    return frames


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--width", type=int, default=640)
    ap.add_argument("--height", type=int, default=384)
    ap.add_argument("--frames", type=int, default=24)
    ap.add_argument("--frame-batch", type=int, default=1)
    ap.add_argument("--warm", type=int, default=2,
                    help="frames excluded from the count (the IDR seeds "
                         "the decay fetch hint for ~8 completions; warm "
                         "past it to measure steady state)")
    ap.add_argument("--traces", default="scroll,window,desktop")
    args = ap.parse_args()

    from selkies_tpu.models.frameprep import delta_buckets_for, tile_width_for
    from selkies_tpu.pipeline.elements import scroll_trace, window_move_trace

    # size the scroll region to stay inside the encoder's delta buckets
    # (a region dirtier than the largest bucket takes the full-upload
    # path and the delta/cache machinery never engages)
    ntx = ((args.width + 15) // 16 * 16) // tile_width_for(args.width)
    buckets = delta_buckets_for(args.width, args.height)
    bands = max(2, min(8, (buckets[-1] if buckets else 8) // ntx))

    traces = {}
    names = args.traces.split(",")
    if "scroll" in names:
        traces["scroll"] = scroll_trace(args.width, args.height, args.frames,
                                        bands=bands)
    if "window" in names:
        traces["window"] = window_move_trace(args.width, args.height, args.frames)
    if "desktop" in names:
        if (args.width, args.height) == (1920, 1080):
            import bench

            traces["desktop"] = bench._desktop_trace(args.frames)
        else:
            traces["desktop"] = _desktop_like(args.width, args.height, args.frames)
    for name, frames in traces.items():
        on = _run(frames, tile_cache=1024, packed=True,
                  frame_batch=args.frame_batch, warm=args.warm)
        off = _run(frames, tile_cache=0, packed=False,
                   frame_batch=args.frame_batch, warm=args.warm)
        ratio_up = off["bytes_up_per_frame"] / max(on["bytes_up_per_frame"], 1e-9)
        ratio_down = off["bytes_down_per_frame"] / max(on["bytes_down_per_frame"], 1e-9)
        print(json.dumps({
            "trace": name,
            "cache_on": on,
            "cache_off": off,
            "uplink_reduction": round(ratio_up, 2),
            "downlink_reduction": round(ratio_down, 2),
        }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
