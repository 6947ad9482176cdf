#!/usr/bin/env python3
"""Microbenchmark of the host completion path (downlink bytes -> RBSP)
over synthetic sparse buffers — no device in the loop,
so completion regressions are measurable anywhere.

Compares, per density/geometry/layout:

  * dense-expand baseline: unpack_p_sparse_{var,packed} (bitmap expand +
    scatter into dense (M, 26, 16) arrays -> PFrameCoeffs) followed by
    pack_slice_p_fast (the native dense packer's int16 re-copy + walk) —
    the completion path PR 1 shipped, measured at pack_ms ~110 ms/frame
    on the 1080p bench trace (BENCH_r05);
  * sparse-native: p_sparse_wire_views (zero-copy) +
    pack_slice_p_sparse_rbsp walking only non-skip MBs.

Byte equality is asserted on every case before timing. Run:

    JAX_PLATFORMS=cpu python tools/profile_pack.py [--iters N]
"""

from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from selkies_tpu.models.h264.bitstream import StreamParams  # noqa: E402
from selkies_tpu.models.h264.compact import (  # noqa: E402
    p_sparse_wire_views,
    unpack_p_compact,
    unpack_p_sparse_packed,
    unpack_p_sparse_var,
)
from selkies_tpu.models.h264 import native  # noqa: E402
from selkies_tpu.models.h264.sparse_ref import build_p_sparse_wire, synth_pfc  # noqa: E402

NSCAP = 4096
CAP_ROWS = 4096


def _best_of(fn, iters: int) -> float:
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best * 1e3


def profile_case(name: str, mbh: int, mbw: int, *, skip_frac: float,
                 row_density: float, packed: bool, cap_rows: int = CAP_ROWS,
                 nscap: int = NSCAP, iters: int = 5, seed: int = 0,
                 lane_density: float = 0.25):
    p = StreamParams(width=mbw * 16, height=mbh * 16, qp=30)
    rng = np.random.default_rng(seed)
    pfc = synth_pfc(rng, mbh, mbw, skip_frac=skip_frac, row_density=row_density,
                    lane_density=lane_density)
    fused, dense, buf = build_p_sparse_wire(pfc, nscap, cap_rows, packed=packed)
    meta = np.ascontiguousarray(fused[:8]).view(np.int32)
    n, ns = int(meta[0]), int(meta[3])
    extra = buf[cap_rows:n] if n > cap_rows else None
    unpack = unpack_p_sparse_packed if packed else unpack_p_sparse_var

    def baseline():
        pfc2, rows = unpack(fused, 30, mbh, mbw, nscap, cap_rows, extra)
        if pfc2 is None:  # ns > nscap: dense-header fallback
            pfc2 = unpack_p_compact(dense, rows, 30)
        return native.pack_slice_p_fast(pfc2, p, frame_num=1)

    base_au = baseline()
    t_unpack = _best_of(lambda: unpack(fused, 30, mbh, mbw, nscap, cap_rows, extra),
                        iters)
    t_base = _best_of(baseline, iters)

    t_sparse = None
    if ns <= nscap and native.sparse_native_available():
        def sparse():
            wire = p_sparse_wire_views(fused, mbh, mbw, nscap, cap_rows,
                                       packed, extra)
            return native.pack_slice_p_sparse_native(wire, p, 1, 30)

        assert sparse() == base_au, f"{name}: sparse-native differs from oracle"
        t_sparse = _best_of(sparse, iters)

    live_kb = 2 * (8 + n * 16 + ns * 4) / 1024
    line = (f"{name:<34} ns={ns:>5} rows={n:>6} (~{live_kb:7.1f} KB live) | "
            f"dense-expand {t_base:7.2f} ms (unpack {t_unpack:6.2f})")
    if t_sparse is not None:
        line += f" | sparse-native {t_sparse:6.2f} ms | speedup {t_base / t_sparse:5.1f}x"
    else:
        line += " | sparse-native n/a (dense fallback or no libcavlc)"
    print(line)
    return t_base, t_sparse


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=5, help="best-of iterations")
    args = ap.parse_args()
    if not native.native_available():
        print("libcavlc.so unavailable: baseline runs the pure-Python packer "
              "and would take minutes at 1080p — build native/ first")
    print(f"sparse_native_available: {native.sparse_native_available()}")

    # densities calibrated to the bench desktop trace (encoder.py:
    # typing ~1k live rows; the post-window-switch decay tail runs ns up
    # to ~3k, n up to ~3.5k — the regime BENCH_r05 measured at
    # pack_ms 110.55); "busy" is a stress point past anything the trace
    # produces, where shared entropy-coding cost bounds the win
    speedups = []
    for packed in (False, True):
        lay = "packed" if packed else "var"
        print(f"\n-- 1080p (68x120 MBs), {lay} layout --")
        for nm, sf, rd, ld in (("typing (2% coded)", 0.98, 0.25, 0.25),
                               ("decay tail (37% coded)", 0.63, 0.045, 0.2),
                               ("busy (40% coded)", 0.60, 0.4, 0.25)):
            tb, ts = profile_case(f"1080p {nm}", 68, 120, skip_frac=sf,
                                  row_density=rd, lane_density=ld,
                                  packed=packed, iters=args.iters)
            if ts and nm != "busy (40% coded)":
                speedups.append(tb / ts)
    print("\n-- geometry / regime sweep --")
    profile_case("720p busy (40% coded) var", 45, 80, skip_frac=0.6,
                 row_density=0.4, packed=False, iters=args.iters)
    profile_case("1080p cap_rows spill (cap 1k)", 68, 120, skip_frac=0.6,
                 row_density=0.4, packed=True, cap_rows=1024, iters=args.iters)
    profile_case("1080p dense fallback (ns>nscap)", 68, 120, skip_frac=0.2,
                 row_density=0.4, packed=False, nscap=1024, iters=args.iters)
    profile_case("4k busy (30% coded) packed", 135, 240, skip_frac=0.7,
                 row_density=0.35, packed=True, iters=args.iters)

    group_speedup = profile_group(iters=args.iters)
    if speedups:
        print(f"\n1080p single-frame completion speedup (dense-expand -> "
              f"sparse-native, trace regimes): min {min(speedups):.1f}x, "
              f"max {max(speedups):.1f}x")
        if group_speedup:
            print(f"1080p grouped completion speedup (serial dense-expand -> "
                  f"fanned sparse-native, {os.cpu_count()} cores): "
                  f"{group_speedup:.1f}x amortized")
    return 0


def profile_group(iters: int = 3, k: int = 8):
    """Amortized per-frame completion of a K-frame delta group: the old
    path (serial dense-expand on one worker, what _complete_batch did)
    vs the new one (sparse-native fanned per-slot across a pack pool).
    This is the shape the encoder actually runs at steady state."""
    if not native.sparse_native_available():
        return None
    from concurrent.futures import ThreadPoolExecutor

    mbh, mbw = 68, 120
    p = StreamParams(width=mbw * 16, height=mbh * 16, qp=30)
    frames = []
    for i in range(k):
        rng = np.random.default_rng(1000 + i)
        pfc = synth_pfc(rng, mbh, mbw, skip_frac=0.63, row_density=0.045,
                        lane_density=0.2)
        fused, dense, buf = build_p_sparse_wire(pfc, NSCAP, CAP_ROWS, packed=True)
        frames.append(fused)

    def one_dense(fused):
        pfc2, _ = unpack_p_sparse_packed(fused, 30, mbh, mbw, NSCAP, CAP_ROWS, None)
        return native.pack_slice_p_fast(pfc2, p, frame_num=1)

    def one_sparse(fused):
        wire = p_sparse_wire_views(fused, mbh, mbw, NSCAP, CAP_ROWS, True, None)
        return native.pack_slice_p_sparse_native(wire, p, 1, 30)

    serial_aus = [one_dense(f) for f in frames]
    pool = ThreadPoolExecutor(max_workers=min(os.cpu_count() or 2, k))
    fanned_aus = list(pool.map(one_sparse, frames))
    assert fanned_aus == serial_aus, "fanned sparse group differs from serial dense"
    t_serial = _best_of(lambda: [one_dense(f) for f in frames], iters)
    t_fanned = _best_of(lambda: list(pool.map(one_sparse, frames)), iters)
    pool.shutdown()
    print(f"\n-- grouped completion, K={k} decay-tail frames @1080p --")
    print(f"serial dense-expand (old _complete_batch): {t_serial / k:7.2f} ms/frame")
    print(f"fanned sparse-native (new, {min(os.cpu_count() or 2, k)} workers):"
          f"      {t_fanned / k:7.2f} ms/frame")
    return t_serial / t_fanned


if __name__ == "__main__":
    sys.exit(main())
