#!/usr/bin/env python3
"""Benchmark entrypoint — run by the driver on real TPU hardware.

Prints ONE JSON line:
    {"metric": "...", "value": N, "unit": "...", "vs_baseline": N}

Metric: sustained 1080p60 encode FPS on one TPU chip (BASELINE.md north
star: sustain 60 fps / <16 ms per frame). vs_baseline is achieved_fps / 60,
so 1.0 == reference parity.

The bench measures the flagship path available at the current milestone:
the full tpuh264enc frame step once it exists, otherwise the capture→I420
conversion stage alone (clearly labelled).

Alternate suites (each runs INSTEAD of the flagship row): ``--scenario``
(per-scenario fps/latency rows), ``--capacity`` (sessions-at-SLO ramp),
``--impair`` (the recovery-ladder impairment gauntlet, docs/recovery.md).
"""

from __future__ import annotations

import json
import sys
import time

import numpy as np


BASELINE_FPS = 60.0
H, W = 1080, 1920
WARMUP = 3
ITERS = 30

# named geometries for --resolution; anything else parses as WxH
RESOLUTIONS = {
    "720p": (1280, 720),
    "1080p": (1920, 1080),
    "1440p": (2560, 1440),
    "4k": (3840, 2160),
    "4k-dci": (4096, 2160),
    "8k": (7680, 4320),
}


def _parse_resolutions(spec: str) -> list[tuple[str, int, int]]:
    """"1080p,4k" / "3840x2160" -> [(label, width, height), ...]."""
    out = []
    for token in spec.split(","):
        token = token.strip().lower()
        if not token:
            continue
        if token in RESOLUTIONS:
            w, h = RESOLUTIONS[token]
        else:
            try:
                w_s, h_s = token.split("x")
                w, h = int(w_s), int(h_s)
            except ValueError:
                raise SystemExit(
                    f"--resolution {token!r}: use {sorted(RESOLUTIONS)} "
                    f"or WxH") from None
        out.append((token, w, h))
    return out or [("1080p", W, H)]


def _result(metric: str, fps: float, unit: str = "fps@1080p",
            **extra: float) -> None:
    doc = {
        "metric": metric,
        "value": round(fps, 2),
        "unit": unit,
        "vs_baseline": round(fps / BASELINE_FPS, 3),
    }
    # per-stage means ride along: device_stage_latency_ms is each
    # frame's dispatch->resolve time through the device stage (queueing
    # in its group + execute + fetch) observed during the SAME single
    # timed pass — no extra runs.
    # It splits as upload_ms (host convert + h2d/dispatch enqueue) +
    # step_ms (dispatch -> device outputs ready) + fetch_ms (d2h
    # transfer) so a regression attributes to the right sub-stage; with
    # SELKIES_BANDS>1 `bands` and per-band `band_step_ms` ride along too.
    doc.update({k: (round(v, 2) if isinstance(v, float) else v)
                for k, v in extra.items()})
    print(json.dumps(doc))


def _desktop_trace(n: int = 60, w: int = W, h: int = H) -> list[np.ndarray]:
    """A realistic desktop-streaming trace — the reference's headline
    workload (remote desktop, README.md:7): a mostly-static screen with a
    busy terminal region (text updates touching a few 16-row bands per
    frame), a moving cursor, and a full-screen window switch twice per
    second. Matches what ximagesrc+XDamage would hand the reference.
    Region geometry scales with the resolution (`--resolution 4k`); at
    1080p the trace is byte-identical to the historical fixed-geometry
    one, so the trajectory's bench rows stay comparable."""
    rng = np.random.default_rng(42)
    sx, sy = w / W, h / H

    def _wallpaper(seed):
        r = np.random.default_rng(seed)
        base = r.integers(40, 200, size=(-(-h // 40), -(-w // 40), 4),
                          dtype=np.uint8)
        return np.ascontiguousarray(
            np.kron(base, np.ones((40, 40, 1), np.uint8))[:h, :w])

    desk_a, desk_b = _wallpaper(1), _wallpaper(2)
    for d in (desk_a, desk_b):
        # "window" fill
        d[int(260 * sy):int(780 * sy), int(360 * sx):int(1560 * sx)] = (
            248, 248, 248, 0)
    frames = []
    cur = desk_a.copy()
    which = 0
    line_w = int(1150 * sx)
    for i in range(n):
        if i % 30 == 29:
            # window switch: full-frame change
            which ^= 1
            cur = (desk_b if which else desk_a).copy()
        else:
            # terminal output: one new text line (1 band) + scroll of a
            # 4-band tail of the text area = <=5 dirty bands, bucket 8
            row = int(288 * sy) + ((i * 16) % 64)
            glyphs = rng.integers(0, 2, size=(12, line_w // 6 + 1),
                                  dtype=np.uint8) * 255
            line = np.kron(glyphs, np.ones((1, 6), np.uint8))[:, :line_w]
            x0 = int(380 * sx)
            cur[row : row + 12, x0 : x0 + line_w, :3] = line[..., None]
            # cursor blink: one more band
            cur[int(700 * sy):int(700 * sy) + 12, x0:x0 + 12] = (
                (0, 0, 0, 0) if i % 2 else (248, 248, 248, 0))
        frames.append(cur.copy())
    return frames


def bench_full_encoder(w: int = W, h: int = H) -> tuple[float, dict] | None:
    """Steady-state IP-GOP desktop encode (IDR once, then P frames; delta
    band uploads for partial updates, full uploads on window switches,
    on-device motion estimation). Uses the pipelined submit/flush API
    exactly like the live VideoPipeline does."""
    try:
        from selkies_tpu.models.h264.encoder import TPUH264Encoder
    except ImportError:
        return None
    from selkies_tpu.models.registry import default_frame_batch, default_pipeline_depth

    from selkies_tpu.parallel.bands import bands_from_env, grid_from_env

    frames = _desktop_trace(ITERS, w, h)
    grid = grid_from_env()
    if grid is not None and max(grid) > 1 or bands_from_env() > 1:
        # SELKIES_BANDS>1 / SELKIES_TILE_GRID: bench the band/tile-
        # parallel encoder the registry would build — the timed loop
        # below is identical (submit/flush), and the JSON gains bands /
        # cols / band_step_ms for per-slice attribution
        from selkies_tpu.parallel.bands import BandedH264Encoder

        rows_, cols_ = grid if grid is not None else (bands_from_env(), 1)
        enc = BandedH264Encoder(w, h, qp=28, bands=rows_, cols=cols_)
        enc.encode_frame(frames[0])   # IDR (compiles the I step)
        enc.encode_frame(frames[1])   # P (compiles the band P step)
        enc.encode_frame(frames[1])   # static all-skip
    else:
        # grouped-dispatch depth + in-flight cap come from the SAME
        # deployment-aware defaults the live pipeline uses
        # (registry.default_frame_batch/default_pipeline_depth, PERF.md)
        enc = TPUH264Encoder(w, h, qp=28,
                             frame_batch=min(12, default_frame_batch()),
                             pipeline_depth=default_pipeline_depth())
        # warmup compiles every executable the trace uses: IDR full,
        # grouped delta scans (K=8 and K=4), single delta, P full, static
        enc.encode_frame(frames[0])  # IDR full
        fb = enc.frame_batch
        i = 1
        for _ in range(fb):  # consecutive deltas fill one group -> K=fb scan
            enc.submit(frames[i]); i += 1
        enc.flush()
        for _ in range(max(2, fb // 2)):  # half group -> K=fb/2 scan
            enc.submit(frames[i]); i += 1
        enc.flush()
        enc.encode_frame(frames[i])  # single delta (straggler path)
        enc.encode_frame(frames[29 % len(frames)])  # window switch -> full P
        enc.encode_frame(frames[29 % len(frames)])  # static
        # LTR scene-cache warmup: switching back to the remembered desktop
        # compiles the restore executable (non-donating scatter) + the
        # device plane-snapshot step — both used by the steady-state loop
        enc.encode_frame(frames[0])
        enc.encode_frame(frames[1])
    # ONE timed pass — steady state, no best-of (every pass must be
    # fast, not the luckiest one; the trace includes the window-switch
    # full-frame changes)
    done = 0
    sums = {k: 0.0 for k in ("device_ms", "pack_ms", "unpack_ms", "cavlc_ms",
                             "upload_ms", "step_ms", "fetch_ms",
                             "classify_ms", "convert_ms", "h2d_ms")}
    bands = 1
    cols = 1
    band_step_sums: list[float] = []
    band_step_n = 0
    # which payload each P downlink shipped (coeff rows vs device-entropy
    # bits vs a dense fallback; "none" = no downlink, e.g. static frames)
    # — future rounds track WHICH path busy frames took, not just totals
    mode_counts: dict[str, int] = {}

    def _account(stats) -> None:
        nonlocal bands, cols, band_step_sums, band_step_n
        for k in sums:
            sums[k] += getattr(stats, k, 0.0)
        mode = getattr(stats, "downlink_mode", "") or "none"
        mode_counts[mode] = mode_counts.get(mode, 0) + 1
        bands = max(bands, getattr(stats, "bands", 1))
        cols = max(cols, getattr(stats, "cols", 1))
        bs = getattr(stats, "band_step_ms", ())
        if bs:
            if len(band_step_sums) < len(bs):
                band_step_sums = list(band_step_sums) + [0.0] * (
                    len(bs) - len(band_step_sums))
            for b, ms in enumerate(bs):
                band_step_sums[b] += ms
            band_step_n += 1

    lb0 = enc.link_bytes.snapshot()  # link-byte baseline (excl. warmup)
    t0 = time.perf_counter()
    for i in range(ITERS):
        for _, stats, _ in enc.submit(frames[i % len(frames)]):
            done += 1
            _account(stats)
    for _, stats, _ in enc.flush():
        done += 1
        _account(stats)
    dt = time.perf_counter() - t0
    lb1 = enc.link_bytes.snapshot()
    up = sum(v - lb0.get(k, 0) for k, v in lb1.items() if k.startswith("up_"))
    down = sum(v - lb0.get(k, 0) for k, v in lb1.items() if k.startswith("down_"))
    # device-entropy frames account under down_bits* stages — split the
    # downlink into its coefficient and final-slice-bits components so
    # the trajectory shows the ISSUE-7 conversion, not just the total
    bits = sum(v - lb0.get(k, 0) for k, v in lb1.items()
               if k.startswith("down_bits"))
    assert done == ITERS, f"pipeline lost frames: {done}/{ITERS}"
    means = {k: v / done for k, v in sums.items()}
    means["bytes_up_per_frame"] = up / done
    means["bytes_down_per_frame"] = down / done
    means["bytes_down_coeff_per_frame"] = (down - bits) / done
    means["bytes_down_bits_per_frame"] = bits / done
    means["downlink_mode"] = mode_counts
    if bands > 1 and band_step_n:
        means["bands"] = bands
        means["band_step_ms"] = [round(s / band_step_n, 2)
                                 for s in band_step_sums]
    if cols > 1:
        means["cols"] = cols
    enc.close()
    return ITERS / dt, means


# ---------------------------------------------------------------------------
# scenario bench suite (ROADMAP item 5 / docs/policy.md): per-workload
# rows instead of the single desktop trace, so every future PR reports
# fps / latency / link bytes PER SCENARIO — and the policy engine's
# per-scenario wins are measurable against the static defaults.
# ---------------------------------------------------------------------------

SCENARIOS = ("idle", "typing", "scroll", "window_drag", "video", "game")
SCENARIO_FPS = 60.0  # paced tick rate: latency percentiles are only
                     # meaningful against the cadence a live session has


def _scenario_trace(name: str, n: int, w: int, h: int,
                    seed: int = 11) -> list[np.ndarray]:
    """Synthetic per-scenario frame traces (BGRx uint8), deterministic.

    idle         static desktop, cursor blink every 30 frames
    typing       a new 12-row glyph line every 3rd frame (~20 cps)
    scroll       full-width texture region scrolling 16 rows/frame
                 (pipeline/elements.scroll_trace — the tile-cache
                 headline case)
    window_drag  a tile-periodic window sliding one tile/frame
                 (window_move_trace)
    video        a centered half-size region with new content every
                 OTHER frame (30 fps playback on a 60 fps tick)
    game         full-frame motion every frame
    """
    from selkies_tpu.pipeline.elements import scroll_trace, window_move_trace

    if name == "scroll":
        return scroll_trace(w, h, n, bands=8, seed=seed)
    if name == "window_drag":
        return window_move_trace(w, h, n, seed=seed)
    rng = np.random.default_rng(seed)
    base = np.full((h, w, 4), 230, np.uint8)
    base[: h // 10] = (70, 60, 60, 0)
    frames: list[np.ndarray] = []
    if name == "idle":
        cur = base.copy()
        for i in range(n):
            if i % 30 == 0:
                on = (i // 30) % 2
                cur[h // 2 : h // 2 + 12, w // 4 : w // 4 + 12] = (
                    (0, 0, 0, 0) if on else (230, 230, 230, 0))
            frames.append(cur.copy())
        return frames
    if name == "typing":
        cur = base.copy()
        line_w = min(w - 64, 1024)
        for i in range(n):
            if i % 3 == 0:
                row = h // 4 + ((i // 3) * 16) % (h // 2)
                glyphs = rng.integers(0, 2, (12, line_w // 6 + 1),
                                      np.uint8) * 255
                line = np.kron(glyphs, np.ones((1, 6), np.uint8))[:, :line_w]
                cur[row : row + 12, 32 : 32 + line_w, :3] = line[..., None]
            frames.append(cur.copy())
        return frames
    if name == "video":
        # sliding window over a long random strip: content NEVER repeats
        # (np.roll would cycle within the trace, letting the tile cache
        # remap a "video" — unrealistically)
        rh, rw = (h // 2) // 16 * 16, (w // 2) // 16 * 16
        y0, x0 = (h - rh) // 2 // 16 * 16, (w - rw) // 2 // 16 * 16
        strip = rng.integers(0, 255, (rh, rw + 24 * (n // 2 + 1), 4),
                             np.uint8)
        cur = base.copy()
        for i in range(n):
            if i % 2 == 0:
                off = 24 * (i // 2)
                cur[y0 : y0 + rh, x0 : x0 + rw] = strip[:, off : off + rw]
            frames.append(cur.copy())
        return frames
    if name == "game":
        world = rng.integers(0, 255, (h, w, 4), np.uint8)
        for i in range(n):
            f = np.roll(world, 40 * i, axis=1)
            # fresh per-frame band: the roll alone would repeat the
            # exact frame every w/gcd(40,w) ticks
            f[:16] = rng.integers(0, 255, (16, w, 4), np.uint8)
            x = (i * 48) % (w - 64)
            f[h // 3 : h // 3 + 64, x : x + 64] = (250, 40, 40, 0)
            frames.append(f)
        return frames
    raise SystemExit(f"unknown scenario {name!r} (one of {SCENARIOS})")


def _scenario_damage(name: str, i: int, w: int, h: int):
    """Per-frame damage-rect hints for the synthetic scenario traces —
    what an XDamage-armed capture layer would report (capture.py):
    authoritative SUPERSETS of the pixels _scenario_trace changes at
    frame i, as (x, y, w, h) tuples. None = unknown (full scan; frame 0
    of each pass switches the whole trace content). Byte-neutral by the
    FramePrep.scan superset contract; the hinted-vs-full AU byte
    identity is pinned by tests/test_frontend_parallel.py (the bench
    rows report identical bytes_up/down either way)."""
    if i == 0:
        return None
    if name == "idle":
        # cursor blink touches one 12x12 block every 30th frame
        return ([(w // 4, h // 2, 12, 12)] if i % 30 == 0 else [])
    if name == "typing":
        if i % 3 != 0:
            return []
        row = h // 4 + ((i // 3) * 16) % (h // 2)
        line_w = min(w - 64, 1024)
        return [(32, row, line_w, 12)]
    if name == "scroll":
        # scroll_trace(bands=8, band0=2): rows 32..32+128 change
        return [(0, 32, w, 8 * 16)]
    if name == "window_drag":
        # window_move_trace: window (6 bands x 3 tiles) at y0=32 slides
        # one tile per frame — old + new positions bound the change
        # (window_move_x is the trace's own position formula, so the
        # hint can never drift from what the generator draws)
        from selkies_tpu.models.frameprep import tile_width_for
        from selkies_tpu.pipeline.elements import window_move_x

        tile_w = tile_width_for(w)
        x0, x1 = sorted((window_move_x(i - 1, w, tile_w),
                         window_move_x(i, w, tile_w)))
        return [(x0, 32, x1 - x0 + 3 * tile_w, 6 * 16)]
    if name == "video":
        if i % 2 != 0:
            return []
        rh, rw = (h // 2) // 16 * 16, (w // 2) // 16 * 16
        y0, x0 = (h - rh) // 2 // 16 * 16, (w - rw) // 2 // 16 * 16
        return [(x0, y0, rw, rh)]
    return None  # game: full-frame motion, a hint saves nothing


def bench_scenario(name: str, w: int, h: int, n: int,
                   policy_on: bool, damage_on: bool = False) -> dict:
    """One scenario row: drive the production encoder over the scenario
    trace at a paced 60 fps tick, twice — an untimed SETTLE pass (the
    policy classifies, transitions and pays any knob-change compile
    there) and a TIMED pass measuring the settled steady state. The
    row therefore compares postures, not transition costs. With
    ``damage_on`` the submit carries the trace's damage-rect hints
    (_scenario_damage), bounding the classify scan like a live XDamage
    capture would."""
    from selkies_tpu.models.h264.encoder import TPUH264Encoder
    from selkies_tpu.models.registry import (
        default_frame_batch, default_pipeline_depth)

    enc = TPUH264Encoder(w, h, qp=28,
                         frame_batch=min(12, default_frame_batch()),
                         pipeline_depth=default_pipeline_depth())
    runtime = None
    pending: list = []
    if policy_on:
        from selkies_tpu.policy import (
            EncoderActuator, PolicyEngine, PolicyRuntime, preset_from_env)

        engine = PolicyEngine(session="bench", preset=preset_from_env())
        runtime = PolicyRuntime(engine, EncoderActuator(
            lambda: enc, drain=lambda: pending.extend(enc.flush())))

    def run_pass(chunk) -> dict:
        submit_t: dict[int, float] = {}
        lats: list[float] = []
        active_lats: list[float] = []
        sums = {k: 0.0 for k in ("device_ms", "pack_ms", "unpack_ms",
                                 "cavlc_ms", "upload_ms", "step_ms",
                                 "fetch_ms", "classify_ms", "convert_ms",
                                 "h2d_ms")}
        modes: dict[str, int] = {}
        done = 0

        def _account(outs) -> None:
            nonlocal done
            now = time.perf_counter()
            for _au, stats, meta in outs:
                done += 1
                lat = (now - submit_t.pop(meta)) * 1e3
                lats.append(lat)
                # active = the frame carried new content to the client
                # (statics are ~0 ms host-side all-skips and would bury
                # the percentiles that matter for interactivity)
                if getattr(stats, "upload_kind", "") != "static":
                    active_lats.append(lat)
                for k in sums:
                    sums[k] += getattr(stats, k, 0.0)
                m = getattr(stats, "downlink_mode", "") or "none"
                modes[m] = modes.get(m, 0) + 1

        lb0 = enc.link_bytes.snapshot()
        t0 = time.perf_counter()
        next_tick = t0
        last_tick = t0
        for i, frame in enumerate(chunk):
            now = time.perf_counter()
            if now < next_tick:
                time.sleep(next_tick - now)
            now = time.perf_counter()
            next_tick = max(next_tick + 1.0 / SCENARIO_FPS,
                            now - 0.5 / SCENARIO_FPS)
            submit_t[i] = time.perf_counter()
            dmg = _scenario_damage(name, i, w, h) if damage_on else None
            outs = enc.submit(frame, None, i, damage=dmg)
            _account(outs)
            if runtime is not None:
                runtime.tick([s for _, s, _ in outs],
                             interval_ms=(now - last_tick) * 1e3)
                last_tick = now
                if pending:  # an actuation drained in-flight frames
                    _account(pending)
                    pending.clear()
        _account(enc.flush())
        dt = time.perf_counter() - t0
        lb1 = enc.link_bytes.snapshot()
        assert done == len(chunk), f"lost frames: {done}/{len(chunk)}"
        up = sum(v - lb0.get(k, 0) for k, v in lb1.items()
                 if k.startswith("up_"))
        down = sum(v - lb0.get(k, 0) for k, v in lb1.items()
                   if k.startswith("down_"))
        lats.sort()
        active_lats.sort()
        pct = active_lats or lats
        row = {k: v / done for k, v in sums.items()}
        row["fps"] = done / dt
        row["p50_latency_ms"] = pct[len(pct) // 2]
        row["p95_latency_ms"] = pct[int(len(pct) * 0.95)]
        row["active_frames"] = len(active_lats)
        row["bytes_up_per_frame"] = up / done
        row["bytes_down_per_frame"] = down / done
        row["downlink_mode"] = modes
        return row

    # two independently-seeded trace halves: the settle pass classifies
    # + actuates + compiles, the timed pass measures steady state over
    # FRESH content — a content-addressed cache only gets the hits the
    # scenario legitimately produces (replaying the settle frames would
    # make everything pool-resident by pass 2), and only one pass's
    # frames are resident at a time (a 1080p trace is ~2 GB per pass)
    settle = _scenario_trace(name, n, w, h, seed=11)
    run_pass(settle)
    del settle
    # recompile sentinel (monitoring/jitprof.py): the timed pass runs
    # over a settled encoder, so its compile count SHOULD be zero — a
    # non-zero `compiles` field in a scenario row means an executable-
    # reuse discipline (bucket ladders, snap-to-compiled batch caps,
    # policy dwell) broke under this workload
    from selkies_tpu.monitoring import jitprof

    sentinel = jitprof.install()
    c0 = sentinel.stats()["compiles"]
    row = run_pass(_scenario_trace(name, n, w, h, seed=12))
    row["compiles"] = sentinel.stats()["compiles"] - c0
    if runtime is not None:
        st = runtime.engine.stats()
        row["policy_scenario"] = st["scenario"]
        row["policy_transitions"] = sum(st["transitions"].values())
        row["policy_disarmed"] = st["disarmed"]
    enc.close()
    row["scenario"] = name
    row["policy"] = int(policy_on)
    row["damage"] = int(damage_on)
    return row


def bench_codec_encoder(codec: str, w: int = W, h: int = H) -> tuple[float, dict] | None:
    """Per-codec row for the --codec sweep: the encoder the registry
    would negotiate for `codec` (signalling/negotiate.py) driven over
    the same desktop trace through the plain encode_frame interface.
    None when the codec's backing library is absent in this image.

    The JSON mirrors the h264 row where the stages exist: device_ms is
    the row's encode stage (libaom/libvpx on CPU, or the device step),
    pack_ms its convert+stitch time; au_bytes_per_frame is what the
    client downlink ships.  Link-byte fields are device-path specific
    and omitted for the library-backed rows."""
    from selkies_tpu.signalling.negotiate import CODEC_ROWS, codec_available

    if codec not in CODEC_ROWS or not codec_available(codec):
        return None
    from selkies_tpu.models.registry import create_encoder

    enc = create_encoder(CODEC_ROWS[codec], width=w, height=h, fps=60)
    frames = _desktop_trace(ITERS, w, h)
    # warmup: keyframe, delta, static (compiles the front-end step /
    # fills the tile-column payload cache)
    enc.encode_frame(frames[0])
    enc.encode_frame(frames[1])
    enc.encode_frame(frames[1])
    sums = {"device_ms": 0.0, "pack_ms": 0.0}
    au_bytes = 0
    static = idrs = 0
    cols = 1
    t0 = time.perf_counter()
    for i in range(ITERS):
        au = enc.encode_frame(frames[i % len(frames)])
        au_bytes += len(au)
        stats = enc.last_stats
        if stats is not None:
            sums["device_ms"] += getattr(stats, "device_ms", 0.0)
            sums["pack_ms"] += getattr(stats, "pack_ms", 0.0)
            idrs += bool(getattr(stats, "idr", False))
            cols = max(cols, getattr(stats, "cols", 1))
    dt = time.perf_counter() - t0
    static = getattr(enc, "static_frames", 0)
    means = {k: v / ITERS for k, v in sums.items()}
    means["au_bytes_per_frame"] = au_bytes / ITERS
    means["idr_frames"] = idrs
    means["static_frames"] = static
    if cols > 1:
        means["cols"] = cols
    means["codec"] = codec
    if hasattr(enc, "close"):
        enc.close()
    return ITERS / dt, means


# ---------------------------------------------------------------------------
# capacity bench (--capacity): sessions-at-SLO curves. Ramps N scenario-
# mix sessions on one fleet service until the tick's p95 latency (or its
# throughput floor) breaches the per-scenario SLO targets
# (policy/presets.SLO_TARGETS), once with the serial lockstep tick and
# once with the occupancy scheduler (parallel/occupancy.py) — the
# delta IS the overlap win, and the emitted max_sessions_at_slo rows
# are the measured capacity curve build_digest serves to the cluster
# router via SELKIES_CAPACITY_FILE (cluster/membership.py).
# ---------------------------------------------------------------------------

# scenario mixes: session i of an N-session ramp plays mix[i % len].
# "desktop" is the fleet's bread-and-butter tenancy (mostly interactive,
# one video watcher per four desks); "interactive" is a call-center /
# thin-client floor (no full-motion rows at all)
CAPACITY_MIXES = {
    "desktop": ("typing", "idle", "scroll", "video"),
    "interactive": ("typing", "window_drag", "idle", "typing"),
}

# bench scenario names -> SLO_TARGETS vocabulary (policy/classifier.py)
_SLO_KEY = {"window_drag": "drag"}


def bench_capacity(w: int, h: int, frames_per_pass: int, mixes: list[str],
                   max_sessions: int) -> list[dict]:
    """One capacity row per (mix, mode): ramp N until the SLO breaks.

    Every N builds a fresh BandedFleetService (bands=1 — one chip per
    session, the density carve) and free-runs the tick over per-session
    scenario traces: each tick's wall time is every member session's
    capture->deliver latency (the tick returns all AUs together), so
    per-session p95 == tick p95 and the per-session fps floor is the
    achieved tick rate. N passes while every DISTINCT scenario in the
    mix meets its p95 ceiling and fps floor; the ramp stops at the
    first breach and reports the last passing N."""
    import jax

    from selkies_tpu.parallel.occupancy import OccupancyScheduler
    from selkies_tpu.parallel.serving import BandedFleetService
    from selkies_tpu.monitoring.slo import scenario_targets

    chips = len(jax.devices())
    targets = scenario_targets()
    rows = []
    for mix_name in mixes:
        cycle = CAPACITY_MIXES[mix_name]
        for mode in ("lockstep", "overlap"):
            max_ok, ramp = 0, []
            for n in range(1, max_sessions + 1):
                scens = [cycle[i % len(cycle)] for i in range(n)]
                traces = [
                    _scenario_trace(s, frames_per_pass, w, h, seed=11 + i)
                    for i, s in enumerate(scens)
                ]
                svc = BandedFleetService(n, w, h, bands=1)
                sched = (OccupancyScheduler.for_service(svc)
                         if mode == "overlap" else None)
                tick = svc.encode_tick if sched is None else sched.encode_tick
                try:
                    for t in range(min(8, frames_per_pass)):  # settle/compile
                        tick(np.stack([tr[t] for tr in traces]))
                    lats = []
                    t_start = time.perf_counter()
                    for t in range(frames_per_pass):
                        t0 = time.perf_counter()
                        tick(np.stack([tr[t] for tr in traces]))
                        lats.append((time.perf_counter() - t0) * 1e3)
                    elapsed = time.perf_counter() - t_start
                finally:
                    if sched is not None:
                        sched.close()
                    svc.close()
                fps = frames_per_pass / elapsed
                p50 = float(np.percentile(lats, 50))
                p95 = float(np.percentile(lats, 95))
                ok = all(
                    p95 <= targets[_SLO_KEY.get(s, s)].p95_ms
                    and fps >= targets[_SLO_KEY.get(s, s)].fps_floor
                    for s in set(scens))
                step = {"sessions": n, "p50_ms": round(p50, 1),
                        "p95_ms": round(p95, 1), "fps_per_session": round(fps, 2),
                        "slo_ok": ok}
                if sched is not None:
                    step["overlap_ratio"] = sched.stats()["overlap_ratio"]
                ramp.append(step)
                if not ok:
                    break
                max_ok = n
            rows.append({
                "bench": "capacity", "mode": mode, "chips": chips,
                "codec": "h264", "mix": mix_name,
                "max_sessions_at_slo": max_ok, "ramp": ramp,
            })
    return rows


# ---------------------------------------------------------------------------
# impairment gauntlet (--impair): the recovery ladder under trace-driven
# loss. Encoded scenario AUs replay through the deterministic link
# profiles (transport/impair.py PROFILES) into a receiver that actually
# attempts recovery (transport/receiver.py): NACK scheduling back into
# the sender's RTX ring, ULP FEC rebuild, freeze deadline. Everything
# runs on a simulated 60 fps clock — no sleeping, seeded RNGs — so
# BENCH_impair_r01.json ratchets stably (check_bench_regress --impair).
# ---------------------------------------------------------------------------

IMPAIR_SCENARIOS = ("typing", "video")  # light + full-motion packet mix
IMPAIR_FPS = 60.0


def _encode_scenario_aus(name: str, n: int, w: int, h: int,
                         qp: int = 28,
                         entropy_coder: str | None = None,
                         ) -> list[tuple[bytes, bool]]:
    """Encode the scenario trace once -> [(au, is_idr), ...]; the same
    AUs replay through every impairment profile. The quality suite
    reuses this with explicit QPs to sweep the tpuh264enc ladder (and,
    since ISSUE 20, with an explicit entropy coder to sweep the
    cavlc-vs-cabac axis on the same rungs)."""
    from selkies_tpu.models.h264.encoder import TPUH264Encoder
    from selkies_tpu.models.registry import (
        default_frame_batch, default_pipeline_depth)

    enc = TPUH264Encoder(w, h, qp=qp,
                         frame_batch=min(12, default_frame_batch()),
                         pipeline_depth=default_pipeline_depth(),
                         entropy_coder=entropy_coder)
    aus: dict[int, tuple[bytes, bool]] = {}
    try:
        for i, frame in enumerate(_scenario_trace(name, n, w, h, seed=11)):
            for au, stats, meta in enc.submit(frame, None, i):
                aus[meta] = (bytes(au), bool(getattr(stats, "idr", meta == 0)))
        for au, stats, meta in enc.flush():
            aus[meta] = (bytes(au), bool(getattr(stats, "idr", meta == 0)))
    finally:
        enc.close()
    return [aus[i] for i in sorted(aus)]


def _impair_run(profile: str, scenario: str,
                aus: list[tuple[bytes, bool]]) -> dict:
    """One gauntlet cell: replay `aus` through `profile`'s link model
    with the full recovery ladder in the loop."""
    import heapq
    import itertools

    from selkies_tpu.transport.impair import LoopbackSender, TraceImpairment
    from selkies_tpu.transport.receiver import RecoveringReceiver
    from selkies_tpu.transport.recovery import RecoveryController
    from selkies_tpu.transport.rtp import RtpPacket
    from selkies_tpu.transport.webrtc import rtcp

    sim = {"s": 0.0}  # simulated wall clock, seconds
    trace = TraceImpairment(profile, seed=17)
    heap: list[tuple[float, int, bytes]] = []  # (deliver_ms, tie, wire)
    tie = itertools.count()
    mode = ["media"]  # what the capture below is watching the peer send
    sent_bytes = {"media": 0, "fec": 0, "rtx": 0}

    def on_wire(wire: bytes) -> None:
        kind = mode[0]
        if kind == "media":
            try:  # FEC parity rides the media path; classify by RED pt
                if RtpPacket.parse(wire).payload[0] & 0x7F == 99:
                    kind = "fec"
            except (ValueError, IndexError):
                pass
        sent_bytes[kind] += len(wire)
        now_ms = sim["s"] * 1e3
        for delay_ms, data in trace.admit(wire, now_ms):
            heapq.heappush(heap, (now_ms + delay_ms, next(tie), data))

    ls = LoopbackSender(on_wire=on_wire, fec_percentage=20,
                        clock=lambda: sim["s"])
    rx = RecoveringReceiver(session=f"{profile}/{scenario}")
    rc = RecoveryController(session=f"{profile}/{scenario}", enabled=True,
                            clock=lambda: sim["s"])
    fec_peak = [0]
    idr_req = [False]

    def _set_fec(pct: int) -> None:
        fec_peak[0] = max(fec_peak[0], pct)
        ls.pc.set_fec_percentage(pct)

    rc.on_set_fec = _set_fec
    rc.on_force_idr = lambda: idr_req.__setitem__(0, True)
    ls.pc.on_nack = rc.on_nack
    ls.pc.on_unrecoverable = rc.on_unrecoverable
    rc.attach()  # clean link starts at 0 % FEC, not the static default

    tick_ms = 1000.0 / IMPAIR_FPS
    last_adm = last_drop = 0
    t_ms = 0.0

    def pump(t_ms: float) -> None:
        while heap and heap[0][0] <= t_ms:
            dms, _, data = heapq.heappop(heap)
            rx.receive(data, dms)
        seqs = rx.poll(t_ms)
        if seqs:
            mode[0] = "rtx"
            ls.pc._on_srtcp(rtcp.build_nack(1, ls.pc.video_ssrc, seqs))
            mode[0] = "media"

    try:
        for i, (au, idr) in enumerate(aus):
            t_ms = i * tick_ms
            sim["s"] = t_ms / 1e3
            mode[0] = "media"
            ls.pc.send_video(au, int(i * 90000 // IMPAIR_FPS),
                             idr=idr or idr_req[0])
            idr_req[0] = False
            pump(t_ms)
            if (i + 1) % int(IMPAIR_FPS) == 0:
                # one RR-shaped loss report per simulated second
                adm, drop = trace.admitted, trace.dropped
                d_adm, d_drop = adm - last_adm, drop - last_drop
                last_adm, last_drop = adm, drop
                rc.on_loss_report(d_drop / d_adm if d_adm else 0.0)
        # post-roll: let late deliveries, NACK retries and the freeze
        # deadline settle before closing the books
        end_ms = t_ms + 1000.0
        while t_ms < end_ms:
            t_ms += tick_ms
            sim["s"] = t_ms / 1e3
            pump(t_ms)
        rx.flush()
    finally:
        ls.close()
    st, rs = rx.stats(), rc.stats()
    overhead = sent_bytes["fec"] + sent_bytes["rtx"]
    return {
        "bench": "impair", "profile": profile, "scenario": scenario,
        "frames_sent": len(aus),
        "recovered_ratio": round(st["recovered_ratio"], 4),
        "frames_total": st["frames_total"],
        "frames_frozen": st["frames_frozen"],
        "frames_repaired": st["frames_repaired"],
        "recovery_ms_p50": st["recovery_ms_p50"],
        "recovery_ms_p95": st["recovery_ms_p95"],
        "media_bytes": sent_bytes["media"],
        "fec_bytes": sent_bytes["fec"],
        "rtx_bytes": sent_bytes["rtx"],
        "overhead_pct": round(100.0 * overhead / max(1, sent_bytes["media"]), 2),
        "packets_lost": trace.dropped,
        "packets_admitted": trace.admitted,
        "losses_detected": st["losses_detected"],
        "repaired_rtx": st["repaired_rtx"],
        "repaired_fec": st["repaired_fec"],
        "nacks_sent": st["nacks_sent"],
        "fec_pct_peak": fec_peak[0],
        "fec_pct_final": rs["fec_pct"],
        "idr_forced": rs["idr_forced"],
        "degrades": rs["degrades"],
    }


def bench_impair(w: int, h: int, n_frames: int, profiles: list[str],
                 scenarios: list[str]) -> list[dict]:
    """One row per (profile, scenario): encode each scenario once, then
    replay the same AUs through every profile's link model."""
    rows = []
    for scen in scenarios:
        aus = _encode_scenario_aus(scen, n_frames, w, h)
        for profile in profiles:
            rows.append(_impair_run(profile, scen, aus))
    return rows


# ---------------------------------------------------------------------------
# rate/quality suite (docs/quality.md): per-scenario rate-distortion
# points — tpuh264enc across its QP ladder, x264 preset anchors and vp9
# across a bitrate ladder — each scored by decoding the WHOLE stream
# through the codec's reference oracle (monitoring/quality.GopDecoder)
# and comparing decoded luma against the pre-encode I420 source. Point
# rows carry mean PSNR/SSIM/VMAF (vmaf_kind says proxy vs real CLI);
# bdrate rows summarise each test curve against each x264 anchor curve
# with the classic BD-rate integral. Deterministic traces + intra-only
# oracles => BENCH_quality_r02.json ratchets stably
# (check_bench_regress --quality).
# ---------------------------------------------------------------------------

QUALITY_FPS = 60.0
QUALITY_QP_LADDER = (24, 28, 32, 36)          # tpuh264enc sweep
QUALITY_RATE_LADDER = (500, 1000, 2000, 4000)  # kbps, x264/vp9 sweeps
QUALITY_X264_ANCHORS = ("ultrafast", "veryfast")


def _mean_scores(refs: list[np.ndarray], lumas: list[np.ndarray]) -> dict:
    """Mean PSNR/SSIM/VMAF over decoded-vs-source luma pairs; PSNR is
    capped at the probe's 99 dB ceiling so lossless frames (idle
    scenario) keep the mean finite."""
    from selkies_tpu.monitoring.quality import PSNR_CAP_DB, score_planes

    ps, ss, vs, kind = [], [], [], "proxy"
    for ref, dec in zip(refs, lumas):
        sc = score_planes(ref, dec)
        ps.append(min(sc.psnr_db, PSNR_CAP_DB))
        ss.append(sc.ssim)
        vs.append(sc.vmaf)
        kind = sc.vmaf_kind
    n = max(1, len(ps))
    return {"psnr_db": round(sum(ps) / n, 3), "ssim": round(sum(ss) / n, 5),
            "vmaf": round(sum(vs) / n, 2), "vmaf_kind": kind,
            "frames_scored": len(ps)}


def _quality_point(scenario: str, refs: list[np.ndarray],
                   aus: list[bytes], codec: str) -> dict | None:
    """Decode one encoded stream through its oracle and score it.
    None when the oracle dropped frames (refuse to mis-align)."""
    from selkies_tpu.monitoring.quality import GopDecoder

    lumas = GopDecoder(codec).decode_all(aus)
    if len(lumas) < len(aus):
        return None
    kbps = (sum(len(a) for a in aus) * 8.0 * QUALITY_FPS
            / max(1, len(aus)) / 1000.0)
    return {"rate_kbps": round(kbps, 1),
            **_mean_scores(refs, lumas[:len(refs)])}


def bench_quality(scenarios: list[str], w: int, h: int,
                  n_frames: int) -> list[dict]:
    """Rate/quality suite: point rows (one per scenario x encoder x
    rung) then bdrate rows (one per scenario x test-encoder x x264
    anchor). x264/vp9 rungs are skipped with a stderr note when the
    library is absent; BD-rate rows need >= 2 points per curve."""
    from selkies_tpu.models.libvpx_enc import (
        _bgrx_to_i420_np, libvpx_available)
    from selkies_tpu.models.x264enc import X264Encoder, x264_available
    from selkies_tpu.monitoring.quality import bd_rate

    rows: list[dict] = []
    for scen in scenarios:
        trace = _scenario_trace(scen, n_frames, w, h, seed=11)
        refs = [_bgrx_to_i420_np(f)[0] for f in trace]
        curves: dict[str, list[tuple[float, float]]] = {}

        def point(encoder: str, preset: str, aus: list[bytes],
                  codec: str, scen=scen, refs=refs, curves=curves) -> None:
            pt = _quality_point(scen, refs, aus, codec)
            if pt is None:
                print(json.dumps({
                    "metric": f"quality {scen} {encoder} {preset} skipped",
                    "note": "oracle dropped frames"}), file=sys.stderr)
                return
            curves.setdefault(encoder, []).append(
                (pt["rate_kbps"], pt["psnr_db"]))
            rows.append({"bench": "quality", "kind": "point",
                         "scenario": scen, "encoder": encoder,
                         "preset": preset, "codec": codec, **pt})

        # both entropy backends sweep the same QP ladder: the structure
        # pass is shared, so the cabac curve isolates pure coder gain
        # (encoder name "tpuh264enc" stays the CAVLC row r01 committed)
        for coder, encoder in (("cavlc", "tpuh264enc"),
                               ("cabac", "tpuh264enc-cabac")):
            for qp in QUALITY_QP_LADDER:
                aus = [a for a, _ in
                       _encode_scenario_aus(scen, n_frames, w, h, qp=qp,
                                            entropy_coder=coder)]
                point(encoder, f"qp{qp}", aus, "h264")
        if x264_available():
            for preset in QUALITY_X264_ANCHORS:
                for kbps in QUALITY_RATE_LADDER:
                    enc = X264Encoder(w, h, fps=int(QUALITY_FPS),
                                      bitrate_kbps=kbps, preset=preset)
                    aus = [enc.encode_frame(f) for f in trace]
                    point(f"x264-{preset}", f"{kbps}kbps", aus, "h264")
        else:
            print(json.dumps({"metric": f"quality {scen} x264 skipped",
                              "note": "libx264 unavailable"}),
                  file=sys.stderr)
        if libvpx_available():
            from selkies_tpu.models.libvpx_enc import LibVpxEncoder

            for kbps in QUALITY_RATE_LADDER:
                enc = LibVpxEncoder(w, h, fps=int(QUALITY_FPS),
                                    bitrate_kbps=kbps)
                aus = [enc.encode_frame(f) for f in trace]
                point("vp9", f"{kbps}kbps", aus, "vp9")
        else:
            print(json.dumps({"metric": f"quality {scen} vp9 skipped",
                              "note": "libvpx unavailable"}),
                  file=sys.stderr)

        # every test curve vs the x264 anchors, PLUS the coder-axis row:
        # tpuh264enc-cabac anchored on tpuh264enc (same structure pass,
        # same ladder) is the headline bitrate cut the ratchet holds
        anchors = [e for e in curves if e.startswith("x264-")]
        if "tpuh264enc" in curves:
            anchors.append("tpuh264enc")
        for encoder, pts in curves.items():
            if encoder.startswith("x264-"):
                continue
            for anchor in anchors:
                if anchor == encoder or (anchor == "tpuh264enc"
                                         and encoder != "tpuh264enc-cabac"):
                    continue
                bd = bd_rate(curves[anchor], pts)
                if bd is None:
                    continue
                rows.append({"bench": "quality", "kind": "bdrate",
                             "scenario": scen, "encoder": encoder,
                             "anchor": anchor,
                             "bd_rate_pct": round(bd, 2)})
    return rows


def bench_convert_only() -> float:
    import jax

    from selkies_tpu.ops.colorspace import bgrx_to_i420

    frames = [jax.device_put(f) for f in _desktop_trace(4)]
    out = bgrx_to_i420(frames[0])
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for i in range(ITERS):
        out = bgrx_to_i420(frames[i % len(frames)])
    jax.block_until_ready(out)
    dt = time.perf_counter() - t0
    return ITERS / dt


def main() -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--resolution", default=None,
        help="comma-separated geometry rows to bench: named "
             f"({', '.join(sorted(RESOLUTIONS))}) or WxH; one JSON line "
             "per resolution, each with the upload/step/fetch/pack split. "
             "Default: 1080p plus a 4K row on a real TPU backend (4K on "
             "the CPU backend takes minutes, so CI runs stay 1080p-only)")
    ap.add_argument(
        "--scenario", default=None,
        help="comma-separated scenario sweep (or 'all'): "
             f"{', '.join(SCENARIOS)}. One JSON row per scenario at the "
             "first --resolution: fps, p50/p95 capture->deliver latency, "
             "bytes_up/down_per_frame, stage split. Runs INSTEAD of the "
             "flagship desktop row (docs/policy.md)")
    ap.add_argument(
        "--scenario-frames", type=int, default=240,
        help="frames per scenario pass (two passes run: settle + timed)")
    ap.add_argument(
        "--policy", type=int, choices=(0, 1), default=None,
        help="scenario suite only: 1 drives the scenario-adaptive policy "
             "engine (selkies_tpu/policy), 0 static default knobs. "
             "Default follows SELKIES_POLICY")
    ap.add_argument(
        "--damage", type=int, choices=(0, 1), default=0,
        help="scenario suite only: 1 submits the traces' damage-rect "
             "hints (what an XDamage capture reports), bounding the "
             "classify scan; byte-identical to 0 by the superset "
             "contract (FramePrep.scan)")
    ap.add_argument(
        "--capacity", nargs="?", const="all", default=None,
        help="capacity ramp (or a comma mix list: "
             f"{', '.join(sorted(CAPACITY_MIXES))}): ramp N scenario-mix "
             "sessions until p95 latency breaches the per-scenario SLO "
             "targets, lockstep AND occupancy-overlapped, one JSON row "
             "per (mix, mode) with max_sessions_at_slo — the measured "
             "capacity curve SELKIES_CAPACITY_FILE feeds to the cluster "
             "digest. Runs INSTEAD of the flagship row")
    ap.add_argument(
        "--capacity-frames", type=int, default=96,
        help="frames per capacity ramp step (after an 8-frame settle)")
    ap.add_argument(
        "--capacity-max", type=int, default=8,
        help="ramp ceiling: stop raising N at this many sessions even "
             "if the SLO still holds")
    ap.add_argument(
        "--impair", nargs="?", const="all", default=None,
        help="impairment gauntlet (or a comma profile list: lte_handover, "
             "hotel_wifi, v2x): replay encoded scenario traces through "
             "deterministic link-loss profiles into a recovering receiver "
             "(NACK/RTX + FEC + forced-IDR ladder), one JSON row per "
             "(profile, scenario) with recovered-vs-frozen ratio, recovery "
             "latency p50/p95 and rtx/fec overhead bytes. Runs INSTEAD of "
             "the flagship row (docs/recovery.md)")
    ap.add_argument(
        "--impair-frames", type=int, default=300,
        help="frames per impairment cell (replayed at a simulated 60 fps, "
             "so 300 frames = 5 s of link trace per cell)")
    ap.add_argument(
        "--impair-scenarios", default=",".join(IMPAIR_SCENARIOS),
        help="comma-separated scenarios to encode for the gauntlet "
             f"(default {','.join(IMPAIR_SCENARIOS)})")
    ap.add_argument(
        "--quality", nargs="?", const="all", default=None,
        help="rate/quality suite (or a comma scenario list: "
             f"{', '.join(SCENARIOS)}): encode each scenario across the "
             "tpuh264enc QP ladder plus x264-preset and vp9 bitrate "
             "ladders, decode every stream through its reference oracle "
             "and score PSNR/SSIM/VMAF vs the pre-encode source; point "
             "rows per rung, BD-rate rows vs the x264 anchors. Runs "
             "INSTEAD of the flagship row (docs/quality.md)")
    ap.add_argument(
        "--quality-frames", type=int, default=90,
        help="frames per quality cell (every decoded frame is scored)")
    ap.add_argument(
        "--codec", default=None,
        help="comma-separated codec sweep (h264,av1,vp9,...): one JSON "
             "line per codec at each --resolution, from the encoder row "
             "per-client negotiation would pick (signalling/negotiate.py). "
             "h264 runs the full pipelined bench; library-backed rows run "
             "the plain encode_frame loop. Codecs whose libraries are "
             "absent are skipped with a note")
    args = ap.parse_args()
    if args.capacity:
        mixes = (sorted(CAPACITY_MIXES)
                 if args.capacity.strip().lower() == "all"
                 else [m.strip().lower() for m in args.capacity.split(",")
                       if m.strip()])
        for m in mixes:
            if m not in CAPACITY_MIXES:
                raise SystemExit(f"unknown capacity mix {m!r} (one of "
                                 f"{sorted(CAPACITY_MIXES)})")
        label, w, h = _parse_resolutions(args.resolution or "512x288")[0]
        for row in bench_capacity(w, h, max(30, args.capacity_frames),
                                  mixes, max(1, args.capacity_max)):
            _result(
                f"capacity {row['codec']} {label} chips={row['chips']} "
                f"mix={row['mix']} ({row['mode']})",
                float(row["max_sessions_at_slo"]), unit="sessions@slo",
                **{k: v for k, v in row.items() if k != "codec"},
                resolution=label, codec=row["codec"])
        return 0
    if args.impair:
        from selkies_tpu.transport.impair import PROFILES

        profiles = (sorted(PROFILES)
                    if args.impair.strip().lower() == "all"
                    else [p.strip().lower() for p in args.impair.split(",")
                          if p.strip()])
        for p in profiles:
            if p not in PROFILES:
                raise SystemExit(f"unknown impairment profile {p!r} "
                                 f"(one of {sorted(PROFILES)})")
        scenarios = [s.strip().lower() for s in
                     args.impair_scenarios.split(",") if s.strip()]
        for s in scenarios:
            if s not in SCENARIOS:
                raise SystemExit(f"unknown scenario {s!r} (one of "
                                 f"{list(SCENARIOS)})")
        label, w, h = _parse_resolutions(args.resolution or "512x288")[0]
        for row in bench_impair(w, h, max(60, args.impair_frames),
                                profiles, scenarios):
            _result(
                f"impair {row['profile']} {row['scenario']} {label}",
                float(row["recovered_ratio"]), unit="recovered_ratio",
                **row, resolution=label)
        return 0
    if args.quality:
        names = ([*SCENARIOS] if args.quality.strip().lower() == "all"
                 else [s.strip().lower() for s in args.quality.split(",")
                       if s.strip()])
        for s in names:
            if s not in SCENARIOS:
                raise SystemExit(f"unknown scenario {s!r} (one of "
                                 f"{list(SCENARIOS)})")
        label, w, h = _parse_resolutions(args.resolution or "512x288")[0]
        for row in bench_quality(names, w, h, max(30, args.quality_frames)):
            if row["kind"] == "point":
                _result(
                    f"quality {row['scenario']} {row['encoder']} "
                    f"{row['preset']} {label}",
                    float(row["psnr_db"]), unit="psnr_db",
                    **row, resolution=label)
            else:
                _result(
                    f"bdrate {row['scenario']} {row['encoder']} "
                    f"vs {row['anchor']} {label}",
                    float(row["bd_rate_pct"]), unit="bd_rate_pct",
                    **row, resolution=label)
        return 0
    if args.resolution is None:
        import jax

        args.resolution = ("1080p,4k" if jax.default_backend() == "tpu"
                           else "1080p")
    if args.scenario:
        from selkies_tpu.policy import policy_enabled

        names = ([*SCENARIOS] if args.scenario.strip().lower() == "all"
                 else [s.strip().lower() for s in args.scenario.split(",")
                       if s.strip()])
        policy_on = (policy_enabled() if args.policy is None
                     else bool(args.policy))
        label, w, h = _parse_resolutions(args.resolution)[0]
        for name in names:
            row = bench_scenario(name, w, h, max(60, args.scenario_frames),
                                 policy_on, damage_on=bool(args.damage))
            fps = row.pop("fps")
            row["resolution"] = label
            label_bits = "policy" if policy_on else "static"
            if args.damage:
                label_bits += "+damage"
            _result(f"scenario {name} {label} encode ({label_bits})", fps,
                    unit=f"fps@{label}", **row)
        return 0
    codecs = [c.strip().lower() for c in (args.codec or "h264").split(",")
              if c.strip()]
    ran = False
    for label, w, h in _parse_resolutions(args.resolution):
        for codec in codecs:
            if codec == "h264":
                continue  # the flagship row below
            row = bench_codec_encoder(codec, w, h)
            if row is None:
                print(json.dumps({"metric": f"{codec} {label} skipped",
                                  "note": "codec library unavailable"}),
                      file=sys.stderr)
                continue
            ran = True
            c_fps, c_means = row
            c_means["resolution"] = label
            _result(f"{codec} {label} IP-GOP encode fps", c_fps,
                    unit=f"fps@{label}", **c_means)
        if "h264" not in codecs:
            continue
        out = bench_full_encoder(w, h)
        if out is None:
            break
        ran = True
        fps, means = out
        # bytes_up/down_per_frame: host<->device link bytes — lets
        # future rounds track the link terms without a separate
        # profiling pass. pack_ms splits into
        # unpack_ms (downlink bytes -> packer-ready coefficients) +
        # cavlc_ms (entropy pack + NAL), device_stage_latency_ms into
        # upload_ms + step_ms + fetch_ms, so the trajectory attributes
        # each regression to the right sub-stage.
        means["device_stage_latency_ms"] = means.pop("device_ms")
        means["resolution"] = label
        means["codec"] = "h264"
        _result(f"tpuh264enc {label} IP-GOP encode fps (1 chip)", fps,
                unit=f"fps@{label}", **means)
    if not ran:
        _result("capture->I420 convert fps (encoder pending)", bench_convert_only())
    return 0


if __name__ == "__main__":
    sys.exit(main())
