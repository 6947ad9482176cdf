#!/usr/bin/env python3
"""Bring the served 1080p tpuh264enc path up on a TPU chip, in one process.

Run from the checkout root on a machine with a TPU: ``python chip_smoke.py``.
The phases run in order and any failure exits non-zero:

1. device  -- JAX must report a TPU; there is no CPU fallback.
2. native  -- rebuild native/ from its sources; the CAVLC/CABAC packer
   and frameprep must load from there.
3. encoder -- tpuh264enc from the registry at 1920x1080, default knobs,
   on its TPU branches (Pallas ME, device entropy), over the bench's
   desktop trace at fixed QP. Every AU must be sha256-equal to the
   digests a CPU run of the same function recorded (XLA ME, host pack),
   decode with FFmpeg (cv2) to the encoder's recon, and keep luma PSNR
   above PSNR_FLOOR_DB. Then a few 3840x2160 frames, the same checks.
4. served  -- the real Orchestrator (synthetic 1080p60 capture, WS
   /media client) delivers >= 60 decodable AUs, IDR first, with no
   software fallback and no supervisor escalation past WARN.

``--chips 4`` runs only the ``--tpu_sessions`` path: four 1080p sessions
over four chips, each on its own chip and byte-identical to a solo
encode. ``--record-digests`` (CPU backend only) rewrites
chip_smoke_digests.json from phase 3's encode.

The last line of stdout is ``{"ok": true, "device": {...}}`` on success.
"""

from __future__ import annotations

import argparse
import asyncio
import hashlib
import json
import os
import subprocess
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
DIGESTS = ROOT / "chip_smoke_digests.json"
W, H = 1920, 1080
# (width, height, frames): 32 desktop-trace frames hold one full-screen
# window switch (frame 29); the 4K run is an IDR plus two deltas
CASES = ((W, H, 32), (3840, 2160, 3))
QP = 28
# worst-frame luma PSNR of recon vs source at QP 28, as the CPU run that
# recorded the digests measured it: 38.05 dB at 1080p (the window switch,
# coded at the scene-cut QP boost) and 54.23 dB at 4K; 1 dB of margin
PSNR_FLOOR_DB = 37.0
SERVED_AUS = 60


class SmokeError(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeError(msg)


class Compiles:
    """XLA backend compiles and persistent-cache lookups since last take()."""

    def __init__(self):
        import jax.monitoring

        self.n, self.secs, self.hits, self.misses = 0, 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.secs += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def take(self) -> str:
        out = (f"{self.n} backend compiles in {self.secs:.1f} s, cache "
               f"{self.hits} hits / {self.misses} misses")
        self.n, self.secs, self.hits, self.misses = 0, 0.0, 0, 0
        return out


# -- phase 1 ----------------------------------------------------------------

def device_info(chips: int) -> dict:
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    check(info["platform"] == "tpu", f"JAX found no TPU: {info}")
    check(len(devs) >= chips, f"need {chips} chips, JAX sees {len(devs)}")
    print(f"device: {info['kind']} x{info['count']}", flush=True)
    return info


# -- phase 2 ----------------------------------------------------------------

def build_native() -> None:
    native_dir = ROOT / "native"
    subprocess.run(["make", "-B", "-s", "-C", str(native_dir)], check=True)
    from selkies_tpu.models import frameprep
    from selkies_tpu.models.h264 import native

    for mod, name in ((native, "libcavlc.so"), (frameprep, "libframeprep.so")):
        lib = mod._load()
        check(lib is not None and Path(lib._name) == native_dir / name,
              f"{name} did not load from {native_dir}: {lib}")
    check(native.native_available() and native.sparse_native_available()
          and native.cabac_native_available(), "native packer entries missing")
    print(f"native: libcavlc.so + libframeprep.so built and loaded from "
          f"{native_dir}", flush=True)


# -- phase 3 ----------------------------------------------------------------

def _pallas_interpret_flags(w: int, h: int) -> list[bool]:
    """`interpret` of every pallas_call in the traced device-entropy P step."""
    import jax
    import jax.numpy as jnp

    from selkies_tpu.models.h264 import encoder

    ph, pw = (h + 15) // 16 * 16, (w + 15) // 16 * 16
    y = jax.ShapeDtypeStruct((ph, pw), jnp.uint8)
    c = jax.ShapeDtypeStruct((ph // 2, pw // 2), jnp.uint8)
    qp = jax.ShapeDtypeStruct((), jnp.int32)
    jaxpr = jax.make_jaxpr(encoder._p_bits_step)(y, c, c, qp, y, c, c)
    flags: list[bool] = []

    def walk(jp):
        for eqn in jp.eqns:
            if eqn.primitive.name == "pallas_call":
                flags.append(bool(eqn.params["interpret"]))
            for v in eqn.params.values():
                for sub in v if isinstance(v, (tuple, list)) else (v,):
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns"):
                        walk(inner)

    walk(jaxpr.jaxpr)
    return flags


def encode_trace(w: int, h: int, n: int) -> dict:
    """Phase 3's encode: the registry's tpuh264enc with default knobs over
    the desktop trace at fixed QP (no rate control in the loop), one
    frame at a time so each frame's recon (the reference planes it
    leaves) can be read. The pipelined submit path the live pipeline
    uses emits the same bytes (checked on the CPU when the digests were
    recorded) and runs on the chip in the served phase."""
    from bench import _desktop_trace
    from selkies_tpu.models.registry import create_encoder

    frames = _desktop_trace(n, w, h)
    enc = create_encoder("tpuh264enc", width=w, height=h, qp=QP)
    knobs = {"device_entropy": bool(enc.device_entropy),
             "entropy_coder": enc.entropy_coder,
             "frame_batch": enc.frame_batch}
    ph, pw = enc._pad_h, enc._pad_w
    aus, recon = [], []
    t0 = time.perf_counter()
    try:
        for f in frames:
            aus.append(enc.encode_frame(f))
            ry, ru, rv = (np.asarray(p) for p in enc._ref)
            recon.append((ry.reshape(ph, pw)[:h, :w],
                          ru.reshape(ph // 2, pw // 2)[:h // 2, :w // 2],
                          rv.reshape(ph // 2, pw // 2)[:h // 2, :w // 2]))
    finally:
        enc.close()
    return {"frames": frames, "aus": aus, "recon": recon, "knobs": knobs,
            "seconds": time.perf_counter() - t0}


def pipelined_aus(w: int, h: int, n: int) -> list[bytes]:
    """The same trace through submit/flush (grouped delta dispatch)."""
    from bench import _desktop_trace
    from selkies_tpu.models.registry import create_encoder

    enc = create_encoder("tpuh264enc", width=w, height=h, qp=QP)
    try:
        aus = [au for f in _desktop_trace(n, w, h)
               for au, _, _ in enc.submit(f)]
        return aus + [au for au, _, _ in enc.flush()]
    finally:
        enc.close()


def sha256s(aus: list[bytes]) -> list[str]:
    return [hashlib.sha256(au).hexdigest() for au in aus]


class _CaptureFd2:
    """FFmpeg inside cv2 logs decode errors straight to fd 2."""

    def __enter__(self):
        sys.stderr.flush()
        self._tmp = tempfile.TemporaryFile()
        self._saved = os.dup(2)
        os.dup2(self._tmp.fileno(), 2)
        return self

    def __exit__(self, *exc):
        os.dup2(self._saved, 2)
        os.close(self._saved)
        self._tmp.seek(0)
        self.text = self._tmp.read().decode(errors="replace")
        self._tmp.close()
        sys.stderr.write(self.text)


def decode(aus: list[bytes], workdir: Path, raw: bool) -> list[np.ndarray]:
    """Decode an AU stream with FFmpeg via cv2: BGR frames, or with
    ``raw`` the decoder's own luma plane. Fails on any decoder error."""
    import cv2

    path = workdir / "stream.h264"
    path.write_bytes(b"".join(aus))
    params = [cv2.CAP_PROP_CONVERT_RGB, 0] if raw else []
    out = []
    with _CaptureFd2() as err:
        cap = cv2.VideoCapture(str(path), cv2.CAP_FFMPEG, params)
        while True:
            ok, frame = cap.read()
            if not ok:
                break
            out.append(frame)
        cap.release()
    errors = [ln for ln in err.text.splitlines() if "[h264 @" in ln]
    check(not errors, f"decoder errors: {errors[:5]}")
    return out


def _bgr_of(y, u, v) -> np.ndarray:
    """BT.601 limited-range I420 -> BGR (the matrix FFmpeg's swscale uses,
    tests/test_h264_conformance.py)."""
    yf = (y.astype(int) - 16) * 1.164383
    up = np.repeat(np.repeat(u.astype(int) - 128, 2, 0), 2, 1)
    vp = np.repeat(np.repeat(v.astype(int) - 128, 2, 0), 2, 1)
    r = yf + 1.596027 * vp
    g = yf - 0.391762 * up - 0.812968 * vp
    b = yf + 2.017232 * up
    return np.clip(np.stack([b, g, r], -1) + 0.5, 0, 255).astype(int)


def check_encode(w: int, h: int, run: dict, digests: list[str] | None,
                 workdir: Path) -> float:
    """Checks (a) digests, (b) decode == recon, (c) PSNR; returns the
    worst frame's luma PSNR."""
    from selkies_tpu.models.frameprep import _numpy_convert_pad

    aus, recon, n = run["aus"], run["recon"], len(run["frames"])
    check(len(aus) == n, f"{w}x{h}: {len(aus)} AUs for {n} frames")
    got = sha256s(aus)
    if digests is not None:
        bad = [i for i, (a, b) in enumerate(zip(got, digests)) if a != b]
        check(len(digests) == n and not bad,
              f"{w}x{h}: AUs differ from the CPU digests at frames {bad}")
    lumas = decode(aus, workdir, raw=True)
    check(len(lumas) == n, f"{w}x{h}: decoded {len(lumas)} of {n} AUs")
    for i, (luma, (ry, _, _)) in enumerate(zip(lumas, recon)):
        check(luma.shape == (h, w) and np.array_equal(luma, ry),
              f"{w}x{h}: decoded luma != recon at frame {i}")
    bgrs = decode(aus, workdir, raw=False)
    check(len(bgrs) == n, f"{w}x{h}: BGR decode gave {len(bgrs)} of {n}")
    for i, (bgr, planes) in enumerate(zip(bgrs, recon)):
        d = np.abs(bgr.astype(int) - _bgr_of(*planes))
        check(d.mean() < 1.5 and d.max() <= 4,
              f"{w}x{h}: decoded colour != recon at frame {i} "
              f"(mean {d.mean():.3f}, max {d.max()})")
    psnrs = []
    for f, (ry, _, _) in zip(run["frames"], recon):
        src = _numpy_convert_pad(f, h + (h & 1), w + (w & 1))[0][:h, :w]
        mse = np.mean((src.astype(float) - ry) ** 2)
        psnrs.append(99.0 if mse == 0 else 10 * np.log10(255.0 ** 2 / mse))
    worst = min(psnrs)
    check(worst >= PSNR_FLOOR_DB,
          f"{w}x{h}: luma PSNR {worst:.2f} dB < floor {PSNR_FLOOR_DB}")
    return worst


def encoder_phase(workdir: Path, compiles: Compiles) -> None:
    from selkies_tpu.models.h264 import encoder_core

    check(encoder_core._use_pallas_me(W), "Pallas ME is off at 1920 px")
    flags = _pallas_interpret_flags(W, H)
    check(flags and not any(flags),
          f"P step pallas_call interpret flags: {flags}")
    digests = json.loads(DIGESTS.read_text())
    for w, h, n in CASES:
        run = encode_trace(w, h, n)
        check(run["knobs"]["device_entropy"],
              f"{w}x{h}: device entropy is off: {run['knobs']}")
        worst = check_encode(w, h, run, digests[f"{w}x{h}"], workdir)
        print(f"encoder {w}x{h}: {n} AUs sha256-equal to CPU digests, "
              f"decode == recon, worst luma PSNR {worst:.2f} dB; "
              f"knobs {run['knobs']}; {run['seconds']:.1f} s incl. cold "
              f"compile ({compiles.take()})", flush=True)


def record_digests(workdir: Path) -> None:
    import jax

    check(jax.default_backend() == "cpu", "digests are recorded on the CPU")
    doc = {"_": "sha256 per AU of chip_smoke.encode_trace on the CPU backend "
                "(XLA ME, host pack); written by chip_smoke.py "
                "--record-digests, never from a chip"}
    for w, h, n in CASES:
        run = encode_trace(w, h, n)
        worst = check_encode(w, h, run, None, workdir)
        check(pipelined_aus(w, h, n) == run["aus"],
              f"{w}x{h}: pipelined AUs differ from one-at-a-time AUs")
        doc[f"{w}x{h}"] = sha256s(run["aus"])
        print(f"recorded {w}x{h}: {n} AUs, worst luma PSNR {worst:.2f} dB, "
              f"knobs {run['knobs']}", flush=True)
    DIGESTS.write_text(json.dumps(doc, indent=1) + "\n")


# -- phase 4 ----------------------------------------------------------------

async def _serve(workdir: Path) -> dict:
    import aiohttp

    from selkies_tpu.config import FLAGS, Config
    from selkies_tpu.input_host import FakeBackend, MemoryClipboard
    from selkies_tpu.orchestrator import Orchestrator
    from selkies_tpu.pipeline.elements import SyntheticSource
    from selkies_tpu.transport.websocket import (FLAG_KEYFRAME, KIND_VIDEO,
                                                 parse_media_frame)

    values = {fl.name: fl.default for fl in FLAGS}
    values.update(
        addr="127.0.0.1", port=0, framerate=60, encoder="tpuh264enc",
        capture_width=W, capture_height=H, enable_cursors=False,
        stun_host="127.0.0.1",
        json_config=str(workdir / "selkies_config.json"),
        rtc_config_json=str(workdir / "rtc.json"))
    orch = Orchestrator(Config(values=values))
    check(isinstance(orch.app.source, SyntheticSource),
          f"capture is {type(orch.app.source).__name__}, not synthetic")
    orch.input.backend = FakeBackend()
    orch.input.clipboard = MemoryClipboard()
    run_task = asyncio.ensure_future(orch.run())
    video: list[tuple[int, bytes, float]] = []
    try:
        for _ in range(600):
            if orch.server._runner is not None and orch.server._runner.addresses:
                break
            await asyncio.sleep(0.05)
        base = f"http://127.0.0.1:{orch.server.bound_port}"
        async with aiohttp.ClientSession() as http:
            ws = await http.ws_connect(base + "/media")
            t_connect = time.perf_counter()
            deadline = t_connect + 600
            while len(video) < SERVED_AUS and time.perf_counter() < deadline:
                msg = await asyncio.wait_for(ws.receive(), 300)
                if msg.type == aiohttp.WSMsgType.BINARY:
                    kind, flags, _, payload = parse_media_frame(msg.data)
                    if kind == KIND_VIDEO:
                        video.append((flags, payload, time.perf_counter()))
                elif msg.type == aiohttp.WSMsgType.TEXT:
                    obj = json.loads(msg.data)
                    if obj.get("type") == "ping":
                        await ws.send_str(f"pong,{obj['data']['start_time']}")
                else:
                    break
            await ws.close()
        app = orch.app
        return {
            "video": video, "t_connect": t_connect,
            "software_fallback": app.software_fallback,
            "supervisor": app.supervisor.stats(),
            "encoder": type(app.encoder).__name__,
        }
    finally:
        await orch.server.stop()
        try:
            await asyncio.wait_for(run_task, 30)
        except (asyncio.TimeoutError, asyncio.CancelledError):
            run_task.cancel()


def served_phase(workdir: Path, compiles: Compiles) -> None:
    os.environ.pop("DISPLAY", None)  # synthetic capture, never a local X
    res = asyncio.run(_serve(workdir))
    video = res["video"]
    check(len(video) >= SERVED_AUS,
          f"served {len(video)} video AUs, want {SERVED_AUS}")
    from selkies_tpu.transport.websocket import FLAG_KEYFRAME

    check(video[0][0] & FLAG_KEYFRAME, "first served AU is not an IDR")
    check(not res["software_fallback"], "app fell back to the software row")
    sup = res["supervisor"]
    escalated = {k: sup[k] for k in ("idrs_forced", "restarts", "degrades",
                                     "recycles") if sup[k]}
    check(not escalated and sup["degrade_level"] == 0,
          f"supervisor escalated past WARN: {sup}")
    check(res["encoder"] == "TPUH264Encoder",
          f"served by {res['encoder']}, not the TPU row")
    frames = decode([p for _, p, _ in video], workdir, raw=False)
    check(len(frames) == len(video) and all(
        f.shape == (H, W, 3) for f in frames),
        f"decoded {len(frames)} of {len(video)} served AUs at 1920x1080")
    first, last = video[0][2], video[-1][2]
    fps = (len(video) - 1) / (last - first) if last > first else float("nan")
    print(f"served: {len(video)} AUs decoded at {W}x{H}, IDR first, "
          f"software_fallback=False, supervisor {sup['rung']} "
          f"(failures {sup['failures']}, deadline misses "
          f"{sup['deadline_misses']}); first AU {first - res['t_connect']:.1f} s "
          f"after connect; observed {fps:.1f} fps delivered; "
          f"{compiles.take()}", flush=True)


# -- --chips 4 --------------------------------------------------------------

def sessions_phase(n: int, compiles: Compiles, ticks: int = 8) -> None:
    """--tpu_sessions: n 1080p sessions over n chips vs n solo encodes."""
    import jax

    from bench import _desktop_trace
    from selkies_tpu.models.h264.encoder import TPUH264Encoder
    from selkies_tpu.parallel.serving import MultiSessionH264Service

    devs = jax.devices()[:n]
    trace = _desktop_trace(ticks + 3 * (n - 1), W, H)
    # session k streams its own slice of the trace (distinct text lines)
    per = [trace[3 * k: 3 * k + ticks] for k in range(n)]
    svc = MultiSessionH264Service(n, W, H, qp=QP, devices=devs)
    streams: list[list[bytes]] = [[] for _ in range(n)]
    t0 = time.perf_counter()
    try:
        for t in range(ticks):
            pending = svc.dispatch_tick(np.stack([per[k][t] for k in range(n)]))
            placed = {}
            # the resident per-session reference planes (recon luma)
            for shard in svc.enc._ref[0].addressable_shards:
                placed[shard.index[0].start or 0] = shard.device
            check(sorted(placed) == list(range(n))
                  and len(set(placed.values())) == n
                  and set(placed.values()) == set(devs),
                  f"session shards not one per chip: {placed}")
            for k, au in enumerate(svc.complete_tick(pending)):
                streams[k].append(au)
    finally:
        svc.close()
    tick_s = time.perf_counter() - t0
    for k in range(n):
        # host entropy, like the service (tests/test_multi_session_serving.py);
        # device entropy against the host packer is phase 3's digest check
        solo = TPUH264Encoder(width=W, height=H, qp=QP, host_convert=False,
                              frame_batch=1, device_entropy=False)
        ref = [solo.encode_frame(f) for f in per[k]]
        solo.close()
        bad = [i for i, (a, b) in enumerate(zip(streams[k], ref)) if a != b]
        check(not bad, f"session {k} diverged from its solo encode at {bad}")
    print(f"sessions: {n} x {W}x{H} on {n} distinct chips, {ticks} ticks "
          f"({tick_s:.1f} s incl. compile), each byte-identical to solo; "
          f"{compiles.take()}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--record-digests", action="store_true")
    args = ap.parse_args(argv)
    try:
        with tempfile.TemporaryDirectory() as tmp:
            workdir = Path(tmp)
            if args.record_digests:
                build_native()
                record_digests(workdir)
                return 0
            info = device_info(args.chips)
            compiles = Compiles()
            build_native()
            if args.chips > 1:
                sessions_phase(args.chips, compiles)
            else:
                encoder_phase(workdir, compiles)
                served_phase(workdir, compiles)
    except (SmokeError, subprocess.CalledProcessError) as e:
        print(f"FAIL: {type(e).__name__}: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    try:
        rc = main()
    except BaseException:  # noqa: BLE001 - report, then exit non-zero below
        import traceback

        traceback.print_exc()
        rc = 1
    sys.stdout.flush()
    sys.stderr.flush()
    # completion/pack pools and the served app's threads must not keep a
    # finished (or failed) smoke holding the chip
    os._exit(rc)
