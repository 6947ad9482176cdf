"""Multi-session serving: N session streams from one sharded device step.

Builds on parallel/sessions.MultiSessionEncoder (the v5e-8 placement:
one 1080p60 stream per chip, BASELINE.md) and adds everything a serving
path needs per session: GOP state (frame_num / idr_pic_id /
force_keyframe), per-session QP, coefficient fetch, and concurrent
host-side CAVLC packing — one worker per session, since entropy packing
is independent per stream.

Reference context: the reference scales out with one OS process per
session and Kubernetes placement (SURVEY §2.6); here a single host
process drives the whole slice and hands each transport its own Annex-B
access units. Output streams are bit-identical to N solo TPUH264Encoder
instances fed the same frames (tests/test_multi_session_serving.py).
"""

from __future__ import annotations

import logging
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from selkies_tpu.models.frameprep import FramePrep
from selkies_tpu.models.h264.bitstream import StreamParams, write_pps, write_sps
from selkies_tpu.models.h264.native import pack_slice_fast, pack_slice_p_fast
from selkies_tpu.models.h264.numpy_ref import FrameCoeffs, PFrameCoeffs
from selkies_tpu.monitoring.tracing import tracer
from selkies_tpu.parallel.sessions import MultiSessionEncoder
from selkies_tpu.resilience.devhealth import check_device_faults

logger = logging.getLogger("parallel.serving")

__all__ = ["BandedFleetService", "MultiSessionH264Service", "SoftwareFleetService"]


class _SessionState:
    __slots__ = ("frames_since_idr", "idr_pic_id", "force_idr", "qp")

    def __init__(self, qp: int):
        self.frames_since_idr = 0
        self.idr_pic_id = 0
        self.force_idr = True
        self.qp = qp


class MultiSessionH264Service:
    """N synchronized session streams; one batched sharded encode/tick.

    The step ticks in lockstep (frames come in as a batch, one per
    session) but GOP policy is fully per-session: the mixed tick is a
    shard_map whose per-chip lax.cond picks the IDR or P branch from
    that session's own force_keyframe/GOP state, so one client's PLI
    recovery no longer drags every session onto the IDR executable.
    Only the very first tick (no reference planes exist yet) uses the
    batch-wide IDR step.
    """

    def __init__(self, n_sessions: int, width: int, height: int, *,
                 qp: int = 28, fps: int = 60, devices=None):
        from selkies_tpu.utils.jaxcache import enable_persistent_compilation_cache

        # service rebuilds (the fleet supervisor's RESTART rung) reload the
        # sharded step from the disk cache instead of recompiling
        enable_persistent_compilation_cache()
        # the device step runs on MB-aligned planes; the SPS crops the
        # padding back off (1080p encodes as 1088 rows)
        pad_w, pad_h = (width + 15) // 16 * 16, (height + 15) // 16 * 16
        self.enc = MultiSessionEncoder(n_sessions, pad_w, pad_h, devices=devices)
        self.n = n_sessions
        # per-session IDR flags of the most recent tick (the serving loop
        # needs them for keyframe framing + VBV accounting, mirroring the
        # solo encoder's last_stats pattern). The batched multi-session
        # step has no per-frame downlink attribution, so last_modes stays
        # "" here (unattributed) rather than guessing "coeff".
        self.last_idrs: list[bool] = [True] * n_sessions
        self.last_modes: list[str] = [""] * n_sessions
        self.params = StreamParams(width=width, height=height, qp=qp, fps=fps)
        self._headers = write_sps(self.params) + write_pps(self.params)
        self.sessions = [_SessionState(qp) for _ in range(n_sessions)]
        self._pool = ThreadPoolExecutor(max_workers=n_sessions, thread_name_prefix="ms-pack")
        # host-side BGRx->I420 (the solo encoder's production path): one
        # native converter per session, run concurrently on the pack pool
        # — removes the ~14 ms/tick on-device colorspace + padded-frame
        # cost that held the mixed tick at ~43 fps/session (PERF.md)
        self._preps = [FramePrep(width, height, pad_w, pad_h, nslots=2)
                       for _ in range(n_sessions)]
        # persistent batch planes: workers copy each session's converted
        # planes into its slice, avoiding a fresh np.stack allocation
        # every tick (~4.5 MB/session of alloc+copy at 1080p); the
        # remaining host->device copy is the sharded device_put itself
        self._batch_y = np.empty((n_sessions, pad_h, pad_w), np.uint8)
        self._batch_u = np.empty((n_sessions, pad_h // 2, pad_w // 2), np.uint8)
        self._batch_v = np.empty((n_sessions, pad_h // 2, pad_w // 2), np.uint8)
        # the session mesh's chips, for the device:<chip> fault site —
        # a seeded schedule can kill/wedge/flap one chip of the lockstep
        # batch mid-stream (resilience/devhealth.py)
        self.devices = list(np.asarray(self.enc.mesh.devices).flat)

    def set_qp(self, session: int, qp: int) -> None:
        if not 0 <= qp <= 51:
            raise ValueError(f"qp {qp} out of range")
        self.sessions[session].qp = int(qp)

    def force_keyframe(self, session: int) -> None:
        self.sessions[session].force_idr = True

    def encode_tick(self, frames: np.ndarray) -> list[bytes]:
        """(N, H, W, 4) BGRx batch -> one Annex-B access unit per session.

        Composed of :meth:`dispatch_tick` + :meth:`complete_tick` — the
        occupancy scheduler's split (parallel/occupancy.py) — so the
        overlapped path is byte-identical to this one by construction."""
        return self.complete_tick(self.dispatch_tick(frames))

    def dispatch_tick(self, frames: np.ndarray) -> tuple:
        """Front half of :meth:`encode_tick`: per-session host conversion
        plus the ASYNC sharded device step dispatch. The returned token
        holds unfetched device arrays — the chips are stepping while the
        caller's thread moves on (jax dispatch returns before the step
        completes); :meth:`complete_tick` fetches and packs."""
        if frames.shape[0] != self.n:
            raise ValueError(f"expected {self.n} frames, got {frames.shape[0]}")
        check_device_faults(self.devices)
        idrs = np.array(
            [s.force_idr or s.frames_since_idr == 0 for s in self.sessions], bool
        )
        # concurrent per-session host conversion (native frameprep)
        def _convert_into(i: int) -> None:
            y, u, v = self._preps[i].convert(frames[i])
            np.copyto(self._batch_y[i], y)
            np.copyto(self._batch_u[i], u)
            np.copyto(self._batch_v[i], v)

        with tracer.span("convert"):
            list(self._pool.map(_convert_into, range(self.n)))
        batch = (self._batch_y, self._batch_u, self._batch_v)
        qps = np.array([s.qp for s in self.sessions], np.int32)
        with tracer.span("device-step"):
            if self.enc._ref is None:
                # first tick: no reference planes exist, everyone starts a GOP
                idrs[:] = True
                out = self.enc.encode_idr(batch, qps)
            else:
                out = self.enc.encode_mixed(batch, qps, idrs)
        return (out, idrs)

    def complete_tick(self, pending: tuple) -> list[bytes]:
        """Back half of :meth:`encode_tick`: coefficient fetch (this is
        where the device wait lives), concurrent per-session CAVLC pack,
        and the GOP state advance."""
        out, idrs = pending
        # fetch the coefficient batch once, then pack per session in
        # parallel (independent streams). Branch-filler fields are
        # skipped when no session took that branch — the all-zero
        # luma_dc/mode tensors alone are ~0.5 MB/session/tick of dead
        # d2h on a per-byte-priced link.
        i_only = {"luma_mode", "chroma_mode", "luma_dc"}
        p_only = {"mvs", "skip"}
        skip_keys = (i_only if not idrs.any() else set()) | (
            p_only if idrs.all() else set())
        with tracer.span("fetch"):
            host = {k: np.asarray(v) for k, v in out.items() if k not in skip_keys}
        with tracer.span("pack"):
            futures = [
                self._pool.submit(self._pack_one, i, host, bool(idrs[i]))
                for i in range(self.n)
            ]
            aus = [f.result() for f in futures]
        self.last_idrs = [bool(x) for x in idrs]
        for s, idr in zip(self.sessions, idrs):
            if idr:
                s.frames_since_idr = 1
                s.idr_pic_id = (s.idr_pic_id + 1) % 2
                s.force_idr = False
            else:
                s.frames_since_idr += 1
        return aus

    def _pack_one(self, i: int, host: dict, idr: bool) -> bytes:
        s = self.sessions[i]
        if idr:
            fc = FrameCoeffs(
                luma_mode=host["luma_mode"][i], chroma_mode=host["chroma_mode"][i],
                luma_dc=host["luma_dc"][i], luma_ac=host["luma_ac"][i],
                chroma_dc=host["chroma_dc"][i], chroma_ac=host["chroma_ac"][i],
                qp=int(s.qp),
            )
            nal = pack_slice_fast(
                fc, self.params, frame_num=0, idr=True, idr_pic_id=s.idr_pic_id
            )
            return self._headers + nal
        pfc = PFrameCoeffs(
            mvs=host["mvs"][i], skip=host["skip"][i], luma_ac=host["luma_ac"][i],
            chroma_dc=host["chroma_dc"][i], chroma_ac=host["chroma_ac"][i],
            qp=int(s.qp),
        )
        return pack_slice_p_fast(pfc, self.params, frame_num=s.frames_since_idr % 256)

    def close(self) -> None:
        self._pool.shutdown(wait=False, cancel_futures=True)


class BandedFleetService:
    """Band-parallel fleet service: N sessions, each band-split across
    its OWN row of chips (parallel/bands.py), behind the
    MultiSessionH264Service interface.

    This is the other end of the chips-per-session trade the session
    mesh makes: MultiSessionH264Service maps one session per chip
    (8 sessions on a v5e-8); with SELKIES_BANDS=B this service carves
    the slice into N = chips // B rows and gives every session B-way
    intra-frame parallelism instead — 2 sessions x 4 bands serves 4K
    where one chip cannot. Sessions are fully independent (per-session
    GOP, QP, multi-slice access units), so there is no lockstep device
    tick to shard; the per-session encoders dispatch concurrently from
    the service pool and each session's pack fan-out uses its encoder's
    own band pool."""

    def __init__(self, n_sessions: int, width: int, height: int, *,
                 qp: int = 28, fps: int = 60, bands: int | None = None,
                 cols: int | None = None,
                 devices=None, rows: list[list] | None = None,
                 codecs: list[str] | None = None,
                 shared: bool | None = None):
        from selkies_tpu.parallel.bands import (
            BandedH264Encoder, bands_from_env, grid_from_env,
            partition_devices)
        from selkies_tpu.utils.jaxcache import enable_persistent_compilation_cache

        enable_persistent_compilation_cache()
        self.n = n_sessions
        # per-session negotiated codec (signalling/negotiate.py): h264
        # rides the band/tile H.264 mesh, av1/vp9 ride the tile-column
        # codec mesh (parallel/codec_mesh.py) on the same chip row. The
        # placer's codec record seeds this on service rebuilds so a
        # supervisor restart keeps every session's negotiated codec.
        self.codecs = [c.lower() if c else "h264"
                       for c in (codecs or ["h264"] * n_sessions)]
        # sessions whose codec changed but whose re-carve hasn't landed
        # yet — recompile-sentinel attribution handoff (set_codec ->
        # recarve)
        self._codec_pending: set[int] = set()
        if bands is None and cols is None:
            grid = grid_from_env()
            if grid is not None:
                bands, cols = grid  # SELKIES_TILE_GRID=RxC owns the carve
            else:
                bands = bands_from_env()
        bands = 1 if bands is None else max(1, int(bands))
        # cols: per-session 2D tile grid (each session's row of chips is
        # an R×C mesh; a session's chip budget is bands*cols)
        self.cols = 1 if cols is None else max(1, int(cols))
        # shared small-slice carve (placer.shared): rows round-robin one
        # chip each but every session still band-slices at the REQUESTED
        # count (identical bytes, no parallelism). Distinguished from a
        # quarantine-SHRUNK row, which genuinely re-slices into fewer
        # bands — _row_bands branches on this.
        self.shared_carve = bool(shared) if shared is not None else False
        if rows is None:
            # no placer-managed carve handed in: one-shot static carve
            try:
                rows = partition_devices(n_sessions, bands * self.cols,
                                         devices)
            except ValueError:
                # slice too small for n x bands: every session falls back
                # to a single-device band-sliced encode (identical bytes),
                # round-robined across the chips that DO exist — passing
                # the full device list through would instead build every
                # session's band mesh over the same first `bands` chips
                from selkies_tpu.resilience.devhealth import get_device_pool

                devs = list(devices if devices is not None
                            else get_device_pool().healthy_devices())
                rows = [[devs[k % len(devs)]] for k in range(n_sessions)]
                self.shared_carve = True
        self._width, self._height = width, height
        self._qp, self._fps, self._bands_req = qp, fps, bands
        # an empty row means the session is PARKED: its chips are lent
        # out (lifecycle re-carve) and it has no client, so it encodes
        # nothing until recarve() hands it a row again. Row width drives
        # the band count (_row_bands): a service rebuild mid-borrow
        # reads the placer's live rows, and the borrower must come back
        # on its enlarged mesh, not the constructor default
        self.encoders = [
            self._build_encoder(k, rows[k]) if rows[k] else None
            for k in range(n_sessions)
        ]
        live = next((e for e in self.encoders
                     if e is not None and getattr(e, "codec", "") == "h264"),
                    None)
        self.bands = live.bands if live is not None else bands
        self.last_idrs: list[bool] = [True] * n_sessions
        # per-session P-downlink payload mode of the most recent tick
        # ("coeff"/"bits"/"dense", "" = IDR/static/parked) — feeds
        # selkies_downlink_mode_total from the fleet serving loop
        self.last_modes: list[str] = [""] * n_sessions
        self._pool = ThreadPoolExecutor(max_workers=n_sessions,
                                        thread_name_prefix="band-fleet")

    def set_qp(self, session: int, qp: int) -> None:
        enc = self.encoders[session]
        if enc is not None:
            enc.set_qp(qp)

    def set_bitrate(self, session: int, kbps: int) -> None:
        """Per-session rate retarget for the library-CBR codec rows
        (vp9; the lossless AV1 splice accepts and ignores it). The
        H.264 rows stay QP-driven through set_qp."""
        enc = self.encoders[session]
        if enc is not None and hasattr(enc, "set_bitrate"):
            enc.set_bitrate(int(kbps))

    def force_keyframe(self, session: int) -> None:
        enc = self.encoders[session]
        if enc is not None:
            enc.force_keyframe()

    def set_codec(self, session: int, codec: str) -> bool:
        """Record a session's negotiated codec; returns True when it
        changed (the caller then re-carves, which rebuilds the encoder
        on the session's row through _build_encoder)."""
        codec = (codec or "h264").lower()
        if codec == self.codecs[session]:
            return False
        # recompile-sentinel attribution: the caller's re-carve (possibly
        # deferred past an in-flight tick) rebuilds this session's
        # encoder for the new codec — those compiles belong to the
        # negotiation, not a chip shuffle; recarve() consumes the flag
        self._codec_pending.add(session)
        self.codecs[session] = codec
        return True

    def _build_encoder(self, session: int, devices: list):
        """One session's encoder on its chip row, by negotiated codec.
        av1/vp9 mesh their tile columns over the row's chips; anything
        that fails to build degrades to the H.264 band encoder (and
        resets the codec record) so the session always streams."""
        codec = self.codecs[session]
        if codec not in ("av1", "vp9", "h264"):
            # a codec the fleet has no per-session row for (vp8/h265
            # negotiate fine on solo hosts): degrade the RECORD too, so
            # session_codec reports what actually streams and the
            # negotiation answer corrects to h264 instead of wrapping
            # H.264 AUs in the wrong payloader
            logger.warning("fleet has no %s session row; session %d "
                           "degrades to h264", codec, session)
            self.codecs[session] = "h264"
            codec = "h264"
        try:
            if codec == "av1":
                from selkies_tpu.parallel.codec_mesh import (
                    TileColumnAV1Encoder, budget_cols)

                # budget_cols applies the SELKIES_TILE_COLS clamp the
                # negotiation layer documents — the row's chip count is
                # the budget, the knob bounds it
                return TileColumnAV1Encoder(
                    self._width, self._height, fps=self._fps,
                    cols=budget_cols(len(devices)), devices=devices)
            if codec == "vp9":
                from selkies_tpu.parallel.codec_mesh import (
                    TileColumnVP9Encoder, budget_cols)

                return TileColumnVP9Encoder(
                    self._width, self._height, fps=self._fps,
                    cols=budget_cols(len(devices)), devices=devices)
        except Exception:
            logger.exception(
                "session %d %s encoder build failed; degrading to h264",
                session, codec)
            self.codecs[session] = "h264"
        from selkies_tpu.parallel.bands import BandedH264Encoder

        return BandedH264Encoder(
            self._width, self._height, qp=self._qp, fps=self._fps,
            bands=self._row_bands(devices), cols=self.cols, devices=devices)

    def _row_bands(self, row) -> int:
        """Band count for a device row: borrowed chips ENLARGE the band
        mesh — a row wider than the constructor band count re-slices the
        frame across every chip it holds (that is the whole point of
        borrowing; ``band_mesh`` only places the first ``bands`` devices,
        so without this the borrowed chips would sit idle). With a 2D
        tile grid the enlargement adds whole BAND-ROWS of ``cols`` chips
        (a lender's row is bands*cols chips, so loans arrive in grid
        multiples); a remainder smaller than one grid row cannot carry a
        slice row and stays idle. The encoder itself clamps via
        ``usable_bands`` when the geometry's MB rows do not divide into
        that many bands — at such geometries the extra chips cannot
        carry a slice and the band count (and the bytes) stay exactly
        the constructor carve's.

        A row SMALLER than the constructor carve in a non-shared
        placement means the health plane quarantined a chip out of it:
        the session rebuilds on a SHRUNK mesh (fewer bands; grid carves
        round down in whole band-rows of ``cols`` chips), degrading to
        the plain single-band/single-chip encode at 1 surviving chip.
        The shared small-slice carve is exempt — its 1-chip rows always
        band-slice at the requested count (identical bytes by contract,
        parallel/bands.py)."""
        n = len(row) // self.cols
        if self.shared_carve or n >= self._bands_req:
            return max(self._bands_req, n)
        return max(1, n)

    def recarve(self, session: int, devices: list) -> None:
        """Rebuild one session's encoder on a new device row (the
        lifecycle re-carve: the session borrowed band chips or returned
        them). GOP phase / QP carry over via checkpoint/restore and the
        restored encoder opens with a forced IDR. Byte continuity: while
        the effective band count is unchanged (a return to the original
        row, or an enlargement clamped by the geometry) the stream from
        that IDR is byte-identical to a never-re-carved encoder fed the
        same frames (mesh and single-device placements already produce
        identical bytes per band — tests/test_band_slices.py); a borrow
        window that does enlarge the mesh re-slices the frame into more
        bands — a decodable multi-slice continuation opened by the
        forced IDR — and the round-trip back to the original row is
        byte-identical to the oracle from its first post-IDR frame.
        Callers must not have an encode_tick in flight (the fleet defers
        the swap exactly like a supervisor service restart). An empty
        ``devices`` row parks the session (its chips are lent out and it
        has no client — encoding its unwatched frames would oversubscribe
        the lent chips); a later recarve with a row un-parks it. On any
        exception the old encoder is left untouched and keeps serving:
        the ``migrate`` fault fires in checkpoint_session before any
        state is read, and a restore-side failure closes the half-built
        replacement before propagating (no leaked pack pool / device
        buffers)."""
        from selkies_tpu.monitoring import jitprof
        from selkies_tpu.parallel.lifecycle import (
            checkpoint_session, restore_session)

        # recompile sentinel (monitoring/jitprof.py): the rebuilt
        # encoder's executables compile lazily on its first ticks —
        # attribute them to whichever rebuild owns this call (a pending
        # set_codec means the re-carve is a negotiation's vehicle)
        if session in self._codec_pending:
            self._codec_pending.discard(session)
            jitprof.mark("codec_switch",
                         f"session-{session}:{self.codecs[session]}")
        else:
            jitprof.mark("recarve", f"session-{session}")
        old = self.encoders[session]
        if not devices:
            self.encoders[session] = None
            if old is not None:
                try:
                    old.close()
                except Exception:
                    logger.exception("closing parked encoder %d", session)
            return
        # checkpoint/restore is the H.264 GOP contract (idr_pic_id
        # parity etc.) — it only carries across an h264 -> h264 rebuild.
        # A codec switch (or a non-h264 re-carve) opens fresh with the
        # encoder's own forced keyframe instead.
        h264_to_h264 = (old is not None
                        and self.codecs[session] == "h264"
                        and getattr(old, "codec", "h264") == "h264")
        ck = checkpoint_session(self, session) if h264_to_h264 else None
        # the new encoder is built with the SERVICE's constructor qp, not
        # the session's current dynamic qp: params.qp feeds the PPS
        # pic_init_qp and every slice_qp_delta, so baking the dynamic qp
        # in would shift all deltas vs a never-re-carved encoder. The
        # dynamic qp carries over via restore_session -> set_qp.
        enc = self._build_encoder(session, devices)
        if ck is not None:
            try:
                restore_session(ck, enc)
            except Exception:
                try:
                    enc.close()
                except Exception:
                    logger.exception(
                        "closing failed replacement encoder %d", session)
                raise
        self.encoders[session] = enc
        if old is not None:
            try:
                old.close()
            except Exception:
                logger.exception("closing re-carved encoder %d", session)

    def encode_tick(self, frames: np.ndarray) -> list[bytes]:
        if frames.shape[0] != self.n:
            raise ValueError(f"expected {self.n} frames, got {frames.shape[0]}")

        def _one(i: int) -> bytes:
            enc = self.encoders[i]
            if enc is None:  # parked: chips lent away, no client
                return b""
            return enc.encode_frame(frames[i])

        # span "encode" (the synchronous encode_frame vocabulary), NOT
        # "device-step": this covers fetch + host unpack/pack too, and a
        # trace reader triaging a wedged tick must not pin host CAVLC
        # time on the TPU. The per-band step/fetch/pack spans inside
        # each encoder carry the device-vs-host split.
        with tracer.span("encode"):
            aus = list(self._pool.map(_one, range(self.n)))
        self.last_idrs = [bool(e.last_stats.idr) if e is not None else False
                          for e in self.encoders]
        self.last_modes = [
            getattr(e.last_stats, "downlink_mode", "") if e is not None else ""
            for e in self.encoders]
        return aus

    def close(self) -> None:
        self._pool.shutdown(wait=False, cancel_futures=True)
        for enc in self.encoders:
            if enc is not None:
                enc.close()


class SoftwareFleetService:
    """Degraded-mode fleet service: N independent software encoders behind
    the MultiSessionH264Service interface (encode_tick / set_qp /
    force_keyframe / last_idrs / close).

    The resilience ladder's last load-shedding rung (resilience/
    supervisor.py): when the sharded TPU step is persistently failing, the
    fleet swaps this in so sessions keep streaming off the CPU x264 row
    (models/x264enc.py; the registry degrades that to solo TPU encoders
    when libx264 is absent). Slower and lockstep-unsharded, but alive.
    """

    def __init__(self, n_sessions: int, width: int, height: int, *,
                 qp: int = 28, fps: int = 60,
                 bitrate_kbps: int | list[int] = 2000,
                 encoder: str = "x264enc"):
        from selkies_tpu.models.registry import create_encoder

        self.n = n_sessions
        # per-session bitrates: each slot's CBR/GCC target carries over
        # into degraded mode (a scalar applies to every session)
        if isinstance(bitrate_kbps, int):
            bitrate_kbps = [bitrate_kbps] * n_sessions
        self.encoders = [
            create_encoder(encoder, width=width, height=height, fps=fps,
                           bitrate_kbps=int(bitrate_kbps[i]), qp=qp)
            for i in range(n_sessions)
        ]
        self._qps = [qp] * n_sessions
        self.last_idrs: list[bool] = [True] * n_sessions
        self.last_modes: list[str] = [""] * n_sessions
        self._pool = ThreadPoolExecutor(max_workers=n_sessions,
                                        thread_name_prefix="sw-fleet")

    def set_qp(self, session: int, qp: int) -> None:
        self._qps[session] = int(qp)
        enc = self.encoders[session]
        if hasattr(enc, "set_qp"):
            enc.set_qp(int(qp))

    def set_bitrate(self, session: int, kbps: int) -> None:
        """Live per-session rate retarget (x264's CBR owns the quantizer,
        so the GCC/client drive lands here, not in set_qp)."""
        enc = self.encoders[session]
        if hasattr(enc, "set_bitrate"):
            enc.set_bitrate(int(kbps))

    def force_keyframe(self, session: int) -> None:
        self.encoders[session].force_keyframe()

    def encode_tick(self, frames: np.ndarray) -> list[bytes]:
        if frames.shape[0] != self.n:
            raise ValueError(f"expected {self.n} frames, got {frames.shape[0]}")

        def _one(i: int) -> bytes:
            return self.encoders[i].encode_frame(frames[i], self._qps[i])

        aus = list(self._pool.map(_one, range(self.n)))
        self.last_idrs = [bool(e.last_stats.idr) for e in self.encoders]
        self.last_modes = [getattr(e.last_stats, "downlink_mode", "")
                           for e in self.encoders]
        return aus

    def close(self) -> None:
        self._pool.shutdown(wait=False, cancel_futures=True)
        for enc in self.encoders:
            if hasattr(enc, "close"):
                try:
                    enc.close()
                except Exception:  # noqa: silent-except-audited — best-effort teardown
                    pass
