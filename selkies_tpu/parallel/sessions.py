"""Multi-session chip placement: N concurrent streams on an N-chip slice.

The reference scales out by running one OS process per session and
delegating fleet placement to Kubernetes (SURVEY §2.6: coturn-web
informers, addons/example). The TPU-native design inverts this: ONE host
process drives a whole slice (the v5e-8 scale target in BASELINE.md — 8x
1080p60 sessions, one stream per chip) through a single jitted program
sharded over a `session` mesh axis.

There is no cross-session communication, so XLA partitions the batched
encode step into per-chip programs with zero collectives — each chip holds
its own session's reference frame (the P-frame state) in HBM between
frames, and only quantized coefficients come back to the host for entropy
packing (one CPU thread per session can pack concurrently; CAVLC packing
is independent per stream).

Frames enter as a (N, H, W, 4) batch sharded on axis 0; per-session QP
comes in as an (N,) vector so each session's rate controller retunes
independently without recompilation.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from selkies_tpu.models.h264.encoder_core import (
    encode_frame_p_planes,
    encode_frame_planes,
)
from selkies_tpu.ops.colorspace import bgrx_to_i420

__all__ = ["MultiSessionEncoder", "dryrun"]


def _session_mesh(n: int, devices=None) -> Mesh:
    if devices is None:
        # single source of chip enumeration (resilience/devhealth.py):
        # a fleet service rebuilt after a chip quarantine places its
        # session mesh on the surviving chips. The lockstep carve needs
        # one DISTINCT chip per session and cannot shrink its session
        # count, so when quarantines leave fewer healthy chips than
        # sessions the mesh falls back to the full enumeration: the
        # rebuild stays BUILDABLE (the pre-health-plane behavior)
        # instead of raising until probation — a genuinely dead chip
        # still fails the single SPMD batch tick, and the supervisor
        # ladder's software-fleet rung is the availability floor there
        from selkies_tpu.resilience.devhealth import get_device_pool

        pool = get_device_pool()
        healthy = pool.healthy_devices()
        if len(healthy) >= n:
            devices = healthy[:n]
        else:
            import logging

            logging.getLogger("parallel.sessions").warning(
                "session mesh needs %d chips but only %d are healthy; "
                "using the full enumeration (quarantined chips included)",
                n, len(healthy))
            devices = pool.all_devices()[:n]
    devs = np.array(devices)
    if len(devs) < n:
        raise ValueError(f"need {n} devices, have {len(devs)}")
    return Mesh(devs[:n], axis_names=("session",))


class MultiSessionEncoder:
    """Batched per-chip encode for N independent sessions.

    All sessions share one geometry (the common fleet case: identical
    1080p60 streams); heterogeneous fleets run one instance per geometry
    group. The per-session reference frames live sharded in HBM.
    """

    def __init__(self, n_sessions: int, width: int, height: int, devices=None,
                 host_convert: bool = True):
        if width % 16 or height % 16:
            raise ValueError("multi-session geometry must be MB-aligned")
        self.n = n_sessions
        self.width = width
        self.height = height
        # host_convert (production default): BGRx->I420 runs on the host
        # (native frameprep, one worker per session) and the device tick
        # is pure encode — the on-device colorspace + padded-frame
        # handling cost ~14 ms/tick of the 8x1080p60 envelope (PERF.md
        # round-3 measurement); the serving layer owns the conversion.
        # host_convert=False keeps conversion in the jit (link-rich
        # PCIe-local hosts that prefer 4 B/px uploads of raw BGRx).
        self.host_convert = bool(host_convert)
        self.mesh = _session_mesh(n_sessions, devices)
        shard = NamedSharding(self.mesh, P("session"))

        if self.host_convert:
            def one_i(y, u, v, qp):
                return encode_frame_planes(y, u, v, qp)

            def one_p(y, u, v, qp, ry, ru, rv):
                return encode_frame_p_planes(y, u, v, ry, ru, rv, qp)

            n_in_i, n_in_p = 4, 7
        else:
            def one_i(frame, qp):
                y, u, v = bgrx_to_i420(frame)
                return encode_frame_planes(y, u, v, qp)

            def one_p(frame, qp, ry, ru, rv):
                y, u, v = bgrx_to_i420(frame)
                return encode_frame_p_planes(y, u, v, ry, ru, rv, qp)

            n_in_i, n_in_p = 2, 5

        self._step_i = jax.jit(
            jax.vmap(one_i),
            in_shardings=(shard,) * n_in_i,
            out_shardings=shard,
        )
        self._step_p = jax.jit(
            jax.vmap(one_p),
            in_shardings=(shard,) * n_in_p,
            out_shardings=shard,
            donate_argnums=tuple(range(n_in_p - 3, n_in_p)),
        )

        # mixed per-session I/P tick: shard_map gives each chip a REAL
        # lax.cond on its own is_idr scalar (SPMD code, device-varying
        # predicate), so one session forcing an IDR no longer drags the
        # whole batch onto the IDR executable. Branch outputs are unified
        # to one tree (zeros for the other branch's fields); compute per
        # chip is one branch only.
        mbh, mbw = height // 16, width // 16

        def one_mixed(*args):
            if self.host_convert:
                y, u, v, qp, idr, ry, ru, rv = args
            else:
                frame, qp, idr, ry, ru, rv = args
                y, u, v = bgrx_to_i420(frame)

            def branch_i(_):
                out = encode_frame_planes(y, u, v, qp)
                out["mvs"] = jnp.zeros((mbh, mbw, 2), jnp.int32)
                out["skip"] = jnp.zeros((mbh, mbw), bool)
                return out

            def branch_p(_):
                out = encode_frame_p_planes(y, u, v, ry, ru, rv, qp)
                out["luma_mode"] = jnp.zeros((mbh, mbw), jnp.int32)
                out["chroma_mode"] = jnp.zeros((mbh, mbw), jnp.int32)
                out["luma_dc"] = jnp.zeros((mbh, mbw, 4, 4), jnp.int32)
                return out

            return jax.lax.cond(idr, branch_i, branch_p, None)

        def mixed(*arrs):
            out = one_mixed(*(a[0] for a in arrs))
            return jax.tree_util.tree_map(lambda a: a[None], out)

        spec = P("session")
        n_in_m = 8 if self.host_convert else 6
        self._step_mixed = jax.jit(
            jax.shard_map(
                mixed, mesh=self.mesh,
                in_specs=(spec,) * n_in_m, out_specs=spec,
                # the encode scans carry replicated-initialized state that
                # becomes device-varying after one step; skip the varying-
                # axis type check (every input/output is fully sharded)
                check_vma=False,
            ),
            donate_argnums=tuple(range(n_in_m - 3, n_in_m)),
        )
        self._shard = shard
        self._ref = None

    def put_frames(self, frames: np.ndarray):
        """(N, H, W, 4) uint8 host batch -> session-sharded device array."""
        return jax.device_put(frames, self._shard)

    def _put_inputs(self, frames_or_planes):
        """host_convert: (y, u, v) batched plane arrays; else BGRx batch."""
        if self.host_convert:
            y, u, v = frames_or_planes
            return (jax.device_put(np.asarray(y), self._shard),
                    jax.device_put(np.asarray(u), self._shard),
                    jax.device_put(np.asarray(v), self._shard))
        return (self.put_frames(np.asarray(frames_or_planes)),)

    def _keep_ref(self, out):
        # recon planes are internal decoder state: they are donated into the
        # next P step, so they must NOT escape in the public return (a caller
        # holding them would hit deleted-buffer errors one frame later)
        self._ref = (
            out.pop("recon_y"),
            out.pop("recon_u"),
            out.pop("recon_v"),
        )
        return out

    def encode_idr(self, frames, qps: np.ndarray):
        out = dict(self._step_i(*self._put_inputs(frames), jnp.asarray(qps, jnp.int32)))
        return self._keep_ref(out)

    def encode_p(self, frames, qps: np.ndarray):
        if self._ref is None:
            raise RuntimeError("encode_idr must run first (no reference frames)")
        out = dict(
            self._step_p(
                *self._put_inputs(frames), jnp.asarray(qps, jnp.int32), *self._ref
            )
        )
        return self._keep_ref(out)

    def encode_mixed(self, frames, qps: np.ndarray, idrs: np.ndarray):
        """Per-session I/P in ONE device tick: idrs (N,) bool selects the
        branch per chip. Requires an established reference (first tick
        goes through encode_idr). `frames` is (y, u, v) plane batches in
        host_convert mode, a BGRx batch otherwise."""
        if self._ref is None:
            raise RuntimeError("encode_idr must run first (no reference frames)")
        out = dict(
            self._step_mixed(
                *self._put_inputs(frames), jnp.asarray(qps, jnp.int32),
                jnp.asarray(np.asarray(idrs, bool)), *self._ref
            )
        )
        return self._keep_ref(out)


def _host_planes(frames: np.ndarray):
    """Batched host BGRx->I420 through the PRODUCTION converter
    (FramePrep — the same native path serving.py runs per session), so
    the dryrun validates the conversion that actually ships."""
    from selkies_tpu.models.frameprep import FramePrep

    n, h, w, _ = frames.shape
    prep = FramePrep(w, h, w, h, nslots=1)
    ys, us, vs = zip(*(tuple(np.array(p, copy=True) for p in prep.convert(f))
                       for f in frames))
    return np.stack(ys), np.stack(us), np.stack(vs)


def dryrun(n_devices: int) -> None:
    """Driver hook: compile + run the FULL multi-session step (IDR path and
    steady-state P path with ME) over an n-device session mesh, tiny
    shapes — the PRODUCTION host-convert mode plus the device-convert
    variant."""
    h = w = 64
    rng = np.random.default_rng(0)
    enc = MultiSessionEncoder(n_devices, w, h)  # host_convert production mode
    frames = rng.integers(0, 256, (n_devices, h, w, 4), dtype=np.uint8)
    qps = np.full(n_devices, 28, np.int32)
    out_i = enc.encode_idr(_host_planes(frames), qps)
    jax.block_until_ready(out_i)
    frames2 = np.roll(frames, 3, axis=2)
    out_p = enc.encode_p(_host_planes(frames2), qps)
    jax.block_until_ready(out_p)
    assert out_p["mvs"].shape == (n_devices, h // 16, w // 16, 2)
    assert enc._ref[0].shape == (n_devices, h, w)
    # per-session coefficient tensors must be sharded one-session-per-chip
    visible = {d for s in out_p["luma_ac"].addressable_shards for d in [s.device]}
    assert len(visible) == n_devices
    # the PRODUCTION serving tick is the mixed shard_map step (per-chip
    # lax.cond on is_idr) — compile and run it with a heterogeneous
    # branch vector so a lowering break can't slip past the dryrun
    idrs = np.zeros(n_devices, bool)
    idrs[::2] = True  # heterogeneous for any n >= 2: branch divergence real
    out_m = enc.encode_mixed(_host_planes(np.roll(frames2, 2, axis=1)), qps, idrs)
    jax.block_until_ready(out_m)
    assert out_m["mvs"].shape == (n_devices, h // 16, w // 16, 2)
    assert out_m["luma_mode"].shape == (n_devices, h // 16, w // 16)
    # device-convert variant stays compilable (PCIe-local deployments)
    enc2 = MultiSessionEncoder(n_devices, w, h, host_convert=False)
    out2 = enc2.encode_idr(frames, qps)
    jax.block_until_ready(out2)
