"""Main-path programs compiled for a described TPU v5e, without a chip.

The TPU compiler is installed with JAX, so the Pallas kernel and the
jitted device steps compile here for a v5e:2x2 topology at their real
sizes: a kernel that the chip's compiler would refuse (misaligned
slice, too much VMEM, a program that does not fit HBM) fails here at no
chip time. Nothing runs, so these say nothing about results or speed.

The device steps choose their TPU branches from ``jax.default_backend()``
(encoder_core._use_pallas_me, pallas_me's interpret default), which is
the CPU here; each test steers them onto the TPU branch itself.
"""

from __future__ import annotations

import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

W, H = 1920, 1080
PAD_H = (H + 15) // 16 * 16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep these out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture
def tpu_branches(monkeypatch):
    """Take the branches a TPU backend would take while tracing."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _planes(sharding, h=PAD_H, w=W):
    def sds(shape, dtype=jnp.uint8):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    y, u, v = sds((h, w)), sds((h // 2, w // 2)), sds((h // 2, w // 2))
    qp = sds((), jnp.int32)
    return (y, u, v, qp, y, u, v)


def _compile(fn, args):
    # a fresh wrapper, so no trace cached from a CPU-branch call is reused
    compiled = jax.jit(lambda *a: fn(*a)).lower(*args).compile()
    return compiled.as_text()


def test_pallas_me_kernel_1080p(topo, tpu_branches):
    from selkies_tpu.models.h264.numpy_ref import MV_PAD
    from selkies_tpu.models.h264.pallas_me import hier_me_mc_pallas

    one = SingleDeviceSharding(topo.devices[0])
    y, _, _, _, ref_y, ref_u, ref_v = _planes(one)

    def me(cur, ry, ru, rv):
        pads = [jnp.pad(p, MV_PAD, mode="edge") for p in (ry, ru, rv)]
        return hier_me_mc_pallas(cur, ry, *pads, interpret=False)

    assert "tpu_custom_call" in _compile(me, (y, ref_y, ref_u, ref_v))


@pytest.mark.parametrize("step", ["_p_bits_step", "_p_toks_step"])
def test_p_entropy_step_1080p(topo, tpu_branches, monkeypatch, step):
    """Full-P step with device CAVLC bits / CABAC tokens and Pallas ME.

    The emission compiles once per rung of its activity ladder, the same
    code at a different slot count; all rungs take ~80 s to compile here,
    so the test keeps the smallest and the chip run compiles the rest."""
    from selkies_tpu.models.h264 import device_cabac, device_cavlc, encoder

    ladder = device_cavlc.bits_buckets
    for mod in (device_cavlc, device_cabac):
        monkeypatch.setattr(mod, "bits_buckets", lambda m: ladder(m)[:1])
    one = SingleDeviceSharding(topo.devices[0])
    assert "tpu_custom_call" in _compile(getattr(encoder, step), _planes(one))


def test_p_step_4k_xla_me(topo, tpu_branches):
    """3840 px is past the kernel's 128-MB row limit: XLA ME."""
    from selkies_tpu.models.h264.encoder_core import encode_frame_p_planes

    one = SingleDeviceSharding(topo.devices[0])
    y, u, v, qp, ry, ru, rv = _planes(one, h=2160, w=3840)
    hlo = _compile(encode_frame_p_planes, (y, u, v, ry, ru, rv, qp))
    assert "tpu_custom_call" not in hlo


def test_four_session_step_1080p(topo, tpu_branches):
    """The --tpu_sessions lockstep tick, one 1080p session per chip."""
    from selkies_tpu.parallel.serving import MultiSessionH264Service

    svc = MultiSessionH264Service(4, W, H, devices=topo.devices)
    try:
        mesh = svc.enc.mesh
        assert isinstance(mesh, Mesh) and mesh.devices.size == 4
        shard = NamedSharding(mesh, P("session"))

        def sds(shape, dtype=jnp.uint8):
            return jax.ShapeDtypeStruct((4, *shape), dtype, sharding=shard)

        y, c = sds((PAD_H, W)), sds((PAD_H // 2, W // 2))
        args = (y, c, c, sds((), jnp.int32), sds((), jnp.bool_), y, c, c)
        compiled = svc.enc._step_mixed.lower(*args).compile()
        assert "tpu_custom_call" in compiled.as_text()
    finally:
        svc.close()
