"""Named scopes inside the jitted encoder steps, the tracer's pts tag,
and FrameStats.handoff_wait_ms."""

import re
from functools import partial

import jax
import numpy as np
import pytest

from selkies_tpu.models.h264 import encoder as E
from selkies_tpu.models.h264.device_cavlc import bits_buckets
from selkies_tpu.monitoring.tracing import Tracer

SCOPES = {"enc.ingest", "enc.intra", "enc.me", "enc.tq", "enc.entropy.structure",
          "enc.entropy.compact", "enc.entropy.emit", "enc.downlink"}
P_SCOPES = SCOPES - {"enc.intra"}
# 16 x 17 = 272 MBs: past the 256 bucket, so the CAVLC bucket switch and
# its compaction branch are in the program
H, W = 256, 272


def _u8(*shape):
    return jax.ShapeDtypeStruct(shape, np.uint8)


def _steps():
    y4 = [_u8(H // 4, W)] * 4
    uv = [_u8(H // 2, W // 2)] * 2
    ref = [_u8(H, W), _u8(H // 2, W // 2), _u8(H // 2, W // 2)]
    qp = jax.ShapeDtypeStruct((), np.int32)
    m = (H // 16) * (W // 16)
    scatter = partial(E._p_scatter_step, nscap=m, cap=1024, tile_w=W,
                      entropy=(4096, 16, bits_buckets(m), "cavlc"))
    return {
        "p_bits": (E._p_bits_step_chunked, (*y4, *uv, qp, *ref), P_SCOPES),
        "i_planes": (E._i_planes_step_chunked, (*y4, *uv, qp),
                     {"enc.ingest", "enc.intra", "enc.downlink"}),
        "p_scatter": (scatter, (_u8(4 * (4 + 24 * W)), qp, *ref, *ref), P_SCOPES),
        "p_toks": (E._p_toks_step_chunked, (*y4, *uv, qp, *ref), P_SCOPES),
    }


@pytest.mark.parametrize("name", ["p_bits", "i_planes", "p_scatter", "p_toks"])
def test_step_carries_its_scopes(name):
    fn, args, want = _steps()[name]
    text = jax.jit(fn).lower(*args).as_text(debug_info=True)
    found = {part for loc in re.findall(r'loc\("([^"]*)"', text)
             for part in loc.split("/") if part.startswith("enc.")}
    assert found == want


def test_the_steps_cover_the_vocabulary():
    assert set().union(*(want for *_, want in _steps().values())) == SCOPES


def test_span_takes_a_pts():
    t = Tracer()
    t.disable()
    assert t.span("pack", pts=5) is t.span("step")  # the one no-op object
    t.enable()
    with t.span("pack", pts=90000):
        pass
    assert t.summary()["pack"]["count"] == 1


def _frames(n, w=64, h=48):
    rng = np.random.default_rng(3)
    frames = [rng.integers(0, 255, (h, w, 4), np.uint8) for _ in range(n)]
    frames[3] = frames[2].copy()  # a static repeat
    return frames


def test_handoff_wait_on_every_p_frame():
    enc = E.TPUH264Encoder(64, 48, qp=30, frame_batch=1)
    out = []
    for i, f in enumerate(_frames(6)):
        out += enc.submit(f, meta=i)
    out += enc.flush()
    enc.close()
    stats = {meta: s for _au, s, meta in out}
    assert sorted(stats) == list(range(6))
    assert stats[0].idr
    for meta in (1, 2, 4, 5):
        assert not stats[meta].idr and stats[meta].handoff_wait_ms >= 0
    assert stats[3].upload_kind == "static" and stats[3].handoff_wait_ms == 0
