"""The ME roofline's operation and byte count, against a hand count, and
the reader's share arithmetic."""

import pytest

from benchmark.metrics import me_roofline
from benchmark.run import RunData
from benchmark.trace import Reduction

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_work_by_hand_at_two_macroblocks():
    # 32x16: 2 MBs; 76 candidates x 256 px x 3 ops + 2 x 64 x 8 chroma ops
    ops, nbytes = me_roofline.work(32, 16)
    assert me_roofline.CANDIDATES == 76
    assert ops == 2 * 76 * 256 * 3 + 2 * 2 * 64 * 8
    # luma 512 B read (cur) + ref luma 512 + chroma 256, preds 512 + 256,
    # MVs 2 MBs x 2 x int16
    assert nbytes == 512 + 512 + 256 + 512 + 256 + 8


def test_padding_rounds_up_to_whole_macroblocks():
    assert me_roofline.work(1920, 1080) == me_roofline.work(1920, 1088)


def test_bound_at_1080p_is_hbm_bytes():
    which, least = me_roofline.bound(1920, 1080, PEAKS)
    assert which == "hbm_bytes"
    assert least == pytest.approx(me_roofline.work(1920, 1080)[1] / 819e9)


def _run(red):
    return RunData(seconds=1.0, fps=60.0, width=1920, height=1080, setup_s=1.0,
                   t_open=0.0, captures=[], delivered=[], peaks=PEAKS, trace=red)


def test_share_from_kernel_time():
    _, least = me_roofline.bound(1920, 1080, PEAKS)
    red = Reduction(window_s=1.0, busy_s=0.5, module_s={},
                    op_s={"_me_mc_call.1": 40 * least * 4, "fusion": 1.0},
                    gaps=[], kernel_calls={"_me_mc_call.1": 40, "fusion": 9})
    assert me_roofline.read(_run(red)) == pytest.approx(25.0)
    # no kernel events: nothing to read, never a 0
    red.op_s, red.kernel_calls = {"fusion": 1.0}, {"fusion": 9}
    assert me_roofline.read(_run(red)) is None
    assert me_roofline.read(_run(None)) is None
