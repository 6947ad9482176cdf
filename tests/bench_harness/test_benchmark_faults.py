"""The check fails a run whose timed path is broken underneath: the
control (each AU credited to the capture before its own) and one fault
of each kind a one-chip encoder cell can have. The chip check is
satisfied inside the test; the rest is a whole run at a tiny size."""

import pytest

from benchmark import run
from benchmark.faults import Fault

from _tiny import CPU, PEAKS, SEED, tiny_cell


@pytest.mark.parametrize("fault", ["stale", "state_unchanged", "half_batch",
                                   "token_altered"])
def test_broken_timed_path_is_not_correct(fault, monkeypatch):
    monkeypatch.setattr(run, "WARM_ROUND_S", 1.5)
    out = run.run_cell(tiny_cell(), SEED + 7, 4.0, False, CPU, PEAKS, Fault(fault))
    assert out["correct"] is False, out["checks"]


def test_bf16_sad_swaps_in_the_rounded_kernel(monkeypatch):
    # the fault reads only on the chip (the CPU's f32 dot is exact); here:
    # it plants PR 21's single MXU dot in place of the two exact halves
    from selkies_tpu.models.h264 import pallas_me

    monkeypatch.setattr(pallas_me, "_me_mc_kernel", pallas_me._me_mc_kernel)
    sound = pallas_me._me_mc_kernel
    Fault("bf16_sad").plant()
    assert pallas_me._me_mc_kernel is not sound
    assert pallas_me._me_mc_kernel.__code__.co_filename == pallas_me.__file__
    Fault("none").plant()
