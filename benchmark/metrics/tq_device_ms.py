"""Device self time of the P residual, transform, quantization,
reconstruction and skip decision (scope ``enc.tq``) per delivered frame,
from the profiler trace reduced by benchmark/scopes.py."""

from benchmark.scopes import per_frame_ms


def read(run):
    return per_frame_ms(run, ("enc.tq",))
