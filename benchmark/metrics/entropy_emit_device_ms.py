"""Device self time of the device entropy coder's emission (scope
``enc.entropy.emit``: VLC lookup, bit packing and stream merge, and the
bucket switch around them) per delivered frame, from the profiler trace
reduced by benchmark/scopes.py."""

from benchmark.scopes import per_frame_ms


def read(run):
    return per_frame_ms(run, ("enc.entropy.emit",))
