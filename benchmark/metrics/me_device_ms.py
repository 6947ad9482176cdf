"""Device self time of ME + MC (scope ``enc.me``: reference padding and
the Pallas or XLA search) per delivered frame, from the profiler trace
reduced by benchmark/scopes.py."""

from benchmark.scopes import per_frame_ms


def read(run):
    return per_frame_ms(run, ("enc.me",))
