"""Device self time of the downlink packing (scope ``enc.downlink``: the
fused prefix and meta, compact or sparse coefficient layouts and the
dense fallback header) per delivered frame, from the profiler trace
reduced by benchmark/scopes.py."""

from benchmark.scopes import per_frame_ms


def read(run):
    return per_frame_ms(run, ("enc.downlink",))
