"""Device self time of the device entropy coder's per-MB structure pass
and its coded-MB compaction (scopes ``enc.entropy.structure`` and
``enc.entropy.compact``) per delivered frame, from the profiler trace
reduced by benchmark/scopes.py."""

from benchmark.scopes import per_frame_ms


def read(run):
    return per_frame_ms(run, ("enc.entropy.structure", "enc.entropy.compact"))
