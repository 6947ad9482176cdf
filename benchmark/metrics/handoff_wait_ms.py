"""Encoder hand-off per delivered frame: FrameStats.handoff_wait_ms, how
long a finished AU waited between its completion worker returning it and
the encoder handing it out. None where the encoder's FrameStats has no
such field."""

from benchmark.metrics._util import stats_mean


def read(run):
    if not any(hasattr(d.stats, "handoff_wait_ms") for d in run.delivered):
        return None
    return stats_mean(run, ("handoff_wait_ms",))
