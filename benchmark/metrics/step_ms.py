"""Device step seen from the host, per delivered frame: FrameStats.step_ms,
step dispatch to outputs ready, device queueing included."""

from benchmark.metrics._util import stats_mean


def read(run):
    return stats_mean(run, ("step_ms",))
