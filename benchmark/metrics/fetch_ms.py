"""d2h fetch per delivered frame: FrameStats.fetch_ms."""

from benchmark.metrics._util import stats_mean


def read(run):
    return stats_mean(run, ("fetch_ms",))
