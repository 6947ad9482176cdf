"""Host front-end per delivered frame: the encoder's FrameStats
classify + convert + h2d (models/frameprep.py, tilecache.py)."""

from benchmark.metrics._util import stats_mean


def read(run):
    return stats_mean(run, ("classify_ms", "convert_ms", "h2d_ms"))
