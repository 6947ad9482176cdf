"""Host completion per delivered frame: FrameStats.pack_ms (unpack + CAVLC
pack and slice assembly)."""

from benchmark.metrics._util import stats_mean


def read(run):
    return stats_mean(run, ("pack_ms",))
