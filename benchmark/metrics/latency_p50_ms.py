"""Median over every AU received in the window of client receive time
minus the due time of the content frame the AU carries."""

from benchmark.metrics._util import latencies_ms, percentile


def read(run):
    return percentile(latencies_ms(run), 50)
