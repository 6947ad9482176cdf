"""Arithmetic shared by the readers."""

import numpy as np


def latencies_ms(run) -> list:
    return [(d.t_recv - d.due) * 1e3 for d in run.delivered]


def percentile(values: list, q: float):
    """Linear-interpolated percentile over all values; None when empty."""
    return float(np.percentile(values, q)) if values else None


def stats_mean(run, fields: tuple):
    """Mean over delivered frames of the sum of FrameStats fields."""
    vals = [sum(getattr(d.stats, f, 0.0) for f in fields)
            for d in run.delivered if d.stats is not None]
    return float(np.mean(vals)) if vals else None
