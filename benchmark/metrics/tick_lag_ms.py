"""Pipeline tick loop: mean over window captures that picked up new
content of how long the oldest content frame not yet captured had been
due when the capture ran (capture time - due time of previous capture's
content index + 1). Skipped ticks show as lag of the frames they left."""


def read(run):
    lags = []
    prev = None
    for c in run.captures:
        if prev is not None and c.k > prev.k:
            lags.append((c.t - run.due(prev.k + 1)) * 1e3)
        prev = c
    return sum(lags) / len(lags) if lags else None
