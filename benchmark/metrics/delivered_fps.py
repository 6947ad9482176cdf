"""Distinct content frames whose AU the client received in the window,
per second of the window."""


def read(run):
    return len({d.k for d in run.delivered}) / run.seconds
