"""Device time of the encoder's jitted step executables per delivered
frame, from the profiler trace: the union of each XLA module's events
whose module name holds "step", summed over those modules."""


def read(run):
    if run.trace is None or not run.delivered:
        return None
    secs = sum(v for k, v in run.trace.module_s.items() if "step" in k)
    if secs <= 0:
        return None
    return secs * 1e3 / len(run.delivered)
