"""Process start to the window opening: native load, orchestrator start,
client connect, warm-up of every executable the window uses."""


def read(run):
    return run.setup_s
