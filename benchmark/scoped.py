"""A traced run of one cell that also reads the program's own names:

    python3 benchmark/scoped.py --workload <cell> --seed <n> --seconds <s> \
        [--keep-trace DIR]

It is ``benchmark/run.py --trace 1`` with three additions, all outside
run.py:

* the program tracer (``selkies_tpu/monitoring/tracing.py``) is enabled
  and reset when the profiler starts, just before the window opens, and
  put back as it was when the profiler stops; its summary of the window
  goes to stderr. Its spans then reach the trace as ``selkies.*``;
* the trace is loaded and reduced by ``benchmark/scopes.py``: device self
  time per named scope and idle gaps labelled by the program's spans (a
  ``trace scopes:`` line on stderr, the labels in ``breakdown``);
* the scope metrics are read besides the cell's per-layer metrics.

``--keep-trace`` copies the ``.xplane.pb`` there before run.py removes
it. The result line has run.py's form.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
from pathlib import Path
from types import SimpleNamespace

ROOT = Path(__file__).resolve().parents[1]
if sys.path and Path(sys.path[0]).resolve() == Path(__file__).resolve().parent:
    sys.path[0] = str(ROOT)
elif str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark import run, scopes, trace  # noqa: E402

SCOPE_METRICS = ("me_device_ms", "tq_device_ms", "entropy_structure_device_ms",
                 "entropy_emit_device_ms", "downlink_device_ms")


def _trace_api(keep: str | None) -> SimpleNamespace:
    """What run.py calls as ``trace.*``, with scopes' load/reduce/summary."""

    def load(path: str):
        if keep:
            os.makedirs(keep, exist_ok=True)
            shutil.copy(path, keep)
        return scopes.load(path)

    return SimpleNamespace(WINDOW_SPAN=trace.WINDOW_SPAN, load=load,
                           reduce=scopes.reduce, summary=scopes.summary,
                           breakdown=trace.breakdown)


def _tracer_follows_profiler() -> None:
    """Enable and reset the program tracer with jax.profiler.start_trace;
    restore it and print its summary with stop_trace."""
    import jax.profiler

    from selkies_tpu.monitoring.tracing import tracer

    start, stop = jax.profiler.start_trace, jax.profiler.stop_trace
    was = tracer.enabled

    def start_trace(*a, **kw):
        start(*a, **kw)
        tracer.enable()
        tracer.reset()

    def stop_trace():
        if not was:
            tracer.disable()
        print(f"tracer: {json.dumps(tracer.summary())}", file=sys.stderr,
              flush=True)
        stop()

    jax.profiler.start_trace, jax.profiler.stop_trace = start_trace, stop_trace


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep-trace", default=None)
    args = ap.parse_args(argv)
    os.environ.update(run.CACHE_ENV)  # before JAX is imported
    cell = run.load_cell(args.workload)
    cell.per_layer = cell.per_layer + [
        {"name": n, "unit": "ms/frame"} for n in SCOPE_METRICS]
    run.ensure_native()
    try:
        device = run.check_device(cell.chips)
        peaks = run.load_peaks(device["kind"])
    except run.NoChip as e:
        print(f"benchmark: {e}", file=sys.stderr, flush=True)
        return 3
    run.trace = _trace_api(args.keep_trace)
    _tracer_follows_profiler()
    out = run.run_cell(cell, args.seed, args.seconds, True, device, peaks)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    try:
        rc = main()
    except BaseException:  # noqa: BLE001 - report, then exit non-zero below
        import traceback

        traceback.print_exc()
        rc = 1
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(rc)
