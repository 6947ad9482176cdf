"""One reader per metric, found by the metric's name in BENCHMARK.json.

Each module defines ``read(run) -> float | None`` over a
``benchmark.run.RunData``; None means the reader found nothing to read
in this run, and the harness leaves the metric out of the line.
"""
