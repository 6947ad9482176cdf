"""The scope reduction (benchmark/scopes.py): self time per op and per
named scope on a hand-built TPU-shaped trace with nesting, and the
program's spans on the profiler clock in a recorded CPU trace."""

import time

import jax
import numpy as np
import pytest

from benchmark import scopes, trace
from benchmark.scopes import Op, ScopedTrace, Span
from benchmark.trace import Event

STEP = "jit__p_bits_step_chunked(7)"


def _nested_trace():
    dev = "/device:TPU:0"
    ops = [
        Op("fusion.9", 50, 30, "jit_convert(2)", None),
        Op("fusion.1", 100, 50, STEP, "enc.me"),
        # the bucket switch encloses its branch: a compaction and a while
        # loop, whose body holds an emission op and an unscoped one
        Op("cond.11", 200, 300, STEP, "enc.entropy.emit"),
        Op("fusion.60", 210, 80, STEP, "enc.entropy.compact"),
        Op("while.25", 300, 160, STEP, "enc.entropy.emit"),
        Op("fusion.70", 310, 40, STEP, "enc.entropy.emit"),
        Op("fusion.71", 360, 40, STEP, None),
        Op("fusion.13", 600, 50, STEP, "enc.tq"),
        Op("copy.1", 700, 20, STEP, None),
        Op("fusion.2", 900, 200, STEP, "enc.downlink"),  # straddles the end
    ]
    mods = [Event("jit_convert(2)", 50, 30), Event(STEP, 100, 660),
            Event(STEP, 900, 200)]
    host = [Event("bench.window", 0, 1000), Event("bench.capture", 0, 40)]
    spans = [Span("selkies.step", 100, 900, 7), Span("selkies.h2d", 510, 80, 8),
             Span("selkies.pack", 730, 30, 7)]
    return ScopedTrace(ops={dev: ops}, modules={dev: mods}, host=host,
                       spans=spans)


def test_self_times_subtract_nested_ops():
    tr = _nested_trace()
    ops = tr.ops["/device:TPU:0"]
    selfs = dict(zip((o.name for o in ops), scopes.self_times(ops, 0, 1000)))
    assert selfs == {"fusion.9": 30, "fusion.1": 50, "cond.11": 60,
                     "fusion.60": 80, "while.25": 80, "fusion.70": 40,
                     "fusion.71": 40, "fusion.13": 50, "copy.1": 20,
                     "fusion.2": 100}


def test_scope_self_time_and_the_unscoped_remainder():
    red = scopes.reduce(_nested_trace())
    ns = pytest.approx
    assert red.busy_s == ns(550e-9)
    assert sum(red.self_s.values()) == ns(red.busy_s)
    assert red.scope_s == ns({"enc.me": 50e-9, "enc.entropy.emit": 180e-9,
                              "enc.entropy.compact": 80e-9, "enc.tq": 50e-9,
                              "enc.downlink": 100e-9})
    assert red.step_self_s == ns(520e-9)
    assert red.unscoped_step_s == ns(60e-9)
    # the existing reduction is untouched: per-op inclusive time, modules
    assert red.op_s["cond.11"] == ns(300e-9)
    assert red.module_s[STEP] == ns(760e-9)
    assert set(trace.breakdown(red)) == {"device_ops", "idle_gaps"}


def test_gaps_take_the_innermost_program_span():
    red = scopes.reduce(_nested_trace())
    assert red.gaps == [
        ("selkies.step", pytest.approx(180e-9)),  # pack covers 30 of 180
        ("selkies.h2d", pytest.approx(100e-9)),   # inside step, 80 of 100
        ("bench.capture", pytest.approx(50e-9)),  # no program span there
        ("selkies.step", pytest.approx(50e-9)),
        ("selkies.step", pytest.approx(50e-9)),
        ("host", pytest.approx(20e-9)),
    ]


def test_scope_of_takes_the_innermost_vocabulary_name():
    assert scopes.scope_of("jit(f)/enc.entropy.emit/cond/branch_2_fun/"
                           "enc.entropy.compact/scatter") == "enc.entropy.compact"
    assert scopes.scope_of("jit(f)/while/body/add") is None


def test_summary_names_the_scopes():
    tr = _nested_trace()
    text = scopes.summary(tr, scopes.reduce(tr))
    line = next(x for x in text.splitlines() if x.startswith("trace scopes:"))
    assert "enc.entropy.emit 0.00 ms" in line and "88.46% of their" in line


@pytest.fixture
def compiled_here():
    """Executables compiled by this code in this process: a persistent
    cache entry that another revision compiled carries that revision's
    op metadata (the cache key leaves it out), and earlier programs of
    the same name in the process would blur the CPU backend's match by
    name."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    jax.clear_caches()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _record(tmp_path, enc, frames, metas, sleep_span):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
        for f, m in zip(frames, metas):
            enc.submit(f, meta=m)
        # the device idles while the host is inside this span
        with sleep_span():
            time.sleep(0.3)
        enc.submit(frames[0], meta=metas[-1] + 1)
        enc.flush()
    jax.profiler.stop_trace()
    return str(sorted(tmp_path.rglob("*.xplane.pb"))[-1])


def test_program_spans_on_the_profiler_clock(tmp_path, compiled_here):
    from selkies_tpu.models.h264.encoder import TPUH264Encoder
    from selkies_tpu.monitoring.tracing import tracer

    rng = np.random.default_rng(11)
    frames = [rng.integers(0, 255, (48, 64, 4), np.uint8) for _ in range(5)]
    enc = TPUH264Encoder(64, 48, qp=30, frame_batch=1, pipeline_depth=0)
    for f in frames[:2]:  # compile the IDR and P steps before tracing
        enc.encode_frame(f)
    metas = [9000, 9001, 9002]
    was = tracer.enabled
    tracer.enable()
    try:
        path = _record(tmp_path / "on", enc, frames[2:], metas,
                       lambda: tracer.span("pack", pts=4242))
        tracer.disable()
        quiet = _record(tmp_path / "off", enc, frames[2:], [100, 101, 102],
                        lambda: tracer.span("pack", pts=4243))
    finally:
        tracer.enabled = was
        tracer.reset()
        enc.close()
    tr = scopes.load(path)
    by_name: dict = {}
    for s in tr.spans:
        by_name.setdefault(s.name, set()).add(s.pts)
    for name in ("selkies.classify", "selkies.step", "selkies.pack"):
        assert set(metas) <= by_name[name], name
    assert 4242 in by_name["selkies.pack"]
    red = scopes.reduce(tr)
    assert red.gaps[0][0] == "selkies.pack"
    assert red.gaps[0][1] > 0.2
    # the P step's ops map to their scopes through the trace's HLO protos
    assert {"enc.ingest", "enc.me", "enc.tq", "enc.downlink"} <= set(red.scope_s)
    assert scopes.load(quiet).spans == []
