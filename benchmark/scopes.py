"""Reduction of a JAX profiler trace by the program's own names.

``benchmark/trace.py`` names device ops by HLO instruction and labels idle
gaps by the benchmark's ``bench.*`` spans. This module adds what the
program says about itself:

* the named scope of each device op: the innermost name of ``SCOPES``
  (the vocabulary of ``selkies_tpu/monitoring/tracing.py``) in the op's
  ``op_name`` metadata. The metadata comes from the trace file itself:
  its ``/host:metadata`` plane holds the HLO proto of every program that
  ran, and an op maps to its scope by (module, instruction name);
* self time per op: its duration less the union of the op events nested
  inside it on the same device line (a conditional encloses its branch's
  ops, a while loop its body's), both clipped to the window. Self times
  sum to busy time. ``scope_s`` sums them per scope, ``unscoped_step_s``
  is what ops with no scope hold inside the ``*step*`` modules;
* idle gaps labelled by the program's ``selkies.*`` spans (the tracer's
  profiler twins): the innermost span that covers at least half of the
  gap, else the ``bench.*`` label ``trace.py`` gives, else ``host``.

``load``, ``reduce`` and ``summary`` take and return what their namesakes
in ``trace.py`` do, extended, so a caller swaps them in one line; the
``breakdown`` of a reduction here keeps its keys.
"""

from __future__ import annotations

import bisect
from dataclasses import dataclass, field

from benchmark import trace
from benchmark.trace import Event, Reduction, Trace

SCOPES = ("enc.ingest", "enc.intra", "enc.me", "enc.tq",
          "enc.entropy.structure", "enc.entropy.compact", "enc.entropy.emit",
          "enc.downlink")
SPAN_PREFIX = "selkies."
METADATA_PLANE = "/host:metadata"


@dataclass
class Op(Event):
    module: str = ""
    scope: str | None = None


@dataclass
class Span(Event):
    pts: object = None  # the frame's 90 kHz pts, where the span has one


@dataclass
class ScopedTrace(Trace):
    spans: list = field(default_factory=list)  # the program's selkies.* spans


@dataclass
class ScopedReduction(Reduction):
    scope_s: dict = field(default_factory=dict)  # scope -> device self seconds
    step_self_s: float = 0.0       # self time of all ops in *step* modules
    unscoped_step_s: float = 0.0   # ... of those with no scope
    self_s: dict = field(default_factory=dict)   # op name -> self seconds


def scope_of(op_name: str) -> str | None:
    """The innermost vocabulary name in an op-name path, else None."""
    for part in reversed(op_name.split("/")):
        if part in SCOPES:
            return part
    return None


# -- reading the trace file ---------------------------------------------------

def _varint(buf, i: int) -> tuple[int, int]:
    shift = out = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message; length-delimited
    values are memoryview slices, fixed-width ones are skipped as bytes."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wt = key & 7
        if wt == 0:
            v, i = _varint(buf, i)
        elif wt == 2:
            ln, i = _varint(buf, i)
            v, i = buf[i:i + ln], i + ln
        elif wt in (1, 5):
            w = 8 if wt == 1 else 4
            v, i = buf[i:i + w], i + w
        else:
            raise ValueError(f"protobuf wire type {wt} not handled")
        yield key >> 3, v


def _first(buf, num: int):
    return next((v for k, v in _fields(buf) if k == num), None)


def _hlo_op_names(hlo_proto) -> dict:
    """HloProto bytes -> {instruction name: op_name metadata}."""
    module = _first(hlo_proto, 1)                    # HloProto.hlo_module
    out = {}
    for k, comp in _fields(module):
        if k != 3:                                   # .computations
            continue
        for k2, inst in _fields(comp):
            if k2 != 2:                              # .instructions
                continue
            name = md = None
            for k3, v in _fields(inst):
                if k3 == 1:                          # .name
                    name = bytes(v).decode()
                elif k3 == 7:                        # .metadata
                    md = _first(v, 2)                # OpMetadata.op_name
            if name is not None and md is not None:
                out[name] = bytes(md).decode()
    return out


def program_op_names(path: str) -> dict:
    """{program name as the trace names it, e.g. 'jit_f(5)': {instruction:
    op_name}} from the trace's metadata plane; {} where it has none."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    for k, plane in _fields(space):
        if k != 1 or bytes(_first(plane, 2) or b"").decode() != METADATA_PLANE:
            continue
        stat_names, events = {}, []
        for k2, v in _fields(plane):
            if k2 == 5:                              # stat_metadata map entry
                md = _first(v, 2)
                stat_names[_first(md, 1)] = bytes(_first(md, 2) or b"").decode()
            elif k2 == 4:                            # event_metadata map entry
                events.append(_first(v, 2))
        out = {}
        for md in events:
            name = bytes(_first(md, 2) or b"").decode()
            for k3, stat in _fields(md):
                if k3 != 5:                          # XEventMetadata.stats
                    continue
                if stat_names.get(_first(stat, 1)) == "Hlo Proto":
                    proto = _first(stat, 6)          # XStat.bytes_value
                    if proto is not None:
                        out[name] = _hlo_op_names(proto)
        return out
    return {}


def _op_names_for(programs: dict, module: str) -> dict:
    """A module's instruction -> op_name map: by its exact program name
    (a TPU trace names both alike), else merged over the programs of its
    base name (the CPU backend's op events name the module without the
    program id)."""
    if module in programs:
        return programs[module]
    base = module.rsplit("(", 1)[0]
    merged: dict = {}
    for name, ops in programs.items():
        if name.rsplit("(", 1)[0] == base:
            merged.update(ops)
    return merged


def _module_at(mods: list, starts: list, t: float) -> str:
    """Name of the module event (``mods`` sorted by start) holding time t."""
    i = bisect.bisect_right(starts, t) - 1
    return mods[i].name if i >= 0 and t < mods[i].end_ns else ""


def load(path: str) -> ScopedTrace:
    """trace.load, with each device op's module and scope and the
    program's selkies.* spans (``pts`` where the span carries one)."""
    from jax.profiler import ProfileData

    base = trace.load(path)
    programs = program_op_names(path)
    pd = ProfileData.from_file(path)
    tr = ScopedTrace(ops={}, modules=base.modules, host=base.host)
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(SPAN_PREFIX):
                    tr.spans.append(Span(e.name, e.start_ns, e.duration_ns,
                                         dict(e.stats).get("pts")))
    cache: dict = {}
    for dev, ops in base.ops.items():
        mods = sorted(base.modules.get(dev, []), key=lambda e: e.start_ns)
        starts = [m.start_ns for m in mods]
        scoped = []
        for e in ops:
            module = _module_at(mods, starts, e.start_ns)
            if module not in cache:
                cache[module] = _op_names_for(programs, module)
            op_name = cache[module].get(e.name)
            scoped.append(Op(e.name, e.start_ns, e.dur_ns, module,
                             scope_of(op_name) if op_name else None))
        tr.ops[dev] = scoped
    return tr


# -- reducing it ----------------------------------------------------------------

def self_times(ops: list, lo: float, hi: float) -> list:
    """Per op (same order), its duration clipped to [lo, hi) less the union
    of the clipped ops nested inside it. Ops of one device line nest as a
    forest; an op that only overlaps another is a root of its own."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i].start_ns, -ops[i].end_ns))
    kids: list = [[] for _ in ops]
    stack: list = []
    for i in order:
        while stack and ops[stack[-1]].end_ns <= ops[i].start_ns:
            stack.pop()
        if stack and ops[i].end_ns <= ops[stack[-1]].end_ns:
            kids[stack[-1]].append(i)
        stack.append(i)
    out = []
    for i, op in enumerate(ops):
        own = max(0.0, min(op.end_ns, hi) - max(op.start_ns, lo))
        inner = trace.union([(ops[k].start_ns, ops[k].end_ns) for k in kids[i]],
                            lo, hi)
        out.append(own - sum(e - s for s, e in inner))
    return out


def span_label(spans: list, s: float, e: float) -> str | None:
    """The innermost selkies.* span covering at least half of [s, e)."""
    best = None
    for h in spans:
        if 2 * (min(e, h.end_ns) - max(s, h.start_ns)) >= e - s:
            if best is None or h.dur_ns < best.dur_ns:
                best = h
    return None if best is None else best.name


def reduce(tr: ScopedTrace, top: int = 10) -> ScopedReduction:
    """trace.reduce, plus self time per op and per scope, and idle gaps
    labelled by the program's spans first."""
    red = trace.reduce(tr, top)
    win = next(h for h in tr.host if h.name == trace.WINDOW_SPAN)
    lo, hi = win.start_ns, win.end_ns
    bench = [h for h in tr.host if h.name != trace.WINDOW_SPAN]
    scope_ns: dict = {}
    self_ns: dict = {}
    step_ns = unscoped_ns = 0.0
    gaps = []
    for ops in tr.ops.values():
        for op, t in zip(ops, self_times(ops, lo, hi)):
            self_ns[op.name] = self_ns.get(op.name, 0.0) + t
            if op.scope is not None:
                scope_ns[op.scope] = scope_ns.get(op.scope, 0.0) + t
            if "step" in op.module:
                step_ns += t
                if op.scope is None:
                    unscoped_ns += t
        merged = trace.union([(e.start_ns, e.end_ns) for e in ops], lo, hi)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps.extend((s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s)
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    ndev = max(1, len(tr.ops))
    return ScopedReduction(
        window_s=red.window_s, busy_s=red.busy_s, module_s=red.module_s,
        op_s=red.op_s, kernel_calls=red.kernel_calls,
        gaps=[(span_label(tr.spans, s, e) or trace._label(bench, s, e),
               (e - s) / 1e9) for s, e in gaps],
        scope_s={k: v / ndev / 1e9 for k, v in scope_ns.items()},
        step_self_s=step_ns / ndev / 1e9,
        unscoped_step_s=unscoped_ns / ndev / 1e9,
        self_s={k: v / ndev / 1e9 for k, v in self_ns.items()},
    )


def summary(tr: ScopedTrace, red: ScopedReduction, top: int = 12) -> str:
    """trace.summary plus a ``trace scopes:`` line and the top self times."""
    items = sorted(red.scope_s.items(), key=lambda kv: -kv[1])
    cover = (100.0 * (1 - red.unscoped_step_s / red.step_self_s)
             if red.step_self_s > 0 else 0.0)
    selfs = sorted(red.self_s.items(), key=lambda kv: -kv[1])[:top]
    return "\n".join([
        trace.summary(tr, red, top),
        "trace scopes: " + "; ".join(f"{k} {v * 1e3:.2f} ms" for k, v in items)
        + f"; unscoped in *step* modules {red.unscoped_step_s * 1e3:.2f} ms "
        f"({cover:.2f}% of their self time scoped); spans "
        f"{len(tr.spans)}",
        "trace self: " + "; ".join(f"{k} {v * 1e3:.2f} ms" for k, v in selfs)])


def per_frame_ms(run, scopes: tuple):
    """Device self time of ``scopes`` per delivered frame, in ms; None where
    the run's reduction has no scopes (an untraced run, a trace reduced
    without this module, or a program without the scopes)."""
    scope_s = getattr(run.trace, "scope_s", None)
    if not scope_s or not run.delivered:
        return None
    secs = sum(scope_s.get(s, 0.0) for s in scopes)
    return secs * 1e3 / len(run.delivered) if secs > 0 else None
