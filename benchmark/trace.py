"""Reduction of a JAX profiler trace to device busy time, per-module and
per-op device time, and idle gaps labelled by what the host was doing.

``load`` reads an ``.xplane.pb`` with ``jax.profiler.ProfileData``. On a
TPU the device planes are ``/device:TPU:<n>``: op events come from their
``XLA Ops`` line and module events from their ``XLA Modules`` line. On the
CPU backend (the tests' recorded trace) XLA ops run on host threads and
carry ``hlo_op`` / ``hlo_module`` stats; those are the device events
there, and modules are the union of their ops. Host spans are the
benchmark's own ``jax.profiler.TraceAnnotation`` events, named
``bench.*``; ``bench.window`` bounds the measured window.
"""

from __future__ import annotations

from dataclasses import dataclass, field

WINDOW_SPAN = "bench.window"


@dataclass
class Event:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclass
class Trace:
    # per device: op events and module events
    ops: dict = field(default_factory=dict)      # device -> [Event]
    modules: dict = field(default_factory=dict)  # device -> [Event]
    host: list = field(default_factory=list)     # bench.* spans


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    tr = Trace()
    tpu = [p for p in pd.planes if p.name.startswith("/device:TPU:")]
    for plane in tpu:
        for line in plane.lines:
            if line.name == "XLA Ops":
                tr.ops[plane.name] = [Event(_op_name(e.name), e.start_ns, e.duration_ns)
                                      for e in line.events]
            elif line.name == "XLA Modules":
                tr.modules[plane.name] = [Event(e.name, e.start_ns, e.duration_ns)
                                          for e in line.events]
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("bench."):
                    tr.host.append(Event(e.name, e.start_ns, e.duration_ns))
                elif not tpu:
                    stats = dict(e.stats)
                    if "hlo_op" in stats:
                        dev = f"/device:CPU:{stats.get('device_ordinal', 0)}"
                        ev = Event(e.name, e.start_ns, e.duration_ns)
                        tr.ops.setdefault(dev, []).append(ev)
                        tr.modules.setdefault(dev, []).append(
                            Event(str(stats.get("hlo_module", "?")),
                                  e.start_ns, e.duration_ns))
    return tr


def _op_name(name: str) -> str:
    """A TPU op event is named by its HLO text ('%cond.11 = (...) ...'):
    keep the instruction name."""
    return name.split(" = ", 1)[0].lstrip("%")


def union(intervals: list, lo: float, hi: float) -> list:
    """Merged [start, end) intervals clipped to [lo, hi)."""
    out: list = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


@dataclass
class Reduction:
    window_s: float
    busy_s: float                 # mean over devices of the busy union
    module_s: dict                # module name -> device seconds (union per module)
    op_s: dict                    # op name -> device seconds (sum of durations)
    gaps: list                    # [(label, seconds)] longest idle gaps first
    kernel_calls: dict            # op name -> number of events in the window

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def reduce(tr: Trace, top: int = 10) -> Reduction:
    spans = [h for h in tr.host if h.name == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"trace has no {WINDOW_SPAN} span")
    lo = spans[0].start_ns
    hi = spans[0].end_ns
    window_ns = hi - lo
    busy, module_ns, op_ns, calls, gaps = [], {}, {}, {}, []
    labels = [h for h in tr.host if h.name != WINDOW_SPAN]
    for dev, ops in tr.ops.items():
        merged = union([(e.start_ns, e.end_ns) for e in ops], lo, hi)
        busy.append(sum(e - s for s, e in merged))
        for e in ops:
            d = min(e.end_ns, hi) - max(e.start_ns, lo)
            if d > 0:
                op_ns[e.name] = op_ns.get(e.name, 0.0) + d
                calls[e.name] = calls.get(e.name, 0) + 1
        by_mod: dict = {}
        for e in tr.modules.get(dev, []):
            by_mod.setdefault(e.name, []).append((e.start_ns, e.end_ns))
        for name, iv in by_mod.items():
            module_ns[name] = module_ns.get(name, 0.0) + sum(
                e - s for s, e in union(iv, lo, hi))
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps.extend((s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s)
    ndev = max(1, len(tr.ops))
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    return Reduction(
        window_s=window_ns / 1e9,
        busy_s=sum(busy) / ndev / 1e9,
        module_s={k: v / ndev / 1e9 for k, v in module_ns.items()},
        op_s={k: v / ndev / 1e9 for k, v in op_ns.items()},
        gaps=[(_label(labels, s, e), (e - s) / 1e9) for s, e in gaps],
        kernel_calls=calls,
    )


def _label(spans: list, s: float, e: float) -> str:
    """The bench.* host span that covers most of [s, e), else 'host'."""
    best, cover = "host", 0.0
    for h in spans:
        c = min(e, h.end_ns) - max(s, h.start_ns)
        if c > cover:
            best, cover = h.name, c
    return best


def breakdown(red: Reduction, top: int = 10) -> dict:
    ops = sorted(red.op_s.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in red.gaps[:top]]}


def summary(tr: Trace, red: Reduction, top: int = 12) -> str:
    """A few lines for stderr: what the trace held and where the time went."""
    lines = [f"trace: devices {sorted(tr.ops)}, window {red.window_s:.3f} s, "
             f"busy {red.busy_s:.4f} s"]
    for title, d in (("modules", red.module_s), ("ops", red.op_s)):
        items = sorted(d.items(), key=lambda kv: -kv[1])[:top]
        lines.append(f"trace {title}: " + "; ".join(
            f"{k} {v * 1e3:.2f} ms" for k, v in items))
    # kernels hide below the top ops: name every custom call
    custom = {k: v for k, v in red.op_s.items() if "kernel" in k or "custom" in k}
    lines.append("trace kernels: " + "; ".join(
        f"{k} {v * 1e3:.2f} ms x{red.kernel_calls[k]}" for k, v in sorted(custom.items())))
    return "\n".join(lines)
