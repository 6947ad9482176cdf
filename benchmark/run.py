"""Served-path benchmark of selkies-tpu: one cell of BENCHMARK.json.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One run builds the real ``Orchestrator`` with the cell's configuration,
puts the benchmark's content source in place of the capture, connects one
WS ``/media`` client in this process (on its own thread and event loop),
warms up on the cell's own traffic until no executable is new, opens the
window, measures for ``--seconds``, checks the AUs the client received
against the reference (benchmark/check.py) and prints the result as the
last line of stdout. ``--trace 1`` records a profiler trace of the window
and reports the cell's per-layer metrics instead of its end-to-end ones.
There is no CPU fallback: without a TPU (or with fewer chips than the
cell asks for) the run exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import faulthandler  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
# run as a script, this directory is sys.path[0]: its modules must not
# shadow top-level names (the stdlib's trace), so the repo root replaces it
if sys.path and Path(sys.path[0]).resolve() == Path(__file__).resolve().parent:
    sys.path[0] = str(ROOT)
elif str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark import check, trace  # noqa: E402
from benchmark.content import Scene  # noqa: E402
from benchmark.faults import FAULTS, Fault  # noqa: E402
from benchmark.source import ContentSource, Record, instrument  # noqa: E402

BENCH = Path(__file__).resolve().parent
# the persistent compile cache lives in the checkout, at a fixed path, with
# no size cap (a capped cache evicts the encoder's executables between runs)
CACHE_ENV = {"JAX_COMPILATION_CACHE_DIR": str(ROOT / ".jax_cache"),
             "JAX_COMPILATION_CACHE_MAX_SIZE": "-1"}
# warm-up content lives far from the window's indices, so the window
# never replays what warm-up sent; bases are multiples of 60 (every
# mix's periods divide it) plus a phase of 0..WARM_PHASES-1 frames
WARM_BASE, WARM_STRIDE, WARM_PHASES = 60 * 10**10, 60 * 10**6, 4
# warm-up rounds of WARM_ROUND_S (each then waits for WARM_QUIET_S
# without a compile or a compile-cache load); cold compiles of a
# device-entropy step take ~100 s each on v5e, hence the long cap
WARM_ROUND_S, WARM_QUIET_S, WARM_MAX_S = 3.0, 1.0, 1000.0
WARM_QUIET_ROUNDS = 2
# AUs in flight at the close get this long to land (they count for
# lost_aus, not for the metrics)
DRAIN_S = 1.0
CHECK_SAMPLE = 96


class NoChip(RuntimeError):
    pass


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: list
    per_layer: list
    limits: dict


def load_cell(name: str, root: Path = ROOT) -> Cell:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; one of {sorted(cells)}")
    w = cells[name]
    cfg = next(c for c in spec["configs"] if c["name"] == w["config"])

    def mine(metrics):
        return [m for m in metrics if name in m.get("workloads", [name])]

    return Cell(
        name=name, chips=int(w["chips"]),
        config=json.loads((root / cfg["file"]).read_text()),
        mix=json.loads((BENCH / "traffic" / f"{w['traffic']}.json").read_text()),
        end_to_end=mine(spec["end_to_end"]), per_layer=mine(spec["per_layer"]),
        limits=json.loads((BENCH / "limits" / f"{name}.json").read_text()))


def check_device(chips: int) -> dict:
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def load_peaks(kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())
    if kind not in table:
        raise NoChip(f"device kind {kind!r} is not in benchmark/peaks.json")
    return table[kind]


def ensure_native() -> None:
    """Build the native packer/front-end only where the checkout lacks them."""
    native = ROOT / "native"
    missing = [n for n in ("libcavlc.so", "libframeprep.so")
               if not (native / n).exists()]
    if missing:
        subprocess.run(["make", "-s", "-C", str(native), *missing], check=True)


# -- the client ---------------------------------------------------------------

@dataclass
class Received:
    t: float
    flags: int
    pts: int
    payload: bytes


class MediaClient:
    """One WS /media client on its own thread and event loop, so receive
    times are not held up by the server's loop. Parses the media header
    itself (u8 kind, u8 flags, u16 seq, u32 pts, network order)."""

    def __init__(self, url: str):
        self.url = url
        self.video: list[Received] = []
        self.error: BaseException | None = None
        self._loop: asyncio.AbstractEventLoop | None = None
        self._stop: asyncio.Event | None = None
        self._ready = threading.Event()
        self._thread = threading.Thread(target=self._main, name="bench-client")

    def start(self) -> None:
        self._thread.start()
        self._ready.wait(30)

    def stop(self) -> None:
        if self._loop is not None:
            self._loop.call_soon_threadsafe(self._stop.set)
        self._thread.join(30)

    def _main(self) -> None:
        try:
            asyncio.run(self._run())
        except BaseException as e:  # noqa: BLE001 - reported by the harness
            self.error = e
        finally:
            self._ready.set()

    async def _run(self) -> None:
        import struct

        import aiohttp
        import jax.profiler

        header = struct.Struct("!BBHI")
        self._loop = asyncio.get_running_loop()
        self._stop = asyncio.Event()
        async with aiohttp.ClientSession() as http:
            async with http.ws_connect(self.url, max_msg_size=0) as ws:
                self._ready.set()
                stop = asyncio.ensure_future(self._stop.wait())
                while True:
                    recv = asyncio.ensure_future(ws.receive())
                    await asyncio.wait({recv, stop},
                                       return_when=asyncio.FIRST_COMPLETED)
                    if not recv.done():
                        recv.cancel()
                        break
                    msg = recv.result()
                    t = time.perf_counter()
                    if msg.type == aiohttp.WSMsgType.BINARY:
                        with jax.profiler.TraceAnnotation("bench.client_recv"):
                            kind, flags, _seq, pts = header.unpack_from(msg.data)
                            if kind == 1:
                                self.video.append(Received(
                                    t, flags, pts, bytes(msg.data[header.size:])))
                    elif msg.type == aiohttp.WSMsgType.TEXT:
                        obj = json.loads(msg.data)
                        if isinstance(obj, dict) and obj.get("type") == "ping":
                            await ws.send_str(f"pong,{obj['data']['start_time']}")
                    else:
                        break
                stop.cancel()


# -- one run ------------------------------------------------------------------

@dataclass
class Delivered:
    k: int
    due: float
    t_recv: float
    qp: int | None
    idr: bool
    stats: object


@dataclass
class RunData:
    """What the metric readers see (benchmark/metrics/*.py)."""
    seconds: float
    fps: float
    width: int
    height: int
    setup_s: float
    t_open: float
    captures: list
    delivered: list
    peaks: dict
    trace: object = None

    def due(self, k: int) -> float:
        return self.t_open + k / self.fps


def _orchestrator(cfg: dict, workdir: str):
    from selkies_tpu.config import FLAGS, Config
    from selkies_tpu.input_host import FakeBackend, MemoryClipboard
    from selkies_tpu.orchestrator import Orchestrator

    values = {fl.name: fl.default for fl in FLAGS}
    values.update(
        addr="127.0.0.1", port=0, framerate=int(cfg["framerate"]),
        encoder=cfg["encoder"], capture_width=int(cfg["width"]),
        capture_height=int(cfg["height"]),
        video_bitrate=int(cfg["video_bitrate_kbps"]),
        congestion_control=bool(cfg["congestion_control"]),
        enable_cursors=False, stun_host="127.0.0.1",
        json_config=os.path.join(workdir, "selkies_config.json"),
        rtc_config_json=os.path.join(workdir, "rtc.json"))
    orch = Orchestrator(Config(values=values))
    orch.input.backend = FakeBackend()
    orch.input.clipboard = MemoryClipboard()
    return orch


class _Compiles:
    """XLA compiles and persistent-cache loads seen by jax.monitoring, with
    the program frame that asked for each. A compile is in flight from
    the start of its tracing to the end of its backend compile."""

    PHASES = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax.monitoring

        self.n, self.inflight, self.last, self.events = 0, 0, time.perf_counter(), []
        self._lock = threading.Lock()
        jax.monitoring.register_scalar_listener(self._started)
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def quiet_for(self) -> float:
        """Seconds since the last compile or load; 0 while one runs."""
        with self._lock:
            return 0.0 if self.inflight else time.perf_counter() - self.last

    def _started(self, event, _value, **_):
        if event in self.PHASES:
            with self._lock:
                self.inflight += 1
                self.last = time.perf_counter()

    def _duration(self, event, secs, **_):
        if event in self.PHASES:
            self._note("compile" if event == self.PHASES[-1] else None, secs, done=True)

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self._note("cache load", 0.0)

    def _note(self, what, secs: float, done: bool = False) -> None:
        with self._lock:
            if done:
                self.inflight = max(0, self.inflight - 1)
            self.last = time.perf_counter()
            if what is None:
                return
            self.n += 1
            t = self.last
        import traceback

        site = [f for f in traceback.extract_stack()
                if "selkies_tpu" in f.filename][-1:]
        with self._lock:
            self.events.append((t, what, secs, "".join(
                f"{Path(f.filename).name}:{f.lineno}" for f in site)))


async def _measure(cell: Cell, seed: int, seconds: float, traced: bool,
                   fault: Fault, peaks: dict, workdir: str) -> tuple:
    import jax

    cfg = cell.config
    fps = float(cfg["framerate"])
    record = Record()
    scene = Scene(cell.mix, int(cfg["width"]), int(cfg["height"]), seed)
    source = ContentSource(scene, fps, bool(cell.mix.get("damage")), record)
    compiles = _Compiles()
    fault.plant()
    orch = _orchestrator(cfg, workdir)
    orch.app.source = source
    encoder = orch.app.encoder
    instrument(encoder, source, record, fault)
    run_task = asyncio.ensure_future(orch.run())
    client = None
    try:
        for _ in range(600):
            if orch.server._runner is not None and orch.server._runner.addresses:
                break
            await asyncio.sleep(0.05)
        client = MediaClient(f"http://127.0.0.1:{orch.server.bound_port}/media")
        source.start_clock(time.perf_counter(), WARM_BASE, window=False)
        client.start()
        t_warm = time.perf_counter()
        while not client.video:
            if client.error is not None or time.perf_counter() - t_warm > WARM_MAX_S:
                raise RuntimeError(f"no AU reached the client: {client.error}")
            await asyncio.sleep(0.1)
        # warm-up rounds start the way the window does (forced IDR, content
        # clock restarted) at each of WARM_PHASES content phases, so every
        # delta-group size the window's first frames can form is built,
        # then repeat until one whole round loads and compiles nothing
        quiet = 0
        for r in range(1, 1 << 30):
            n0, a0 = compiles.n, len(client.video)
            source.start_clock(time.perf_counter(),
                               WARM_BASE + r * WARM_STRIDE + r % WARM_PHASES,
                               window=False)
            orch.app.force_keyframe()
            await asyncio.sleep(WARM_ROUND_S)
            while compiles.quiet_for() < WARM_QUIET_S:
                await asyncio.sleep(0.1)
            # quiet: nothing compiled or loaded, and AUs kept arriving
            flowing = len(client.video) - a0 >= WARM_ROUND_S
            quiet = quiet + 1 if compiles.n == n0 and flowing else 0
            print(f"warm-up round {r}: {compiles.n - n0} compiles or loads "
                  f"{[e[1:] for e in compiles.events[n0:]]}, "
                  f"{len(client.video) - a0} AUs, "
                  f"{time.perf_counter() - t_warm:.1f} s", file=sys.stderr, flush=True)
            if r >= WARM_PHASES and quiet >= WARM_QUIET_ROUNDS:
                break
            if time.perf_counter() - t_warm > WARM_MAX_S:
                raise RuntimeError("warm-up did not settle")
        warm_compiles = compiles.n
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if traced else None
        if traced:
            # the Python tracer (on by default) slowed the served path to a
            # sixth of its rate on the chip; host spans and device ops stay
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        window_span = jax.profiler.TraceAnnotation(trace.WINDOW_SPAN)
        c0 = compiles.n
        window_span.__enter__()
        t_open = time.perf_counter()
        source.start_clock(t_open, 0, window=True)
        orch.app.force_keyframe()
        await asyncio.sleep(seconds)
        t_close = time.perf_counter()
        window_span.__exit__(None, None, None)
        in_window = compiles.n - c0
        if sum(c.window for c in record.captures) < seconds * fps / 4:
            # a stalled tick loop: say where every thread is
            print("window: few captures; thread stacks at the close:",
                  file=sys.stderr, flush=True)
            faulthandler.dump_traceback(sys.stderr, all_threads=True)
        for t, what, secs, site in compiles.events[c0:]:
            print(f"window {what} at +{t - t_open:.3f} s ({secs:.2f} s) from {site}",
                  file=sys.stderr, flush=True)
        await asyncio.sleep(DRAIN_S)
        if traced:
            jax.profiler.stop_trace()
        mem = jax.devices()[0].memory_stats() or {}
    finally:
        if client is not None:
            client.stop()
        # the orchestrator's own shutdown closes its signalling client
        # first; stopping the server alone waits out its open sockets
        await orch.shutdown()
        try:
            await asyncio.wait_for(run_task, 30)
        except (asyncio.TimeoutError, asyncio.CancelledError):
            run_task.cancel()
        await asyncio.to_thread(encoder.close)
    if client.error is not None:
        raise RuntimeError(f"client failed: {client.error!r}")
    info = {"t_open": t_open, "t_close": t_close, "warm_compiles": warm_compiles,
            "compiles_in_window": in_window, "trace_dir": trace_dir,
            "memory_peak_bytes": int(mem.get("peak_bytes_in_use", 0)),
            "supervisor": orch.app.supervisor.stats(),
            "software_fallback": orch.app.software_fallback}
    return record, client.video, scene, info


def _assemble(cell: Cell, record: Record, video: list, info: dict, peaks: dict,
              seconds: float, fault: Fault):
    """-> (RunData, received [(payload, idr)], window indices, credit, lost)."""
    cfg = cell.config
    t_open, t_close = info["t_open"], info["t_close"]
    caps = [c for c in record.captures if c.window]
    index = {id(c): i for i, c in enumerate(caps)}
    received, window_idx, credit, delivered = [], [], [], []
    arrived = set()
    for i, r in enumerate(video):
        received.append((r.payload, bool(r.flags & 1)))
        sub = record.by_pts.get(r.pts)
        if sub is None or not sub.cap.window:
            continue
        arrived.add(id(sub.cap))
        if not t_open <= r.t < t_close:
            continue
        window_idx.append(i)
        credit.append(caps[fault.credit(caps, index[id(sub.cap)])].k)
        delivered.append(Delivered(sub.cap.k, sub.cap.due, r.t, sub.qp,
                                   bool(r.flags & 1), sub.stats))
    # a window frame whose submit failed, or that the encoder completed
    # before the close and whose AU never arrived (the drain gives it
    # DRAIN_S more)
    lost = sum(1 for s in record.by_pts.values()
               if s.cap.window and s.cap.t < t_close and id(s.cap) not in arrived
               and (s.failed or (s.t_done is not None and s.t_done < t_close)))
    run = RunData(seconds=seconds, fps=float(cfg["framerate"]),
                  width=int(cfg["width"]), height=int(cfg["height"]),
                  setup_s=t_open - T_PROCESS, t_open=t_open,
                  captures=[c for c in caps if c.t < t_close],
                  delivered=delivered, peaks=peaks)
    return run, received, window_idx, credit, lost


def read_metrics(run: RunData, metrics: list) -> dict:
    out = {}
    for m in metrics:
        reader = importlib.import_module(f"benchmark.metrics.{m['name']}")
        v = reader.read(run)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def run_cell(cell: Cell, seed: int, seconds: float, traced: bool, device: dict,
             peaks: dict, fault: Fault = Fault()) -> dict:
    """One whole run after the device check; -> the result line's object."""
    from selkies_tpu.utils.jaxcache import enable_persistent_compilation_cache

    enable_persistent_compilation_cache()
    os.environ.pop("DISPLAY", None)  # the benchmark's source, never a local X
    with tempfile.TemporaryDirectory(prefix="bench-") as workdir:
        record, video, scene, info = asyncio.run(_measure(
            cell, seed, seconds, traced, fault, peaks, workdir))
        run, received, window_idx, credit, lost = _assemble(
            cell, record, video, info, peaks, seconds, fault)
        red = None
        if traced:
            pbs = sorted(Path(info["trace_dir"]).rglob("*.xplane.pb"))
            tr = trace.load(str(pbs[-1]))
            shutil.rmtree(info["trace_dir"], ignore_errors=True)
            red = trace.reduce(tr)
            run.trace = red
            print(trace.summary(tr, red), file=sys.stderr, flush=True)
        metrics = read_metrics(run, cell.per_layer if traced else cell.end_to_end)
        values = check.compare(received, window_idx, credit, scene, lost,
                               CHECK_SAMPLE, seed, workdir)
    qps = sorted(d.qp for d in run.delivered if d.qp is not None)
    print(f"readings: {values}; qp of window AUs "
          f"{(qps[0], qps[len(qps) // 2], qps[-1]) if qps else None}",
          file=sys.stderr, flush=True)
    correct, checks = check.judge(values, cell.limits["limits"])
    dev = dict(device, memory_peak_bytes=info["memory_peak_bytes"])
    if red is not None:
        dev.update(busy_s=red.busy_s, window_s=red.window_s)
    print(f"window: {len(run.delivered)} AUs received, {len(run.captures)} "
          f"captures, compiles in window {info['compiles_in_window']} "
          f"(warm-up {info['warm_compiles']}), supervisor "
          f"{info['supervisor'].get('rung')}, software_fallback "
          f"{info['software_fallback']}", file=sys.stderr, flush=True)
    out = {"correct": correct, "attempted": len(window_idx) + lost,
           "failed": lost + (0 if values["decode_errors"] == 0 else 1),
           "metrics": metrics, "device": dev}
    if red is not None:
        out["breakdown"] = trace.breakdown(red)
    out["window_compiles"] = info["compiles_in_window"]
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--fault", choices=FAULTS, default="none",
                    help="plant a fault or the control (never in the driver's runs)")
    args = ap.parse_args(argv)
    os.environ.update(CACHE_ENV)  # before JAX is imported
    cell = load_cell(args.workload)
    ensure_native()
    try:
        device = check_device(cell.chips)
        peaks = load_peaks(device["kind"])
    except NoChip as e:
        print(f"benchmark: {e}", file=sys.stderr, flush=True)
        return 3
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace), device,
                   peaks, Fault(args.fault))
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
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
    # the served app's worker pools must not keep a finished run holding
    # the chip
    os._exit(rc)
