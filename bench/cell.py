"""One run of one benchmark cell: set-up, the measured window, the metrics
and the check of what the window served.

Everything that belongs to one configuration, traffic mix or metric is a
file found by name; this module runs any of them.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from bench import traffic as T

ROOT = Path(__file__).resolve().parents[1]
#: how long past the window's deadline the harness waits for answers due
#: in it before it counts them as never come
GRACE_S = 60.0


# -- finding things by name ---------------------------------------------------

def load_spec(root: Path = ROOT) -> Dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_file_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    config_dir: Path          # where the configuration's files lie
    traffic: Dict
    reference: Any            # the configuration's plain reference module
    e2e: List[Dict]           # end-to-end metric entries this cell reports
    per_layer: List[Dict]     # per-layer metric entries this cell reports


def _applies(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(workload: str, root: Path = ROOT) -> Cell:
    """The cell named ``workload`` with its configuration, traffic mix,
    reference and metrics, all found by name under ``bench/``."""
    spec = load_spec(root)
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    cfg_file = root / {c["name"]: c for c in spec["configs"]}[w["config"]]["file"]
    config = json.loads(cfg_file.read_text())
    mix = json.loads((root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    return Cell(
        name=workload, chips=int(w["chips"]), config=config,
        config_dir=cfg_file.parent, traffic=mix,
        reference=load_module(cfg_file.parent / config["reference"]),
        e2e=[m for m in spec["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, workload)],
    )


def metric_reader(name: str, root: Path = ROOT) -> Callable:
    return load_module(root / "bench" / "metrics" / f"{name}.py").read


# -- what the metric readers see ----------------------------------------------

@dataclasses.dataclass
class Context:
    """The measured window as the metric readers see it."""

    setup_s: float
    window_s: float                 # window start -> last answer
    samples_done: int
    requests_done: int
    counters: Dict[str, float]      # program counters, change over the window
    spans: List[Dict]               # program spans of the window (trace runs)
    trace: Optional[Dict]           # trace_reduce.reduce (trace runs)
    flops_per_sample: int
    peak: Optional[Dict]            # peaks.json entry of this device


# -- set-up ---------------------------------------------------------------------

def make_params(cell: Cell, seed: int) -> Dict[str, np.ndarray]:
    """Every published weight from the seed, in one jitted call on the
    device, then to the host as float32 arrays."""
    import jax

    key = jax.random.fold_in(jax.random.key(int(seed) & 0xFFFFFFFF), int(seed) >> 32)
    params = jax.jit(lambda k: cell.reference.init_params(k, cell.config))(key)
    return {k: np.asarray(v, np.float32) for k, v in jax.device_get(params).items()}


#: input streams: the window's requests, and the warm-up batches
REQUESTS, WARMUP = 2, 3


def sample_inputs(cell: Cell, seed: int, index: int, n: int, xshape,
                  stream: int = REQUESTS) -> List[np.ndarray]:
    """``n`` inputs of one request (or warm-up batch), from the seed, drawn
    as the configuration's reference module says."""
    rng = T.rng_for(seed, stream, index)
    return [np.asarray(cell.reference.draw_input(rng, xshape), np.float32)
            for _ in range(n)]


def reachable_sizes(mix: Dict) -> List[int]:
    """Every dispatch size the server can form under this closed-loop mix:
    coalesced multiples of the request size, up to ``max_batch``."""
    s = int(mix["samples"])
    return [k * s for k in range(1, int(mix["max_batch"]) // s + 1)] or [s]


@dataclasses.dataclass
class Served:
    """The system under test, built for one run."""

    source: Any                     # the application's IR before matching
    server: Any                     # repro.core.serving.CosimServer
    params: Dict[str, np.ndarray]   # the published weights (the reference's)
    weights: Dict[str, np.ndarray]  # the program's inputs made from them
    xshape: tuple
    offloads: Dict[str, int]        # intrinsic -> calls in the matched program

    def envs(self, xs: List[np.ndarray]) -> List[Dict[str, Any]]:
        return [dict(self.weights, x=x) for x in xs]


def build(cell: Cell, seed: int) -> Served:
    """The program as a user serves it: the configuration's application at
    its size, flexible matching, and a ``CosimServer`` built as
    ``launch/serve.py --cosim`` builds it (the ``pipelined`` engine), with
    weights made from the seed."""
    from repro.core import ir
    from repro.core.compile import compile_program
    from repro.core.serving import CosimServer

    app = load_module(cell.config_dir / cell.config["app"])
    source = app.build(cell.config)
    program = compile_program(source).program
    params = make_params(cell, seed)
    weights = app.program_weights(params, cell.config)
    have = {v.name: tuple(v.shape) for v in ir.postorder(source)
            if isinstance(v, ir.Var) and v.name != "x"}
    if {k: v.shape for k, v in weights.items()} != have:
        raise ValueError(f"weights {sorted(weights)} do not match the program's {have}")
    mix = cell.traffic
    server = CosimServer(engine="pipelined", queue_depth=int(mix["queue_depth"]),
                         max_batch=int(mix["max_batch"]), seed=abs(int(seed)) % 2**63)
    server.add_program(cell.name, program, weights)
    offloads: Dict[str, int] = {}
    for n in ir.postorder(program):
        if isinstance(n, ir.Call) and ir.accel_op_target(n.op):
            offloads[n.op] = offloads.get(n.op, 0) + 1
    xshape = next(v.shape for v in ir.postorder(program)
                  if isinstance(v, ir.Var) and v.name == "x")
    return Served(source, server, params, weights, tuple(xshape), offloads)


def warm_up(cell: Cell, s: Served, seed: int) -> None:
    """Compile every shape this mix can make the server dispatch, through
    the served path itself: ``start(warmup=1)``, then, for each padded
    dispatch size the mix can reach, one request whose samples share their
    payloads and one whose samples do not (the simulators compile a runner
    variant for each)."""
    from repro.core import ila

    s.server.start(warmup=1)
    buckets = sorted({ila.batch_bucket(n) for n in reachable_sizes(cell.traffic)})
    for i, b in enumerate(buckets):
        xs = sample_inputs(cell, seed, i, b, s.xshape, WARMUP)
        for batch in ([xs[0]] * b, xs):
            s.server.submit(cell.name, envs=s.envs(batch)).result()


# -- the window -----------------------------------------------------------------

def _counter_values(registry) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for m in registry.snapshot():
        if m.get("type") == "counter":
            out[m["name"]] = out.get(m["name"], 0.0) + float(m["value"])
    return out


class CompileCounter:
    """Counts JAX traces and backend compiles while it is entered."""

    def __init__(self):
        self.counts = {"traces": 0, "compiles": 0}

    def _listener(self, event, duration, **_kw):
        if event == "/jax/core/compile/jaxpr_trace_duration":
            self.counts["traces"] += 1
        elif event == "/jax/core/compile/backend_compile_duration":
            self.counts["compiles"] += 1

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._listener)
        return self.counts

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._listener)
        return False


def drive(cell: Cell, s: Served, seed: int, seconds: float, annotate=None):
    """The measured window. Returns ``(outcomes, inputs, t0, t_close)``:
    every request with its handle, each request's inputs by index, the
    window's start and the moment its last answer came."""
    mix = cell.traffic
    inputs: Dict[int, List[np.ndarray]] = {}

    def submit(r: T.Request):
        inputs[r.index] = sample_inputs(cell, seed, r.index, r.samples, s.xshape)
        return s.server.submit(cell.name, envs=s.envs(inputs[r.index]))

    outcomes, t0 = T.drive_closed(
        submit, lambda h: h.wait(seconds + GRACE_S),
        clients=int(mix["clients"]), samples=int(mix["samples"]),
        seconds=seconds, annotate=annotate)
    deadline = t0 + seconds + GRACE_S
    for o in outcomes:
        o.handle.wait(max(0.0, deadline - time.perf_counter()))
    answered = [o.handle.t_done for o in outcomes if o.handle.status == "done"]
    return outcomes, inputs, t0, max(answered, default=time.perf_counter())


def _trace_window(s: Served, prof_dir: str, t_window: float, log) -> Optional[Dict]:
    """Reduce the profiler's trace of the window, with the program's spans
    placed on the trace's clock by the window's own mark."""
    from bench import trace_reduce
    from repro.core.telemetry import TELEMETRY

    spans = TELEMETRY.spans()
    t_sync = time.perf_counter()  # the span clock's origin, from one probe
    TELEMETRY.record_span("telemetry.sync", t_sync, t_sync)
    origin = t_sync - TELEMETRY.spans()[-1]["ts"] * 1e-6
    if TELEMETRY.spans_dropped:
        log(f"bench: {TELEMETRY.spans_dropped} program spans dropped; "
            f"span metrics left out", file=sys.stderr)
        spans = []
    pb = sorted(Path(prof_dir).rglob("*.xplane.pb"))
    if not pb:
        return None
    devices, host = trace_reduce.read_planes(str(pb[-1]))
    w0 = trace_reduce.window_of(host)[0]

    def at(t_perf: float) -> float:
        return w0 + (t_perf - t_window) * 1e9

    # spans on the program's synthetic request lanes (a request's lifetime,
    # its queue wait) say what a request waited for, not what the host did
    extra = [(sp["name"], at(origin + sp["ts"] * 1e-6),
              at(origin + (sp["ts"] + sp["dur"]) * 1e-6)) for sp in spans
             if not isinstance(sp["tid_key"][0], tuple)]
    out = trace_reduce.reduce(devices, host, extra_host=extra)
    out["spans"] = spans
    return out


@dataclasses.dataclass
class Window:
    """What one measured window left behind."""

    outcomes: List[T.Outcome]
    inputs: Dict[int, List[np.ndarray]]
    t_window: float                 # perf_counter when the window opened
    t0: float                       # the first request's due time
    t_close: float                  # the last answer's time
    in_window: Dict[str, int]       # JAX traces and compiles in the window
    counters: Dict[str, float]      # program counters, change over the window
    trace: Optional[Dict]           # the reduced trace, with the window's spans
    memory_peak_bytes: int


def measure(cell: Cell, s: Served, seed: int, seconds: float, trace: bool,
            log=print) -> Window:
    """The measured window on a warmed-up server, traced or not; reads the
    device's peak memory when it closes. Leaves the server running."""
    import jax

    from repro.core.telemetry import TELEMETRY

    before = _counter_values(s.server.metrics)
    prof_dir, annotate, mark = None, None, T.null_context
    if trace:
        TELEMETRY.enable(capacity=1 << 17)
        TELEMETRY.reset()
        prof_dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(prof_dir, profiler_options=opts)
        annotate = mark = jax.profiler.TraceAnnotation
    with CompileCounter() as counts, mark("bench.window"):
        t_window = time.perf_counter()
        outcomes, inputs, t0, t_close = drive(cell, s, seed, seconds, annotate)
    in_window = dict(counts)
    reduced = None
    if trace:
        jax.profiler.stop_trace()
        reduced = _trace_window(s, prof_dir, t_window, log)
        TELEMETRY.disable()
        shutil.rmtree(prof_dir, ignore_errors=True)
    after = _counter_values(s.server.metrics)
    mem = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use", 0)
    return Window(outcomes, inputs, t_window, t0, t_close, in_window,
                  {k: after.get(k, 0.0) - before.get(k, 0.0) for k in after},
                  reduced, int(mem))


def finish(cell: Cell, s: Served, w: Window, trace: bool, *, t_process: float,
           root: Path = ROOT, log=print) -> Dict:
    """The metrics and the check of one window. Returns the result line's
    object without ``device``, plus ``memory_peak_bytes`` and, in a traced
    run, ``trace``."""
    import jax

    from bench import check, flops

    done = [o for o in w.outcomes if o.handle.status == "done"]
    late_ms = [max(0.0, (o.submitted - o.due_abs) * 1e3) for o in w.outcomes]
    log(f"bench: {len(w.outcomes)} requests, {len(done)} answered; the generator "
        f"ran late by up to {max(late_ms, default=0.0):.3f} ms (mean "
        f"{sum(late_ms) / max(1, len(late_ms)):.3f} ms); {w.in_window['traces']} "
        f"traces and {w.in_window['compiles']} compiles in the window",
        file=sys.stderr)
    try:
        peak = flops.peak(jax.devices()[0].device_kind)
    except KeyError:
        peak = None  # a metric that needs it finds nothing to read
    reduced = dict(w.trace) if w.trace else None
    ctx = Context(
        setup_s=w.t0 - t_process, window_s=w.t_close - w.t0,
        samples_done=sum(o.request.samples for o in done),
        requests_done=len(done), counters=w.counters,
        spans=reduced.pop("spans") if reduced else [], trace=reduced,
        flops_per_sample=flops.flops_per_sample(s.source), peak=peak,
    )
    metrics = {}
    for m in (cell.per_layer if trace else cell.e2e):
        v = metric_reader(m["name"], root)(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    served = {o.request.index: (w.inputs[o.request.index], o.handle.outputs)
              for o in done}
    checks = check.compare(cell, s.params, served, s.offloads,
                           unanswered=len(w.outcomes) - len(done), log=log)
    out = {
        "correct": check.passed(checks),
        "attempted": len(w.outcomes),
        "failed": len(w.outcomes) - len(done),
        "metrics": metrics,
        "memory_peak_bytes": w.memory_peak_bytes,
        "in_window": w.in_window,
        "generator_late_ms_max": max(late_ms, default=0.0),
    }
    if reduced is not None:
        out["trace"] = reduced
    out["checks"] = checks
    return out


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_process: float, root: Path = ROOT, log=print, keep=None) -> Dict:
    """One whole run: set-up, the window, then (with the server closed) the
    metrics and the check. ``keep(cell, served, window)``, where given, is
    called last (the readings of the limits use it)."""
    cell = resolve(workload, root)
    s = build(cell, seed)
    warm_up(cell, s, seed)
    w = measure(cell, s, seed, seconds, trace, log)
    s.server.close(drain=True)
    out = finish(cell, s, w, trace, t_process=t_process, root=root, log=log)
    if keep is not None:
        keep(cell, s, w)
    return out
