"""The unified telemetry layer (repro.core.telemetry) and its wiring.

* spans: nesting/parent attribution, trace-id inheritance, cross-thread
  record_span, thread-safety of concurrent recording;
* ring buffer: bounded memory with explicit drop accounting — saturation
  drops the oldest span and counts it, never silently truncates;
* metrics: P-square streaming percentiles vs numpy on known
  distributions (and exact small-sample quantiles), counter/gauge
  semantics, registry snapshots, the documented name convention;
* export: Perfetto/chrome trace_event JSON schema validity (metadata +
  complete events, stable tids, synthetic tracks), and spans recorded
  natively in a jax.profiler session, nested, on their own thread;
* sharded campaign: worker-side span export merged into the parent
  buffer (unit-level drain/ingest + a real spawn-worker campaign);
* disabled mode: the fast path returns one shared no-op span and records
  nothing (the <2% serving-overhead budget's mechanism).
"""
import json
import threading
import time

import numpy as np
import pytest

from repro.core.telemetry import (
    TELEMETRY, Histogram, MetricsRegistry, Telemetry, check_metric_names,
)


@pytest.fixture
def tel():
    """A private Telemetry instance (tests must not perturb the process
    singleton other suites' Executors attach to)."""
    t = Telemetry(capacity=1024)
    t.enable()
    return t


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------


def test_span_nesting_records_parent_and_order(tel):
    with tel.span("serving.request", rid=7) as outer:
        with tel.span("pipeline.pack"):
            pass
        with tel.span("pipeline.readback"):
            pass
        outer.set(outcome="done")
    spans = tel.spans()
    names = [s["name"] for s in spans]
    # children exit (and land in the ring) before the enclosing span
    assert names == ["pipeline.pack", "pipeline.readback", "serving.request"]
    by = {s["name"]: s for s in spans}
    assert by["pipeline.pack"]["args"]["parent"] == "serving.request"
    assert by["pipeline.readback"]["args"]["parent"] == "serving.request"
    assert "parent" not in by["serving.request"].get("args", {})
    assert by["serving.request"]["args"]["outcome"] == "done"
    # children are contained in the parent's [ts, ts+dur] window
    p = by["serving.request"]
    for c in ("pipeline.pack", "pipeline.readback"):
        assert by[c]["ts"] >= p["ts"]
        assert by[c]["ts"] + by[c]["dur"] <= p["ts"] + p["dur"] + 1e-3


def test_trace_id_inheritance_and_explicit_override(tel):
    with tel.trace("req-1"):
        assert tel.current_trace() == "req-1"
        with tel.span("serving.dispatch"):
            pass
        with tel.span("pipeline.pack", trace_id="req-override"):
            pass
    assert tel.current_trace() is None
    by = {s["name"]: s for s in tel.spans()}
    assert by["serving.dispatch"]["trace_id"] == "req-1"
    assert by["pipeline.pack"]["trace_id"] == "req-override"


def test_record_span_explicit_endpoints_and_track(tel):
    import time
    t0 = time.perf_counter()
    t1 = t0 + 0.25
    tel.record_span("serving.queue_wait", t0, t1, trace_id="req-3",
                    track="req:3", rid=3)
    (s,) = tel.spans()
    assert abs(s["dur"] - 0.25e6) < 1.0  # microseconds
    assert s["trace_id"] == "req-3"
    assert s["tid_key"][0] == ("track", "req:3")


def test_span_recording_is_thread_safe():
    tel = Telemetry(capacity=100_000)
    tel.enable()
    n_threads, per = 8, 400
    ctr = tel.counter("telemetry.test_total")

    def work(i):
        with tel.trace(f"t{i}"):
            for _ in range(per):
                with tel.span("campaign.tier"):
                    ctr.inc()

    threads = [threading.Thread(target=work, args=(i,)) for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert ctr.value == n_threads * per
    assert tel.spans_recorded == n_threads * per
    assert tel.spans_dropped == 0
    spans = tel.spans()
    assert len(spans) == n_threads * per
    # every span kept its own thread's trace binding
    per_trace = {}
    for s in spans:
        per_trace[s["trace_id"]] = per_trace.get(s["trace_id"], 0) + 1
    assert per_trace == {f"t{i}": per for i in range(n_threads)}


def test_ring_buffer_drops_oldest_and_counts():
    tel = Telemetry(capacity=8)
    tel.enable()
    for i in range(30):
        with tel.span("pipeline.pack", i=i):
            pass
    assert tel.spans_recorded == 30
    assert tel.spans_dropped == 22  # no silent truncation
    kept = [s["args"]["i"] for s in tel.spans()]
    assert kept == list(range(22, 30))  # oldest dropped first
    # the exported trace advertises the drop count
    events = tel.trace_events()
    assert len([e for e in events if e["ph"] == "X"]) == 8


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------


def test_p2_percentiles_track_numpy_on_known_distributions():
    rng = np.random.default_rng(7)
    for xs in (
        rng.lognormal(0.0, 1.0, 20_000),
        rng.standard_normal(20_000) * 3.0 + 10.0,
        rng.exponential(2.0, 20_000),
    ):
        h = Histogram("pipeline.test_ms", {})
        for x in xs:
            h.observe(float(x))
        for q in (0.5, 0.95, 0.99):
            est = h.percentile(q)
            ref = float(np.percentile(xs, q * 100))
            scale = float(np.percentile(np.abs(xs), 99)) or 1.0
            assert abs(est - ref) / scale < 0.05, (q, est, ref)
        snap = h.snapshot()
        assert snap["count"] == len(xs)
        assert snap["min"] == xs.min() and snap["max"] == xs.max()
        assert abs(snap["mean"] - xs.mean()) < 1e-6 * max(1.0, abs(xs.mean()))


def test_p2_small_samples_are_exact_order_statistics():
    h = Histogram("pipeline.test", {})
    for v in (5.0, 1.0, 3.0):
        h.observe(v)
    for q in (0.5, 0.95, 0.99):
        assert h.percentile(q) == pytest.approx(
            float(np.percentile([5.0, 1.0, 3.0], q * 100)))


def test_counter_gauge_semantics_and_reset():
    reg = MetricsRegistry()
    c = reg.counter("executor.invocations", target="vta")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    g = reg.gauge("serving.queue_depth")
    g.set(4)
    g.set_max(2)   # running max keeps the larger value
    assert g.value == 4.0
    g.set_max(9)
    assert g.value == 9.0
    # same (name, labels) -> same object; same name, new labels -> new
    assert reg.counter("executor.invocations", target="vta") is c
    assert reg.counter("executor.invocations", target="hlscnn") is not c
    with pytest.raises(TypeError):
        reg.gauge("executor.invocations", target="vta")
    reg.reset()
    assert c.value == 0.0 and g.value == 0.0


def test_registry_snapshot(tel):
    tel.counter("campaign.mutants").inc(3)
    tel.histogram("serving.latency_ms").observe(5.0)
    snap = {e["name"]: e for e in tel.metrics_snapshot()}
    assert snap["campaign.mutants"]["value"] == 3.0
    assert snap["serving.latency_ms"]["count"] == 1
    assert snap["serving.latency_ms"]["p50"] == 5.0
    assert "telemetry.spans_recorded" in snap


def test_metric_name_convention():
    assert check_metric_names([
        "serving.queue_depth", "pipeline.pack_s", "executor.invocations",
        "fragments.hits", "campaign.mutant_s", "telemetry.spans_dropped",
    ]) == []
    bad = ["Serving.queue", "queue_depth", "serving.", "serving.Queue",
           "unknown.layer", "serving.a-b"]
    assert check_metric_names(bad) == bad
    # the live process registries (executor/serving scopes attach here)
    assert TELEMETRY.check_names() == []


def test_attached_registries_are_weakly_held(tel):
    reg = MetricsRegistry(scope="executor")
    tel.attach(reg)
    reg.counter("executor.invocations").inc()
    assert any(e["name"] == "executor.invocations"
               for e in tel.metrics_snapshot())
    del reg
    import gc
    gc.collect()
    assert not any(e["name"] == "executor.invocations"
                   for e in tel.metrics_snapshot())


# ---------------------------------------------------------------------------
# Perfetto export
# ---------------------------------------------------------------------------


def test_trace_export_is_valid_trace_event_json(tel, tmp_path):
    with tel.trace("req-0"):
        with tel.span("serving.dispatch", app="resmlp"):
            with tel.span("pipeline.pack"):
                pass
    import time
    t0 = time.perf_counter()
    tel.record_span("serving.request", t0, t0 + 0.01, trace_id="req-0",
                    track="req:0", rid=0)
    path = str(tmp_path / "trace.json")
    tel.export_trace(path)
    data = json.load(open(path))
    assert set(data) >= {"traceEvents", "displayTimeUnit", "otherData"}
    events = data["traceEvents"]
    xs = [e for e in events if e["ph"] == "X"]
    ms = [e for e in events if e["ph"] == "M"]
    assert len(xs) == 3 and ms
    for e in xs:
        assert isinstance(e["name"], str)
        assert isinstance(e["ts"], (int, float))
        assert isinstance(e["dur"], (int, float)) and e["dur"] >= 0
        assert isinstance(e["tid"], int) and isinstance(e["pid"], int)
        assert e["cat"] in ("serving", "pipeline", "executor", "fragments",
                            "campaign", "telemetry")
    # every tid used by an event has a thread_name metadata record
    named = {e["tid"] for e in ms if e["name"] == "thread_name"}
    assert {e["tid"] for e in xs} <= named
    # the synthetic request lane is its own track, named req:0
    req = next(e for e in xs if e["name"] == "serving.request")
    lane_names = {e["tid"]: e["args"]["name"] for e in ms
                  if e["name"] == "thread_name"}
    assert lane_names[req["tid"]] == "req:0"
    # trace ids ride in args so Perfetto search correlates the flame
    assert all(e["args"]["trace_id"] == "req-0" for e in xs)


def test_drain_and_ingest_merge_worker_spans(tel):
    worker = Telemetry(capacity=64)
    worker.enable()
    with worker.span("campaign.tier", trace_id="vta:identity@wr_x",
                     tier="vt2"):
        pass
    shipped = worker.drain_spans()
    assert worker.spans() == []  # drained: worker memory stays bounded
    tel.ingest(shipped, source="worker3")
    (s,) = tel.spans()
    assert s["name"] == "campaign.tier"
    assert s["trace_id"] == "vta:identity@wr_x"
    assert s["tid_key"][1].startswith("worker3:")
    # merged spans export like native ones
    evs = [e for e in tel.trace_events() if e["ph"] == "X"]
    assert evs[0]["args"]["trace_id"] == "vta:identity@wr_x"


# ---------------------------------------------------------------------------
# the profiler's trace
# ---------------------------------------------------------------------------


def _profiled(tmp_path, body):
    """Run ``body`` inside a jax.profiler session; the host plane's lines
    of the recorded xplane as ``[[(name, start_ns, end_ns)]]``."""
    import jax
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    (pb,) = tmp_path.rglob("*.xplane.pb")
    pd = ProfileData.from_file(str(pb))
    (host,) = [p for p in pd.planes if p.name == "/host:CPU"]
    return [[(e.name, e.start_ns, e.end_ns) for e in ln.events]
            for ln in host.lines]


def test_spans_land_natively_in_the_profiler_trace(tel, tmp_path):
    def work():
        with tel.span("pipeline.pack", op="fasr_lstm"):
            time.sleep(0.004)
            with tel.span("pipeline.plan"):
                time.sleep(0.006)
            time.sleep(0.002)

    def body():
        t = threading.Thread(target=work)
        t.start()
        t.join()
        with tel.span("serving.dispatch"):
            pass

    lines = _profiled(tmp_path / "on", body)
    by_line = [{n: (s, e) for n, s, e in ln if "." in n} for ln in lines]
    (mine,) = [ln for ln in by_line if "pipeline.pack" in ln]
    # parent and child on one line: the thread that did the work, which is
    # not the line of the span opened on the main thread
    assert "pipeline.plan" in mine and "serving.dispatch" not in mine
    assert any("serving.dispatch" in ln for ln in by_line)
    (p0, p1), (c0, c1) = mine["pipeline.pack"], mine["pipeline.plan"]
    assert p0 <= c0 and c1 <= p1
    own = {s["name"]: s["dur"] * 1e3 for s in tel.spans()}  # ns
    assert abs((p1 - p0) - own["pipeline.pack"]) < 1e6
    assert abs((c1 - c0) - own["pipeline.plan"]) < 1e6

    tel.disable()
    lines = _profiled(tmp_path / "off", work)
    assert not any(n.startswith("pipeline.") for ln in lines for n, _s, _e in ln)


# ---------------------------------------------------------------------------
# disabled mode
# ---------------------------------------------------------------------------


def test_disabled_mode_is_zero_allocation_and_records_nothing():
    tel = Telemetry()
    assert not tel.enabled  # disabled by default
    s1 = tel.span("serving.request")
    s2 = tel.span("pipeline.pack")
    # one shared no-op object: the hot path allocates no span state
    assert s1 is s2
    for _ in range(100):
        with tel.span("serving.request") as s:
            s.set(outcome="ignored")
    import time
    tel.record_span("serving.request", time.perf_counter(),
                    time.perf_counter())
    assert tel.spans_recorded == 0
    assert tel.spans_dropped == 0
    assert tel.spans() == []


def test_enable_disable_roundtrip():
    tel = Telemetry()
    tel.enable(capacity=4)
    with tel.span("serving.dispatch"):
        pass
    assert tel.spans_recorded == 1
    tel.disable()
    with tel.span("serving.dispatch"):
        pass
    assert tel.spans_recorded == 1
    tel.reset()
    assert tel.spans() == [] and tel.spans_recorded == 0


# ---------------------------------------------------------------------------
# instrumented layers
# ---------------------------------------------------------------------------


def test_executor_summaries_are_registry_views():
    import repro.accel  # noqa: F401  (registers the bundled targets)
    from repro.core import ir
    from repro.core.codegen import Executor

    rng = np.random.default_rng(0)
    w = (rng.standard_normal((8, 16)) * 0.1).astype(np.float32)
    b = np.zeros((8,), np.float32)
    prog = ir.call("fasr_linear", ir.Var("x", (4, 16)),
                   ir.Var("w", w.shape), ir.Var("b", b.shape))
    env = {"x": rng.standard_normal((4, 16)).astype(np.float32),
           "w": w, "b": b}
    ex = Executor("ila", engine="pipelined")
    ex.run_many(prog, [env])
    stages = ex.stage_seconds
    assert set(stages) == {"pack_s", "dispatch_s", "readback_s"}
    assert stages["pack_s"] > 0 and stages["dispatch_s"] > 0
    # the dict view IS the registry counters
    by_name = {e["name"]: e for e in ex.metrics.snapshot()}
    for k, v in stages.items():
        assert by_name[f"pipeline.{k}"]["value"] == v
    summ = ex.stats_summary()
    assert summ["flexasr"]["invocations"] == 1
    assert by_name["executor.invocations"]["value"] == 1
    assert summ["flexasr"]["commands"] == by_name["executor.commands"]["value"]
    assert ex.pipeline_summary()["groups"] == by_name["pipeline.groups"]["value"]
    ex.reset_stats()
    assert sum(ex.stage_seconds.values()) == 0.0
    assert ex.stats_summary().get("flexasr", {}).get("invocations", 0) == 0
    assert ex.metrics.names()  # metrics survive reset (zeroed, not dropped)


def test_serving_reject_reasons_are_aggregated_counters():
    import repro.accel  # noqa: F401  (registers the bundled targets)
    from repro.core import ir
    from repro.core.serving import CosimServer

    rng = np.random.default_rng(0)
    w = (rng.standard_normal((8, 16)) * 0.1).astype(np.float32)
    b = np.zeros((8,), np.float32)
    prog = ir.call("fasr_linear", ir.Var("x", (4, 16)),
                   ir.Var("w", w.shape), ir.Var("b", b.shape))
    srv = CosimServer(engine="pipelined", queue_depth=1, seed=0)
    srv.add_program("tiny", prog, {"w": w, "b": b})
    # no dispatch thread started: submissions stay queued, so the second
    # and third hit the depth-1 admission bound
    h1 = srv.submit("tiny")
    h2 = srv.submit("tiny")
    h3 = srv.submit("tiny")
    assert h1.status == "queued"
    assert h2.rejected and h3.rejected
    assert srv.summary()["rejected"] == {"queue_full": 2}
    by_name = {}
    for e in srv.metrics.snapshot():
        by_name.setdefault(e["name"], []).append(e)
    (rej,) = by_name["serving.rejected"]
    assert rej["labels"] == {"reason": "queue_full"} and rej["value"] == 2.0
    assert by_name["serving.queue_depth"][0]["value"] == 1.0
    assert by_name["serving.submitted"][0]["value"] == 3.0
    srv.close(drain=False)


def test_sharded_campaign_merges_worker_spans():
    """A real spawn-worker campaign with tracing on: the workers' tier
    spans come back through the result queue and land in the parent's
    buffer on per-worker lanes, trace-correlated by mutant key."""
    from repro.core import campaign as campaign_mod

    TELEMETRY.reset()
    TELEMETRY.enable()
    try:
        result = campaign_mod.run_campaign_sharded(
            workers=1, mutant_timeout=300.0, trace_spans=True,
            targets=("vecunit",), faults=("identity",), apps=(),
            engine="pipelined", devices_per_target=1,
            op_samples=1, vt2_n=2, seed=0, stat_calib_seeds=0,
        )
        assert len(result.reports) == 1
        spans = [s for s in TELEMETRY.spans() if s["name"] == "campaign.tier"]
        assert spans, "worker tier spans did not reach the parent"
        key = result.reports[0].key
        assert all(s["trace_id"] == key for s in spans)
        assert {s["args"]["tier"] for s in spans} >= {"static", "vt2"}
        assert all(s["tid_key"][1].startswith("worker") for s in spans)
        # escape-matrix counters aggregated parent-side
        snap = {(e["name"], tuple(sorted(e["labels"].items()))): e
                for e in TELEMETRY.metrics_snapshot()}
        assert snap[("campaign.mutants", ())]["value"] >= 1.0
        assert ("campaign.escaped", ()) in snap  # identity escapes
    finally:
        TELEMETRY.disable()
        TELEMETRY.reset()
