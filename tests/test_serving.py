"""Continuous-batching co-sim serving tests (repro.core.serving).

Pins the three serving contracts the benchmark assumes:

* coalesced results are bit-exact vs serving the same requests serially
  (per-request seeded operands + batch-composition-independent engines);
* admission control rejects — immediately, with a reason — rather than
  queueing unboundedly under a saturating burst;
* shutdown drains: every accepted request is served before close()
  returns, and post-shutdown submissions are rejected;
* traced, the server spans its host work, with one compile span per
  jitted runner or batched read it creates.
"""
import numpy as np
import pytest

import repro.accel  # noqa: F401  (registers the bundled targets)
from repro.core import ila, ir
from repro.core.codegen import Executor
from repro.core.serving import (
    CosimServer, DONE, REJECT_BACKLOG, REJECT_QUEUE_FULL, REJECT_SHUTDOWN,
    request_rng,
)


def _tiny_program(I=16, O=8, seed=0):
    """relu(fasr_linear(x, w, b)): one accelerator call + a host epilogue —
    small enough that serving tests run in seconds."""
    rng = np.random.default_rng(seed)
    w = (rng.standard_normal((O, I)) * 0.1).astype(np.float32)
    b = (rng.standard_normal((O,)) * 0.1).astype(np.float32)
    expr = ir.call(
        "relu",
        ir.call("fasr_linear", ir.Var("x", (4, I)), ir.Var("w", w.shape),
                ir.Var("b", b.shape)),
    )
    return expr, {"w": w, "b": b}


def _server(**kw):
    kw.setdefault("engine", "pipelined")
    kw.setdefault("pipeline_chunk", 2)
    srv = CosimServer(**kw)
    expr, params = _tiny_program()
    srv.add_program("tiny", expr, params)
    return srv


# ---------------------------------------------------------------------------
# coalescing: bit-exact vs serial
# ---------------------------------------------------------------------------


def test_coalesced_equals_serial_bit_exact():
    """Submit a burst of batch-1 requests before start(): the dispatch
    thread wakes to a full queue and must coalesce them into shared
    vmapped dispatches, and every request's outputs must be bit-identical
    to running its (seed, request_id)-derived envs alone on a fresh
    one-device executor."""
    srv = _server(seed=3, max_batch=8, queue_depth=32)
    handles = [srv.submit("tiny", batch=1) for _ in range(6)]
    try:
        srv.start(warmup=1, warm_batch=2)
        outs = {h.id: h.result(timeout=300) for h in handles}
    finally:
        srv.close(drain=True)
    assert any(h.coalesced_with > 0 for h in handles), (
        "a 6-request pre-start burst never shared a dispatch: coalescing "
        "is not happening"
    )
    assert srv.summary()["coalesced_max"] > 1

    serial = Executor("ila", engine="pipelined")
    expr, _params = _tiny_program()
    for h in handles:
        envs = srv.request_envs("tiny", h.id, 1)
        # the request's operands are a pure function of (seed, id)
        np.testing.assert_array_equal(
            envs[0]["x"], h.envs[0]["x"],
            err_msg="request_envs is not reproducing the served operands")
        (ref,) = serial.run_many(expr, envs)
        assert len(outs[h.id]) == 1
        np.testing.assert_array_equal(
            np.asarray(ref), outs[h.id][0],
            err_msg=f"request {h.id}: coalesced result differs from serial")


def test_warmup_leaves_engine_and_cost_models_unchanged():
    """start(warmup=1) warms through the serving engine itself: it never
    switches the executor's engine and never refits a target's CostModel
    (job pricing stays the analytic one every single-device run uses)."""
    import copy

    from repro.core.ila import TARGETS

    def models():
        return {t.name: (copy.deepcopy(vars(t.cost_model)),
                         t.cost_model.job_cycles(1000.0))
                for t in TARGETS.all() if t.cost_model is not None}

    srv = _server(seed=1, max_batch=4)
    ex = srv.executor
    seen = []
    for name in ("run_many", "submit_many"):
        call = getattr(ex, name)

        def spy(*a, _call=call, **kw):
            seen.append(ex.engine)
            return _call(*a, **kw)

        setattr(ex, name, spy)
    before = models()
    srv.start(warmup=1)
    try:
        assert seen and set(seen) == {"pipelined"}
        assert ex.engine == "pipelined"
        assert models() == before
    finally:
        srv.close()


def test_served_requests_leave_no_growing_record_on_the_executor():
    """Serving more requests grows nothing on the executor but ``stats``
    (which ``collect_stats`` governs): no per-request or per-group log."""
    srv = _server(seed=2, max_batch=4)
    ex = srv.executor

    def sizes():
        out = {k: len(v) for k, v in vars(ex).items()
               if k != "stats" and isinstance(v, (list, dict, set, tuple))}
        out["metrics"] = len(ex.metrics.snapshot())
        return out

    def serve(n):
        for _ in range(n):
            srv.submit("tiny", batch=2).result(timeout=300)

    srv.start(warmup=1)
    try:
        serve(2)
        first, n_stats = sizes(), len(ex.stats)
        serve(6)
        assert sizes() == first
        assert len(ex.stats) > n_stats
    finally:
        srv.close()


def test_request_rng_is_interleaving_independent():
    """The operand stream is keyed by (seed, request_id) alone."""
    a = request_rng(7, 12).standard_normal(8)
    b = request_rng(7, 12).standard_normal(8)
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, request_rng(7, 13).standard_normal(8))
    assert not np.array_equal(a, request_rng(8, 12).standard_normal(8))


def test_serving_batch_ladder_restored_after_close():
    """start() switches the vmapped batch axis to the serving ladder;
    close() must restore the process-wide default (other tests and the
    campaign path rely on pow2 buckets)."""
    assert ila.batch_bucket(6) == 8  # pow2 default
    srv = _server()
    srv.start(warmup=0)
    try:
        assert ila.batch_bucket(6) == 6  # serving ladder: 3/4-pow2 step
    finally:
        srv.close()
    assert ila.batch_bucket(6) == 8


# ---------------------------------------------------------------------------
# admission control
# ---------------------------------------------------------------------------


def test_burst_beyond_queue_depth_is_rejected():
    """A saturating burst: the queue admits queue_depth requests, the rest
    are rejected immediately with reason queue_full, and every accepted
    request is still served (drain on close)."""
    srv = _server(queue_depth=2, coalesce=False)
    handles = [srv.submit("tiny", batch=1) for _ in range(5)]
    rejected = [h for h in handles if h.rejected]
    accepted = [h for h in handles if not h.rejected]
    assert len(rejected) == 3 and len(accepted) == 2
    for h in rejected:
        assert h.reject_reason == REJECT_QUEUE_FULL
        assert h.done()  # rejection resolves the handle instantly
        with pytest.raises(RuntimeError, match="queue_full"):
            h.result(timeout=1)
    srv.start(warmup=1, warm_batch=2)
    try:
        for h in accepted:
            assert len(h.result(timeout=300)) == 1
    finally:
        srv.close(drain=True)
    assert srv.summary()["rejected"] == {REJECT_QUEUE_FULL: 3}


def test_backlog_cycle_backpressure_rejects():
    """With max_backlog_cycles below two requests' estimated cost, the
    second pre-start submission is shed with reason backlog."""
    srv = _server(queue_depth=64)
    est = srv._apps["tiny"].est_cycles_per_sample
    assert est > 0, "CostModel produced no estimate for fasr_linear"
    srv.max_backlog_cycles = 1.5 * est
    h1 = srv.submit("tiny", batch=1)
    h2 = srv.submit("tiny", batch=1)
    assert not h1.rejected
    assert h2.rejected and h2.reject_reason == REJECT_BACKLOG
    srv.start(warmup=1, warm_batch=2)
    try:
        h1.result(timeout=300)
        # served work retires its cycles: admission reopens
        h3 = srv.submit("tiny", batch=1)
        assert not h3.rejected
        h3.result(timeout=300)
    finally:
        srv.close(drain=True)


# ---------------------------------------------------------------------------
# shutdown semantics
# ---------------------------------------------------------------------------


def test_close_drains_inflight_and_rejects_new():
    """close(drain=True) serves every accepted request — none are dropped
    or cancelled — and submissions after shutdown are rejected with
    reason shutdown."""
    srv = _server(max_batch=4, queue_depth=32)
    srv.start(warmup=1, warm_batch=2)
    handles = [srv.submit("tiny", batch=1) for _ in range(7)]
    accepted = [h for h in handles if not h.rejected]
    assert accepted, "every submission was rejected before close()"
    srv.close(drain=True)
    for h in accepted:
        assert h.status == DONE, f"request {h.id} was dropped at shutdown"
        assert len(h.outputs) == 1
    late = srv.submit("tiny", batch=1)
    assert late.rejected and late.reject_reason == REJECT_SHUTDOWN


def test_close_without_drain_cancels_queued():
    srv = _server(coalesce=False, queue_depth=32)
    handles = [srv.submit("tiny", batch=1) for _ in range(4)]
    # never started: nothing is in flight, every request is still queued
    srv.close(drain=False)
    assert all(h.status == "cancelled" for h in handles)


def test_serve_cli_exits_nonzero_when_a_request_fails(monkeypatch, capsys):
    """`launch/serve.py --cosim`: a planner that raises once measured
    requests start (warmup already passed) marks those requests failed;
    the CLI must then exit non-zero and name the first error rather than
    report the run as served."""
    import dataclasses
    import sys

    from repro.core import serving
    from repro.launch import jax_cache, serve

    # keep this test process's compile cache where it was
    monkeypatch.setattr(jax_cache, "enable_compile_cache", lambda: "")

    def boom(ctx, x, args):
        raise ValueError("planner exploded")

    target, intr = ila.TARGETS.intrinsic("fasr_linear")
    start = serving.CosimServer.start

    def start_then_break(self, *a, **kw):
        out = start(self, *a, **kw)
        monkeypatch.setitem(ila.TARGETS._by_op, "fasr_linear",
                            (target, dataclasses.replace(intr, planner=boom)))
        return out

    monkeypatch.setattr(serving.CosimServer, "start", start_then_break)
    monkeypatch.setattr(sys, "argv", [
        "serve", "--cosim", "LSTM-WLM", "--requests", "2", "--batch", "1",
        "--engine", "pipelined"])
    with pytest.raises(SystemExit) as ei:
        serve.main()
    assert ei.value.code not in (0, None)
    assert "planner exploded" in str(ei.value.code)
    assert "ValueError: planner exploded" in capsys.readouterr().err


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_placement(monkeypatch, tmp_path, from_env):
    """The entry points' compile cache: where ``JAX_COMPILATION_CACHE_DIR``
    says, with nothing set in code; else ``.jax_cache/`` at the checkout
    root, a fixed path (the cache key includes it)."""
    from pathlib import Path

    import jax

    from repro.launch import jax_cache

    prev = jax.config.jax_compilation_cache_dir
    if from_env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        got = jax_cache.enable_compile_cache()
        if from_env:
            assert got == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == prev
        else:
            root = Path(__file__).resolve().parents[1]
            assert got == str(root / ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


# ---------------------------------------------------------------------------
# tracing
# ---------------------------------------------------------------------------


def _runner_keys(srv):
    """Every jitted data runner (per ILA) and batched read (per executor)
    created so far."""
    keys = {(t.ila.name, k) for t in ila.TARGETS.all()
            for k in getattr(t.ila, "_data_runners", {})}
    return keys | {("read", k) for k in srv.executor._batched_reads}


def test_traced_server_spans_its_host_work():
    """Tracing on, a pipelined server records where its host time goes:
    waits on the pack worker, planning and stacking inside pack, host
    evaluation of the nodes not offloaded, accuracy statistics, and one
    ``executor.compile`` span per jitted runner or batched read it creates
    (a shape no other test here uses, so its runners are new). An
    identical request on the warm server compiles nothing."""
    from repro.core.telemetry import TELEMETRY

    srv = CosimServer(engine="pipelined", pipeline_chunk=2, seed=0)
    expr, params = _tiny_program(I=24, O=12, seed=5)
    srv.add_program("odd", expr, params)
    rng = np.random.default_rng(1)
    envs = [dict(params, x=rng.standard_normal((4, 24)).astype(np.float32))
            for _ in range(4)]
    srv.start(warmup=0)
    TELEMETRY.enable()
    TELEMETRY.reset()
    try:
        before = _runner_keys(srv)
        srv.submit("odd", envs=envs).result(timeout=300)
        created = _runner_keys(srv) - before
        first = TELEMETRY.spans()
        TELEMETRY.reset()
        srv.submit("odd", envs=envs).result(timeout=300)
        again = TELEMETRY.spans()
    finally:
        TELEMETRY.disable()
        TELEMETRY.reset()
        srv.close()
    assert {s["name"] for s in first} >= {
        "pipeline.pack_wait", "pipeline.plan", "pipeline.stack",
        "executor.host_eval", "executor.stats", "executor.compile"}
    compiles = [s["args"] for s in first if s["name"] == "executor.compile"]
    assert created and len(compiles) == len(created)
    assert {c["kind"] for c in compiles} <= {"data_runner", "read"}
    assert all(c["ila"] == "flexasr" for c in compiles)
    packs = [s["args"] for s in first if s["name"] == "pipeline.pack"]
    assert packs and all(a["op"] == "fasr_linear" for a in packs)
    evals = {s["args"]["op"] for s in first if s["name"] == "executor.host_eval"}
    # program inputs an accelerator call takes come from the environments
    # as they are: no host evaluation (no device round trip) of them
    assert evals >= {"relu", "fasr_linear.operands"} and "var" not in evals
    assert not [s for s in again if s["name"] == "executor.compile"]


def _read_cache(srv):
    m = srv.executor.metrics
    return tuple(m.find(f"executor.read_cache.{k}")[0].value
                 for k in ("hits", "misses"))


def test_warm_server_reuses_vta_reads():
    """A program that offloads a VTA relu: once warm, the server serves
    identical requests with no ``executor.compile`` span of any kind (VTA's
    reads are one function per tile layout, so the executor's jitted read
    is found again), its jitted reads stay as many as they were, and the
    read cache counts hits and no new misses."""
    from repro.core.telemetry import TELEMETRY

    rng = np.random.default_rng(7)
    w = (rng.standard_normal((20, 16)) * 0.1).astype(np.float32)
    b = (rng.standard_normal((20,)) * 0.1).astype(np.float32)
    expr = ir.call(
        "vta_relu",
        ir.call("fasr_linear", ir.Var("x", (4, 16)), ir.Var("w", w.shape),
                ir.Var("b", b.shape)),
    )
    srv = CosimServer(engine="pipelined", pipeline_chunk=2, seed=0)
    srv.add_program("vta", expr, {"w": w, "b": b})
    # 3 samples in chunks of 2: a batched group and a lone job per node
    envs = [dict(x=rng.standard_normal((4, 16)).astype(np.float32), w=w, b=b)
            for _ in range(3)]
    srv.start(warmup=0)
    TELEMETRY.enable()
    TELEMETRY.reset()
    try:
        first = srv.submit("vta", envs=envs).result(timeout=300)
        compiled = [s["args"] for s in TELEMETRY.spans()
                    if s["name"] == "executor.compile"]
        TELEMETRY.reset()
        again = srv.submit("vta", envs=envs).result(timeout=300)
        recompiled = [s for s in TELEMETRY.spans() if s["name"] == "executor.compile"]
        reads, (hits, misses) = len(srv.executor._batched_reads), _read_cache(srv)
        for _ in range(5):
            srv.submit("vta", envs=envs).result(timeout=300)
        reads_after, (hits_after, misses_after) = \
            len(srv.executor._batched_reads), _read_cache(srv)
    finally:
        TELEMETRY.disable()
        TELEMETRY.reset()
        srv.close()
    # one jit(vmap(read)) for the pair, one jit(read) for the lone job
    assert sum(c["ila"] == "vta" and c["kind"] == "read" for c in compiled) == 2
    assert not recompiled
    for a, b in zip(first, again):
        np.testing.assert_array_equal(a, b)
    assert reads_after == reads
    assert misses_after == misses and hits_after > hits
