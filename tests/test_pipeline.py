"""Pipelined-engine unit tests: the Executor's stage plumbing and engine
selection, stats reset semantics, the host/dispatch split of the batched
simulation tiers, and the bench JSON writer.

Target-parameterized pipelined bit-exactness/determinism/mesh coverage
lives in tests/test_target_conformance.py; these tests pin the pieces the
conformance suite exercises only indirectly.
"""
import json
import types

import numpy as np
import pytest

from repro.accel import flexasr as fa
from repro.core import ila as ila_mod, ir
from repro.core.codegen import ENGINES, Executor


def _linear_program(T=8, I=32, O=16, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((T, I)).astype(np.float32)
    w = (rng.standard_normal((O, I)) * 0.1).astype(np.float32)
    b = (rng.standard_normal((O,)) * 0.1).astype(np.float32)
    expr = ir.call("fasr_linear", ir.Var("x", x.shape), ir.Var("w", w.shape),
                   ir.Var("b", b.shape))
    return expr, {"x": x, "w": w, "b": b}


# ---------------------------------------------------------------------------
# host/dispatch split (ILA + CompiledFragment)
# ---------------------------------------------------------------------------


def test_prepare_batch_run_prepared_matches_run_batch():
    """The two-phase (pack worker / dispatch thread) path is the same
    computation as the one-shot run_batch."""
    rng = np.random.default_rng(0)
    w = (rng.standard_normal((16, 32)) * 0.1).astype(np.float32)
    b = np.zeros((16,), np.float32)
    frag = fa.linear_fragment(w, b)
    datas = [fa.pack_linear_data(
        frag, rng.standard_normal((8, 32)).astype(np.float32)) for _ in range(3)]
    import jax

    ref = np.asarray(jax.vmap(fa.read_full)(frag.run_batch(datas)))
    prepared = frag.prepare_batch(datas)
    out = np.asarray(jax.vmap(fa.read_full)(frag.run_prepared(prepared)))
    np.testing.assert_array_equal(ref, out)


def test_mesh_pad_rounds_to_mesh_multiple(monkeypatch):
    """Batch bucketing under a mesh pads to a multiple of the mesh size."""
    assert ila_mod.mesh_pad(8) == 8  # no mesh: identity
    fake = types.SimpleNamespace(devices=np.zeros(3))
    monkeypatch.setattr(ila_mod, "_STREAM_MESH", fake)
    assert ila_mod.mesh_pad(8) == 9
    assert ila_mod.mesh_pad(3) == 3
    assert ila_mod.mesh_pad(1) == 3


def test_set_stream_mesh_single_device_disables(monkeypatch):
    """spec=1 can never shard: the mesh is disabled, not built trivially."""
    assert ila_mod.set_stream_mesh(1) is None
    assert ila_mod.stream_mesh() is None


# ---------------------------------------------------------------------------
# Executor stats: reset semantics and stage timers
# ---------------------------------------------------------------------------


def test_reset_stats_clears_devices_timers_and_timings():
    """reset_stats() must also zero per-device accumulated cycles/jobs and
    the per-stage timers, so post-reset stats_summary() utilization only
    reflects post-reset work (the serving path resets between warmup and
    measured requests)."""
    expr, env = _linear_program()
    ex = Executor("ila", engine="pipelined", devices_per_target=2)
    ex.run_many(expr, [env, env, env])
    assert ex.stats
    assert sum(ex.stage_seconds.values()) > 0
    devs = [d for ds in ex.devices._devices.values() for d in ds]
    assert sum(d.n_jobs for d in devs) >= 3
    ex.reset_stats()
    assert not ex.stats
    assert sum(ex.stage_seconds.values()) == 0.0
    for d in devs:
        assert d.busy_cycles == 0.0 and d.n_jobs == 0 and d.n_groups == 0
    summary = ex.stats_summary()
    for row in summary.values():
        for dev_row in row.get("devices", {}).values():
            assert dev_row["jobs"] == 0 and dev_row["est_cycles"] == 0.0
    # the warm caches survive the reset: a re-run records fresh stats
    ex.run_many(expr, [env])
    assert ex.stats
    devs_after = ex.stats_summary()["flexasr"]["devices"]
    assert sum(r["jobs"] for r in devs_after.values()) == 1


def test_pipeline_summary_reports_stage_seconds():
    expr, env = _linear_program()
    ex = Executor("ila", engine="pipelined", pipeline_chunk=2)
    ex.run_many(expr, [env] * 4)
    stages = ex.pipeline_summary()
    assert stages["pack_s"] > 0 and stages["dispatch_s"] > 0
    assert stages["groups"] >= 1
    assert stages["overlap_s"] <= stages["pack_s"]


# ---------------------------------------------------------------------------
# engine selection: one compiled execution path plus its references
# ---------------------------------------------------------------------------


def test_engines_are_the_served_path_and_its_references():
    assert ENGINES == ("pipelined", "fused", "jit", "eager")


def test_compiled_engine_is_rejected():
    with pytest.raises(ValueError, match="unknown engine 'compiled'"):
        Executor("ila", engine="compiled")


def test_default_engine_is_pipelined_and_bit_exact_vs_jit(monkeypatch):
    """An Executor built with no engine serves a bundled application on
    the pipelined engine, bit-for-bit with the jit-scan reference."""
    from repro.core import apps
    from repro.core.compile import compile_program

    monkeypatch.delenv("REPRO_ENGINE", raising=False)
    expr, params = apps.build_resmlp(seed=0)
    program = compile_program(expr).program
    xshape = next(v.shape for v in ir.postorder(program)
                  if isinstance(v, ir.Var) and v.name == "x")
    rng = np.random.default_rng(5)
    envs = [dict(params, x=rng.standard_normal(xshape).astype(np.float32))
            for _ in range(2)]
    ex = Executor("ila")
    assert ex.engine == "pipelined"
    got = ex.run_many(program, envs)
    assert ex.pipeline_summary()["groups"] >= 1  # the pack stage ran
    ref = Executor("ila", engine="jit").run_many(program, envs)
    for r, g in zip(ref, got):
        np.testing.assert_array_equal(np.asarray(r), np.asarray(g))


# ---------------------------------------------------------------------------
# bench JSON writer
# ---------------------------------------------------------------------------


def test_write_bench_json_merges_and_rewrites(tmp_path):
    import sys

    sys.path.insert(0, "benchmarks")
    try:
        from _bench_io import write_bench_json
    finally:
        sys.path.pop(0)
    path = str(tmp_path / "BENCH_cosim.json")
    write_bench_json([("a", 1.0, "one"), ("b", 2.0, "two")], path=path)
    write_bench_json([("b", 3.0, "updated")], path=path)  # merge
    data = json.load(open(path))
    assert data["schema"] == 1 and "generated_unix" in data
    assert data["rows"]["a"]["us_per_call"] == 1.0
    assert data["rows"]["b"] == {"us_per_call": 3.0, "derived": "updated"}
    write_bench_json([("c", 4.0, "only")], path=path, fresh=True)
    data = json.load(open(path))
    assert set(data["rows"]) == {"c"}
