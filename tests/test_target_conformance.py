"""Registry conformance suite: every registered AcceleratorTarget, zero
bespoke per-backend tests.

Parameterized over **all** targets in ``repro.core.ila.TARGETS`` and every
intrinsic they declare (via each intrinsic's ``sample`` generator, which
draws random operands within the target's declared capability limits):

* ideal-vs-numerics (VT1-style): the ILA co-simulation of each intrinsic
  tracks the fp32 IR-interpreter oracle within the intrinsic's declared
  tolerance;
* engine parity: eager per-command simulation == jit scan == pipelined
  fragment fast path == batched ``run_many``, bit-for-bit;
* rewrite soundness: each target-declared VT2 fragment pair agrees under
  ideal semantics, and compiling the compiler-IR side against that target
  alone extracts the intrinsic while preserving interpretation;
* coverage: every registered target receives >= 1 offload from at least one
  of the stock applications under a default (all-targets) compile;
* cost conformance: every registered target declares a CostModel pricing
  every intrinsic it claims; costs are positive and monotone in batch size;
  calibration fits predicted command counts to the Executor's observations;
* selection policy: when two targets claim one op, the default policy picks
  the target whose CostModel is cheaper and ``forbid``/``prefer`` flip the
  mapping (checked with synthetic competing targets, registered and
  unregistered inside the test — no bundled backend is named);
* multi-device scheduling: with ``devices_per_target=2`` results stay
  bit-exact and ``stats_summary`` reports per-device utilization;
* pipelined engine: chunked pack/sim pipelining is bit-exact vs the
  one-device, default-chunk run for every target and device count,
  deterministic across runs
  (identical results AND stable assemble/stat order), and the mesh-sharded
  batch tier matches unsharded execution (skipped on single-device hosts —
  CI forces 4 virtual devices with XLA_FLAGS).

* fused engine: ``engine="fused"`` matches the pipelined engine within each
  intrinsic's declared tolerance for every target and device count —
  targets registering fused runners (``declare_fused``) take the fast
  path, everything else falls back to per-group fragment-compiler
  execution — and
  the forced XLA-fallback lowering (``REPRO_FUSED_FALLBACK=1``) is
  exercised explicitly so the ``jnp`` leg stays covered even on hosts
  where Pallas lowers natively.

Set ``REPRO_DEVICES_PER_TARGET=2`` (as CI does in a dedicated step) to run
the *whole* suite through the multi-device scheduler path, and/or
``REPRO_ENGINE=pipelined`` / ``REPRO_ENGINE=fused`` (every Executor
constructed without an explicit engine — including the ones inside
cosim/serving helpers — picks it up) to run it through the async pipeline
or the fused fast path.

A new backend that registers through ``repro.accel.target`` is covered here
automatically — this file never names a target.
"""
import os

import numpy as np
import pytest

from repro.core import apps, ir, validate
from repro.core.codegen import Executor
from repro.core.compile import SelectionPolicy, compile_program
from repro.core.ila import ILA, TARGETS

_DEVICES = int(os.environ.get("REPRO_DEVICES_PER_TARGET", "1"))


def _intrinsic_params():
    out = []
    for t in TARGETS.all():
        for op, intr in t.intrinsics.items():
            if intr.sample is not None:
                out.append(pytest.param(t, intr, id=f"{t.name}:{op}"))
    return out


def _case(t, intr, seed):
    rng = np.random.default_rng(seed)
    args, attrs = intr.sample(rng)
    vs = tuple(ir.Var(f"_{i}", a.shape) for i, a in enumerate(args))
    expr = ir.call(intr.op, *vs, **attrs)
    env = {f"_{i}": a for i, a in enumerate(args)}
    return expr, env


def _executor(t, intr, **kw):
    kw.setdefault("devices_per_target", _DEVICES)
    return Executor("ila", target_options={t.name: intr.options}, **kw)


@pytest.mark.parametrize("t,intr", _intrinsic_params())
def test_ideal_vs_numerics_within_declared_tol(t, intr):
    """Custom-numerics co-simulation tracks the fp32 oracle (VT1-style)."""
    for seed in (0, 1):
        expr, env = _case(t, intr, seed)
        ideal = np.asarray(Executor("ideal").run(expr, env))
        got = np.asarray(_executor(t, intr).run(expr, env))
        assert got.shape == ideal.shape
        err = validate.frob_rel_err(ideal, got)
        assert err <= intr.tol, f"{t.name}:{intr.op} rel err {err} > tol {intr.tol}"


@pytest.mark.parametrize("t,intr", _intrinsic_params())
def test_engines_bit_exact(t, intr):
    """eager per-command == jit scan == pipelined fast path == run_many."""
    expr, env = _case(t, intr, 2)
    _, env2 = _case(t, intr, 3)
    out_j = np.asarray(_executor(t, intr, engine="jit").run(expr, env))
    out_e = np.asarray(_executor(t, intr, engine="eager").run(expr, env))
    out_p = np.asarray(_executor(t, intr, engine="pipelined").run(expr, env))
    np.testing.assert_array_equal(out_p, out_j, err_msg=f"{t.name}:{intr.op} pipelined != jit")
    np.testing.assert_array_equal(out_p, out_e, err_msg=f"{t.name}:{intr.op} pipelined != eager")
    # batched path: same env twice through one vmapped call per node
    outs_m = _executor(t, intr, engine="pipelined").run_many(expr, [env, env])
    for o in outs_m:
        np.testing.assert_array_equal(
            out_p, np.asarray(o), err_msg=f"{t.name}:{intr.op} run_many != run"
        )
    # a second distinct sample keeps its own numerics when batched
    ref2 = np.asarray(_executor(t, intr).run(expr, env2))
    outs_m2 = _executor(t, intr).run_many(expr, [env, env2])
    np.testing.assert_array_equal(ref2, np.asarray(outs_m2[1]))


@pytest.mark.parametrize("mode", ("auto", "fallback"))
@pytest.mark.parametrize("ndev", (1, 2), ids=("1dev", "2dev"))
@pytest.mark.parametrize("t,intr", _intrinsic_params())
def test_fused_parity_within_declared_tol(t, intr, ndev, mode, monkeypatch):
    """engine="fused" tracks the pipelined engine at one device within the
    intrinsic's declared tolerance (bit-exact where the fused numerics
    round-trip the fragment-compiler arithmetic — asserted per-target in
    test_fused.py), through
    run and run_many, across device counts. ``mode=fallback`` forces the
    XLA-fused lowering (``REPRO_FUSED_FALLBACK=1``) so the ``jnp`` leg is
    covered even on hosts where Pallas lowers natively; targets without a
    registered fused runner execute per-group on the fragment-compiler tier
    and must stay bit-exact either way."""
    if intr.planner is None:
        pytest.skip("pass-through intrinsic: nothing to fuse")
    if mode == "fallback":
        monkeypatch.setenv("REPRO_FUSED_FALLBACK", "1")
    expr, env = _case(t, intr, 2)
    _, env2 = _case(t, intr, 3)
    ref = np.asarray(_executor(t, intr, engine="pipelined",
                               devices_per_target=1).run(expr, env))
    got = np.asarray(
        _executor(t, intr, engine="fused", devices_per_target=ndev).run(expr, env)
    )
    assert got.shape == ref.shape
    err = validate.frob_rel_err(ref, got)
    assert err <= intr.tol, (
        f"{t.name}:{intr.op} fused-vs-pipelined rel err {err} > tol {intr.tol}"
    )
    # batched parity through run_many (the vmapped/fused-dispatch path)
    refs = _executor(t, intr, engine="pipelined",
                     devices_per_target=1).run_many(expr, [env, env2, env])
    outs = _executor(t, intr, engine="fused",
                     devices_per_target=ndev).run_many(expr, [env, env2, env])
    for r, o in zip(refs, outs):
        e = validate.frob_rel_err(np.asarray(r), np.asarray(o))
        assert e <= intr.tol, (
            f"{t.name}:{intr.op} fused run_many rel err {e} > tol {intr.tol}"
        )


def _vt2_params():
    out = []
    for t in TARGETS.all():
        for case in t.vt2_cases(8, 32):
            out.append(pytest.param(t, case, id=f"{t.name}:{case.name}"))
    return out


@pytest.mark.parametrize("t,case", _vt2_params())
def test_rewrite_soundness_vt2_and_extraction(t, case):
    """VT2 over abstract types + interpret-before/after compile equality."""
    assert validate.vt2_check(case, n=5)
    res = compile_program(case.ir_fragment, targets=(t.name,), flexible=True)
    assert res.accelerator_calls.get(t.name, 0) >= 1, (
        f"{t.name}:{case.name} did not extract an intrinsic"
    )
    rng = np.random.default_rng(0)
    env = {k: rng.standard_normal(s).astype(np.float32)
           for k, s in case.var_shapes.items()}
    np.testing.assert_allclose(
        np.asarray(ir.interpret(case.ir_fragment, env)),
        np.asarray(ir.interpret(res.program, env)),
        rtol=1e-5, atol=1e-5,
    )


@pytest.fixture(scope="module")
def app_offloads():
    out = {}
    for name, (builder, _) in apps.APPLICATIONS.items():
        expr, _params = builder()
        out[name] = compile_program(expr).accelerator_calls
    return out


@pytest.mark.parametrize("tname", TARGETS.names())
def test_every_target_offloaded_by_some_app(app_offloads, tname):
    """Default (all-targets) compiles exercise every registered backend —
    a new target starts receiving offloads with zero compiler edits."""
    hits = {app: calls.get(tname, 0) for app, calls in app_offloads.items()}
    assert any(n >= 1 for n in hits.values()), f"{tname} never offloaded: {hits}"


# ---------------------------------------------------------------------------
# CostModel conformance
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("t", TARGETS.all(), ids=TARGETS.names())
def test_cost_model_prices_every_claimed_intrinsic(t):
    """Every registered target declares a CostModel covering every intrinsic
    it claims, and pricing realistic operands yields positive cycles.
    Sampled intrinsics price their sample's shapes; pass-through markers
    (no sample) price a generic tensor."""
    assert t.cost_model is not None, f"{t.name} declares no CostModel"
    rng = np.random.default_rng(0)
    for op, intr in t.intrinsics.items():
        assert t.cost_model.covers(op), f"{t.name} does not price {op!r}"
        if intr.sample is not None:
            args, attrs = intr.sample(rng)
            shapes = [np.shape(a) for a in args]
        else:
            shapes, attrs = [(8, 8)], {}
        est = t.cost_model.estimate(op, attrs, shapes)
        assert est.cycles > 0, f"{t.name}:{op} non-positive cycles {est}"
        assert est.commands >= 0 and est.bytes_moved >= 0


@pytest.mark.parametrize("t,intr", _intrinsic_params())
def test_cost_monotone_in_batch_size(t, intr):
    """Scaling the data operand's leading dimension (the batch/row axis for
    every declared intrinsic) must strictly increase estimated cycles and
    never decrease commands or bytes."""
    rng = np.random.default_rng(0)
    args, attrs = intr.sample(rng)
    shapes = [np.shape(a) for a in args]

    def scaled(k):
        # scale the data operand's leading dim; elementwise mates (operands
        # sharing the data operand's full shape) scale with it so the op
        # stays broadcast-legal
        out = [
            ((s[0] * k,) + tuple(s[1:]))
            if (i == 0 or tuple(s) == tuple(shapes[0])) else tuple(s)
            for i, s in enumerate(shapes)
        ]
        return out

    e1 = t.cost_model.estimate(intr.op, attrs, scaled(1))
    e4 = t.cost_model.estimate(intr.op, attrs, scaled(4))
    assert e1.cycles > 0 and e1.commands > 0
    assert e4.cycles > e1.cycles, f"{t.name}:{intr.op} cycles not monotone"
    assert e4.commands >= e1.commands and e4.bytes_moved >= e1.bytes_moved


@pytest.mark.parametrize("t,intr", _intrinsic_params())
def test_calibration_fits_observed_commands(t, intr):
    """CostModel.calibrate fits per-op command scales so predictions match
    the interface command counts the Executor actually observed."""
    expr, env = _case(t, intr, 5)
    ex = _executor(t, intr)
    ex.run(expr, env)
    observed = sum(s.n_commands for s in ex.stats if s.op == intr.op)
    if observed == 0:
        pytest.skip("intrinsic records no commands")
    saved = dict(t.cost_model.command_scale)
    try:
        ex.calibrate_cost_models()
        shapes = [np.shape(env[f"_{i}"]) for i in range(len(env))]
        attrs = dict(expr.attrs)
        refit = t.cost_model.estimate(intr.op, attrs, shapes)
        assert refit.commands == pytest.approx(observed, rel=1e-6), (
            f"{t.name}:{intr.op} calibrated commands {refit.commands} "
            f"!= observed {observed}"
        )
    finally:
        t.cost_model.command_scale.clear()
        t.cost_model.command_scale.update(saved)


# ---------------------------------------------------------------------------
# Selection policy: two targets claim one op
# ---------------------------------------------------------------------------


@pytest.fixture
def competing_targets():
    """Two synthetic targets both claiming the (otherwise unclaimed) host op
    ``maximum``, with cost models an order of magnitude apart. Registered
    for the duration of the test only — the registry is restored after."""
    from repro.accel.target import (
        AcceleratorTarget, CostModel, Intrinsic, register_target,
        unregister_target,
    )
    from repro.core.egraph import P, Rewrite, V as PV

    def build(name, op, cycles_per_elem):
        target = AcceleratorTarget(name, ILA(name))
        target.add_intrinsic(Intrinsic(
            op,
            shape=lambda attrs, cs: tuple(np.broadcast_shapes(cs[0], cs[1])),
            ideal=lambda attrs, a: np.maximum(a[0], a[1]),
        ))
        costs = CostModel(name)

        def price(attrs, shapes, c=cycles_per_elem):
            n = int(np.prod(np.broadcast_shapes(*shapes)))
            return 2 * n // 16 + 1, 12 * n, c * n

        costs.op(op)(price)
        target.add_cost_model(costs)
        target.add_rewrites(lambda op=op: [
            Rewrite(f"{name}-max", P("maximum", PV("a"), PV("b")),
                    P(op, PV("a"), PV("b")))
        ])
        return register_target(target)

    cheap = build("t_cheap", "tcheap_max", 1.0)
    pricey = build("t_pricey", "tpricey_max", 50.0)
    try:
        yield cheap, pricey
    finally:
        unregister_target(cheap)
        unregister_target(pricey)


def test_policy_picks_cheaper_target_and_overrides_flip(competing_targets):
    """Two targets claim one op: the default (cheapest) policy selects the
    target whose CostModel predicts fewer cycles; ``forbid`` removes it and
    flips the mapping; ``prefer`` overrides the cost ranking."""
    cheap, pricey = competing_targets
    a, b = ir.Var("a", (8, 8)), ir.Var("b", (8, 8))
    prog = ir.call("maximum", a, b)
    names = (cheap.name, pricey.name)

    res = compile_program(prog, targets=names)
    assert res.accelerator_calls[cheap.name] == 1
    assert res.accelerator_calls[pricey.name] == 0

    res = compile_program(prog, targets=names,
                          policy=SelectionPolicy(forbid=(cheap.name,)))
    assert res.accelerator_calls[cheap.name] == 0
    assert res.accelerator_calls[pricey.name] == 1

    res = compile_program(prog, targets=names,
                          policy=SelectionPolicy(prefer=(pricey.name,)))
    assert res.accelerator_calls[cheap.name] == 0
    assert res.accelerator_calls[pricey.name] == 1


# ---------------------------------------------------------------------------
# Multi-device scheduling
# ---------------------------------------------------------------------------


def _first_sampled_intrinsic(t):
    for intr in t.intrinsics.values():
        if intr.sample is not None and intr.planner is not None:
            return intr
    return None


@pytest.mark.parametrize("t", TARGETS.all(), ids=TARGETS.names())
def test_multi_device_bit_exact_and_utilization_reported(t):
    """devices_per_target=2: scheduled execution stays bit-identical to the
    single-device run, and stats_summary grows per-device rows with
    estimated cycles and utilization."""
    intr = _first_sampled_intrinsic(t)
    if intr is None:
        pytest.skip(f"{t.name} declares no runnable sampled intrinsic")
    expr, env = _case(t, intr, 7)
    _, env2 = _case(t, intr, 8)
    ex1 = _executor(t, intr, devices_per_target=1)
    ex2 = _executor(t, intr, devices_per_target=2)
    outs1 = ex1.run_many(expr, [env, env2, env])
    outs2 = ex2.run_many(expr, [env, env2, env])
    for o1, o2 in zip(outs1, outs2):
        np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))
    summary = ex2.stats_summary()[t.name]
    assert summary["est_cycles"] > 0
    devs = summary["devices"]
    assert len(devs) == 2
    for row in devs.values():
        assert {"jobs", "groups", "est_cycles", "utilization"} <= set(row)
    assert any(r["utilization"] == 1.0 for r in devs.values())
    assert sum(r["jobs"] for r in devs.values()) >= 3


# ---------------------------------------------------------------------------
# Pipelined engine: bit-exactness, determinism, mesh sharding
# ---------------------------------------------------------------------------


def _pipelined_envs(t, intr, n=5):
    """n environments (two distinct samples interleaved) for one intrinsic,
    plus reference outputs from the pipelined engine at one device and its
    default chunk."""
    expr, env = _case(t, intr, 11)
    _, env2 = _case(t, intr, 12)
    envs = [env, env2, env, env2, env][:n]
    ref = _executor(t, intr, engine="pipelined",
                    devices_per_target=1).run_many(expr, envs)
    return expr, envs, ref


@pytest.mark.parametrize("ndev", (1, 2), ids=("1dev", "2dev"))
@pytest.mark.parametrize("t,intr", _intrinsic_params())
def test_pipelined_bit_exact_across_device_counts(t, intr, ndev):
    """engine="pipelined" with pipeline_chunk=2 (several pack/sim pipeline
    stages) matches the one-device, default-chunk reference bit-for-bit
    through run_many, for every registered target and device count
    (chunked planning + async dispatch + LPT scheduling must not change
    results)."""
    if intr.planner is None:
        pytest.skip("pass-through intrinsic: nothing to pipeline")
    expr, envs, ref = _pipelined_envs(t, intr)
    ex = _executor(t, intr, engine="pipelined", devices_per_target=ndev,
                   pipeline_chunk=2)
    outs = ex.run_many(expr, envs)
    for r, o in zip(ref, outs):
        np.testing.assert_array_equal(
            np.asarray(r), np.asarray(o),
            err_msg=f"{t.name}:{intr.op} pipelined != reference ({ndev} devices)",
        )


@pytest.mark.parametrize("t,intr", _intrinsic_params())
def test_pipelined_deterministic_and_stable_order(t, intr):
    """Two pipelined runs produce identical results AND identical stat
    sequences: materialization and stat recording follow submission order
    at the assemble barrier, never pack-worker timing."""
    if intr.planner is None:
        pytest.skip("pass-through intrinsic: nothing to pipeline")
    expr, envs, _ = _pipelined_envs(t, intr)
    ex1 = _executor(t, intr, engine="pipelined", pipeline_chunk=2)
    ex2 = _executor(t, intr, engine="pipelined", pipeline_chunk=2)
    outs1 = ex1.run_many(expr, envs)
    outs2 = ex2.run_many(expr, envs)
    for a, b in zip(outs1, outs2):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    trace1 = [(s.op, s.backend, s.n_commands) for s in ex1.stats]
    trace2 = [(s.op, s.backend, s.n_commands) for s in ex2.stats]
    assert trace1 == trace2 and trace1, (
        f"{t.name}:{intr.op} pipelined stat order is not stable"
    )


@pytest.mark.parametrize("t,intr", _intrinsic_params())
def test_mesh_sharded_batch_parity(t, intr):
    """run_data_batch/simulate_batch with the batch axis sharded over a
    host-device Mesh is bit-exact vs unsharded execution. Skips gracefully
    on single-device hosts; CI runs it with
    XLA_FLAGS=--xla_force_host_platform_device_count=4."""
    import jax

    from repro.core import ila as ila_mod

    if len(jax.devices()) < 2:
        pytest.skip("single-device host: stream mesh disabled")
    if intr.planner is None:
        pytest.skip("pass-through intrinsic: nothing to batch")
    expr, envs, ref = _pipelined_envs(t, intr)
    mesh = ila_mod.set_stream_mesh("auto")
    assert mesh is not None
    try:
        outs = _executor(t, intr, engine="pipelined",
                         pipeline_chunk=2).run_many(expr, envs)
        outs_p = _executor(t, intr, engine="pipelined").run_many(expr, envs)
        outs_f = _executor(t, intr, engine="fused").run_many(expr, envs)
    finally:
        ila_mod.set_stream_mesh(None)
    for r, o, p, f in zip(ref, outs, outs_p, outs_f):
        np.testing.assert_array_equal(
            np.asarray(r), np.asarray(o),
            err_msg=f"{t.name}:{intr.op} mesh-sharded batch != unsharded",
        )
        np.testing.assert_array_equal(
            np.asarray(r), np.asarray(p),
            err_msg=f"{t.name}:{intr.op} mesh+pipelined != unsharded",
        )
        err = validate.frob_rel_err(np.asarray(r), np.asarray(f))
        assert err <= intr.tol, (
            f"{t.name}:{intr.op} mesh+fused rel err {err} > tol {intr.tol}"
        )
