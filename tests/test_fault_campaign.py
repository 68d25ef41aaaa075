"""Fault-injection & differential-validation campaign engine tests.

* mutant lifecycle: a full campaign leaves the process-wide registries
  (target registry, IR accel-op extension table) bit-identical — mutant
  registration/unregistration leaks nothing;
* fault-library conformance: every registered target's every Intrinsic is
  covered by >= 1 applicable fault mutator, and every co-simulated
  intrinsic by >= 1 *non-identity* mutator;
* identity control: the no-op fault mutant is bit-exact with the golden
  target across all engines, and a campaign reports zero detections for it
  (no false positives);
* the paper's thesis, quantified (the acceptance run): a campaign over
  >= 3 targets x >= 4 non-identity fault classes on the pipelined engine
  with 2 devices per target contains at least one seeded fault that
  escapes the VT2/VT3 fragment tiers but is detected by an
  application-level metric delta;
* VT2 tolerance threading: targets stamp their declared ``vt2_tol`` onto
  enumerated cases and ``validate.vt2_check`` resolves it (no hard-coded
  1e-5).
"""
import numpy as np
import pytest

from repro.core import campaign as campaign_mod, faults, ir, validate
from repro.core.codegen import Executor
from repro.core.ila import TARGETS


def _registry_snapshot():
    return (
        [(name, id(t)) for name, t in TARGETS._targets.items()],
        {op: (id(t), id(i)) for op, (t, i) in TARGETS._by_op.items()},
        {op: id(spec) for op, spec in ir._ACCEL_EXT.items()},
        set(ir.ACCEL_OPS),
    )


def _first_sampled(t):
    for intr in t.intrinsics.values():
        if intr.planner is not None and intr.sample is not None:
            return intr
    return None


def _case(t, intr, seed):
    rng = np.random.default_rng(seed)
    args, attrs = intr.sample(rng)
    vs = tuple(ir.Var(f"_{i}", a.shape) for i, a in enumerate(args))
    return (
        ir.call(intr.op, *vs, **attrs),
        {f"_{i}": a for i, a in enumerate(args)},
    )


# ---------------------------------------------------------------------------
# Fault library conformance
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("t", TARGETS.all(), ids=TARGETS.names())
def test_every_intrinsic_covered_by_applicable_mutator(t):
    """Every declared Intrinsic is covered by >= 1 applicable fault
    instance, and every co-simulated (planner-backed) intrinsic by >= 1
    non-identity instance — the campaign can stress every op of every
    backend, bundled or plugin."""
    instances = faults.fault_instances(t)
    assert instances, f"{t.name}: no applicable fault instances at all"
    covered = {}
    for inst in instances:
        for op in inst.covers(t):
            covered.setdefault(op, set()).add(inst.fault)
    for op, intr in t.intrinsics.items():
        assert op in covered, f"{t.name}:{op} covered by no fault mutator"
        if intr.planner is not None:
            assert covered[op] - {"identity"}, (
                f"{t.name}:{op} covered only by the identity control"
            )


@pytest.mark.parametrize("t", TARGETS.all(), ids=TARGETS.names())
def test_identity_fault_bit_exact_across_engines(t):
    """The no-op mutant reproduces the golden target bit-for-bit on every
    engine: cloning, planner rebinding and per-mutant fragment caches are
    semantics-free."""
    intr = _first_sampled(t)
    if intr is None:
        pytest.skip(f"{t.name} declares no sampled co-simulated intrinsic")
    expr, env = _case(t, intr, 0)
    opts = {t.name: intr.options}
    golden = np.asarray(Executor("ila", target_options=opts).run(expr, env))
    (inst,) = faults.fault_instances(t, ("identity",))
    mutant = faults.make_mutant(t, inst)
    with faults.swapped_in(mutant):
        for engine in ("pipelined", "jit", "eager"):
            got = np.asarray(
                Executor("ila", engine=engine, target_options=opts).run(expr, env)
            )
            np.testing.assert_array_equal(
                golden, got,
                err_msg=f"{t.name} identity mutant != golden ({engine})",
            )


def test_mutated_write_instruction_holds_on_every_engine():
    """A bulk-mutating fault (write-path semantics change) produces the
    SAME faulty output on the pipelined, jit and eager engines: the
    mutant planner's stream conversion keeps the fragment compiler honest
    when its slice-update lowering assumption is broken."""
    t = TARGETS.get("vecunit")
    (inst,) = faults.fault_instances(t, ("addr_swap",))
    assert inst.mutates_bulk
    intr = t.intrinsics["veu_mul"]
    expr, env = _case(t, intr, 3)
    golden = np.asarray(Executor("ila").run(expr, env))
    mutant = faults.make_mutant(t, inst)
    with faults.swapped_in(mutant):
        outs = {
            engine: np.asarray(Executor("ila", engine=engine).run(expr, env))
            for engine in ("pipelined", "jit", "eager")
        }
    assert validate.frob_rel_err(golden, outs["pipelined"]) > 0, (
        "addr_swap mutant did not perturb the output at all"
    )
    for engine, got in outs.items():
        np.testing.assert_array_equal(
            outs["pipelined"], got,
            err_msg=f"mutated write path drifted between engines ({engine})",
        )


def test_payload_fault_holds_on_every_engine():
    """A payload-transform fault (write-datapath corruption applied
    host-side, keeping the bulk fast path) produces the SAME faulty output
    on all engines: eager/jit consume the transformed full command list,
    pipelined the transformed streams through the rebound fragments."""
    t = TARGETS.get("vecunit")
    (inst,) = faults.fault_instances(t, ("round_floor",))
    assert inst.payload is not None and not inst.mutates_bulk
    intr = t.intrinsics["veu_mul"]
    expr, env = _case(t, intr, 4)
    golden = np.asarray(Executor("ila").run(expr, env))
    mutant = faults.make_mutant(t, inst)
    with faults.swapped_in(mutant):
        outs = {
            engine: np.asarray(Executor("ila", engine=engine).run(expr, env))
            for engine in ("pipelined", "jit", "eager")
        }
    assert validate.frob_rel_err(golden, outs["pipelined"]) > 0, (
        "round_floor mutant did not perturb the output at all"
    )
    for engine, got in outs.items():
        np.testing.assert_array_equal(
            outs["pipelined"], got,
            err_msg=f"payload fault drifted between engines ({engine})",
        )


# ---------------------------------------------------------------------------
# Mutant lifecycle: the registry leak check
# ---------------------------------------------------------------------------


def test_campaign_leaves_registry_bit_identical():
    """A full (apps-free) campaign over two targets and several mutants
    leaves the target registry and the IR accel-op extension table
    bit-identical: same objects, same order, same op ownership."""
    before = _registry_snapshot()
    result = campaign_mod.run_campaign(
        targets=("vecunit", "hlscnn"),
        faults=("identity", "drop_cfg", "trunc_width"),
        apps=(),                      # no app tier: lifecycle-only campaign
        engine="pipelined", devices_per_target=1,
        op_samples=1, vt2_n=2,
    )
    assert len(result.reports) == 6
    assert _registry_snapshot() == before, (
        "campaign leaked registry state (targets, op ownership, or IR "
        "accel-op extension specs changed)"
    )


def test_serial_crash_isolated_and_registry_clean():
    """A mutant that raises mid-ladder (the crash_inject diagnostic fault)
    is recorded as outcome 'crash' with its partial tiers kept; the
    campaign completes every other mutant and the registries come back
    bit-identical (the swapped_in exception path restores everything)."""
    before = _registry_snapshot()
    result = campaign_mod.run_campaign(
        targets=("vecunit",),
        faults=("identity", "drop_cfg", "crash_inject"),
        apps=(), engine="pipelined", devices_per_target=1,
        op_samples=1, vt2_n=2, stat_calib_seeds=0,
    )
    assert _registry_snapshot() == before
    by_fault = {r.fault: r for r in result.reports}
    crash = by_fault["crash_inject"]
    assert crash.outcome == "crash" and crash.detected_at == "crash"
    assert "crash_inject" in crash.error
    assert "vt2" in crash.tiers, "partial tier results were dropped"
    assert by_fault["identity"].outcome == "ok"
    assert by_fault["identity"].detected_at is None
    assert by_fault["drop_cfg"].outcome == "ok"
    assert by_fault["drop_cfg"].detected_at is not None


def test_mutant_raising_inside_app_tier_leaves_registry_clean(monkeypatch):
    """Extends the leak check to the app tier: an application evaluation
    that raises ONLY while a mutant is swapped in (golden prep succeeds)
    must be crash-isolated with the registries restored — the failure
    happens deepest in the ladder, inside the swapped_in window."""
    golden = TARGETS.get("vecunit")

    def fake_prepare(name, n_eval, train_steps, seed):
        def per_example(ex, idx):
            if TARGETS.get("vecunit") is not golden:
                raise RuntimeError("app evaluation blew up on the mutant")
            n = len(list(idx))
            return campaign_mod.PerExample(
                np.zeros((n, 4), np.float64), np.zeros(n, np.float64), 1.0)

        return campaign_mod._App(
            name, "acc", None, {"vecunit": 1}, pool=128,
            per_example=per_example)

    monkeypatch.setattr(campaign_mod, "_prepare_app", fake_prepare)
    before = _registry_snapshot()
    result = campaign_mod.run_campaign(
        targets=("vecunit",), faults=("identity", "drop_cfg"),
        apps=("resmlp",), engine="pipelined", devices_per_target=1,
        op_samples=1, vt2_n=2, stat_calib_seeds=0, ladder="full",
    )
    assert _registry_snapshot() == before, (
        "app-tier crash leaked registry state"
    )
    for r in result.reports:
        assert r.outcome == "crash" and "blew up" in r.error
        # the ladder got as far as the app tier before dying
        assert "op_diff" in r.tiers and "app" not in r.tiers


def test_swap_restores_exact_objects_even_on_error():
    t = TARGETS.get("vecunit")
    before = _registry_snapshot()
    (inst,) = faults.fault_instances(t, ("identity",))
    with pytest.raises(RuntimeError):
        with faults.swapped_in(faults.make_mutant(t, inst)):
            assert TARGETS.get("vecunit") is not t
            raise RuntimeError("boom")
    assert _registry_snapshot() == before


def test_failed_swap_in_leaves_registries_untouched():
    """If the registry swap itself is rejected (e.g. the golden target was
    unregistered meanwhile), NOTHING may change — in particular the IR
    accel-op extension table must not keep mutant specs."""
    from repro.accel.target import register_target, unregister_target

    t = TARGETS.get("vecunit")
    (inst,) = faults.fault_instances(t, ("identity",))
    mutant = faults.make_mutant(t, inst)
    removed_specs = unregister_target(t)
    try:
        before = _registry_snapshot()
        with pytest.raises(KeyError):
            with faults.swapped_in(mutant):
                pass  # pragma: no cover
        assert _registry_snapshot() == before
    finally:
        # vecunit is the last-registered bundled target, so re-registering
        # restores the original order; the displaced spec objects restore
        # the extension table exactly
        register_target(t)
        for op, spec in removed_specs.items():
            ir.restore_accel_op(op, spec)


# ---------------------------------------------------------------------------
# VT2 tolerance threading
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("t", TARGETS.all(), ids=TARGETS.names())
def test_vt2_cases_carry_declared_tolerance(t):
    cases = t.vt2_cases(8, 32)
    for case in cases:
        assert case.tol is not None, f"{t.name}:{case.name} tol not stamped"
        assert case.tol == t.vt2_tol
        # the declared bound must actually hold (threading a tighter
        # tolerance than the historical 1e-5 is only honest if it passes)
        assert validate.vt2_check(case, n=3), (
            f"{t.name}:{case.name} fails at its declared vt2_tol={case.tol}"
        )


def test_vt2_check_explicit_tol_still_overrides():
    t = TARGETS.get("vecunit")
    cases = t.vt2_cases(4, 16)
    assert cases and validate.vt2_check(cases[0], n=2, tol=1e-3)


# ---------------------------------------------------------------------------
# The acceptance campaign: >= 3 targets x >= 4 fault classes, pipelined,
# 2 devices/target, with an application-level-only escape
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def acceptance_campaign():
    return campaign_mod.run_campaign(
        targets=("flexasr", "hlscnn", "vecunit"),
        faults=("identity", "trunc_width", "sat_wrap", "round_floor",
                "drop_cfg", "stale_state"),
        apps=("resmlp",),
        engine="pipelined",
        devices_per_target=2,
        ladder="escalate",
        n_eval=24,
        train_steps=60,
        op_samples=1,
        vt2_n=2,
    )


def test_campaign_runs_at_scale_pipelined_multidevice(acceptance_campaign):
    r = acceptance_campaign
    assert r.config["engine"] == "pipelined"
    assert r.config["devices_per_target"] == 2
    assert len(r.config["targets"]) >= 3
    classes = {m.fault for m in r.reports} - {"identity"}
    assert len(classes) >= 4, f"only fault classes {classes}"
    assert r.mutants_per_sec > 0
    # gross faults are caught before the application tier
    caught_early = [
        m for m in r.reports
        if m.detected_at in ("vt2", "frag_sim", "op_diff")
    ]
    assert caught_early, "no fault caught by any fragment/op tier"


def test_identity_mutants_show_zero_detections(acceptance_campaign):
    ids = [m for m in acceptance_campaign.reports if m.fault == "identity"]
    assert len(ids) == 3
    for m in ids:
        assert m.detected_at is None, (
            f"identity mutant {m.key} falsely detected at {m.detected_at}: "
            f"{ {n: t.detail for n, t in m.tiers.items()} }"
        )


def test_some_fault_escapes_fragments_but_app_level_catches_it(
    acceptance_campaign,
):
    """The paper's application-level-validation result, reproduced as a
    measurement: at least one seeded fault passes the VT2 abstract checks
    AND the co-simulated fragment checks AND the per-op differential test,
    yet moves an end-to-end application metric past the campaign
    threshold."""
    escapees = [m for m in acceptance_campaign.reports if m.app_only]
    assert escapees, (
        "no fault escaped the fragment tiers while being caught at "
        "application level; matrix:\n"
        + campaign_mod.format_matrix(acceptance_campaign)
    )
    for m in escapees:
        assert m.escaped_fragment_checks
        assert m.tiers["app"].detected


# ---------------------------------------------------------------------------
# Range-directed op-tier sampling (op_boundary): closing the sat_wrap escape
# ---------------------------------------------------------------------------


def test_boundary_sampling_makes_sat_wrap_op_tier_detectable():
    """sat_wrap only corrupts activations beyond FlexASR's saturation
    boundary, which uniform standard-normal op-tier operands essentially
    never reach — that is exactly why it is the acceptance campaign's
    application-level-only escape. op_boundary > 0 appends operands from
    ilalint.boundary_inputs (straddling the statically computed boundary)
    to the per-op differential pool and must flip the op tier from miss to
    detect; the default (0) keeps the uniform-only pool so the escape
    phenomenon above stays reproducible."""
    base = dict(
        targets=("flexasr",), faults=("sat_wrap",), apps=(),
        engine="pipelined", devices_per_target=1,
        op_samples=1, vt2_n=2, stat_calib_seeds=0, ladder="full",
    )
    miss = campaign_mod.run_campaign(**base)
    hit = campaign_mod.run_campaign(op_boundary=2, **base)
    assert miss.config["op_boundary"] == 0
    assert hit.config["op_boundary"] == 2
    assert miss.reports and len(miss.reports) == len(hit.reports)
    for m in miss.reports:
        assert m.tiers["op_diff"].detected is False, (
            f"{m.key}: uniform op-tier samples unexpectedly reach the "
            "saturation boundary — the app_only escape test is now vacuous"
        )
    for m in hit.reports:
        assert m.tiers["op_diff"].detected is True, (
            f"{m.key}: boundary-directed samples did not expose sat_wrap "
            f"at the op tier ({m.tiers['op_diff'].detail})"
        )


def test_sharded_runner_refuses_workers_on_a_tpu_backend(monkeypatch):
    """One process per chip: on a TPU backend the sharded runner refuses
    before it spawns anything, naming the rule."""
    import multiprocessing

    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    spawned = []
    monkeypatch.setattr(multiprocessing, "get_context",
                        lambda *a, **k: spawned.append(a))
    assert not campaign_mod.workers_allowed()
    with pytest.raises(RuntimeError, match="one process per chip"):
        campaign_mod.run_campaign_sharded(
            workers=1, targets=("vecunit",), faults=("identity",), apps=())
    assert not spawned
