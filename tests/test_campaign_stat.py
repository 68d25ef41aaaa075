"""The calibrated statistical app tier (`campaign.paired_stats` et al.).

* statistic properties: the paired shift is exactly zero for identical
  outputs, scales with systematic bias, and `bias_t` separates a
  systematic loss shift from symmetric noise;
* seeded evaluation-subset sampling (`campaign._subset`) is
  deterministic, tag- and seed-sensitive, and in-range;
* the false-positive budget holds on identity mutants for EVERY
  registered target over >= 5 seeds: the identity null shift is exactly
  0.0 (the whole stack is deterministic), so the calibrated threshold
  `max(stat_floor, 2 x max null)` admits zero false positives by
  measurement;
* the acceptance result: `round_floor` on FlexASR — previously an
  all-tier escape — is detected by the statistical tier on ResMLP while
  the identity mutant stays undetected at every tier, with zero
  calibration false positives over 5 seeds.
"""
import numpy as np
import pytest

from repro.core import campaign as campaign_mod, faults, ir
from repro.core.codegen import Executor
from repro.core.ila import TARGETS


# ---------------------------------------------------------------------------
# Statistic properties (pure, no simulation)
# ---------------------------------------------------------------------------


def _pe(outputs, losses=None, metric=0.0):
    outputs = np.asarray(outputs, np.float64)
    if losses is None:
        losses = np.zeros(len(outputs))
    return campaign_mod.PerExample(outputs, np.asarray(losses, np.float64),
                                   metric)


def test_paired_shift_is_exactly_zero_for_identical_outputs():
    rng = np.random.default_rng(0)
    o = rng.standard_normal((16, 10))
    loss = rng.standard_normal(16)
    s = campaign_mod.paired_stats(_pe(o, loss), _pe(o.copy(), loss.copy()))
    assert s["shift"] == 0.0
    assert s["bias_t"] == 0.0
    assert s["mean_loss_delta"] == 0.0


def test_paired_shift_scales_with_systematic_bias():
    rng = np.random.default_rng(1)
    o = rng.standard_normal((32, 8))
    # a 1% relative displacement per example -> shift ~= 0.01
    s = campaign_mod.paired_stats(_pe(o), _pe(o * 1.01))
    assert s["shift"] == pytest.approx(0.01, rel=1e-9)
    assert s["shift"] > 1e-3  # above the default stat_floor


def test_bias_t_separates_systematic_shift_from_symmetric_noise():
    rng = np.random.default_rng(2)
    o = rng.standard_normal((64, 4))
    gold_loss = rng.standard_normal(64)
    sym = rng.standard_normal(64) * 0.1          # mean ~ 0: symmetric noise
    sym -= sym.mean()
    systematic = campaign_mod.paired_stats(
        _pe(o, gold_loss), _pe(o * 1.001, gold_loss + 0.05))
    noisy = campaign_mod.paired_stats(
        _pe(o, gold_loss), _pe(o * 1.001, gold_loss + sym))
    assert systematic["bias_t"] > 100 * noisy["bias_t"]
    assert systematic["mean_loss_delta"] == pytest.approx(0.05)


def test_subset_deterministic_tag_and_seed_sensitive():
    a = campaign_mod._subset(128, 24, "eval:resmlp", 0)
    assert a == campaign_mod._subset(128, 24, "eval:resmlp", 0)
    assert len(a) == 24 == len(set(a))
    assert all(0 <= i < 128 for i in a)
    assert a == tuple(sorted(a))
    assert a != campaign_mod._subset(128, 24, "calib:resmlp:0", 0)
    assert a != campaign_mod._subset(128, 24, "eval:resmlp", 1)
    # a pool smaller than n: every row, no repetition
    assert campaign_mod._subset(8, 24, "x", 0) == tuple(range(8))


def test_seed_is_part_of_the_config_fingerprint():
    base = campaign_mod._resolve_config(targets=("vecunit",), seed=0)
    other = campaign_mod._resolve_config(targets=("vecunit",), seed=1)
    assert campaign_mod.config_fingerprint(base) != \
        campaign_mod.config_fingerprint(other)
    # runner knobs are NOT part of it (resume across worker counts)
    assert campaign_mod.config_fingerprint(dict(base, workers=7)) == \
        campaign_mod.config_fingerprint(base)


# ---------------------------------------------------------------------------
# FP budget on identity mutants, every registered target, >= 5 seeds
# ---------------------------------------------------------------------------


def _first_sampled(t):
    for intr in t.intrinsics.values():
        if intr.planner is not None and intr.sample is not None:
            return intr
    return None


@pytest.mark.parametrize("t", TARGETS.all(), ids=TARGETS.names())
def test_identity_fp_budget_holds_over_five_seeds(t):
    """Per-target FP-budget property: the identity mutant's paired shift
    against the golden target is exactly 0.0 on every seeded operand draw,
    so the calibrated threshold max(stat_floor, 2 x max null) == stat_floor
    and the measured false-positive count is zero."""
    intr = _first_sampled(t)
    if intr is None:
        pytest.skip(f"{t.name} declares no sampled co-simulated intrinsic")
    opts = {t.name: intr.options}
    cases = []
    for seed in range(5):
        rng = np.random.default_rng(seed)
        args, attrs = intr.sample(rng)
        vs = tuple(ir.Var(f"_{i}", a.shape) for i, a in enumerate(args))
        expr = ir.call(intr.op, *vs, **attrs)
        env = {f"_{i}": a for i, a in enumerate(args)}
        gold = np.asarray(
            Executor("ila", target_options=opts).run(expr, env), np.float64)
        cases.append((expr, env, gold))
    (inst,) = faults.fault_instances(t, ("identity",))
    mutant = faults.make_mutant(t, inst)
    nulls = []
    with faults.swapped_in(mutant):
        ex = Executor("ila", target_options=opts)
        for expr, env, gold in cases:
            got = np.asarray(ex.run(expr, env), np.float64)
            s = campaign_mod.paired_stats(
                _pe(gold.reshape(1, -1)), _pe(got.reshape(1, -1)))
            nulls.append(s["shift"])
    assert nulls == [0.0] * 5, f"{t.name}: identity nulls nonzero: {nulls}"
    stat_floor = 1e-3
    threshold = max(stat_floor, 2.0 * max(nulls))
    assert threshold == stat_floor
    assert sum(1 for v in nulls if v > threshold) == 0


# ---------------------------------------------------------------------------
# The acceptance result: round_floor caught by the statistical tier
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def stat_campaign():
    return campaign_mod.run_campaign(
        targets=("flexasr",),
        faults=("identity", "round_floor"),
        apps=("resmlp",),
        engine="pipelined",
        devices_per_target=2,
        ladder="full",
        n_eval=24,
        train_steps=60,
        op_samples=1,
        vt2_n=2,
        seed=0,
        stat_floor=1e-3,
        stat_calib_seeds=5,
    )


def test_round_floor_detected_by_statistical_tier(stat_campaign):
    """The PR 6 headline: round_floor on FlexASR escaped every tier in
    PR 5; the paired per-example statistic catches it with a wide margin
    over the calibrated threshold."""
    (rf,) = [m for m in stat_campaign.reports if m.fault == "round_floor"]
    assert rf.outcome == "ok"
    stat = rf.tiers["stat"]
    assert stat.detected is True, (
        f"round_floor escaped the statistical tier: {stat.detail}"
    )
    assert stat.score > 5 * stat.threshold, (
        "detection margin uncomfortably thin: "
        f"shift={stat.score:g} thr={stat.threshold:g}"
    )
    # and it still escapes every fragment/op-level tier (the blind spot
    # application-level validation exists to cover)
    assert rf.escaped_fragment_checks
    assert rf.tiers["op_diff"].detected is False


def test_identity_within_fp_budget_in_full_campaign(stat_campaign):
    (ident,) = [m for m in stat_campaign.reports if m.fault == "identity"]
    assert ident.detected_at is None, (
        f"identity falsely detected at {ident.detected_at}"
    )
    assert ident.tiers["stat"].detected is False
    assert ident.tiers["stat"].score == 0.0
    cal = stat_campaign.stat_calibration
    assert cal["calib_seeds"] == 5
    assert cal["null_shifts"]["flexasr:resmlp"] == [0.0] * 5
    assert cal["thresholds"]["flexasr:resmlp"] == cal["floor"] == 1e-3
    assert cal["false_positives"]["flexasr:resmlp"] == 0


def test_stat_tier_disabled_without_calibration(monkeypatch):
    """stat_calib_seeds=0 turns the statistical tier into a '-' cell even
    when an application is evaluated (no thresholds exist to judge by)."""
    def fake_prepare(name, n_eval, train_steps, seed):
        def per_example(ex, idx):
            n = len(list(idx))
            return campaign_mod.PerExample(
                np.ones((n, 4), np.float64), np.zeros(n, np.float64), 1.0)

        return campaign_mod._App(
            name, "acc", None, {"vecunit": 1}, pool=128,
            per_example=per_example)

    monkeypatch.setattr(campaign_mod, "_prepare_app", fake_prepare)
    r = campaign_mod.run_campaign(
        targets=("vecunit",), faults=("identity",), apps=("resmlp",),
        engine="pipelined", devices_per_target=1, op_samples=1, vt2_n=2,
        stat_calib_seeds=0,
    )
    (rep,) = r.reports
    assert rep.tiers["app"].detected is False
    assert rep.tiers["stat"].detected is None
    assert "uncalibrated" in rep.tiers["stat"].detail
