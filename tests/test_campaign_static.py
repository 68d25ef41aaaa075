"""Campaign tier 0 (static verifier) integration.

Protocol faults that PR 5/6 could only catch by simulating — decode
aliasing and command reordering — must now be flagged by the static tier
with zero simulated commands, and under ``ladder="escalate"`` every
simulated tier below is skipped. Faults outside static scope (ILA-update
wrappers, bulk numeric payload corruption) must keep their established
tier placements: the static tier passes them down the ladder.
"""
import pytest

import repro.accel  # noqa: F401
from repro.core.campaign import TIER_ORDER, format_matrix, run_campaign


@pytest.fixture(scope="module")
def static_campaign():
    return run_campaign(
        targets=["vecunit", "hlscnn"],
        faults=["identity", "decode_alias", "cmd_reorder", "drop_cfg"],
        apps=(),
        engine="pipelined",
        devices_per_target=1,
        ladder="escalate",
        op_samples=1,
        stat_calib_seeds=0,
        seed=0,
    )


def test_static_is_tier_zero():
    assert TIER_ORDER[0] == "static"


def test_every_decode_alias_mutant_caught_statically(static_campaign):
    aliases = [m for m in static_campaign.reports if m.fault == "decode_alias"]
    assert aliases, "fault library produced no decode_alias mutants"
    for m in aliases:
        assert m.detected_at == "static", (
            f"{m.key} first detected at {m.detected_at}"
        )
        assert m.tiers["static"].detected is True
        assert "opcode stream rewritten" in m.tiers["static"].detail


def test_cmd_reorder_sensitive_mutant_caught_statically(static_campaign):
    reorders = [m for m in static_campaign.reports if m.fault == "cmd_reorder"]
    assert reorders, "fault library produced no cmd_reorder mutants"
    caught = [m for m in reorders if m.detected_at == "static"]
    assert caught, "no cmd_reorder mutant detected at the static tier"
    assert any("order-sensitive" in m.tiers["static"].detail for m in caught)


def test_static_detection_skips_every_simulated_tier(static_campaign):
    for m in static_campaign.reports:
        if m.detected_at != "static":
            continue
        for tier in ("vt2", "frag_sim", "op_diff", "app", "stat"):
            r = m.tiers.get(tier)
            assert r is None or r.detected is None, (
                f"{m.key}: simulated tier {tier} ran after static detection"
            )
            if r is not None:
                assert "skipped" in r.detail


def test_identity_passes_static_tier(static_campaign):
    idents = [m for m in static_campaign.reports if m.fault == "identity"]
    assert idents
    for m in idents:
        assert m.tiers["static"].detected is False
        assert m.detected_at is None


def test_wrapper_faults_stay_out_of_static_scope(static_campaign):
    drops = [m for m in static_campaign.reports if m.fault == "drop_cfg"]
    assert drops
    for m in drops:
        assert m.tiers["static"].detected is False
        assert "out of static scope" in m.tiers["static"].detail
        # the simulated ladder still catches the dropped configuration
        assert m.detected_at not in (None, "static"), (
            f"{m.key}: expected a simulated-tier detection, "
            f"got {m.detected_at}"
        )


def test_matrix_and_json_gain_static_column(static_campaign):
    matrix = format_matrix(static_campaign)
    assert "static" in matrix.splitlines()[0] or "static" in matrix
    d = static_campaign.to_json()
    tiers_seen = {t for m in d["mutants"] for t in m["tiers"]}
    assert "static" in tiers_seen
    assert "static" in d["summary"]["first_detection_by_tier"]
    n_static = d["summary"]["first_detection_by_tier"]["static"]
    n_alias = sum(1 for m in static_campaign.reports
                  if m.fault == "decode_alias")
    assert n_static >= n_alias + 1  # all aliases + >= 1 reorder


def test_golden_ilas_not_simulated_by_static_tier(static_campaign):
    """The static tier classifies numpy streams; a fresh analysis of the
    same mutants must not advance any golden ILA trace counter."""
    from repro.core import faults, ilalint
    from repro.core.ila import TARGETS

    t = TARGETS.get("vecunit")
    probes = ilalint.probe_streams(t, seed=0, samples=1)
    before = (t.ila.n_traces_single, t.ila.n_traces_batch)
    for inst in faults.fault_instances(t, ["decode_alias", "cmd_reorder"]):
        hx = inst.host_xform()
        assert hx is not None
        ilalint.analyze_mutation(t, probes, hx)
    assert (t.ila.n_traces_single, t.ila.n_traces_batch) == before
