"""The fault-tolerant sharded campaign runner and checkpoint/resume.

* determinism: the sharded runner's escape matrix hashes identically
  (`matrix_digest`) to the serial runner's for the same config — worker
  count is a throughput knob, never a semantics knob;
* robustness (the PR 6 acceptance scenario): a campaign containing a
  deliberately crashing mutant AND a deliberately hanging mutant
  completes every other mutant, records `crash`/`timeout` outcomes, and
  leaves the parent registries bit-identical;
* checkpoint/resume: a campaign resumed from a partial checkpoint
  produces a bit-identical matrix digest; a checkpoint from a different
  config (fingerprint mismatch) refuses to resume;
* seed reproducibility: same seed -> same digest, different seed ->
  different fingerprint.

Sharded tests run apps-free (per-worker app training would dominate on
small CI hosts); the statistical tier has its own suite
(test_campaign_stat.py).
"""
import json
import os

import pytest

from repro.core import campaign as campaign_mod, ir
from repro.core.ila import TARGETS


def _registry_snapshot():
    return (
        [(name, id(t)) for name, t in TARGETS._targets.items()],
        {op: (id(t), id(i)) for op, (t, i) in TARGETS._by_op.items()},
        {op: id(spec) for op, spec in ir._ACCEL_EXT.items()},
        set(ir.ACCEL_OPS),
    )


_BASE = dict(
    targets=("vecunit",),
    faults=("identity", "drop_cfg", "trunc_width"),
    apps=(), engine="pipelined", devices_per_target=1,
    op_samples=1, vt2_n=2, seed=0, stat_calib_seeds=0,
)


def test_sharded_matrix_digest_matches_serial():
    serial = campaign_mod.run_campaign(**_BASE)
    sharded = campaign_mod.run_campaign_sharded(
        workers=2, mutant_timeout=300.0, **_BASE)
    assert campaign_mod.matrix_digest(serial) == \
        campaign_mod.matrix_digest(sharded)
    # the digest survives a JSON round-trip (what the CI legs compare)
    assert campaign_mod.matrix_digest(json.loads(
        json.dumps(sharded.to_json()))) == campaign_mod.matrix_digest(serial)


def test_sharded_campaign_survives_crash_and_hang(tmp_path):
    """The acceptance scenario: one mutant raises, one hangs; the campaign
    completes the rest, records crash/timeout outcomes, checkpoints, and
    the parent process registries are untouched."""
    before = _registry_snapshot()
    ck = str(tmp_path / "CAMPAIGN.json")
    result = campaign_mod.run_campaign_sharded(
        workers=2, mutant_timeout=6.0, checkpoint=ck,
        **dict(_BASE, faults=("identity", "drop_cfg", "crash_inject",
                              "hang_inject")),
    )
    assert _registry_snapshot() == before
    by_fault = {r.fault: r for r in result.reports}
    assert len(result.reports) == 4
    assert by_fault["crash_inject"].outcome == "crash"
    assert by_fault["crash_inject"].detected_at == "crash"
    assert "crash_inject" in by_fault["crash_inject"].error
    assert by_fault["hang_inject"].outcome == "timeout"
    assert by_fault["hang_inject"].detected_at == "timeout"
    # the healthy mutants completed normally around the failures
    assert by_fault["identity"].outcome == "ok"
    assert by_fault["identity"].detected_at is None
    assert by_fault["drop_cfg"].outcome == "ok"
    assert by_fault["drop_cfg"].detected_at is not None
    s = result.summary()
    assert s["crashes"] == ["vecunit:crash_inject@wr_a"]
    assert s["timeouts"] == ["vecunit:hang_inject@wr_a"]
    # final checkpoint is the complete (non-partial) result
    data = json.load(open(ck))
    assert data["partial"] is False
    assert campaign_mod.matrix_digest(data) == \
        campaign_mod.matrix_digest(result)


def test_resume_from_partial_checkpoint_is_bit_identical(tmp_path):
    ck = str(tmp_path / "CAMPAIGN.json")
    full = campaign_mod.run_campaign(checkpoint=ck, **_BASE)
    data = json.load(open(ck))
    assert data["partial"] is False and len(data["mutants"]) == 3
    # craft the checkpoint an interrupted run would have left behind
    partial = dict(data, partial=True, mutants=data["mutants"][:1])
    with open(ck, "w") as f:
        json.dump(partial, f)
    resumed = campaign_mod.run_campaign(checkpoint=ck, resume=True, **_BASE)
    assert campaign_mod.matrix_digest(resumed) == \
        campaign_mod.matrix_digest(full)
    assert json.load(open(ck))["partial"] is False
    # a fully-completed checkpoint resumes without re-running anything
    again = campaign_mod.run_campaign(checkpoint=ck, resume=True, **_BASE)
    assert campaign_mod.matrix_digest(again) == \
        campaign_mod.matrix_digest(full)


def test_resume_refuses_foreign_fingerprint(tmp_path):
    ck = str(tmp_path / "CAMPAIGN.json")
    campaign_mod.run_campaign(checkpoint=ck, **_BASE)
    with pytest.raises(ValueError, match="fingerprint"):
        campaign_mod.run_campaign(checkpoint=ck, resume=True,
                                  **dict(_BASE, seed=1))


def test_same_seed_reproduces_digest_different_seed_changes_fingerprint():
    a = campaign_mod.run_campaign(**_BASE)
    b = campaign_mod.run_campaign(**_BASE)
    assert campaign_mod.matrix_digest(a) == campaign_mod.matrix_digest(b)
    c = campaign_mod.run_campaign(**dict(_BASE, seed=1))
    assert a.to_json()["fingerprint"] != c.to_json()["fingerprint"]
    assert campaign_mod.matrix_digest(a) != campaign_mod.matrix_digest(c)


def test_checkpoint_write_is_atomic(tmp_path):
    """The tmp file never survives a successful save, and the checkpoint
    parses even though it is rewritten after every mutant."""
    ck = str(tmp_path / "CAMPAIGN.json")
    campaign_mod.run_campaign(checkpoint=ck, **_BASE)
    assert os.path.exists(ck)
    assert not os.path.exists(ck + ".tmp")
    json.load(open(ck))
