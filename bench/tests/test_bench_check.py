"""The check that decides ``correct``, driven through the rest of a run on
the CPU (the harness's look for a chip is skipped): sound runs pass; the
control and each fault a serving cell can have fail.

Faults, planted in the executor where the answers are produced:
  * an answer altered where it is produced;
  * half of a dispatched batch left out, its answers taken from the rest.
The control is the reference one precision step down (the configuration's
``control.precision``), put in the program's place. The full configuration
is served, on a small closed-loop mix (one caller, four samples per
request).
"""
import time

import numpy as np
import pytest

from bench import cell as C
from bench import run as R

MIX = {"clients": 1, "samples": 4, "queue_depth": 4, "max_batch": 4}
SEED = 2**31 + 77


@pytest.fixture(scope="module")
def served():
    from repro.core import serving

    cell = C.resolve("mnist_rnn.closed")
    cell.traffic = dict(MIX)
    s = C.build(cell, SEED)
    C.warm_up(cell, s, SEED)
    yield cell, s
    s.server.close(drain=True)
    serving.ila.set_batch_ladder("pow2")


def _run(cell, s, seconds=0.5):
    w = C.measure(cell, s, SEED, seconds, trace=False, log=lambda *a, **k: None)
    return C.finish(cell, s, w, False, t_process=time.perf_counter(),
                    log=lambda *a, **k: None)


def _patch_submit(monkeypatch, s, fault):
    """Wrap the executor's submit_many so ``fault(envs, outs)`` rewrites what
    it produces."""
    ex = s.server.executor
    orig = ex.submit_many

    class Sub:
        def __init__(self, inner, envs):
            self.inner, self.envs = inner, envs

        def result(self):
            return fault(self.envs, list(self.inner.result()))

    def submit_many(e, envs, prepack=None):
        return Sub(orig(e, envs, prepack=prepack), envs)

    monkeypatch.setattr(ex, "submit_many", submit_many)


def test_sound_run_is_correct(served):
    cell, s = served
    r = _run(cell, s)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] >= 1
    assert r["checks"]["max_rel_err"]["value"] < r["checks"]["max_rel_err"]["limit"]
    assert list(r)[-1] == "checks"
    assert set(r["metrics"]) == {"samples_per_s", "setup_s"}


def test_altered_answer_is_caught(served, monkeypatch):
    cell, s = served

    def alter(envs, outs):
        outs[-1] = np.asarray(outs[-1]) * 1.5
        return outs

    _patch_submit(monkeypatch, s, alter)
    r = _run(cell, s)
    assert not r["correct"]
    assert r["checks"]["max_rel_err"]["value"] > r["checks"]["max_rel_err"]["limit"]


def test_half_batch_left_out_is_caught(served, monkeypatch):
    cell, s = served
    ex = s.server.executor
    orig = ex.submit_many

    def half(e, envs, prepack=None):
        keep = max(1, len(envs) // 2)
        sub = orig(e, envs[:keep])

        class Sub:
            def result(self):
                outs = list(sub.result())
                return [outs[i % keep] for i in range(len(envs))]
        return Sub()

    monkeypatch.setattr(ex, "submit_many", half)
    r = _run(cell, s)
    assert not r["correct"]
    assert r["checks"]["max_rel_err"]["value"] > r["checks"]["max_rel_err"]["limit"]


def test_control_fails(served, monkeypatch):
    """The reference at int4, one step below the accelerators' 8 bits, in
    the program's place must read above the limit."""
    from bench import check

    cell, s = served
    precision = cell.config["control"]["precision"]

    def control(envs, outs):
        low = check.reference_outputs(cell, s.params, [e["x"] for e in envs], precision)
        return [o[None] for o in low]

    _patch_submit(monkeypatch, s, control)
    # the control is judged on a window's widest error: give it a few
    # dozen samples, as a run at the cell's size compares over a thousand
    r = _run(cell, s, seconds=6.0)
    assert not r["correct"], r["checks"]
    assert r["checks"]["max_rel_err"]["value"] > r["checks"]["max_rel_err"]["limit"]


def test_unanswered_request_is_caught(served, monkeypatch):
    cell, s = served

    def lose(envs, outs):
        raise RuntimeError("dispatch lost")

    _patch_submit(monkeypatch, s, lose)
    r = _run(cell, s)
    assert not r["correct"]
    assert r["failed"] >= 1 and r["checks"]["unanswered"]["value"] >= 1


def test_no_tpu_exits_without_a_result(capsys):
    with pytest.raises(SystemExit) as e:
        R.main(["--workload", "mnist_rnn.closed", "--seed", "1", "--seconds", "1",
                "--trace", "0"])
    assert e.value.code not in (0, None)
    assert capsys.readouterr().out == ""


def test_traced_run_reads_per_layer_metrics(served):
    """The traced path end to end on the CPU: the profiler's trace is read,
    the program's spans are placed on it, and the per-layer metrics that
    have something to read appear (the CPU has no TPU plane and no peak, so
    the device metrics and ``cosim.mfu`` find nothing)."""
    cell, s = served
    w = C.measure(cell, s, SEED, 0.5, trace=True, log=lambda *a, **k: None)
    r = C.finish(cell, s, w, True, t_process=time.perf_counter(),
                 log=lambda *a, **k: None)
    assert r["correct"], r["checks"]
    assert set(r["metrics"]) == {"executor.pack_ms_per_sample.closed",
                                 "sim.dispatch_ms_per_sample.closed"}
    assert all(m["value"] > 0 for m in r["metrics"].values())
    assert r["trace"]["window_s"] > 0 and r["trace"]["devices"] == 0
