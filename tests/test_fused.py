"""Fused fast-path tier: target-specific coverage.

The registry conformance suite (test_target_conformance.py) asserts
engine="fused" parity within each intrinsic's *declared* tolerance for
every target, lowering, and device count without naming any backend. This
file pins down the stronger per-target guarantees the fused runners
actually make:

* FlexASR LinearLayer and HLSCNN conv2d XLA-fallback runners replicate the
  compiled tier's arithmetic step for step — bit-exact, not just in-tol;
* the FlexASR LSTM runner hoists the input projection out of the scan
  (fp32 reassociation), so it is held to a tight rel-Frobenius bound far
  below the intrinsic tolerance rather than exactness;
* the Pallas lowerings (forced via REPRO_FUSED_PALLAS=1, interpret-mode on
  CPU) track compiled within the same tight bound;
* runner resolution plumbing: ``declare_fused`` factories fire per
  fragment signature, the memo is lowering-keyed, foreign-ILA fragments
  (campaign mutants sharing a golden key) never take the fast path, and
  ``REPRO_ENGINE=fused`` selects the engine process-wide.
"""
import numpy as np
import pytest

from repro.accel import flexasr as fa, hlscnn as hc
from repro.core import ir, validate
from repro.core.codegen import Executor
from repro.core.ila import ILA, TARGETS, CompiledFragment

#: fused-vs-compiled bound for reassociated (non-bit-exact) lowerings:
#: both sides quantize to the same lattice, so only fp32 summation-order
#: noise below the lattice step survives
TIGHT = 1e-4


RUNNER_PREFIX = {"fasr_linear": "flexasr-linear", "fasr_lstm": "flexasr-lstm",
                 "hlscnn_conv2d": "hlscnn-conv2d"}


def _last_runner(op):
    """The most recently resolved fused runner of ``op``'s family."""
    return [r for r in TARGETS.intrinsic(op)[0].fused_runners()
            if r.name.startswith(RUNNER_PREFIX[op])][-1]


def _run(op, env_args, attrs, engine, options, **kw):
    vs = tuple(ir.Var(f"_{i}", a.shape) for i, a in enumerate(env_args))
    expr = ir.call(op, *vs, **attrs)
    env = {f"_{i}": a for i, a in enumerate(env_args)}
    ex = Executor("ila", engine=engine, target_options=options, **kw)
    return np.asarray(ex.run(expr, env)), ex


def _flexasr_linear_args(seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((64, 96)).astype(np.float32)
    w = (rng.standard_normal((48, 96)) * 0.1).astype(np.float32)
    b = rng.standard_normal((48,)).astype(np.float32)
    return [x, w, b], {}


def _flexasr_lstm_args(seed=0):
    rng = np.random.default_rng(seed)
    xs = rng.standard_normal((24, 1, 48)).astype(np.float32)
    wi = (rng.standard_normal((4 * 32, 48)) * 0.2).astype(np.float32)
    wh = (rng.standard_normal((4 * 32, 32)) * 0.2).astype(np.float32)
    b = rng.standard_normal((4 * 32,)).astype(np.float32)
    return [xs, wi, wh, b], {}


def _hlscnn_conv_args(seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((1, 10, 10, 6)).astype(np.float32)
    w = (rng.standard_normal((3, 3, 6, 8)) * 0.1).astype(np.float32)
    return [x, w], {"strides": (1, 1), "padding": (0, 0)}


CASES = [
    pytest.param("fasr_linear", _flexasr_linear_args,
                 {"flexasr": {}}, True, id="flexasr-linear"),
    pytest.param("fasr_lstm", _flexasr_lstm_args,
                 {"flexasr": {}}, False, id="flexasr-lstm"),
    pytest.param("hlscnn_conv2d", _hlscnn_conv_args,
                 {"hlscnn": {"wgt_bits": 16}}, True, id="hlscnn-conv2d"),
]


@pytest.mark.parametrize("op,make,options,exact", CASES)
def test_xla_fallback_replicates_compiled(op, make, options, exact, monkeypatch):
    """Forced XLA lowering: linear/conv replicate the compiled arithmetic
    bit-for-bit; the LSTM's hoisted projection stays within TIGHT."""
    monkeypatch.setenv("REPRO_FUSED_FALLBACK", "1")
    args, attrs = make()
    ref, _ = _run(op, args, attrs, "pipelined", options)
    got, ex = _run(op, args, attrs, "fused", options)
    if exact:
        np.testing.assert_array_equal(ref, got)
    else:
        assert validate.frob_rel_err(ref, got) <= TIGHT
    # the fast path actually fired: the owning target resolved a runner,
    # and it declares the exactness this test holds it to
    tname = next(iter(options))
    assert ex.cache_info()[tname]["fused_runners"] >= 1
    runner = _last_runner(op)
    assert runner.lowering == "xla" and runner.exact == exact


@pytest.mark.parametrize("op,make,options,exact", CASES)
def test_pallas_lowering_tracks_compiled(op, make, options, exact, monkeypatch):
    """Forced Pallas lowering (interpret-mode on CPU hosts): af_gemm /
    fx_gemm legs track the compiled oracle within TIGHT. The LSTM runner
    has no Pallas leg (no gate re-quantization to fuse) and rides its XLA
    lowering — covered here for the parity contract all the same."""
    monkeypatch.setenv("REPRO_FUSED_PALLAS", "1")
    args, attrs = make()
    ref, _ = _run(op, args, attrs, "pipelined", options)
    got, _ = _run(op, args, attrs, "fused", options)
    assert validate.frob_rel_err(ref, got) <= TIGHT
    # on the CPU backend a Pallas leg is built in interpret mode
    runner = _last_runner(op)
    assert runner.interpret == (runner.lowering == "pallas")
    assert not runner.exact


def test_fused_batch_matches_per_sample_numerics():
    """run_many through the fused engine keeps per-sample numerics: each
    sample's exponent windows travel in its own data stream, so a batch
    mixing two distinct samples reproduces the singleton runs exactly."""
    args1, attrs = _flexasr_linear_args(1)
    args2, _ = _flexasr_linear_args(2)
    x1, w, b = args1
    x2 = args2[0]
    vs = (ir.Var("_0", x1.shape), ir.Var("_1", w.shape), ir.Var("_2", b.shape))
    expr = ir.call("fasr_linear", *vs)
    envs = [{"_0": x1, "_1": w, "_2": b}, {"_0": x2, "_1": w, "_2": b}]
    singles = [
        np.asarray(Executor("ila", engine="fused").run(expr, e)) for e in envs
    ]
    batched = Executor("ila", engine="fused").run_many(expr, envs)
    for s, m in zip(singles, batched):
        np.testing.assert_array_equal(s, np.asarray(m))


def test_fused_runner_refuses_foreign_ila():
    """A fragment bound to a different ILA instance (the fault campaign's
    mutant clones share the golden fragment key) must not resolve a fused
    runner — the runner is built from golden build-time meta and would mask
    the mutation."""
    args, _ = _flexasr_linear_args()
    _x, w, b = args
    frag = fa.linear_fragment(w, b)
    assert fa.TARGET.fused_runner(frag) is not None
    foreign = CompiledFragment(
        ILA("foreign", vwidth=16), frag.key, frag.setup, dict(frag.meta)
    )
    assert fa.TARGET.fused_runner(foreign) is None


def test_fused_memo_is_lowering_keyed(monkeypatch):
    """Flipping REPRO_FUSED_FALLBACK re-resolves the runner: the memo key
    includes the active lowering, so env changes between prepare and
    dispatch never serve a stale lowering."""
    args, _ = _hlscnn_conv_args()
    _x, w = args
    frag = hc.conv2d_fragment(w, (10, 10, 6), (1, 1), wgt_bits=16)
    monkeypatch.setenv("REPRO_FUSED_FALLBACK", "1")
    r_xla = hc.TARGET.fused_runner(frag)
    assert r_xla is not None and r_xla.lowering == "xla"
    monkeypatch.delenv("REPRO_FUSED_FALLBACK")
    monkeypatch.setenv("REPRO_FUSED_PALLAS", "1")
    r_pl = hc.TARGET.fused_runner(frag)
    assert r_pl is not None and r_pl.lowering == "pallas"
    assert r_pl is not r_xla


def test_repro_engine_env_selects_fused(monkeypatch):
    """REPRO_ENGINE=fused is picked up by every Executor constructed
    without an explicit engine (the cosim/serving helpers' path)."""
    monkeypatch.setenv("REPRO_ENGINE", "fused")
    ex = Executor("ila")
    assert ex.engine == "fused"
    args, attrs = _hlscnn_conv_args()
    ref, _ = _run("hlscnn_conv2d", args, attrs, "pipelined",
                  {"hlscnn": {"wgt_bits": 16}})
    vs = tuple(ir.Var(f"_{i}", a.shape) for i, a in enumerate(args))
    env = {f"_{i}": a for i, a in enumerate(args)}
    got = np.asarray(
        Executor("ila", target_options={"hlscnn": {"wgt_bits": 16}}).run(
            ir.call("hlscnn_conv2d", *vs, **attrs), env
        )
    )
    assert validate.frob_rel_err(ref, got) <= TIGHT
