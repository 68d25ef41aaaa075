"""Accelerator ILA tests: custom numerics, simulators, VT checks."""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st  # property tests skip if absent

from repro.accel import flexasr as fa, hlscnn as hc, numerics, vta as vt
from repro.accel.target import PlanContext
from repro.core import ir, validate
from repro.core.ila import FragmentCache

rng = np.random.default_rng(0)


def _random_magnitudes():
    r = np.random.default_rng(14)
    shapes = [(7,), (3, 5), (1, 64), (28, 28)]   # few shapes: few compiles
    return [(r.standard_normal(shapes[i % 4]) * 10.0 ** r.uniform(-30, 30))
            .astype(np.float32) for i in range(400)]


def _mnist_rnn_shapes():
    r = np.random.default_rng(28)
    return [r.standard_normal(s).astype(np.float32) * 0.3
            for s in [(1, 64), (1, 32), (1, 10), (28, 28)]]


_TINY = np.finfo(np.float32).tiny
_EXP_BIAS_CASES = {
    "zeros": lambda: [np.zeros((4, 8), np.float32), -np.zeros((3,), np.float32)],
    "subnormal": lambda: [
        np.full((5,), _TINY / 2, np.float32),
        np.array([0.0, -_TINY / 4, 1e-45], np.float32),
        np.array([np.nextafter(_TINY, 0, dtype=np.float32)], np.float32)],
    "powers_of_two": lambda: [np.array([0.0, 2.0 ** k], np.float32)
                              for k in range(-126, 128)],
    "negative_max": lambda: [np.array([0.5, -3.0, 2.9], np.float32),
                             np.array([-(2.0 ** 40), 1.0], np.float32)],
    "below_power_of_two": lambda: [
        np.array([np.nextafter(np.float32(2.0 ** k), 0, dtype=np.float32)],
                 np.float32) for k in range(-125, 128, 7)],
    "float64_rounds_to_float32": lambda: [
        np.array([2.0 - 2.0 ** -30]), np.array([-(1.0 - 2.0 ** -40), 0.25])],
    "mnist_rnn_shapes": _mnist_rnn_shapes,
    "random_magnitudes": _random_magnitudes,
}


class TestAdaptivFloat:
    def test_representable_fixed_point_of_quantize(self):
        x = rng.standard_normal((64,)).astype(np.float32)
        spec = numerics.AdaptivFloatSpec(8, 3)
        q = numerics.af_quantize(jnp.asarray(x), spec)
        q2 = numerics.af_quantize(q, spec, exp_bias=numerics.af_exp_bias(jnp.asarray(x), spec))
        np.testing.assert_allclose(np.asarray(q), np.asarray(q2))

    def test_zero_and_sign(self):
        spec = numerics.AdaptivFloatSpec(8, 3)
        x = jnp.asarray([0.0, -0.5, 0.5, -2.0, 2.0])
        q = np.asarray(numerics.af_quantize(x, spec))
        assert q[0] == 0.0
        assert (np.sign(q[1:]) == np.array([-1, 1, -1, 1])).all()

    @given(st.lists(st.floats(-100, 100, allow_nan=False), min_size=2, max_size=32))
    @settings(max_examples=30, deadline=None)
    def test_error_bounded_by_mantissa_ulp(self, xs):
        """Property: relative rounding error <= 2^-(m+1) within the normal
        range (no saturation / flush)."""
        spec = numerics.AdaptivFloatSpec(8, 3)
        x = np.asarray(xs, np.float32)
        if np.max(np.abs(x)) == 0:
            return
        bias = float(numerics.af_exp_bias(jnp.asarray(x), spec))
        vmin = 2.0 ** bias
        vmax = (2 - 2 ** -spec.n_man) * 2.0 ** (bias + 2 ** spec.n_exp - 1)
        q = np.asarray(numerics.af_quantize(jnp.asarray(x), spec))
        inside = (np.abs(x) >= vmin) & (np.abs(x) <= vmax)
        rel = np.abs(q[inside] - x[inside]) / np.abs(x[inside])
        assert rel.max(initial=0.0) <= 2.0 ** -(spec.n_man + 1) + 1e-6

    @pytest.mark.parametrize("bias", [-40.0, -18.0, -5.0, 0.0, 20.0])
    def test_matches_float64_reference_at_every_exponent(self, bias):
        """Bit-exact against a float64 NumPy model of the lattice in any
        exponent window, exact powers of two included: the quantizer must not
        lean on XLA's log2/exp2, approximations on the CPU and the TPU."""
        spec = numerics.AdaptivFloatSpec(8, 3)
        m, top = spec.n_man, bias + 2 ** spec.n_exp - 1
        x = np.concatenate([
            rng.standard_normal(4096) * 2.0 ** (bias + 5),
            np.exp2(np.arange(bias - 2, top + 2)),
        ]).astype(np.float32)
        ax = np.abs(x.astype(np.float64))
        e = np.clip(np.floor(np.log2(np.where(ax > 0, ax, 1.0))), bias, top)
        man = np.clip(ax / 2.0 ** e, 1.0, 2.0 - 2.0 ** -m)
        man_q = np.round(man * 2 ** m) / 2 ** m
        bump = man_q >= 2.0
        e2 = np.clip(e + bump, bias, top)
        man_q = np.where(bump & (e2 > e), 1.0, np.minimum(man_q, 2.0 - 2.0 ** -m))
        want = np.where(ax < 2.0 ** (bias - 1), 0.0, man_q * 2.0 ** e2)
        want = (np.sign(x) * want).astype(np.float32)
        got = np.asarray(numerics.af_quantize(jnp.asarray(x), spec,
                                              exp_bias=jnp.float32(bias)))
        np.testing.assert_array_equal(got, want)

    @pytest.mark.parametrize("case", sorted(_EXP_BIAS_CASES))
    @pytest.mark.parametrize("n_exp", [2, 3])
    def test_host_exp_bias_matches_jax(self, case, n_exp):
        """The planners' numpy exponent bias equals ``af_exp_bias`` bit for
        bit, on every tensor the co-sim path can hand it."""
        spec = numerics.AdaptivFloatSpec(8, n_exp)
        for x in _EXP_BIAS_CASES[case]():
            want = float(numerics.af_exp_bias(jnp.asarray(x), spec))
            got = numerics.af_exp_bias_host(x, spec)
            assert type(got) is float
            assert got == want, (case, x.dtype, x.shape, got, want)

    def test_fixed_point_grid(self):
        spec = numerics.FixedPointSpec(8, 3)
        x = jnp.asarray([0.124, -0.3, 5.0, 100.0])
        q = np.asarray(numerics.fx_quantize(x, spec))
        np.testing.assert_allclose(q * 8, np.round(q * 8))   # on the 2^-3 grid
        assert q[3] == spec.qmax / spec.scale                # saturates


class _NoJax:
    def __getattr__(self, name):
        raise AssertionError(f"jnp.{name} on the pack path")


def _fasr_inputs(*shapes, seed=14):
    r = np.random.default_rng(seed)
    return [(r.standard_normal(s) * 10.0 ** r.uniform(-3, 1)).astype(np.float32)
            for s in shapes]


def _pack_linear():
    x, w, b = _fasr_inputs((16, 64), (32, 64), (32,))
    frag = fa.linear_fragment(w, b)
    return [fa.pack_linear_data(frag, x).tail]


def _pack_lstm():
    x, wi, wh, b = _fasr_inputs((28, 28), (256, 28), (256, 64), (256,))
    frag = fa.lstm_fragment(wi, wh, b)
    return [fa.pack_lstm_data(frag, x).tail]


def _pack_pool():
    (x,) = _fasr_inputs((16, 64))
    return [fa.pack_pool_data(fa.pool_fragment(64, "max"), x).tail]


def _pack_layernorm():
    x, g, b = _fasr_inputs((8, 32), (32,), (32,))
    return [fa.pack_layernorm_data(fa.layernorm_fragment(g, b), x).tail]


def _pack_attention():
    q, k, v = _fasr_inputs((8, 16), (12, 16), (12, 16))
    return [fa.pack_attention_data(fa.attention_fragment(16), q, k, v).tail]


def _plan_linear():
    a, w, b = _fasr_inputs((200, 64), (10, 64), (10,))
    jobs, _ = fa.plan_linear(PlanContext(record=lambda *a: None), None, (a, w, b))
    return [j.data.tail for j in jobs]


def _plan_lstm():
    xs, wi, wh, b = _fasr_inputs((28, 3, 28), (256, 28), (256, 64), (256,))
    jobs, _ = fa.plan_lstm(PlanContext(record=lambda *a: None), None, (xs, wi, wh, b))
    return [j.data.tail for j in jobs]


_PACKERS = {f.__name__[1:]: f for f in (
    _pack_linear, _pack_lstm, _pack_pool, _pack_layernorm, _pack_attention,
    _plan_linear, _plan_lstm)}


class TestFlexASR:
    def test_linear_error_magnitude(self):
        x = rng.standard_normal((16, 64)).astype(np.float32)
        w = (rng.standard_normal((32, 64)) * 0.1).astype(np.float32)
        b = (rng.standard_normal((32,)) * 0.1).astype(np.float32)
        cmds, rd = fa.build_linear_fragment(x, w, b)
        out = np.asarray(rd(fa.flexasr.simulate(cmds)))
        err = validate.frob_rel_err(x @ w.T + b, out)
        assert 0 < err < 0.06   # AF8: a few percent (Table 2 magnitude)

    def test_jit_simulator_matches_eager(self):
        x = rng.standard_normal((8, 32)).astype(np.float32)
        w = (rng.standard_normal((16, 32)) * 0.1).astype(np.float32)
        b = np.zeros((16,), np.float32)
        cmds, rd = fa.build_linear_fragment(x, w, b)
        out_e = np.asarray(rd(fa.flexasr.simulate(cmds)))
        out_j = np.asarray(rd(fa.flexasr.simulate_jit(cmds)))
        np.testing.assert_allclose(out_e, out_j, atol=1e-6)

    def test_maxpool_exact_on_device_representable_inputs(self):
        x = np.asarray(numerics.af_quantize(
            jnp.asarray(rng.standard_normal((16, 64)).astype(np.float32)), fa.AF))
        cmds, rd = fa.build_pool_fragment(x, "max")
        out = np.asarray(rd(fa.flexasr.simulate(cmds)))
        np.testing.assert_array_equal(out, x.reshape(8, 2, 64).max(1))

    def test_lstm_close_to_reference(self):
        T, I, H = 8, 32, 16
        x = (rng.standard_normal((T, I)) * 0.5).astype(np.float32)
        wi = (rng.standard_normal((4 * H, I)) * 0.2).astype(np.float32)
        wh = (rng.standard_normal((4 * H, H)) * 0.2).astype(np.float32)
        b = (rng.standard_normal((4 * H,)) * 0.1).astype(np.float32)
        cmds, rd = fa.build_lstm_fragment(x, wi, wh, b)
        out = np.asarray(rd(fa.flexasr.simulate(cmds)))
        ref = np.asarray(ir._lstm(jnp.asarray(x[:, None]), jnp.asarray(wi),
                                  jnp.asarray(wh), jnp.asarray(b)))[:, 0]
        assert validate.frob_rel_err(ref, out) < 0.08

    def test_granularity_mismatch_one_instruction(self):
        """The LSTM maps to ONE fn_start trigger regardless of timesteps
        (the paper's 566-ops-to-1 bridge)."""
        x = (rng.standard_normal((32, 16)) * 0.5).astype(np.float32)
        wi = (rng.standard_normal((32, 16)) * 0.2).astype(np.float32)
        wh = (rng.standard_normal((32, 8)) * 0.2).astype(np.float32)
        b = np.zeros((32,), np.float32)
        cmds, _ = fa.build_lstm_fragment(x, wi, wh, b)
        assert sum(1 for c in cmds if c.opcode == fa.FN_START) == 1

    @pytest.mark.parametrize("planner", sorted(_PACKERS))
    def test_planners_pack_without_jax(self, planner, monkeypatch):
        """FlexASR's packers and planners dispatch no JAX (they run on the
        pack worker), and their exponent windows equal the JAX path's."""
        def numerics_rows(streams):
            return [s.data[s.ops == fa.CFG_NUMERICS] for s in streams]

        with monkeypatch.context() as m:
            def eager(*a, **k):
                raise AssertionError("eager JAX on the pack path")
            m.setattr(numerics, "af_exp_bias", eager)
            m.setattr(fa, "jnp", _NoJax())
            m.setattr(fa, "FRAGMENTS", FragmentCache())
            got = numerics_rows(_PACKERS[planner]())
        with monkeypatch.context() as m:
            m.setattr(fa, "_exp_biases", lambda *ts: [
                float(numerics.af_exp_bias(jnp.asarray(t), fa.AF)) for t in ts])
            m.setattr(fa, "FRAGMENTS", FragmentCache())
            want = numerics_rows(_PACKERS[planner]())
        assert len(got) == len(want) >= 1
        for g, w in zip(got, want):
            assert g.shape == (1, fa.V)
            np.testing.assert_array_equal(g, w)


class TestVTA:
    def test_gemm_exact(self):
        a = rng.integers(-120, 120, (20, 40)).astype(np.float32)
        b = rng.integers(-120, 120, (24, 40)).astype(np.float32)
        cmds, rd = vt.build_gemm_fragment(a, b)
        out = np.asarray(rd(vt.vta.simulate(cmds)))
        np.testing.assert_array_equal(out, a @ b.T)

    def test_alu_relu(self):
        a = rng.integers(-100, 100, (8, 8)).astype(np.float32)
        cmds, rd = vt.build_relu_fragment(a)
        out = np.asarray(rd(vt.vta.simulate(cmds)))
        np.testing.assert_array_equal(out, np.maximum(a, 0))

    def test_requant_shift(self):
        a = np.full((4, 4), 64.0, np.float32)
        b = np.full((4, 4), 2.0, np.float32)
        cmds, rd = vt.build_gemm_fragment(a, b, requant_shift=4)
        out = np.asarray(rd(vt.vta.simulate(cmds)))
        # acc = 64*2*4 = 512; >>4 = 32
        np.testing.assert_array_equal(out, np.full((4, 4), 32.0))

    @pytest.mark.parametrize("planner,n_args,rows_keys", [
        (vt.plan_gemm, 2, ("mt", "nt")),
        (vt.plan_add, 2, ("rt", "ct")),
        (vt.plan_relu, 1, ("rt", "ct")),
    ], ids=["gemm", "add", "relu"])
    def test_planner_reads_are_shared_per_tile_layout(self, planner, n_args, rows_keys):
        """Two planner calls on operands of one shape hand out the same read
        object (the Executor's jitted reads are keyed by it); another tile
        layout gets its own; and the shared read returns, bit for bit, what
        a read built for the one fragment alone returns."""
        ctx = PlanContext(record=lambda *args, **kw: None)
        r = np.random.default_rng(3)

        def plan(rows):
            args = [r.integers(-50, 50, (rows, 40)).astype(np.float32)
                    for _ in range(n_args)]
            return planner(ctx, None, args)[0]

        first, again, other = plan(20), plan(20), plan(36)
        assert first[0].read is again[0].read
        assert other[0].read is not first[0].read

        def own_read(frag):
            rows, cols = (frag.meta[k] for k in rows_keys)
            base = frag.meta["out_base"]

            def read(st):
                region = st["dram"][base * vt.T : (base + rows * cols) * vt.T]
                return region.reshape(rows, cols, vt.T, vt.T).transpose(0, 2, 1, 3) \
                    .reshape(rows * vt.T, cols * vt.T)

            return read

        for j in (*again, *other):
            st = vt.vta.run_data(j.data, state=j.frag.setup_state())
            np.testing.assert_array_equal(np.asarray(j.read(st)),
                                          np.asarray(own_read(j.frag)(st)))


class TestHLSCNN:
    def test_conv_8bit_much_worse_than_16bit(self):
        x = rng.standard_normal((1, 12, 12, 8)).astype(np.float32)
        w = (rng.standard_normal((3, 3, 8, 16)) * 0.05).astype(np.float32)
        errs = {}
        for bits in (8, 16):
            cmds, rd = hc.build_conv2d_fragment(x, w, (1, 1), (0, 0), wgt_bits=bits)
            out = np.asarray(rd(hc.hlscnn.simulate(cmds)))
            ref = np.asarray(ir._conv2d(jnp.asarray(x), jnp.asarray(w), (1, 1), (0, 0)))
            errs[bits] = validate.frob_rel_err(ref, out)
        assert errs[8] > 5 * errs[16]          # the paper's numerics bug
        assert errs[16] < 0.02

    def test_strided_padded_conv(self):
        x = rng.standard_normal((1, 12, 12, 8)).astype(np.float32)
        w = (rng.standard_normal((3, 3, 8, 16)) * 0.05).astype(np.float32)
        cmds, rd = hc.build_conv2d_fragment(x, w, (2, 2), (1, 1), wgt_bits=16)
        out = np.asarray(rd(hc.hlscnn.simulate(cmds)))
        ref = np.asarray(ir._conv2d(jnp.asarray(x), jnp.asarray(w), (2, 2), (1, 1)))
        assert out.shape == ref.shape
        assert validate.frob_rel_err(ref, out) < 0.02


# ---- capacity guards: a matched op is one its planner can run ----------------


def _offloads(expr):
    from repro.core.compile import compile_program

    prog = compile_program(expr).program
    ops = {}
    for n in ir.postorder(prog):
        if isinstance(n, ir.Call) and ir.accel_op_target(n.op):
            ops[n.op] = ops.get(n.op, 0) + 1
    return prog, ops


@pytest.mark.parametrize("biased", [True, False])
def test_wide_k_dense_is_not_vta_gemm_and_runs_tiled(biased):
    """K = 2048 is 128 VTA tiles, past the 64 the input SRAM holds: the
    dense never matches ``vta_gemm`` and runs as a tiled FlexASR linear."""
    from repro.core.codegen import Executor

    rng = np.random.default_rng(0)
    x = rng.standard_normal((8, 2048)).astype(np.float32)
    w = (rng.standard_normal((40, 2048)) * 0.02).astype(np.float32)
    b = (rng.standard_normal(40) * 0.1).astype(np.float32)
    d = ir.dense(ir.Var("x", (8, 2048)), ir.Var("w", (40, 2048)))
    expr = ir.bias_add(d, ir.Var("b", (40,))) if biased else d
    prog, ops = _offloads(expr)
    assert ops == {"fasr_linear": 1}
    env = dict(x=x, w=w, b=b)
    (out,) = Executor("ila", engine="pipelined").run_many(prog, [env])
    want = x @ w.T + (b if biased else 0)
    assert np.linalg.norm(np.asarray(out) - want) / np.linalg.norm(want) < 0.05


def test_vta_guards_admit_what_their_planners_hold():
    """K = 1024 still fits one row tile's input SRAM; an add of rows wider
    than 512 (2 * 32 accumulator tiles) and a relu wider than 1024 stay on
    the host."""
    _, ops = _offloads(ir.dense(ir.Var("x", (4, 1024)), ir.Var("w", (8, 1024))))
    assert ops == {"vta_gemm": 1}
    a, b = ir.Var("a", (4, 2048)), ir.Var("b", (4, 2048))
    assert _offloads(ir.add(a, b))[1] == {}
    assert _offloads(ir.add(ir.Var("a", (4, 512)), ir.Var("b", (4, 512))))[1] == {"vta_add": 1}
    assert _offloads(ir.call("relu", ir.Var("r", (4, 1040))))[1] == {}


def test_vta_gemm_chunks_wide_outputs_to_fit_its_accumulators():
    """Small K with many rows and outputs: the planner keeps every chunk
    within the accumulators and DRAM (this used to assert), exactly."""
    rng = np.random.default_rng(1)
    a = rng.integers(-50, 50, (300, 16)).astype(np.float32)
    b = rng.integers(-50, 50, (300, 16)).astype(np.float32)
    ctx = PlanContext(record=lambda *args, **kw: None)
    jobs, asm = vt.plan_gemm(ctx, None, [a, b])
    out = asm([np.asarray(j.read(vt.vta.run_data(j.data, state=j.frag.setup_state())))[j.window]
               for j in jobs])
    sa = np.abs(a).max() / 127.0
    sb = np.abs(b).max() / 127.0
    a8, b8 = np.clip(np.round(a / sa), -127, 127), np.clip(np.round(b / sb), -127, 127)
    np.testing.assert_allclose(out, (a8 @ b8.T) * sa * sb, rtol=1e-5)
