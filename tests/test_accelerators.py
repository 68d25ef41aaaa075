"""Accelerator ILA tests: custom numerics, simulators, VT checks."""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st  # property tests skip if absent

from repro.accel import flexasr as fa, hlscnn as hc, numerics, vta as vt
from repro.core import ir, validate

rng = np.random.default_rng(0)


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

    def test_fixed_point_grid(self):
        spec = numerics.FixedPointSpec(8, 3)
        x = jnp.asarray([0.124, -0.3, 5.0, 100.0])
        q = np.asarray(numerics.fx_quantize(x, spec))
        np.testing.assert_allclose(q * 8, np.round(q * 8))   # on the 2^-3 grid
        assert q[3] == spec.qmax / spec.scale                # saturates


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
