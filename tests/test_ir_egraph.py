"""IR + e-graph equality saturation tests (flexible matching core)."""
import numpy as np
import pytest

from repro.core import ir
from repro.core.compile import SelectionPolicy, compile_program
from repro.core.egraph import EGraph

rng = np.random.default_rng(0)


def _env(**kw):
    return {k: v.astype(np.float32) for k, v in kw.items()}


class TestIR:
    def test_shape_inference_dense(self):
        a = ir.Var("a", (4, 8))
        w = ir.Var("w", (16, 8))
        assert ir.infer_shape(ir.dense(a, w)) == (4, 16)

    def test_shape_inference_conv(self):
        x = ir.Var("x", (1, 8, 8, 3))
        w = ir.Var("w", (3, 3, 3, 16))
        assert ir.infer_shape(ir.conv2d(x, w, (2, 2), (1, 1))) == (1, 4, 4, 16)

    def test_interpreter_matches_numpy(self):
        a = ir.Var("a", (4, 8))
        w = ir.Var("w", (16, 8))
        b = ir.Var("b", (16,))
        e = ir.bias_add(ir.dense(a, w), b)
        env = _env(a=rng.standard_normal((4, 8)), w=rng.standard_normal((16, 8)),
                   b=rng.standard_normal((16,)))
        got = np.asarray(ir.interpret(e, env))
        want = env["a"] @ env["w"].T + env["b"]
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)

    def test_windows_reduce(self):
        T = ir.Var("T", (8, 6))
        e = ir.call("reduce_max", ir.call("windows", T, wh=2, ww=1, sh=2, sw=1), axis=(2, 3))
        env = _env(T=rng.standard_normal((8, 6)))
        got = np.asarray(ir.interpret(e, env))
        want = env["T"].reshape(4, 2, 6).max(1)
        np.testing.assert_allclose(got, want)


class TestEGraph:
    def test_union_find_congruence(self):
        # f(a) and f(b) merge when a == b (congruence closure)
        from repro.core.egraph import ENode, op_head

        eg = EGraph()
        a = eg.add(ENode(("var", "a", (2, 2), "float32")))
        b = eg.add(ENode(("var", "b", (2, 2), "float32")))
        fa = eg.add(ENode(op_head("relu", ()), (a,)))
        fb = eg.add(ENode(op_head("relu", ()), (b,)))
        assert eg.find(fa) != eg.find(fb)
        eg.merge(a, b)
        eg.rebuild()
        assert eg.find(fa) == eg.find(fb)

    def test_linear_reshape_flexible_match(self):
        a = ir.Var("a", (4, 8))
        b = ir.Var("b", (16, 8))
        c = ir.Var("c", (16,))
        prog = ir.call("add", ir.reshape(ir.dense(a, b), (4, 16)), c)
        res_exact = compile_program(prog, targets=("flexasr",), flexible=False)
        res_flex = compile_program(prog, targets=("flexasr",), flexible=True)
        assert res_exact.accelerator_calls["flexasr"] == 0
        assert res_flex.accelerator_calls["flexasr"] == 1

    def test_conv_im2col_emergent_vta_offload(self):
        """The paper's emergent effect: conv2d offloads to VTA though no
        conv mapping exists — via the im2col compiler-IR rewrite."""
        x = ir.Var("x", (1, 8, 8, 3))
        w = ir.Var("w", (3, 3, 3, 16))
        prog = ir.conv2d(x, w, (1, 1), (0, 0))
        res = compile_program(prog, targets=("vta",), flexible=True)
        assert res.accelerator_calls["vta"] >= 1
        env = _env(x=rng.standard_normal((1, 8, 8, 3)), w=rng.standard_normal((3, 3, 3, 16)))
        np.testing.assert_allclose(
            np.asarray(ir.interpret(prog, env)),
            np.asarray(ir.interpret(res.program, env)),
            rtol=1e-3, atol=1e-4,
        )

    def test_maxpool_figure7_store_load_cancellation(self):
        """Figure 7: (4,4)/(2,2) maxpool -> 4 temporal poolings with exactly
        one store and one load after transfer cancellation."""
        T = ir.Var("T", (64, 64))
        prog = ir.call("reduce_max", ir.call("windows", T, wh=4, ww=4, sh=2, sw=2), axis=(2, 3))
        res = compile_program(prog, targets=("flexasr",), flexible=True, iters=14)
        assert res.accelerator_calls["flexasr"] == 4
        assert ir.count_ops(res.program, lambda c: c.op == "fasr_store") == 1
        assert ir.count_ops(res.program, lambda c: c.op == "fasr_load") == 1
        env = _env(T=rng.standard_normal((64, 64)))
        np.testing.assert_allclose(
            np.asarray(ir.interpret(prog, env)),
            np.asarray(ir.interpret(res.program, env)),
        )

    def test_extraction_preserves_semantics_all_apps(self):
        from repro.core import apps

        for name, (builder, _) in apps.APPLICATIONS.items():
            expr, params = builder()
            res = compile_program(expr, flexible=True)
            env = dict(params)
            xshape = next(v for v in ir.postorder(expr)
                          if isinstance(v, ir.Var) and v.name == "x").shape
            env["x"] = rng.standard_normal(xshape).astype(np.float32)
            r1 = np.asarray(ir.interpret(expr, env))
            r2 = np.asarray(ir.interpret(res.program, env))
            np.testing.assert_allclose(r1, r2, rtol=1e-3, atol=1e-3, err_msg=name)

    def test_cost_driven_selection_and_policy_overrides(self):
        """A bare dense is claimed by two targets (vta_gemm directly;
        fasr_linear via the dense+0-bias introduction): the default policy
        picks by CostModel, ``forbid``/``prefer`` re-route the mapping, and
        every variant preserves semantics."""
        a = ir.Var("a", (4, 32))
        w = ir.Var("w", (16, 32))
        prog = ir.dense(a, w)
        env = _env(a=rng.standard_normal((4, 32)),
                   w=rng.standard_normal((16, 32)))
        ref = np.asarray(ir.interpret(prog, env))
        cases = [
            (None, "vta"),
            (SelectionPolicy(forbid=("vta",)), "flexasr"),
            (SelectionPolicy(prefer=("flexasr",)), "flexasr"),
        ]
        for policy, winner in cases:
            res = compile_program(prog, targets=("flexasr", "vta"), policy=policy)
            other = "flexasr" if winner == "vta" else "vta"
            assert res.accelerator_calls[winner] == 1, (policy, res.accelerator_calls)
            assert res.accelerator_calls[other] == 0, (policy, res.accelerator_calls)
            assert res.stats["extraction"]["op_wins"].get(winner) == 1
            np.testing.assert_allclose(
                ref, np.asarray(ir.interpret(res.program, env)), rtol=1e-4, atol=1e-4)

    def test_extract_failure_reports_diagnostics(self):
        """The extraction error names the unresolved e-class, its candidate
        heads, and the targets consulted (satellite: debuggable failures)."""
        a = ir.Var("a", (4, 32))
        w = ir.Var("w", (16, 32))
        c = ir.Var("c", (16,))
        prog = ir.call("fasr_linear", a, w, c)
        with pytest.raises(RuntimeError) as exc:
            compile_program(prog, targets=("vta",))
        msg = str(exc.value)
        assert "fasr_linear" in msg
        assert "registered targets consulted" in msg
        assert "resolved" in msg

    def test_guard_blocks_oversized_linear(self):
        # a linear beyond FlexASR's 128-wide SRAM maps to fasr_linear, which
        # the planner tiles; attention wider than one invocation, which no
        # planner tiles, is still blocked
        a = ir.Var("a", (4, 512))
        b = ir.Var("b", (512, 512))
        c = ir.Var("c", (512,))
        prog = ir.bias_add(ir.dense(a, b), c)
        res = compile_program(prog, targets=("flexasr",), flexible=False)
        assert res.accelerator_calls["flexasr"] == 1
        q = ir.Var("q", (8, 192))
        res = compile_program(ir.call("attention", q, q, q), targets=("flexasr",),
                              flexible=False)
        assert res.accelerator_calls["flexasr"] == 0


@pytest.mark.parametrize("seed", range(4))
def test_property_random_linear_programs_preserved(seed):
    """Property: compilation preserves semantics on random DAGs of
    supported ops."""
    r = np.random.default_rng(seed)
    d = int(r.integers(4, 32))
    a = ir.Var("a", (4, d))
    w1 = ir.Var("w1", (d, d))
    c1 = ir.Var("c1", (d,))
    e = ir.bias_add(ir.dense(a, w1), c1)
    for i in range(int(r.integers(1, 4))):
        op = ["relu", "tanh", "sigmoid"][int(r.integers(3))]
        e = ir.call(op, e)
        w = ir.Var(f"w{i+2}", (d, d))
        c = ir.Var(f"c{i+2}", (d,))
        e = ir.bias_add(ir.dense(e, w), c)
    res = compile_program(e, flexible=True)
    env = {v.name: r.standard_normal(v.shape).astype(np.float32)
           for v in ir.postorder(e) if isinstance(v, ir.Var)}
    np.testing.assert_allclose(
        np.asarray(ir.interpret(e, env)),
        np.asarray(ir.interpret(res.program, env)),
        rtol=1e-4, atol=1e-4,
    )
