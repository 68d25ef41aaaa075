"""A Moonlight-shaped (DeepSeek-V3) stack on the co-simulation path, at a
small size on the CPU: layer 0 dense, then one MoE layer (hidden 256, 2
heads of 32 + 16 for q/k and 32 for v, latent 64, 8 routed experts of
width 192 of which 4 are held, top-2, 1 shared expert, a 160-id
vocabulary). Widths over 128 exercise the tiled FlexASR linear's K-split
and output tiles."""
import importlib.util
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.accel import flexasr as F
from repro.core import ir
from repro.core.codegen import Executor
from repro.core.compile import compile_program
from repro.core.serving import CosimServer
from repro.core.telemetry import TELEMETRY

CONFIGS = Path(__file__).resolve().parents[1] / "bench" / "configs"
SMALL = dict(hidden_size=256, num_attention_heads=2, qk_nope_head_dim=32,
             qk_rope_head_dim=16, v_head_dim=32, kv_lora_rank=64,
             router_experts=8, n_routed_experts=4, experts_held=[0, 4],
             num_experts_per_tok=2, moe_intermediate_size=192,
             n_shared_experts=1, intermediate_size=384, vocab_size=160,
             num_hidden_layers=2, seq_len=16)
#: AF-8 keeps 4 mantissa bits (up to 2**-5 relative rounding per value);
#: each linear rounds its weights, inputs and outputs, about ten deep, and a
#: token whose AF-8 router scores reorder a near tie takes another expert
AF8_TOL = 0.3


def _load(name):
    spec = importlib.util.spec_from_file_location(name, CONFIGS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load("moonlight_16b_a3b_reference")
APP = _load("moonlight_16b_a3b_app")


def _cfg(**over):
    cfg = json.loads((CONFIGS / "moonlight_16b_a3b.json").read_text())
    cfg.update(SMALL, **over)
    return cfg


def _params(cfg, seed=3):
    p = jax.jit(lambda k: REF.init_params(k, cfg))(jax.random.key(seed))
    return {k: np.asarray(v) for k, v in p.items()}


def _reference(params, cfg, xs, precision="float32"):
    with jax.default_matmul_precision("highest"):
        fwd = jax.jit(lambda p, x: REF.forward(p, x, cfg, precision))
        return np.asarray(fwd({k: jnp.asarray(v) for k, v in params.items()}, np.stack(xs)))


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.linalg.norm(a - b) / np.linalg.norm(b)


@pytest.fixture(scope="module")
def stack():
    cfg = _cfg()
    params = _params(cfg)
    expr = APP.build(cfg)
    weights = APP.program_weights(params, cfg)
    rng = np.random.default_rng(0)
    xs = [REF.draw_ids(rng, (cfg["seq_len"],), cfg["vocab_size"]) for _ in range(2)]
    program = compile_program(expr).program
    return cfg, params, expr, weights, xs, program


@pytest.fixture(scope="module")
def served(stack):
    """The matched program served by a CosimServer (pipelined engine), with
    the program's spans recorded."""
    cfg, params, expr, weights, xs, program = stack
    srv = CosimServer(engine="pipelined", queue_depth=4, max_batch=2)
    srv.add_program("moe", program, weights)
    srv.start(warmup=0)
    TELEMETRY.enable()
    TELEMETRY.reset()
    try:
        outs = srv.submit("moe", envs=[dict(weights, x=x) for x in xs]).result(600)
        spans = TELEMETRY.spans()
    finally:
        TELEMETRY.disable()
        srv.close()
    counters = {m["name"]: m["value"] for m in srv.metrics.snapshot()
                if m["type"] == "counter"}
    return np.stack([np.asarray(o) for o in outs]), spans, counters


def test_a_served_matches_the_reference_and_int4_does_not(stack, served):
    cfg, params, _, _, xs, _ = stack
    ref = _reference(params, cfg, xs)
    got = served[0]
    errs = [_rel(g, r) for g, r in zip(got, ref)]
    assert max(errs) < AF8_TOL, errs
    low = _reference(params, cfg, xs, "int4")
    assert min(_rel(l, r) for l, r in zip(low, ref)) > AF8_TOL


def test_b_program_is_the_reference_in_float32(stack):
    cfg, params, expr, weights, xs, _ = stack
    ref = _reference(params, cfg, xs)
    with jax.default_matmul_precision("highest"):
        got = [np.asarray(ir.interpret(expr, dict(weights, x=x))) for x in xs]
    assert max(_rel(g, r) for g, r in zip(got, ref)) < 1e-5


def _moe_block(cfg, held):
    """The MoE ops of one layer over an input m: routed experts only."""
    T, D = cfg["seq_len"], cfg["hidden_size"]
    m = ir.Var("m", (T, D))
    logits = ir.Var("logits", (T, cfg["router_experts"]))
    w = ir.call("moe_route", logits, ir.Var("bias", (cfg["router_experts"],)),
                top_k=cfg["num_experts_per_tok"], scale=cfg["routed_scaling_factor"],
                held=tuple(held))
    ys = [ir.mul(ir.call("moe_gather", m, w, expert=j, rows=8),
                 ir.Var(f"s{e}", (D,))) for j, e in enumerate(range(*held))]
    return ir.call("moe_combine", w, *ys)


def test_c_expert_shares_add_up_to_the_uncut_layer():
    """Two 4-expert shares, with the shared expert counted once, give the
    layer as 8 held experts give it: in the reference and in the IR."""
    full, a, b = _cfg(experts_held=[0, 8], n_routed_experts=8), _cfg(), \
        _cfg(experts_held=[4, 8])
    params = _params(full)
    rng = np.random.default_rng(1)
    m = rng.standard_normal((3, full["seq_len"], full["hidden_size"])).astype(np.float32)
    p = "model.layers.1.mlp."

    def block(cfg, shared):
        net = REF._Net({k: jnp.asarray(v) for k, v in params.items()}, cfg, "float32")
        with jax.default_matmul_precision("highest"):
            out = net.routed(jnp.asarray(m), p)
            return np.asarray(out + net.mlp(jnp.asarray(m), p + "shared_experts")) if shared \
                else np.asarray(out)

    np.testing.assert_allclose(block(a, True) + block(b, False), block(full, True),
                               rtol=1e-5, atol=1e-5)
    env = {"m": m[0], "logits": rng.standard_normal((full["seq_len"], 8)).astype(np.float32),
           "bias": np.zeros(8, np.float32),
           **{f"s{e}": rng.standard_normal(full["hidden_size"]).astype(np.float32)
              for e in range(8)}}
    parts = [np.asarray(ir.interpret(_moe_block(full, h), env)) for h in ((0, 4), (4, 8))]
    np.testing.assert_allclose(parts[0] + parts[1],
                               np.asarray(ir.interpret(_moe_block(full, (0, 8)), env)),
                               rtol=1e-6, atol=1e-6)


def _numpy_route(logits, bias, k, scale):
    s = 1.0 / (1.0 + np.exp(-logits.astype(np.float64)))
    top = np.argsort(-(s + bias), axis=-1, kind="stable")[:, :k]
    w = np.take_along_axis(s, top, axis=-1)
    w = w / w.sum(-1, keepdims=True) * scale
    full = np.zeros_like(s)
    np.put_along_axis(full, top, w, axis=-1)
    return full


def test_d_routing_matches_numpy_top_k_with_an_unused_expert():
    cfg = _cfg()
    rng = np.random.default_rng(2)
    T, E = cfg["seq_len"], cfg["router_experts"]
    logits = rng.standard_normal((T, E)).astype(np.float32)
    bias = (rng.standard_normal(E) * 0.02).astype(np.float32)
    bias[2] = -10.0  # held expert 2 is never chosen
    want = _numpy_route(logits, bias, cfg["num_experts_per_tok"],
                        cfg["routed_scaling_factor"])[:, 0:4]
    w = ir.interpret(ir.call("moe_route", ir.Var("l", (T, E)), ir.Var("b", (E,)),
                             top_k=cfg["num_experts_per_tok"],
                             scale=cfg["routed_scaling_factor"], held=(0, 4)),
                     {"l": logits, "b": bias})
    np.testing.assert_allclose(np.asarray(w), want, rtol=1e-5, atol=1e-7)
    assert not np.asarray(w)[:, 2].any()
    x = rng.standard_normal((T, 8)).astype(np.float32)
    rows = np.asarray(ir.interpret(ir.call("moe_gather", ir.Var("x", (T, 8)),
                                           ir.Var("w", (T, 4)), expert=2, rows=8),
                                   {"x": x, "w": want}))
    assert rows.shape == (0, 8)
    got = np.asarray(ir.interpret(ir.call("moe_gather", ir.Var("x", (T, 8)),
                                          ir.Var("w", (T, 4)), expert=1, rows=8),
                                  {"x": x, "w": want}))
    n = int((want[:, 1] > 0).sum())
    assert got.shape[0] % 8 == 0 and not got[n:].any()
    np.testing.assert_array_equal(got[:n], x[want[:, 1] > 0])


def test_d_an_expert_with_no_rows_is_served(stack):
    """The served path with a held expert that no token chooses: its
    linears and gating see zero rows and invoke nothing."""
    cfg, params, _, weights, xs, program = stack
    weights = dict(weights)
    bias = np.array(weights["model.layers.1.mlp.gate.e_score_correction_bias"])
    bias[3] = -10.0
    weights["model.layers.1.mlp.gate.e_score_correction_bias"] = bias
    ex = Executor("ila", engine="pipelined")
    outs = ex.run_many(program, [dict(weights, x=x) for x in xs])
    p = dict(params, **{"model.layers.1.mlp.gate.e_score_correction_bias": bias})
    ref = _reference(p, cfg, xs)
    assert max(_rel(o, r) for o, r in zip(outs, ref)) < AF8_TOL
    routed = ex.metrics.find("moe.routed_rows")[0].value
    assert 0 < routed < len(xs) * cfg["seq_len"] * cfg["num_experts_per_tok"]


@pytest.fixture(scope="module")
def wide():
    rng = np.random.default_rng(4)
    w = (rng.standard_normal((200, 300)) * 0.05).astype(np.float32)
    b = (rng.standard_normal(200) * 0.1).astype(np.float32)
    return rng.standard_normal((150, 300)).astype(np.float32), w, b


def test_e_tile_setup_state_is_its_setup_stream(wide):
    """Every tile's post-setup state (edge tiles 72 x 44 included) equals
    what the eager ILA leaves after the tile's own setup stream."""
    _, w, b = wide
    tl = F.TiledLinear(w, b)
    assert (tl.meta["ot"], tl.meta["kt"]) == (2, 3)
    for t in range(tl.n_tiles):
        want = F.flexasr.simulate(tl.tile_setup(t).setup.to_commands())
        got = F.tiled_setup_state(tl, t)
        assert set(got) == set(want)
        for k in want:
            np.testing.assert_array_equal(np.asarray(got[k]), np.asarray(want[k]), err_msg=k)


def test_e_tiled_linear_is_its_tiles_summed_in_k_order(wide):
    """The runner's output is each tile's own LinearLayer invocation,
    simulated command by command, summed over K in order, each with the
    output window the driver sizes from its partial product."""
    x, w, b = wide

    class Ctx:
        record = staticmethod(lambda *a, **k: None)
        count = staticmethod(lambda *a: None)

    (job,), _ = F.plan_tiled_linear(Ctx, None, [x, w, b])
    tl, d = job.frag, job.data
    res = np.asarray(tl.run(d))
    got, (sq_err, sq_ideal) = res[:-1], res[-1, :2]
    for c in range(d.slots.shape[0]):
        rows = min(F.TILE, d.rows - c * F.TILE)
        for o in range(tl.meta["ot"]):
            acc = None
            for k in range(tl.meta["kt"]):
                t = o * tl.meta["kt"] + k
                frag = tl.tile_setup(t)
                wt, bt = tl.resident()[0][t], tl.resident()[1][t]
                bo, _ = F.output_window(jnp.asarray(d.slots[c, k]), wt, bt, rows)
                data = F.DataStream(
                    [F._matrix_bulk(F.BASE_IN, d.slots[c, k][:, : frag.meta["I"]])],
                    F._tail([(F.GB_CFG_GB_CONTROL, (F.MODE_LINEAR, F.TILE)),
                             (F.CFG_NUMERICS, (tl.meta["bw"][t], d.ba[c, k], float(bo))),
                             (F.FN_START, ())]))
                y = np.asarray(F.read_full(F.flexasr.simulate_jit(frag.full_commands(data))))
                acc = y if acc is None else acc + y
            blk = got[c * 128:(c + 1) * 128, o * 128:(o + 1) * 128]
            np.testing.assert_array_equal(blk, acc[:, : blk.shape[1]])
    err = _rel(got[:150, :200], x @ w.T + b)
    assert err < 0.05
    # the statistics the runner returns: squared error against the ideal
    # and squared ideal over the 150 real rows
    np.testing.assert_allclose(np.sqrt(sq_err / sq_ideal), err, rtol=1e-3)
    np.testing.assert_allclose(sq_ideal, np.sum((x @ w.T + b) ** 2), rtol=1e-4)


def test_f_narrow_linear_keeps_the_single_fragment_path():
    """A linear of width 128 or less (MNIST's 64 -> 32) plans one
    LinearLayer fragment per row chunk, as before tiling, and serves
    exactly what the eager ILA computes for it."""
    rng = np.random.default_rng(5)
    x = rng.standard_normal((16, 64)).astype(np.float32)
    w = (rng.standard_normal((32, 64)) * 0.1).astype(np.float32)
    b = (rng.standard_normal(32) * 0.1).astype(np.float32)
    jobs, _ = F.plan_linear(type("C", (), {"record": lambda *a, **k: None,
                                           "chunk_rows": staticmethod(lambda a, n: [a]),
                                           "ncmds": staticmethod(lambda j: 0)})(),
                            None, [x, w, b])
    (job,) = jobs
    assert type(job.frag) is F.CompiledFragment and job.frag.key[0] == "fasr_linear"
    want_data = F.pack_linear_data(job.frag, x)
    assert job.data.sig() == want_data.sig()
    np.testing.assert_array_equal(job.data.tail.data, want_data.tail.data)
    prog = ir.call("fasr_linear", ir.Var("x", (16, 64)), ir.Var("w", (32, 64)),
                   ir.Var("b", (32,)))
    out = Executor("ila", engine="pipelined").run_many(prog, [dict(x=x, w=w, b=b)] * 2)
    cmds, rd = F.build_linear_fragment(x, w, b)
    want = np.asarray(rd(F.flexasr.simulate(cmds)))
    for o in out:
        np.testing.assert_array_equal(np.asarray(o), want)


def test_g_spans_and_counters_follow_the_plan(stack, served):
    """One ``flexasr.tiled_linear`` span per tiled linear per request, the
    ``flexasr.linear_tiles`` counter at the tile invocations those spans
    dispatched, one ``moe.route`` span per MoE layer and sample, one
    ``moe.dispatch`` per held expert and per combine, and every routed row
    counted once."""
    cfg, _, _, _, xs, program = stack
    _, spans, counters = served
    names = [s["name"] for s in spans]
    shapes = {v.name: v.shape for v in ir.postorder(program) if isinstance(v, ir.Var)}
    tiled = [n for n in ir.postorder(program) if isinstance(n, ir.Call)
             and n.op == "fasr_linear" and max(shapes[n.args[1].name]) > F.TILE]
    moe_layers = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    held = cfg["experts_held"][1] - cfg["experts_held"][0]
    assert names.count("moe.route") == moe_layers * len(xs)
    assert names.count("moe.dispatch") == moe_layers * (held + 1) * len(xs)
    tl_spans = [s for s in spans if s["name"] == "flexasr.tiled_linear"]
    assert len(tl_spans) == len(tiled)
    want_tiles = sum(-(-shapes[n.args[1].name][0] // F.TILE)
                     * -(-shapes[n.args[1].name][1] // F.TILE) for n in tiled) * len(xs)
    assert counters["flexasr.linear_tiles"] == want_tiles
    assert sum(s["args"]["tiles"] for s in tl_spans) == want_tiles
    k = cfg["num_experts_per_tok"]
    assert 0 < counters["moe.routed_rows"] <= len(xs) * cfg["seq_len"] * k
