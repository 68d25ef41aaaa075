"""The ``moonlight_16b_a3b`` configuration: the application handed to the
flow computes the reference network (at a reduced copy of the
configuration, on the CPU), matching offloads what the configuration says
(at its own widths), the check fails the control and an altered answer,
and the cell's metric readers read what they should."""
import jax
import numpy as np
import pytest

from bench import cell as C
from bench import check

CELL = "moonlight_16b_a3b.ppl256"
#: the published widths scaled down: 2 layers (dense, then MoE), 2 heads,
#: 8 routed experts of which 4 are held, top-2, a 160-id vocabulary
SMALL = dict(hidden_size=256, num_attention_heads=2, qk_nope_head_dim=32,
             qk_rope_head_dim=16, v_head_dim=32, kv_lora_rank=64,
             router_experts=8, n_routed_experts=4, experts_held=[0, 4],
             num_experts_per_tok=2, moe_intermediate_size=192,
             n_shared_experts=1, intermediate_size=384, vocab_size=160,
             num_hidden_layers=2, seq_len=16)


@pytest.fixture(scope="module")
def parts():
    cell = C.resolve(CELL)
    cell.config.update(SMALL)
    app = C.load_module(cell.config_dir / cell.config["app"])
    params = C.make_params(cell, 2**33 + 15)
    rng = np.random.default_rng(7)
    xs = [cell.reference.draw_ids(rng, (SMALL["seq_len"],), SMALL["vocab_size"])
          for _ in range(4)]
    return cell, app, params, xs


def test_exported_program_is_the_reference(parts):
    from repro.core import ir

    cell, app, params, xs = parts
    expr, weights = app.build(cell.config), app.program_weights(params, cell.config)
    with jax.default_matmul_precision("highest"):
        got = np.stack([np.asarray(ir.interpret(expr, dict(weights, x=x))) for x in xs])
        ref = check.reference_outputs(cell, params, xs)
    assert np.max(check.rel_errors(got, ref)) < 1e-5


def test_matching_offloads_what_the_configuration_says():
    """At the configuration's own widths (matching needs no weights): every
    projection, expert and dense MLP and lm_head a tiled FlexASR linear,
    SiLU gating on VecUnit, no VTA GEMM."""
    from repro.core import ir
    from repro.core.compile import compile_program

    cell = C.resolve(CELL)
    app = C.load_module(cell.config_dir / cell.config["app"])
    program = compile_program(app.build(cell.config)).program
    ops = {}
    for n in ir.postorder(program):
        if isinstance(n, ir.Call) and ir.accel_op_target(n.op):
            ops[n.op] = ops.get(n.op, 0) + 1
    assert ops == cell.config["offloads"]


def test_cut_is_stated_beside_the_source():
    spec = C.load_spec()
    entry = next(c for c in spec["configs"] if c["name"] == "moonlight_16b_a3b")
    cfg = C.resolve(CELL).config
    assert set(entry["reduced"]) == set(cfg["reduced"])
    for key in entry["reduced"]:
        assert cfg["published"][key] != cfg[key]
    assert cfg["experts_held"][1] - cfg["experts_held"][0] == cfg["n_routed_experts"]
    assert cfg["deployment"]["expert_parallel"] * cfg["n_routed_experts"] == \
        cfg["published"]["n_routed_experts"] == cfg["router_experts"]


def _served(xs, outs):
    return {i: ([x], [o]) for i, (x, o) in enumerate(zip(xs, outs))}


def test_check_fails_the_control_and_an_altered_answer(parts):
    cell, _, params, xs = parts
    ref = check.reference_outputs(cell, params, xs)
    offloads = dict(cell.config["offloads"])
    ok = check.compare(cell, params, _served(xs, ref), offloads, 0, log=lambda *a, **k: None)
    assert check.passed(ok)
    low = check.reference_outputs(cell, params, xs, cell.config["control"]["precision"])
    bad = check.compare(cell, params, _served(xs, low), offloads, 0, log=lambda *a, **k: None)
    assert not check.passed(bad)
    altered = [np.array(r) for r in ref]
    altered[2][5] = -altered[2][5]
    bad = check.compare(cell, params, _served(xs, altered), offloads, 0,
                        log=lambda *a, **k: None)
    assert not check.passed(bad)


def _ctx(spans=(), counters=None, trace=None, samples=4):
    return C.Context(setup_s=1.0, window_s=2.0, samples_done=samples,
                     requests_done=samples // 2, counters=counters or {},
                     spans=list(spans), trace=trace, flops_per_sample=0,
                     peak={"bf16_flops_per_s": 197e12})


def test_span_readers():
    spans = [{"name": "moe.route", "ts": 0.0, "dur": 2000.0},
             {"name": "moe.dispatch", "ts": 0.0, "dur": 6000.0},
             {"name": "flexasr.tiled_linear", "ts": 0.0, "dur": 12000.0},
             {"name": "pipeline.pack", "ts": 0.0, "dur": 9e6}]
    moe = C.metric_reader("executor.moe_ms_per_sample.ppl256")
    tiled = C.metric_reader("sim.tiled_linear_ms_per_sample.ppl256")
    assert moe(_ctx(spans)) == pytest.approx(8.0 / 4)
    assert tiled(_ctx(spans)) == pytest.approx(12.0 / 4)
    assert moe(_ctx(spans[2:])) is None and tiled(_ctx(spans[:2])) is None


def test_work_readers():
    work = C.load_module(C.ROOT / "bench" / "configs" / "moonlight_16b_a3b_work.py")
    # the forecast: 142.8 GFLOP a window, routed experts at 0.75 per token
    assert work.expected_flops_per_window() == pytest.approx(142.8e9, rel=1e-3)
    rows = 4 * 256 * 0.75 * 4.0  # 4 windows, 4 MoE layers
    busy = 0.5
    roof = C.metric_reader("flexasr.tiled_linear_roofline.ppl256")
    mfu = C.metric_reader("cosim.mfu.moonlight_16b_a3b.ppl256")
    ctx = _ctx(counters={"moe.routed_rows": rows}, trace={"busy_s": busy})
    macs = work.tiled_linear_macs(4, rows)
    assert roof(ctx) == pytest.approx(100 * 2 * macs / busy / 197e12)
    assert mfu(ctx) == pytest.approx(
        100 * 2 * (macs + 4 * work.attention_macs_per_window()) / 2.0 / 197e12)
    empty = _ctx(samples=0)
    assert roof(empty) is None and mfu(empty) is None
    assert roof(_ctx(counters={"moe.routed_rows": rows})) is None  # untraced
    assert mfu(_ctx()) is None  # a program without the counter


def test_new_metrics_are_listed_for_the_cell_only():
    names = {m["name"] for m in C.resolve(CELL).per_layer}
    new = {"executor.moe_ms_per_sample.ppl256", "sim.tiled_linear_ms_per_sample.ppl256",
           "flexasr.tiled_linear_roofline.ppl256", "cosim.mfu.moonlight_16b_a3b.ppl256"}
    assert new <= names
    assert not new & {m["name"] for m in C.resolve("mnist_rnn.closed").per_layer}
