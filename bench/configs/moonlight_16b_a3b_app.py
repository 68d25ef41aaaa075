"""The ``moonlight_16b_a3b`` network as the application a designer hands to
the co-simulation flow: its IR program over one window of token ids, and
its weights as that program takes them.

What the export does on the way, all exact:

* each bias-free projection gets a zero bias input, so every linear layer
  is FlexASR's LinearLayer pattern ``bias_add(dense(x, w), b)``;
* attention runs per head in 2-D: each head's query, key and value are
  static slices of the published ``q_proj``, ``kv_a_proj_with_mqa`` and
  ``kv_b_proj`` outputs (no matrix is split), the rotary parts rotated by
  ``rope`` with DeepSeek-V3's de-interleave, the heads concatenated into
  ``o_proj``;
* SiLU is ``g * sigmoid(g)``; the two shared experts are one MLP of twice
  the width, as the checkpoint stores them;
* the MoE layer routes over every routed expert (``moe_route``), and
  computes only the held experts, each on the rows routed to it
  (``moe_gather``, zero rows appended up to a multiple of 128 so that the
  expert's operands keep one shape: their outputs are dropped), weighted
  and scattered back (``moe_combine``).
"""
from __future__ import annotations

import numpy as np

#: each expert's routed rows are padded with zero rows to a multiple of
#: this, so its operands take one shape whatever the routing: one FlexASR
#: row chunk (a held expert sees about 24 of a window's 256 tokens)
EXPERT_ROWS = 128


def build(cfg):
    """The application's IR over one window (before flexible matching)."""
    from repro.core import ir

    T, D, V = cfg["seq_len"], cfg["hidden_size"], cfg["vocab_size"]
    H, dn, dr, dv = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                     cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    L, eps, theta = cfg["kv_lora_rank"], cfg["rms_norm_eps"], cfg["rope_theta"]
    lo, hi = cfg["experts_held"]
    shapes = _linear_shapes(cfg)

    def lin(x, name):
        O, I = shapes[name]
        return ir.bias_add(ir.dense(x, ir.Var(name + ".weight", (O, I))),
                           ir.Var(name + ".zero_bias", (O,)))

    def norm(x, name, width):
        return ir.call("rms_norm", x, ir.Var(name + ".weight", (width,)), eps=eps)

    def cols(x, begin, end):
        return ir.call("slice", x, axis=1, begin=begin, end=end)

    def rope(x):
        return ir.call("rope", x, theta=theta, interleaved=True)

    def mlp(x, name):
        g, u = lin(x, name + ".gate_proj"), lin(x, name + ".up_proj")
        return lin(ir.mul(ir.mul(g, ir.call("sigmoid", g)), u), name + ".down_proj")

    h = ir.call("embedding", ir.Var("model.embed_tokens.weight", (V, D)),
                ir.Var("x", (T,)))
    for l in range(cfg["num_hidden_layers"]):
        p = f"model.layers.{l}."
        a = norm(h, p + "input_layernorm", D)
        q = lin(a, p + "self_attn.q_proj")
        kva = lin(a, p + "self_attn.kv_a_proj_with_mqa")
        kv = lin(norm(cols(kva, 0, L), p + "self_attn.kv_a_layernorm", L),
                 p + "self_attn.kv_b_proj")
        k_pe = rope(cols(kva, L, L + dr))
        heads = []
        for i in range(H):
            qb, kb = i * (dn + dr), i * (dn + dv)
            qh = ir.call("concat", cols(q, qb, qb + dn),
                         rope(cols(q, qb + dn, qb + dn + dr)), axis=1)
            kh = ir.call("concat", cols(kv, kb, kb + dn), k_pe, axis=1)
            heads.append(ir.call("attention", qh, kh, cols(kv, kb + dn, kb + dn + dv),
                                 causal=True))
        h = ir.add(h, lin(ir.call("concat", *heads, axis=1), p + "self_attn.o_proj"))
        m = norm(h, p + "post_attention_layernorm", D)
        if l < cfg["first_k_dense_replace"]:
            y = mlp(m, p + "mlp")
        else:
            w = ir.call("moe_route", lin(m, p + "mlp.gate"),
                        ir.Var(p + "mlp.gate.e_score_correction_bias", (cfg["router_experts"],)),
                        top_k=cfg["num_experts_per_tok"],
                        scale=cfg["routed_scaling_factor"], held=(lo, hi))
            ys = [mlp(ir.call("moe_gather", m, w, expert=j, rows=EXPERT_ROWS),
                      p + f"mlp.experts.{e}") for j, e in enumerate(range(lo, hi))]
            y = ir.add(ir.call("moe_combine", w, *ys), mlp(m, p + "mlp.shared_experts"))
        h = ir.add(h, y)
    return lin(norm(h, "model.norm", D), "lm_head")


def _linear_shapes(cfg):
    from importlib import util
    from pathlib import Path

    path = Path(__file__).with_name(cfg["reference"])
    spec = util.spec_from_file_location("moonlight_reference", path)
    ref = util.module_from_spec(spec)
    spec.loader.exec_module(ref)
    return ref.linear_shapes(cfg)


def program_weights(params, cfg):
    """The program's inputs from the reference's weights: the same arrays,
    plus a zero bias per linear layer; frozen (read-only), as served
    weights are, so the tiled FlexASR linears recognise them per request
    without hashing them."""
    out = {k: np.array(v, np.float32) for k, v in params.items()}
    for name, (O, _I) in _linear_shapes(cfg).items():
        out[name + ".zero_bias"] = np.zeros((O,), np.float32)
    for v in out.values():
        v.setflags(write=False)
    return out
