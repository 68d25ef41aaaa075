"""Multiply-accumulates of the ``moonlight_16b_a3b`` configuration, counted
from its widths (``moonlight_16b_a3b.json`` beside this file): what the
metric readers of its cell divide by device time or multiply by rate.

A window of ``seq_len`` tokens runs every linear layer on every token,
except the routed experts, which run on the rows routed to them (the
``moe.routed_rows`` counter); causal attention takes, per head and layer,
``sum_t (t + 1) * (qk_head + v_head)`` for the scores and the weighted sum.
"""
from __future__ import annotations

import json
from pathlib import Path

CONFIG = json.loads(Path(__file__).with_name("moonlight_16b_a3b.json").read_text())


def linear_macs_per_token(cfg=CONFIG) -> int:
    """Every linear layer a token passes through, routed experts left out."""
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    L, Fs = cfg["kv_lora_rank"], cfg["moe_intermediate_size"] * cfg["n_shared_experts"]
    attn = D * H * (dn + dr) + D * (L + dr) + L * H * (dn + dv) + H * dv * D
    dense = cfg["first_k_dense_replace"]
    moe = cfg["num_hidden_layers"] - dense
    return (cfg["num_hidden_layers"] * attn + dense * 3 * D * cfg["intermediate_size"]
            + moe * (D * cfg["router_experts"] + 3 * D * Fs)
            + D * cfg["vocab_size"])


def expert_macs_per_row(cfg=CONFIG) -> int:
    """One routed row through one expert's gate, up and down projections."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def attention_macs_per_window(cfg=CONFIG) -> int:
    T = cfg["seq_len"]
    per_head = T * (T + 1) // 2 * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
                                   + cfg["v_head_dim"])
    return per_head * cfg["num_attention_heads"] * cfg["num_hidden_layers"]


def tiled_linear_macs(samples: int, routed_rows: float, cfg=CONFIG) -> float:
    """The tiled FlexASR linears' work over ``samples`` answered windows
    whose experts were sent ``routed_rows`` rows in all."""
    return (samples * cfg["seq_len"] * linear_macs_per_token(cfg)
            + routed_rows * expert_macs_per_row(cfg))


def expected_flops_per_window(cfg=CONFIG) -> float:
    """Forecast: routed experts at their expected share, num_experts_per_tok
    of router_experts, times the experts held."""
    held = cfg["experts_held"][1] - cfg["experts_held"][0]
    moe_layers = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    rows = (moe_layers * cfg["seq_len"] * cfg["num_experts_per_tok"] * held
            / cfg["router_experts"])
    return 2.0 * (tiled_linear_macs(1, rows, cfg) + attention_macs_per_window(cfg))
