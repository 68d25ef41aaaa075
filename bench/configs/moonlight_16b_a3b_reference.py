"""Plain float32 reference of the ``moonlight_16b_a3b`` configuration, and
the weights and inputs the benchmark serves it with.

Moonlight-16B-A3B is the DeepSeek-V3 architecture (HF ``modeling_deepseek``,
``model_type`` ``deepseek_v3``), cut to one chip's share (the
configuration's ``reduced`` and ``deployment``). Each decoder layer:
RMSNorm -> multi-head latent attention (no query LoRA: ``q_proj`` gives 16
heads of 128 + 64 features; ``kv_a_proj_with_mqa`` gives the 512-wide
latent, RMS-normed by ``kv_a_layernorm``, and one 64-wide rotary key shared
by the heads; ``kv_b_proj`` gives each head's 128-wide key and value; the
rotary features de-interleaved, then rotated; causal softmax at scale
192^-0.5) -> residual -> RMSNorm -> the dense SiLU-gated MLP (layer 0) or
the MoE (sigmoid router over every routed expert, top-6 by score plus the
correction bias, the chosen scores normalised and scaled by 2.446; the
held experts' SiLU-gated MLPs weighted by them; the shared experts as one
MLP) -> residual. Then the final RMSNorm and ``lm_head``. The held experts
are run over every token and weighted by their combine weights, which is
zero for a token not routed to them: the same sum as routing. Imports
nothing of the program. Token ids come as float32 (exact below 2**24).
"""
from __future__ import annotations

import json
from pathlib import Path

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def held(cfg):
    lo, hi = cfg["experts_held"]
    return range(lo, hi)


def linear_shapes(cfg):
    """Every linear layer: name -> (out, in)."""
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    L = cfg["kv_lora_rank"]
    out = {}
    for l in range(cfg["num_hidden_layers"]):
        p = f"model.layers.{l}."
        out[p + "self_attn.q_proj"] = (H * (dn + dr), D)
        out[p + "self_attn.kv_a_proj_with_mqa"] = (L + dr, D)
        out[p + "self_attn.kv_b_proj"] = (H * (dn + dv), L)
        out[p + "self_attn.o_proj"] = (D, H * dv)
        if l < cfg["first_k_dense_replace"]:
            mlps = {p + "mlp": cfg["intermediate_size"]}
        else:
            out[p + "mlp.gate"] = (cfg["router_experts"], D)
            mlps = {p + f"mlp.experts.{e}": cfg["moe_intermediate_size"] for e in held(cfg)}
            mlps[p + "mlp.shared_experts"] = cfg["moe_intermediate_size"] * cfg["n_shared_experts"]
        for m, F in mlps.items():
            out[m + ".gate_proj"] = (F, D)
            out[m + ".up_proj"] = (F, D)
            out[m + ".down_proj"] = (D, F)
    out["lm_head"] = (cfg["vocab_size"], D)
    return out


def param_shapes(cfg):
    """Weight name -> shape, named as the HF checkpoint names them."""
    D = cfg["hidden_size"]
    shapes = {k + ".weight": v for k, v in linear_shapes(cfg).items()}
    shapes["model.embed_tokens.weight"] = (cfg["vocab_size"], D)
    shapes["model.norm.weight"] = (D,)
    for l in range(cfg["num_hidden_layers"]):
        p = f"model.layers.{l}."
        shapes[p + "input_layernorm.weight"] = (D,)
        shapes[p + "post_attention_layernorm.weight"] = (D,)
        shapes[p + "self_attn.kv_a_layernorm.weight"] = (cfg["kv_lora_rank"],)
        if l >= cfg["first_k_dense_replace"]:
            shapes[p + "mlp.gate.e_score_correction_bias"] = (cfg["router_experts"],)
    return shapes


def init_params(key, cfg):
    """Every weight from one key (jit it): linear and embedding weights
    N(0, initializer_range^2), as HF ``_init_weights`` draws them; norm
    gains and the router's correction bias as ``assumed`` says."""
    shapes = param_shapes(cfg)
    keys = dict(zip(sorted(shapes), jax.random.split(key, len(shapes))))
    a = cfg["assumed"]
    out = {}
    for name, shape in shapes.items():
        z = jax.random.normal(keys[name], shape, jnp.float32)
        if name.endswith("norm.weight"):
            out[name] = a["norm_gain"]["mean"] + a["norm_gain"]["std"] * z
        elif name.endswith("e_score_correction_bias"):
            out[name] = a["correction_bias"]["std"] * z
        else:
            out[name] = a["initializer_range"] * z
    return out


#: the vocabulary slice token ids are drawn from: the configuration's
#: ``vocab_size`` (the harness hands ``draw_input`` no configuration)
VOCAB = json.loads(Path(__file__).with_name("moonlight_16b_a3b.json").read_text())["vocab_size"]


def draw_ids(rng, shape, vocab):
    """Token ids uniform over ``[0, vocab)``, as float32 (exact below 2**24)."""
    return rng.integers(0, vocab, shape).astype("float32")


def draw_input(rng, shape):
    """One window of token ids, uniform over the configuration's
    vocabulary slice."""
    return draw_ids(rng, shape, VOCAB)


def _int4(v, axis=None):
    """Symmetric int4 rounding of ``v``, one scale per tensor (or per
    slice along ``axis``)."""
    m = jnp.max(jnp.abs(v), axis=axis, keepdims=axis is not None)
    s = jnp.where(m > 0, m / 7.0, 1.0)
    return jnp.clip(jnp.round(v / s), -7, 7) * s


def _rms_norm(x, g, eps):
    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)) * g


def _rope(x, theta):
    """DeepSeek-V3's rotary embedding of ``x`` (..., T, d) at positions
    0..T-1: de-interleave (even features, then odd), then ``x * cos +
    rotate_half(x) * sin``."""
    *lead, T, d = x.shape
    x = jnp.swapaxes(x.reshape(*lead, T, d // 2, 2), -1, -2).reshape(*lead, T, d)
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    f = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([f, f], axis=-1)
    rot = jnp.concatenate([-x[..., d // 2:], x[..., : d // 2]], axis=-1)
    return x * jnp.cos(ang) + rot * jnp.sin(ang)


class _Net:
    """The forward pass in one precision: ``lin`` is every linear layer,
    with the control's rounding where the accelerator holds 8 bits."""

    def __init__(self, params, cfg, precision):
        self.cfg = cfg
        self.dt = jnp.bfloat16 if precision == "bfloat16" else jnp.float32
        self.q = _int4 if precision == "int4" else (lambda v, axis=None: v)
        self.p = {k: v.astype(self.dt) for k, v in params.items()}

    def lin(self, x, name):
        w = self.q(self.p[name + ".weight"])
        y = jnp.einsum("...i,oi->...o", self.q(x, axis=-1), w, precision=HIGHEST)
        return self.q(y, axis=-1)

    def norm(self, x, name):
        return _rms_norm(x, self.p[name + ".weight"], self.cfg["rms_norm_eps"])

    def mlp(self, x, name):
        g, u = self.lin(x, name + ".gate_proj"), self.lin(x, name + ".up_proj")
        return self.lin(jax.nn.sigmoid(g) * g * u, name + ".down_proj")

    def attention(self, x, p):
        c = self.cfg
        H, dn, dr, dv = (c["num_attention_heads"], c["qk_nope_head_dim"],
                         c["qk_rope_head_dim"], c["v_head_dim"])
        L, T = c["kv_lora_rank"], x.shape[-2]
        q = self.lin(x, p + "q_proj").reshape(*x.shape[:-1], H, dn + dr)
        q = jnp.swapaxes(q, -2, -3)                                 # (..., H, T, 192)
        kva = self.lin(x, p + "kv_a_proj_with_mqa")
        ckv = self.norm(kva[..., :L], p + "kv_a_layernorm")
        k_pe = _rope(kva[..., None, :, L:], c["rope_theta"])      # (..., 1, T, 64)
        kv = jnp.swapaxes(self.lin(ckv, p + "kv_b_proj").reshape(
            *x.shape[:-1], H, dn + dv), -2, -3)                     # (..., H, T, 256)
        qh = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], c["rope_theta"])], -1)
        kh = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
            k_pe, kv.shape[:-1] + (dr,))], -1)
        s = jnp.einsum("...qd,...kd->...qk", qh, kh, precision=HIGHEST) / jnp.sqrt(
            jnp.asarray(dn + dr, self.dt))
        s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
        o = jnp.einsum("...qk,...kd->...qd", jax.nn.softmax(s, axis=-1),
                       kv[..., dn:], precision=HIGHEST)            # (..., H, T, 128)
        o = jnp.swapaxes(o, -2, -3).reshape(*x.shape[:-1], H * dv)
        return self.lin(o, p + "o_proj")

    def combine_weights(self, m, p):
        """The held experts' combine weights (..., T, held)."""
        c = self.cfg
        s = jax.nn.sigmoid(self.lin(m, p + "gate").astype(jnp.float32))
        _, top = jax.lax.top_k(s + self.p[p + "gate.e_score_correction_bias"],
                               c["num_experts_per_tok"])
        w = jnp.take_along_axis(s, top, axis=-1)
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * c["routed_scaling_factor"]
        full = jnp.sum(jax.nn.one_hot(top, s.shape[-1], dtype=w.dtype) * w[..., None], -2)
        lo, hi = c["experts_held"]
        return full[..., lo:hi].astype(self.dt)

    def routed(self, m, p):
        """What the held experts add: each expert's MLP over every token,
        weighted by its combine weight (zero where not routed)."""
        w = self.combine_weights(m, p)
        return sum(w[..., j, None] * self.mlp(m, p + f"experts.{e}")
                   for j, e in enumerate(held(self.cfg)))

    def layer(self, h, l):
        p = f"model.layers.{l}."
        h = h + self.attention(self.norm(h, p + "input_layernorm"), p + "self_attn.")
        m = self.norm(h, p + "post_attention_layernorm")
        if l < self.cfg["first_k_dense_replace"]:
            return h + self.mlp(m, p + "mlp")
        return h + self.routed(m, p + "mlp.") + self.mlp(m, p + "mlp.shared_experts")

    def __call__(self, x):
        h = self.p["model.embed_tokens.weight"][x.astype(jnp.int32)]
        for l in range(self.cfg["num_hidden_layers"]):
            h = self.layer(h, l)
        return self.lin(self.norm(h, "model.norm"), "lm_head")


def forward(params, x, cfg, precision="float32"):
    """Logits over the vocabulary slice of a batch of token windows ``x``
    (B, T) as float ids: (B, T, vocab_size).

    ``precision`` "float32" is the reference. The controls put the same
    network in a lower precision: "int4" rounds to int4 every value the
    accelerator holds in 8 bits (each linear layer's weights, one scale
    per tensor; its inputs and outputs, one scale per row); "bfloat16"
    computes in bfloat16."""
    return _Net(params, cfg, precision)(x).astype(jnp.float32)
