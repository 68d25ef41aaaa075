"""Plain float32 reference of the ``mnist_rnn`` configuration, and the
weights and inputs the benchmark serves it with.

The PyTorch examples' ``mnist_rnn`` network, evaluated as published: an
LSTM over the image's rows (input 28, hidden 64, one layer), its last
step, batch norm with running statistics, dropout (the identity in
evaluation), ``fc1`` 64 -> 32, ReLU, ``fc2`` 32 -> 10. The log-softmax the
example applies last is left out: it shifts each row of logits by one
number and is not part of what is served. Imports nothing of the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def param_shapes(cfg):
    """Weight name -> shape, named as the PyTorch module names them."""
    i, h, f, c = (cfg["input_size"], cfg["hidden_size"], cfg["fc1_out"],
                  cfg["num_classes"])
    return {
        "rnn.weight_ih_l0": (4 * h, i), "rnn.weight_hh_l0": (4 * h, h),
        "rnn.bias_ih_l0": (4 * h,), "rnn.bias_hh_l0": (4 * h,),
        "batchnorm.weight": (h,), "batchnorm.bias": (h,),
        "batchnorm.running_mean": (h,), "batchnorm.running_var": (h,),
        "fc1.weight": (f, h), "fc1.bias": (f,),
        "fc2.weight": (c, f), "fc2.bias": (c,),
    }


def init_params(key, cfg):
    """Every weight from one key (jit it). LSTM and linear layers as
    PyTorch initialises them, U(-1/sqrt(fan), 1/sqrt(fan)) with fan the
    hidden size or the layer's input width; batch norm's statistics and
    affine as the configuration's ``assumed`` says."""
    shapes = param_shapes(cfg)
    keys = dict(zip(sorted(shapes), jax.random.split(key, len(shapes))))

    def uniform(name, lo, hi):
        return jax.random.uniform(keys[name], shapes[name], jnp.float32, lo, hi)

    def normal(name, mean, std):
        return mean + std * jax.random.normal(keys[name], shapes[name], jnp.float32)

    out = {}
    for name in ("rnn.weight_ih_l0", "rnn.weight_hh_l0", "rnn.bias_ih_l0",
                 "rnn.bias_hh_l0"):
        bound = cfg["hidden_size"] ** -0.5
        out[name] = uniform(name, -bound, bound)
    for layer in ("fc1", "fc2"):
        bound = shapes[f"{layer}.weight"][1] ** -0.5
        out[f"{layer}.weight"] = uniform(f"{layer}.weight", -bound, bound)
        out[f"{layer}.bias"] = uniform(f"{layer}.bias", -bound, bound)
    bn = cfg["assumed"]["batchnorm"]
    out["batchnorm.running_mean"] = normal("batchnorm.running_mean", 0.0, bn["mean_std"])
    out["batchnorm.running_var"] = uniform("batchnorm.running_var", *bn["var_range"])
    out["batchnorm.weight"] = normal("batchnorm.weight", 1.0, bn["affine_std"])
    out["batchnorm.bias"] = normal("batchnorm.bias", 0.0, bn["affine_std"])
    return out


def draw_input(rng, shape):
    """One normalized MNIST-like image as the program takes it, (rows, 1,
    columns): ink of intensity U(0, 1) on a share of the pixels chosen so
    that the mean intensity is MNIST's, then the example's normalization."""
    norm = (0.1307, 0.3081)
    ink = rng.uniform(0.0, 1.0, shape) * (rng.uniform(0.0, 1.0, shape) < 2 * norm[0])
    return (ink - norm[0]) / norm[1]


def _int4(v, axis=None):
    """Symmetric int4 rounding of ``v``, one scale per tensor (or per
    slice along ``axis``)."""
    m = jnp.max(jnp.abs(v), axis=axis, keepdims=axis is not None)
    s = jnp.where(m > 0, m / 7.0, 1.0)
    return jnp.clip(jnp.round(v / s), -7, 7) * s


def forward(params, x, cfg, precision="float32"):
    """Logits of a batch of the program's inputs, ``x`` of shape
    (B, rows, 1, columns): (B, num_classes).

    ``precision`` "float32" is the reference. The controls put the same
    network in a lower precision: "int4" rounds to int4 every value the
    accelerators hold in 8 bits (weight matrices; matrix inputs, the LSTM's
    cell and hidden state and each linear layer's output, one scale per
    sample); "bfloat16" computes in bfloat16."""
    dt = jnp.bfloat16 if precision == "bfloat16" else jnp.float32
    q = _int4 if precision == "int4" else (lambda v, axis=None: v)
    p = {k: v.astype(dt) for k, v in params.items()}
    xs = jnp.swapaxes(x[:, :, 0, :], 0, 1).astype(dt)          # (rows, B, columns)
    wi, wh = q(p["rnn.weight_ih_l0"]), q(p["rnn.weight_hh_l0"])
    b = p["rnn.bias_ih_l0"] + p["rnn.bias_hh_l0"]
    H = cfg["hidden_size"]

    def dot(a, w):
        return jnp.dot(q(a, axis=-1), w.T, precision=HIGHEST)

    def cell(carry, x_t):
        h, c = carry
        g = dot(x_t, wi) + dot(h, wh) + b
        i, f = jax.nn.sigmoid(g[:, :H]), jax.nn.sigmoid(g[:, H:2 * H])
        gg, o = jnp.tanh(g[:, 2 * H:3 * H]), jax.nn.sigmoid(g[:, 3 * H:])
        c = q(f * c + i * gg, axis=-1)
        return (q(o * jnp.tanh(c), axis=-1), c), None

    zeros = jnp.zeros((xs.shape[1], H), dt)
    (h, _c), _ = jax.lax.scan(cell, (zeros, zeros), xs)
    eps = cfg["bn_eps"]
    h = ((h - p["batchnorm.running_mean"]) / jnp.sqrt(p["batchnorm.running_var"] + eps)
         * p["batchnorm.weight"] + p["batchnorm.bias"])
    y = jax.nn.relu(q(dot(h, q(p["fc1.weight"])) + p["fc1.bias"], axis=-1))
    return q(dot(y, q(p["fc2.weight"])) + p["fc2.bias"], axis=-1).astype(jnp.float32)
