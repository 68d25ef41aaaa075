"""The ``mnist_rnn`` network as the application a designer hands to the
co-simulation flow: its IR program over one sample, and its weights as
that program takes them.

What an inference export does on the way, and what it leaves exact:

* the LSTM's two biases are summed into one;
* batch norm's running statistics and affine are folded into ``fc1``
  (``fc1(bn(h)) = (W * s) h + (b + W t)``, ``s = gamma / sqrt(var + eps)``,
  ``t = beta - mean * s``), in float64;
* the IR has no slice, so the last step is picked by a constant one-hot
  column: ``reduce_sum(h * last_step, axis=0)``, which is exact and has
  the broadcast shape no accelerator rewrite admits, so it stays on the
  host.
"""
from __future__ import annotations

import numpy as np


def build(cfg):
    """The application's IR over one sample (before flexible matching)."""
    from repro.core import ir

    T, i, h, f, c = (cfg["seq_len"], cfg["input_size"], cfg["hidden_size"],
                     cfg["fc1_out"], cfg["num_classes"])
    x = ir.Var("x", (T, 1, i))
    hs = ir.call("lstm", x, ir.Var("lstm_wi", (4 * h, i)),
                 ir.Var("lstm_wh", (4 * h, h)), ir.Var("lstm_b", (4 * h,)))
    last = ir.call("reduce_sum", ir.call("mul", ir.reshape(hs, (T, h)),
                                         ir.Var("last_step", (T, 1))), axis=0)
    y = ir.bias_add(ir.dense(ir.reshape(last, (1, h)), ir.Var("fc1_w", (f, h))),
                    ir.Var("fc1_b", (f,)))
    y = ir.call("relu", y)
    return ir.bias_add(ir.dense(y, ir.Var("fc2_w", (c, f))), ir.Var("fc2_b", (c,)))


def program_weights(params, cfg):
    """The program's inputs from the reference's published weights."""
    p = {k: np.asarray(v, np.float64) for k, v in params.items()}
    s = p["batchnorm.weight"] / np.sqrt(p["batchnorm.running_var"] + cfg["bn_eps"])
    t = p["batchnorm.bias"] - p["batchnorm.running_mean"] * s
    last = np.zeros((cfg["seq_len"], 1))
    last[-1] = 1.0
    out = {
        "lstm_wi": p["rnn.weight_ih_l0"], "lstm_wh": p["rnn.weight_hh_l0"],
        "lstm_b": p["rnn.bias_ih_l0"] + p["rnn.bias_hh_l0"],
        "last_step": last,
        "fc1_w": p["fc1.weight"] * s[None, :],
        "fc1_b": p["fc1.bias"] + p["fc1.weight"] @ t,
        "fc2_w": p["fc2.weight"], "fc2_b": p["fc2.bias"],
    }
    return {k: v.astype(np.float32) for k, v in out.items()}
