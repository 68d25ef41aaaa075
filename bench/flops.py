"""Operations per sample, counted from the source application's IR, and
the table of device peaks.

Only matrix-like work is counted (multiply-accumulates of convolutions,
dense layers, LSTM gates and attention products, two operations each), as
model FLOP utilization counts it: element-wise ops, quantization and the
simulators' own bookkeeping are not operations of the application.
"""
from __future__ import annotations

import json
from math import prod
from pathlib import Path
from typing import Dict

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def _conv_out(h, k, s, p):
    return (h + 2 * p - k) // s + 1


def _node_flops(op: str, shapes, attrs: Dict) -> int:
    if op == "conv2d" or op == "dw_conv2d":
        (n, h, w, c), (kh, kw, _ci, k) = shapes[0], shapes[1]
        sh, sw = attrs.get("strides", (1, 1))
        ph, pw = attrs.get("padding", (0, 0))
        oh, ow = _conv_out(h, kh, sh, ph), _conv_out(w, kw, sw, pw)
        per_out = kh * kw * (1 if op == "dw_conv2d" else c)
        return 2 * n * oh * ow * per_out * (c if op == "dw_conv2d" else k)
    if op == "dense":
        x, (dout, din) = shapes[0], shapes[1]
        return 2 * prod(x[:-1]) * din * dout
    if op == "lstm":
        (t, n, e), (g, _e) = shapes[0], shapes[1]
        hid = shapes[2][1]
        return 2 * t * n * g * (e + hid)
    if op == "attention":
        (tq, d), (tk, _d) = shapes[0], shapes[1]
        return 2 * 2 * tq * tk * d
    return 0


def flops_per_sample(expr) -> int:
    """Forward operations of one evaluation of ``expr`` (the program over
    one sample), from its shapes. Independent of how the work is offloaded:
    count the *source* program, before flexible matching."""
    from repro.core import ir

    total = 0
    for node in ir.postorder(expr):
        if isinstance(node, ir.Call):
            shapes = [ir.infer_shape(a) for a in node.args]
            total += _node_flops(node.op, shapes, dict(node.attrs))
    return total


def peak(device_kind: str, path: Path = PEAKS_FILE) -> Dict:
    """The published peaks of ``device_kind``; an unknown device is an
    error, never a default."""
    table = json.loads(path.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path.name}; "
                       f"known: {sorted(table)}")
    return table[device_kind]
