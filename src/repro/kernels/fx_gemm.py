"""Pallas TPU kernel: fixed-point quantized GEMM (the HLSCNN conv PE array).

HLSCNN's conv2d lowers host-side to im2col patches; this kernel fuses the
fixed-point lattice projections — 16-bit activations, 8/16-bit weights per
the CFG_DTYPE register — into the VMEM tile pipeline with fp32 MXU
accumulation and a fixed-point re-quantization of the output tile, mirroring
``kernels/af_gemm.py``'s AdaptivFloat idiom. Quantization is idempotent on
already-projected values (the lattice scales are powers of two), so callers
may pre-quantize/mask activations host-side without double-rounding.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..accel.numerics import FixedPointSpec


def _fx_quant(x, scale: float, qmin: float, qmax: float):
    """Fixed-point lattice projection (mirrors numerics.fx_quantize)."""
    q = jnp.clip(jnp.round(x * scale), qmin, qmax)
    return q / scale


def _kernel(x_ref, w_ref, o_ref, *, xs, ws, os_, nk):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    xq = _fx_quant(x_ref[...].astype(jnp.float32), *xs)
    wq = _fx_quant(w_ref[...].astype(jnp.float32), *ws)
    # HIGHEST: 16-bit fixed-point tiles are not exact in bf16, the MXU's
    # input type at the default precision
    o_ref[...] += jax.lax.dot_general(
        xq, wq, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )

    @pl.when(k == nk - 1)
    def _finish():
        o_ref[...] = _fx_quant(o_ref[...], *os_)


def _params(spec: FixedPointSpec):
    return (float(spec.scale), float(spec.qmin), float(spec.qmax))


@functools.partial(
    jax.jit, static_argnames=("x_spec", "w_spec", "o_spec", "bm", "bn", "bk", "interpret")
)
def fx_gemm(
    x: jnp.ndarray,
    w: jnp.ndarray,
    *,
    x_spec: FixedPointSpec,
    w_spec: FixedPointSpec,
    o_spec: FixedPointSpec,
    bm: int = 16,
    bn: int = 128,
    bk: int = 128,
    interpret: bool,
) -> jnp.ndarray:
    """x:(M,K) fp32, w:(N,K) fp32 -> FXq_o(FXq_x(x) @ FXq_w(w)^T):(M,N)."""
    M, K = x.shape
    N, K2 = w.shape
    assert K == K2 and M % bm == 0 and N % bn == 0 and K % bk == 0
    nk = K // bk
    grid = (M // bm, N // bn, nk)
    kern = functools.partial(
        _kernel, xs=_params(x_spec), ws=_params(w_spec), os_=_params(o_spec), nk=nk
    )
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda m, n, k: (m, k)),
            pl.BlockSpec((bn, bk), lambda m, n, k: (n, k)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda m, n, k: (m, n)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        interpret=interpret,
    )(x, w)
