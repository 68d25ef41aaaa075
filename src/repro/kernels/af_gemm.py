"""Pallas TPU kernel: AdaptivFloat quantized GEMM (the FlexASR PE array).

TPU adaptation of FlexASR's AdaptivFloat linear layer: quantize-on-load to
the AF lattice *inside* the kernel (fusing the paper's store->load transfer
elimination of Section 5.1 into the VMEM pipeline: the AF lattice projection
happens while tiles are staged, costing no extra HBM traffic), fp32 MXU
accumulation, AF re-quantization of the output tile.

Exponent biases are per-tensor scalars, prefetched to SMEM-like (1,1) blocks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..accel.numerics import AdaptivFloatSpec, af_quantize

_SPEC = AdaptivFloatSpec(8, 3)


def _kernel(bx_ref, bw_ref, bo_ref, x_ref, w_ref, b_ref, o_ref, *, spec, nk):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    xq = af_quantize(x_ref[...].astype(jnp.float32), spec, bx_ref[0, 0])
    wq = af_quantize(w_ref[...].astype(jnp.float32), spec, bw_ref[0, 0])
    o_ref[...] += jax.lax.dot_general(
        xq, wq, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(k == nk - 1)
    def _finish():
        y = o_ref[...] + b_ref[...]
        o_ref[...] = af_quantize(y, spec, bo_ref[0, 0])


@functools.partial(
    jax.jit, static_argnames=("spec", "bm", "bn", "bk", "interpret")
)
def af_gemm(
    x: jnp.ndarray,
    w: jnp.ndarray,
    b: jnp.ndarray,
    exp_bias_x: jnp.ndarray,
    exp_bias_w: jnp.ndarray,
    exp_bias_o: jnp.ndarray,
    *,
    spec: AdaptivFloatSpec = _SPEC,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    interpret: bool,
) -> jnp.ndarray:
    """x:(M,K) fp32, w:(N,K) fp32, b:(N,) -> AFq(AFq(x)@AFq(w)^T + b):(M,N)."""
    M, K = x.shape
    N, K2 = w.shape
    assert K == K2 and M % bm == 0 and N % bn == 0 and K % bk == 0
    nk = K // bk
    grid = (M // bm, N // bn, nk)
    scalar = lambda v: jnp.asarray(v, jnp.float32).reshape(1, 1)
    kern = functools.partial(_kernel, spec=spec, nk=nk)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda m, n, k: (0, 0)),
            pl.BlockSpec((1, 1), lambda m, n, k: (0, 0)),
            pl.BlockSpec((1, 1), lambda m, n, k: (0, 0)),
            pl.BlockSpec((bm, bk), lambda m, n, k: (m, k)),
            pl.BlockSpec((bn, bk), lambda m, n, k: (n, k)),
            pl.BlockSpec((1, bn), lambda m, n, k: (0, n)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda m, n, k: (m, n)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        interpret=interpret,
    )(scalar(exp_bias_x), scalar(exp_bias_w), scalar(exp_bias_o), x, w, b.reshape(1, N))
