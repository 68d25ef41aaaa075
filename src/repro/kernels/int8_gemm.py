"""Pallas TPU kernel: int8 x int8 -> int32 GEMM (the VTA datapath on the MXU).

TPU adaptation of VTA's 16x16 int8 GEMM core: instead of a systolic tile
ISA, one MXU-aligned Pallas kernel. Block shapes are multiples of the MXU
native 128 lane dimension; operands are staged HBM -> VMEM by BlockSpec
tiling and accumulated in int32 across the K grid axis (revisiting the
output block, standard Pallas accumulation pattern).

Grid: (M/bm, N/bn, K/bk) with K innermost so the output block stays resident
in VMEM across the accumulation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(a_ref, b_ref, o_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    # int8 tiles straight into the MXU with an int32 accumulator (Mosaic
    # refuses an int32 x int32 matmul)
    o_ref[...] += jax.lax.dot_general(
        a_ref[...],
        b_ref[...],
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32,
    )


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def int8_gemm(
    a: jnp.ndarray,
    b: jnp.ndarray,
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    interpret: bool,
) -> jnp.ndarray:
    """a:(M,K) int8, b:(N,K) int8 -> (M,N) int32. Shapes must tile evenly
    (ops.py pads); VMEM working set = bm*bk + bn*bk (int8) + bm*bn (int32)."""
    M, K = a.shape
    N, K2 = b.shape
    assert K == K2 and M % bm == 0 and N % bn == 0 and K % bk == 0
    grid = (M // bm, N // bn, K // bk)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda m, n, k: (m, k)),
            pl.BlockSpec((bn, bk), lambda m, n, k: (n, k)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda m, n, k: (m, n)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.int32),
        interpret=interpret,
    )(a, b)
