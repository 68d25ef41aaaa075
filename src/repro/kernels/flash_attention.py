"""Pallas TPU kernel: FlashAttention with GQA + causal masking.

The framework's serving/training compute hotspot. Online-softmax streaming
over KV blocks: the query block stays VMEM-resident across the KV grid axis;
running max/denominator/accumulator live in VMEM scratch. Block shapes are
MXU-aligned (128 multiples); KV is streamed so the working set is
O(bq*d + bk*d + bq*bk) regardless of sequence length.

GQA: q heads map onto kv heads via the BlockSpec index map (no KV
replication in HBM — the gather happens in the VMEM staging).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *, scale, causal, bq, bk, nkv):
    kv = pl.program_id(2)
    qi = pl.program_id(1)

    @pl.when(kv == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)          # (bq, d)
    k = k_ref[0].astype(jnp.float32)          # (bk, d)
    v = v_ref[0].astype(jnp.float32)          # (bk, d)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if causal:
        q_idx = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_idx = kv * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(q_idx >= k_idx, s, NEG_INF)

    m_prev = m_scr[...]                        # (bq, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_scr[...] = m_new

    @pl.when(kv == nkv - 1)
    def _finish():
        l = l_scr[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "bq", "bk", "interpret")
)
def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    bq: int = 128,
    bk: int = 128,
    interpret: bool,
) -> jnp.ndarray:
    """q:(B,Hq,S,D) k,v:(B,Hkv,S,D) with Hq % Hkv == 0 -> (B,Hq,S,D)."""
    B, Hq, S, D = q.shape
    Hkv = k.shape[1]
    Sk = k.shape[2]
    assert Hq % Hkv == 0 and S % bq == 0 and Sk % bk == 0
    group = Hq // Hkv
    qf = q.reshape(B * Hq, S, D)
    kf = k.reshape(B * Hkv, Sk, D)
    vf = v.reshape(B * Hkv, Sk, D)
    nq, nkv = S // bq, Sk // bk
    scale = 1.0 / (D ** 0.5)

    def q_map(bh, qi, kv):
        return (bh, qi, 0)

    def kv_map(bh, qi, kv):
        b = bh // Hq
        h = bh % Hq
        return (b * Hkv + h // group, kv, 0)

    kern = functools.partial(
        _kernel, scale=scale, causal=causal, bq=bq, bk=bk, nkv=nkv
    )
    out = pl.pallas_call(
        kern,
        grid=(B * Hq, nq, nkv),
        in_specs=[
            pl.BlockSpec((1, bq, D), q_map),
            pl.BlockSpec((1, bk, D), kv_map),
            pl.BlockSpec((1, bk, D), kv_map),
        ],
        out_specs=pl.BlockSpec((1, bq, D), q_map),
        out_shape=jax.ShapeDtypeStruct((B * Hq, S, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, Hq, S, D)
