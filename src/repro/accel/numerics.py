"""Custom accelerator numerics, bit-accurate in JAX.

* **AdaptivFloat** (Tambe et al., DAC'20) — FlexASR's datatype: an n-bit
  float whose exponent range is shifted per-tensor by an integer bias chosen
  from the tensor's max magnitude. We implement quantization exactly:
  normalized mantissa rounded to m bits, exponent clamped to the 2^e window,
  values below the smallest normal flushed to zero, saturation at the top.

* **Fixed point** — HLSCNN's 8/16-bit two's-complement fixed point with a
  static number of fraction bits. The paper's ResNet-20 accuracy collapse
  came from 8-bit weight quantization; the "updated design" widens to 16.

* **int8 symmetric** — VTA's integer GEMM path (scale = amax/127).

All quantizers are ``quantize -> dequantize`` (fake-quant) so downstream
compute can run in fp32 while matching the accelerator's representable set.
They are jit-able and differentiable-through via straight-through estimators
(used when the framework trains quantization-aware).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


# --------------------------------------------------------------------------
# AdaptivFloat
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class AdaptivFloatSpec:
    n_bits: int = 8
    n_exp: int = 3  # exponent field width; mantissa = n_bits - 1 - n_exp

    @property
    def n_man(self) -> int:
        return self.n_bits - 1 - self.n_exp


def floor_log2(ax: jnp.ndarray) -> jnp.ndarray:
    """``floor(log2(ax))`` of positive normal float32 values, read from the
    exponent field. Exact on every backend, where ``jnp.log2`` is not: XLA
    on the CPU and on the TPU rounds ``log2(2**k)`` below ``k`` for some
    ``k``, which floors an exact power of two one binade low."""
    bits = jax.lax.bitcast_convert_type(
        jax.lax.stop_gradient(ax).astype(jnp.float32), jnp.int32)
    return ((bits >> 23) & 0xFF).astype(jnp.float32) - 127.0


def exp2_int(e: jnp.ndarray) -> jnp.ndarray:
    """``2.0 ** e`` for integer-valued ``e``, clamped to the normal float32
    range [-126, 127] and built from the exponent field. Exact on every
    backend, where ``jnp.exp2`` is not (on XLA's CPU, ``exp2(-21.0)`` is
    not ``2**-21``)."""
    e = jnp.clip(jax.lax.stop_gradient(e), -126, 127).astype(jnp.int32)
    return jax.lax.bitcast_convert_type((e + 127) << 23, jnp.float32)


def af_exp_bias(x: jnp.ndarray, spec: AdaptivFloatSpec) -> jnp.ndarray:
    """Per-tensor exponent bias: align the max representable exponent with
    the tensor's max magnitude (AdaptivFloat Algorithm 1)."""
    amax = jnp.max(jnp.abs(x))
    amax = jnp.where(amax == 0, 1.0, amax)
    return floor_log2(amax) - (2 ** spec.n_exp - 1)


def af_exp_bias_host(x, spec: AdaptivFloatSpec) -> float:
    """``float(af_exp_bias(jnp.asarray(x), spec))``, computed in numpy on
    the host. FlexASR's planners pack on the pipelined engine's pack worker
    and must not dispatch JAX: eager device round trips there stall the
    dispatch thread. Equal to ``af_exp_bias`` for every tensor: ``x`` is
    rounded to float32 as ``jnp.asarray`` rounds it, and a max magnitude
    below the smallest normal counts as zero, as XLA flushes subnormals on
    the CPU and on the TPU (a bare exponent read would floor it at -127)."""
    return float(af_exp_bias_amax(np.max(np.abs(np.asarray(x, np.float32))), spec))


def af_exp_bias_amax(amax, spec: AdaptivFloatSpec) -> np.ndarray:
    """``af_exp_bias_host`` of tensors given by their max magnitudes (an
    array of them: one exponent bias per block of a tiled tensor)."""
    amax = np.asarray(amax, np.float32)
    amax = np.where(amax < np.finfo(np.float32).tiny, np.float32(1.0), amax)
    bits = amax.view(np.int32)
    return (((bits >> 23) & 0xFF) - 127 - (2 ** spec.n_exp - 1)).astype(np.float32)


def af_quantize(
    x: jnp.ndarray, spec: AdaptivFloatSpec = AdaptivFloatSpec(), exp_bias=None
) -> jnp.ndarray:
    """Round ``x`` to the nearest AdaptivFloat-representable value."""
    if exp_bias is None:
        exp_bias = af_exp_bias(x, spec)
    m = spec.n_man
    e_lo = exp_bias                       # smallest normal exponent
    e_hi = exp_bias + (2 ** spec.n_exp - 1)  # largest exponent
    sign = jnp.sign(x)
    ax = jnp.abs(x)
    # exponent of each value, clamped into the representable window
    e_x = floor_log2(jnp.where(ax > 0, ax, 1.0))
    e = jnp.clip(e_x, e_lo, e_hi)
    # mantissa in [1, 2): round to m bits
    man = jnp.clip(ax * exp2_int(-e), 1.0, 2.0 - 2.0 ** (-m))
    man_q = jnp.round(man * 2.0 ** m) / 2.0 ** m
    # rounding can push mantissa to 2.0 -> bump exponent (saturating)
    bump = man_q >= 2.0
    e2 = jnp.clip(e + bump, e_lo, e_hi)
    man_q = jnp.where(bump & (e2 > e), 1.0, jnp.minimum(man_q, 2.0 - 2.0 ** (-m)))
    # at most the max normal (2 - 2^-m) * 2^e_hi: saturates above it
    q = man_q * exp2_int(e2)
    # flush-to-zero below half the min normal, 2^(e_lo - 1)
    q = jnp.where(e_x < e_lo - 1, 0.0, q)
    return (sign * q).astype(x.dtype)


def af_ste(x, spec: AdaptivFloatSpec = AdaptivFloatSpec()):
    """Straight-through-estimator fake quant (identity gradient)."""
    return x + jax.lax.stop_gradient(af_quantize(x, spec) - x)


# --------------------------------------------------------------------------
# Fixed point
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class FixedPointSpec:
    n_bits: int = 8
    n_frac: int = 6

    @property
    def scale(self) -> float:
        return float(2 ** self.n_frac)

    @property
    def qmin(self) -> int:
        return -(2 ** (self.n_bits - 1))

    @property
    def qmax(self) -> int:
        return 2 ** (self.n_bits - 1) - 1


# The paper's case study: HLSCNN originally used 8-bit fixed point for
# weights, sized for a wide *value range* (integer headroom) — which leaves
# few fraction bits, heavily quantizing the small-magnitude conv weights of
# trained nets (ResNet-20: 91.55% -> 29.15%). The developers' fix widened
# the datatype to 16 bits (same range, 8 more fraction bits), recovering
# 91.85%. We model exactly that: both specs cover +/-16; the original has a
# 2^-3 grid, the update a 2^-11 grid.
HLSCNN_WEIGHT_ORIGINAL = FixedPointSpec(n_bits=8, n_frac=3)
HLSCNN_WEIGHT_UPDATED = FixedPointSpec(n_bits=16, n_frac=11)
HLSCNN_ACT = FixedPointSpec(n_bits=16, n_frac=8)


def fx_quantize_int(x: jnp.ndarray, spec: FixedPointSpec) -> jnp.ndarray:
    """To the integer (two's complement) representation."""
    q = jnp.round(x * spec.scale)
    return jnp.clip(q, spec.qmin, spec.qmax).astype(jnp.int32)


def fx_dequantize(q: jnp.ndarray, spec: FixedPointSpec) -> jnp.ndarray:
    return q.astype(jnp.float32) / spec.scale


def fx_quantize(x: jnp.ndarray, spec: FixedPointSpec) -> jnp.ndarray:
    """Fake quant: round to the fixed-point lattice."""
    return fx_dequantize(fx_quantize_int(x, spec), spec)


# --------------------------------------------------------------------------
# int8 symmetric (VTA)
# --------------------------------------------------------------------------


def int8_scale(x: jnp.ndarray) -> jnp.ndarray:
    amax = jnp.max(jnp.abs(x))
    return jnp.where(amax == 0, 1.0, amax / 127.0)


def int8_quantize(x: jnp.ndarray, scale=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    if scale is None:
        scale = int8_scale(x)
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def int8_dequantize(q: jnp.ndarray, scale) -> jnp.ndarray:
    return q.astype(jnp.float32) * scale


# --------------------------------------------------------------------------
# Saturation points and rounding grids (shared by the fault library and the
# static range-analysis pass)
# --------------------------------------------------------------------------

# Block-scaled formats (AdaptivFloat, block-fp) renormalize per tensor, so
# their *absolute* overflow point depends on the data, not the spec. 4.5 is
# the modeling constant the fault library uses for the rare-overflow tail of
# unit-scale activations: values beyond it fall outside the window a
# per-block exponent chosen for |x| <~ 1 data can still represent.
BLOCK_SCALED_SAT = 4.5


def fixed_saturation(spec: FixedPointSpec) -> float:
    """Largest representable magnitude (up to one LSB) of a fixed-point
    format: 2^(integer bits)."""
    return float(2.0 ** (spec.n_bits - 1 - spec.n_frac))


def saturation_point(numerics: str) -> float:
    """Absolute saturation/wrap threshold for a target's declared numerics
    string (``AcceleratorTarget.capabilities["numerics"]``)."""
    if numerics.startswith(("fixed", "int8")):
        return fixed_saturation(HLSCNN_ACT)
    return BLOCK_SCALED_SAT


def rounding_grid(numerics: str) -> Optional[float]:
    """Quantization grid spacing near zero for a numerics family, or None
    when the family has no static grid (pure-integer paths rescale
    per-tensor, so a fixed grid is meaningless)."""
    if numerics.startswith("int8"):
        return None
    if numerics.startswith("fixed"):
        return 1.0 / HLSCNN_ACT.scale
    # block-scaled: one mantissa step below the unit binade
    return float(2.0 ** -(AdaptivFloatSpec().n_man + 1))
