"""HLSCNN accelerator ILA (Whatmough et al., VLSI'19) — JAX model.

HLSCNN is a coarse-grained 2D-convolution accelerator operating on 8/16-bit
**fixed point** data in NHWC layout. Its single supported operation in the
paper's prototype is a non-grouped conv2d; padding is done on the host before
invocation (Appendix A).

The paper's key application-level finding (Table 4) lives here: the original
design quantized conv *weights* to 8-bit fixed point, collapsing ResNet-20
accuracy 91.55% -> 29.15%; the developers' update widened weights to 16 bits,
recovering 91.85%. The ILA exposes the weight datatype as a configuration so
the co-simulation can reproduce both designs.

Architectural state:

  act_mem   (ACT_WORDS, V)  activation SRAM (fixed-point values)
  wgt_mem   (WGT_WORDS, V)  weight SRAM
  out_mem   (OUT_WORDS, V)  output SRAM
  + conv geometry registers + datatype select

Instructions: WR_ACT / WR_WGT (one V-lane word per command), CFG_CONV
(geometry), CFG_DTYPE (weight width 8/16), CONV_START.
"""
from __future__ import annotations

import functools
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from ..core import ir
from ..core.egraph import P, V as PV, Rewrite, shape_of
from ..core.ila import (
    ILA, BulkWrite, Command, CompiledFragment, DataStream, FusedRunner,
    PackedStream, _replicated, _shard_batched, fingerprint, fused_lowering,
    fused_pad_streams, named, shard_streams, stream_mesh,
)
from . import numerics
from .target import (
    AcceleratorTarget, CostModel, Intrinsic, SimJob, VT2Case, register_target,
)

V = 16
ACT_WORDS = 8192
WGT_WORDS = 8192
OUT_WORDS = 8192

MAX_H = 16
MAX_W = 16
MAX_C = 32
MAX_K = 32
MAX_KH = 5
MAX_KW = 5

WR_ACT = 0x10
WR_WGT = 0x11
CFG_CONV = 0x20
CFG_DTYPE = 0x21
CONV_START = 0x30

hlscnn = ILA("hlscnn", vwidth=V)

TARGET = AcceleratorTarget(
    "hlscnn",
    hlscnn,
    display_name="HLSCNN",
    capabilities={
        "max_hw": MAX_H, "max_c": MAX_C, "max_k": MAX_K, "max_khw": MAX_KH,
        "numerics": "fixed8/16",
    },
    doc="coarse-grained conv2d accelerator in 8/16-bit fixed point",
    # both VT2 sides lower to the same lax conv in fp32
    vt2_tol=1e-6,
)
FRAGMENTS = TARGET.fragments
# 16-bit fixed / 8 fraction bits saturates at +/-128; conv activations of
# the bundled apps stay within +/-32, so wrap is statically unreachable
TARGET.declare_lint(input_range=(-32.0, 32.0))

hlscnn.state("act_mem", lambda: jnp.zeros((ACT_WORDS, V), jnp.float32))
hlscnn.state("wgt_mem", lambda: jnp.zeros((WGT_WORDS, V), jnp.float32))
hlscnn.state("out_mem", lambda: jnp.zeros((OUT_WORDS, V), jnp.float32))
for reg in ("in_h", "in_w", "in_c", "out_k", "k_h", "k_w", "s_h", "s_w", "wgt_bits"):
    hlscnn.state(reg, (lambda: jnp.zeros((), jnp.float32)))


def _wr(buf_name):
    def update(st, addr, data):
        st = dict(st)
        st[buf_name] = jax.lax.dynamic_update_slice(st[buf_name], data[None, :], (addr, 0))
        return st

    return update


hlscnn.instruction("wr_act", WR_ACT)(_wr("act_mem"))
hlscnn.instruction("wr_wgt", WR_WGT)(_wr("wgt_mem"))


def _cfg(names):
    def update(st, addr, data):
        st = dict(st)
        for i, n in enumerate(names):
            st[n] = data[i]
        return st

    return update


hlscnn.instruction("cfg_conv", CFG_CONV)(
    _cfg(["in_h", "in_w", "in_c", "out_k", "k_h", "k_w", "s_h", "s_w"])
)
hlscnn.instruction("cfg_dtype", CFG_DTYPE)(_cfg(["wgt_bits"]))


ACT_SPEC = numerics.HLSCNN_ACT
W8 = numerics.HLSCNN_WEIGHT_ORIGINAL
W16 = numerics.HLSCNN_WEIGHT_UPDATED


@hlscnn.instruction("conv_start", CONV_START, "run the configured fixed-point conv2d")
def _conv_start(st, addr, data):
    # unpack SRAMs into dense max-size tensors (masked by config regs)
    act = st["act_mem"].reshape(-1)[: MAX_H * MAX_W * MAX_C].reshape(1, MAX_H, MAX_W, MAX_C)
    wgt = st["wgt_mem"].reshape(-1)[: MAX_KH * MAX_KW * MAX_C * MAX_K].reshape(
        MAX_KH, MAX_KW, MAX_C, MAX_K
    )
    mh = (jnp.arange(MAX_H) < st["in_h"]).astype(jnp.float32)
    mw = (jnp.arange(MAX_W) < st["in_w"]).astype(jnp.float32)
    mc = (jnp.arange(MAX_C) < st["in_c"]).astype(jnp.float32)
    mk = (jnp.arange(MAX_K) < st["out_k"]).astype(jnp.float32)
    mkh = (jnp.arange(MAX_KH) < st["k_h"]).astype(jnp.float32)
    mkw = (jnp.arange(MAX_KW) < st["k_w"]).astype(jnp.float32)

    # quantize: activations 16-bit fixed; weights 8 or 16 per CFG_DTYPE
    act_q = numerics.fx_quantize(act, ACT_SPEC)
    w_q8 = numerics.fx_quantize(wgt, W8)
    w_q16 = numerics.fx_quantize(wgt, W16)
    wgt_q = jnp.where(st["wgt_bits"] >= 16, w_q16, w_q8)

    act_q = act_q * mh[None, :, None, None] * mw[None, None, :, None] * mc[None, None, None, :]
    wgt_q = (
        wgt_q
        * mkh[:, None, None, None]
        * mkw[None, :, None, None]
        * mc[None, None, :, None]
        * mk[None, None, None, :]
    )

    # full-size stride-1 conv; stride/geometry masking applied on readout.
    # HIGHEST: 16-bit fixed-point operands are not exact in bf16, which is
    # what the TPU feeds its MXU from f32 at the default precision
    y = jax.lax.conv_general_dilated(
        act_q, wgt_q, window_strides=(1, 1), padding="VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=jax.lax.Precision.HIGHEST,
    )  # (1, MAX_H-MAX_KH+1, MAX_W-MAX_KW+1, MAX_K)
    # accumulators are wide (int32); output re-quantized to 16-bit fixed
    y = numerics.fx_quantize(y, ACT_SPEC)
    oh, ow = y.shape[1], y.shape[2]
    flat = jnp.zeros((OUT_WORDS * V,), jnp.float32)
    flat = flat.at[: oh * ow * MAX_K].set(y.reshape(-1))
    st = dict(st)
    st["out_mem"] = flat.reshape(OUT_WORDS, V)
    return st


# ---------------------------------------------------------------------------
# Driver-side fragment builder — split into a *setup* stream (weight SRAM +
# geometry/datatype config, cached per parameter set) and a *data* stream
# (activation SRAM + CONV_START, re-packed per sample).
# ---------------------------------------------------------------------------

FOH, FOW = MAX_H - MAX_KH + 1, MAX_W - MAX_KW + 1


def _words_rows(vec: np.ndarray) -> np.ndarray:
    """Flatten a tensor into V-lane SRAM words (n_words, V), zero-padded."""
    vec = np.asarray(vec, np.float32).reshape(-1)
    n_words = (len(vec) + V - 1) // V
    buf = np.zeros((n_words * V,), np.float32)
    buf[: len(vec)] = vec
    return buf.reshape(n_words, V)


def _write_words(opcode: int, vec: np.ndarray) -> List[Command]:
    rows = _words_rows(vec)
    return [Command(opcode, i, tuple(rows[i])) for i in range(rows.shape[0])]


def read_full(st) -> jnp.ndarray:
    """Fixed-shape output read (vmap-safe): the full stride-1 conv output;
    callers apply the per-sample stride/geometry slicing host-side."""
    return st["out_mem"].reshape(-1)[: FOH * FOW * MAX_K].reshape(1, FOH, FOW, MAX_K)


def conv2d_fragment(
    w, in_shape, strides=(1, 1), wgt_bits: int = 8, cache: bool = True
) -> CompiledFragment:
    """Setup half: weights resident in wgt SRAM, conv geometry + weight
    datatype configured. ``in_shape`` is the (post-padding) (h, w, c) input
    geometry — part of the device configuration, hence of the cache key."""
    w = np.asarray(w, np.float32)
    h, wd, c = in_shape
    kh, kw, ci, k = w.shape
    assert h <= MAX_H and wd <= MAX_W and c <= MAX_C and k <= MAX_K
    assert kh <= MAX_KH and kw <= MAX_KW
    sh, sw = strides
    key = ("hlscnn_conv2d", (h, wd, c), (sh, sw), int(wgt_bits), fingerprint(w))

    def build():
        wp = np.zeros((MAX_KH, MAX_KW, MAX_C, MAX_K), np.float32)
        wp[:kh, :kw, :c, :k] = w
        cmds = _write_words(WR_WGT, wp)
        cmds.append(Command(CFG_CONV, 0, (h, wd, c, k, kh, kw, sh, sw)))
        cmds.append(Command(CFG_DTYPE, 0, (float(wgt_bits),)))
        setup = PackedStream.from_commands(cmds, V)
        oh, ow = (h - kh) // sh + 1, (wd - kw) // sw + 1
        meta = {"h": h, "wd": wd, "c": c, "k": k, "oh": oh, "ow": ow,
                "sh": sh, "sw": sw, "kh": kh, "kw": kw,
                "wgt_bits": int(wgt_bits), "wp": wp}
        return CompiledFragment(hlscnn, key, setup, meta=meta)

    return FRAGMENTS.get(key, build) if cache else build()


def pack_conv2d_data(frag: CompiledFragment, x) -> DataStream:
    """Data half: one padded sample into act SRAM + trigger."""
    x = np.asarray(x, np.float32)
    m = frag.meta
    assert x.shape == (1, m["h"], m["wd"], m["c"])
    xp = np.zeros((1, MAX_H, MAX_W, MAX_C), np.float32)
    xp[:, : m["h"], : m["wd"], : m["c"]] = x
    bulk = BulkWrite("act_mem", 0, _words_rows(xp), WR_ACT)
    tail = PackedStream.single(CONV_START, 0, (), V)
    return DataStream([bulk], tail)


def out_slice(frag: CompiledFragment):
    """The valid-output window of read_full for this fragment's geometry."""
    m = frag.meta
    return (
        slice(None),
        slice(0, m["oh"] * m["sh"], m["sh"]),
        slice(0, m["ow"] * m["sw"], m["sw"]),
        slice(0, m["k"]),
    )


def build_conv2d_fragment(x, w, strides=(1, 1), padding=(0, 0), wgt_bits: int = 8):
    """conv2d (NHWC x HWIO) -> HLSCNN fragment. Host-side padding per the
    paper; ``wgt_bits`` selects original (8) vs updated (16) design."""
    x, w = np.asarray(x, np.float32), np.asarray(w, np.float32)
    if padding != (0, 0):
        x = np.pad(x, ((0, 0), (padding[0], padding[0]), (padding[1], padding[1]), (0, 0)))
    n, h, wd, c = x.shape
    assert n == 1
    frag = conv2d_fragment(w, (h, wd, c), strides, wgt_bits)
    cmds = frag.full_commands(pack_conv2d_data(frag, x))
    sl = out_slice(frag)

    def read_out(st):
        return read_full(st)[sl]

    return cmds, read_out


# --------------------------------------------------------------------------
# Target declaration: rewrites, planner, validation cases, registration
# --------------------------------------------------------------------------


def _conv_guard(eg, cid, s):
    n, h, w, c = shape_of(eg, s["x"])
    kh, kw, ci, k = shape_of(eg, s["w"])
    ph, pw = s["padding"]
    return (
        h + 2 * ph <= MAX_H
        and w + 2 * pw <= MAX_W
        and c <= MAX_C
        and k <= MAX_K
        and kh <= MAX_KH
        and kw <= MAX_KW
    )


def _rewrites():
    return [
        Rewrite(
            "hlscnn-conv2d",
            P("conv2d", PV("x"), PV("w"), attr_binds=("strides", "padding")),
            P("hlscnn_conv2d", PV("x"), PV("w"), attr_binds=("strides", "padding")),
            guard=_conv_guard,
        ),
    ]


def _ideal_conv2d(a: np.ndarray, w: np.ndarray, strides, padding) -> np.ndarray:
    """numpy (im2col) mirror of ``ir._conv2d`` — NHWC x HWIO, for plan-time
    stats. Planners are the pipelined Executor's pack stage and must not
    dispatch JAX from the pack worker thread."""
    if padding != (0, 0):
        a = np.pad(
            a, ((0, 0), (padding[0], padding[0]), (padding[1], padding[1]), (0, 0))
        )
    kh, kw, _ci, co = w.shape
    sh, sw = strides
    N, H, W, C = a.shape
    oh, ow = (H - kh) // sh + 1, (W - kw) // sw + 1
    cols = np.stack(
        [
            a[:, i : i + oh * sh : sh, j : j + ow * sw : sw, :]
            for i in range(kh)
            for j in range(kw)
        ],
        axis=3,
    )  # (N, OH, OW, KH*KW, C)
    out = cols.reshape(N * oh * ow, kh * kw * C) @ w.reshape(-1, co)
    return out.reshape(N, oh, ow, co)


def plan_conv2d(ctx, x, args):
    a, w = args
    strides = x.attr("strides")
    padding = x.attr("padding")
    wgt_bits = int(ctx.options.get("wgt_bits", 8))
    ideal = _ideal_conv2d(a, w, strides, padding)
    if padding != (0, 0):
        a = np.pad(
            a, ((0, 0), (padding[0], padding[0]), (padding[1], padding[1]), (0, 0))
        )
    frag = conv2d_fragment(w, a.shape[1:], strides, wgt_bits=wgt_bits)
    window = out_slice(frag)
    jobs = [
        SimJob(frag, pack_conv2d_data(frag, a[ni : ni + 1]), read_full, window)
        for ni in range(a.shape[0])
    ]

    def assemble(outs):
        out = np.concatenate(outs, axis=0)
        ctx.record("hlscnn_conv2d", "hlscnn", out, ideal, ctx.ncmds(jobs))
        return out

    return jobs, assemble


def _sample_conv2d(r):
    h = int(r.integers(4, 11))
    c = int(r.integers(1, 9))
    k = int(r.integers(1, 9))
    kh = int(r.integers(1, 4))
    return [
        r.standard_normal((1, h, h, c)).astype(np.float32),
        (r.standard_normal((kh, kh, c, k)) * 0.1).astype(np.float32),
    ], {"strides": (1, 1), "padding": (0, 0)}


def _vt2(dim_t, dim_d):
    x = ir.Var("x", (1, 8, 8, 4))
    wc = ir.Var("wc", (3, 3, 4, 8))
    return [
        VT2Case(
            "conv2d",
            ir.conv2d(x, wc, (1, 1), (0, 0)),
            ir.call("hlscnn_conv2d", x, wc, strides=(1, 1), padding=(0, 0)),
            {"x": (1, 8, 8, 4), "wc": (3, 3, 4, 8)},
        ),
    ]


def _mapping_cases(rng):
    def conv_case():
        x = rng.standard_normal((1, 12, 12, 8)).astype(np.float32)
        w = (rng.standard_normal((3, 3, 8, 16)) * 0.1).astype(np.float32)
        cmds, rd = build_conv2d_fragment(x, w, (1, 1), (0, 0), wgt_bits=16)
        out = rd(hlscnn.simulate(cmds))
        ref = ir._conv2d(jnp.asarray(x), jnp.asarray(w), (1, 1), (0, 0))
        return ref, out

    return [("Conv2D", conv_case)]


# --------------------------------------------------------------------------
# Fused fast-path runner (engine="fused")
#
# CONV_START is a pure function of the activation SRAM once weights and
# geometry are configured, so the fused tier stacks the whole batch of
# activation samples and runs one batched conv with the weight quantization
# (fx lattice + CFG_DTYPE select + geometry masks) hoisted to runner-build
# time. The XLA lowering replays _conv_start's exact lax.conv call
# (bit-exact vs the compiled oracle); the Pallas lowering lowers to im2col
# patches through kernels/fx_gemm.py (different reduction order, so
# tolerance-parity).
# --------------------------------------------------------------------------


def _conv_stack(datas: List[DataStream]):
    """Prepare half (pure numpy): stack activation SRAM images into one
    (B, MAX_H, MAX_W, MAX_C) array, exactly as the bulk writes land them."""
    datas = fused_pad_streams(datas)
    B = len(datas)
    xs = np.zeros((B, MAX_H * MAX_W * MAX_C), np.float32)
    for i, d in enumerate(datas):
        (blk,) = d.bulk
        assert blk.buf == "act_mem" and blk.base == 0
        xs[i] = np.asarray(blk.rows, np.float32).reshape(-1)[: MAX_H * MAX_W * MAX_C]
    return (xs.reshape(B, MAX_H, MAX_W, MAX_C),)


KFLAT = MAX_KH * MAX_KW * MAX_C
KPAD = -(-KFLAT // 128) * 128  # im2col contraction width, lane-aligned


def _conv_pallas(x, wflat, mh, mw, mc, *, wspec, interpret):
    """Per-sample Pallas leg of the fused conv runner: quantize + mask the
    activation SRAM image, im2col it to (FOH*FOW, KPAD) patches and run
    them through the fixed-point GEMM against the flattened weights."""
    from ..kernels.fx_gemm import fx_gemm

    act_q = (numerics.fx_quantize(x, ACT_SPEC)
             * mh[:, None, None] * mw[None, :, None] * mc[None, None, :])
    pats = jnp.stack(
        [act_q[i : i + FOH, j : j + FOW, :]
         for i in range(MAX_KH) for j in range(MAX_KW)],
        axis=2,
    ).reshape(FOH * FOW, KFLAT)
    pats = jnp.pad(pats, ((0, 0), (0, KPAD - KFLAT)))
    y = fx_gemm(pats, wflat, x_spec=ACT_SPEC, w_spec=wspec,
                o_spec=ACT_SPEC, interpret=interpret)
    return y[:, :MAX_K].reshape(1, FOH, FOW, MAX_K)


@functools.lru_cache(maxsize=None)
def fused_conv_pallas(wspec, interpret: bool, mesh=None):
    """The jitted batch dispatch of the Pallas conv leg: vmapped over the
    stacked activation images (B, MAX_H, MAX_W, MAX_C); the flattened
    (128, KPAD) weights and the geometry masks are shared. Sharded per
    device over the stream ``mesh`` when one is given. One jit per weight
    datatype, so every conv layer shares its compilations."""
    return jax.jit(named(shard_streams(jax.vmap(
        functools.partial(_conv_pallas, wspec=wspec, interpret=interpret),
        in_axes=(0, None, None, None, None),
    ), 1, 4, mesh), "hlscnn_fused_conv2d_pallas"))


def _fused_conv2d(frag: CompiledFragment) -> FusedRunner:
    m = frag.meta
    wspec = W16 if m["wgt_bits"] >= 16 else W8
    # weight quantization + geometry masks, hoisted out of the per-batch path
    # (identical to _conv_start's: quantize the padded SRAM image, then mask)
    mkh = (np.arange(MAX_KH) < m["kh"]).astype(np.float32)
    mkw = (np.arange(MAX_KW) < m["kw"]).astype(np.float32)
    mc = (np.arange(MAX_C) < m["c"]).astype(np.float32)
    mk = (np.arange(MAX_K) < m["k"]).astype(np.float32)
    wgt_q = np.asarray(numerics.fx_quantize(jnp.asarray(m["wp"]), wspec))
    wgt_q = (wgt_q * mkh[:, None, None, None] * mkw[None, :, None, None]
             * mc[None, None, :, None] * mk[None, None, None, :])
    mh = jnp.asarray((np.arange(MAX_H) < m["h"]).astype(np.float32))
    mw = jnp.asarray((np.arange(MAX_W) < m["wd"]).astype(np.float32))
    mc_j = jnp.asarray(mc)

    if fused_lowering() == "pallas":
        from ..kernels.ops import pallas_interpret

        interpret = pallas_interpret()
        wflat = np.zeros((128, KPAD), np.float32)
        wflat[:MAX_K, :KFLAT] = wgt_q.reshape(KFLAT, MAX_K).T
        consts = (jnp.asarray(wflat), mh, mw, mc_j)

        def dispatch(prepared):
            (xs,) = prepared
            vf = fused_conv_pallas(wspec, interpret, stream_mesh())
            return vf(_shard_batched(xs), *_replicated(consts))

        return FusedRunner("hlscnn-conv2d-pallas", _conv_stack, dispatch,
                           read=read_full, lowering="pallas",
                           interpret=interpret)

    # XLA leg: replays _conv_start's exact lax.conv call (bit-exact)
    wgt_j = jnp.asarray(wgt_q)

    def one(x):
        act_q = (numerics.fx_quantize(x[None], ACT_SPEC)
                 * mh[None, :, None, None] * mw[None, None, :, None]
                 * mc_j[None, None, None, :])
        y = jax.lax.conv_general_dilated(
            act_q, wgt_j, window_strides=(1, 1), padding="VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=jax.lax.Precision.HIGHEST,
        )
        return numerics.fx_quantize(y, ACT_SPEC)

    vf = jax.jit(named(jax.vmap(one), "hlscnn_fused_conv2d"))

    def dispatch(prepared):
        (xs,) = prepared
        return vf(_shard_batched(xs))

    return FusedRunner("hlscnn-conv2d-xla", _conv_stack, dispatch,
                       read=read_full, lowering="xla", exact=True)


def _fused_factory(frag: CompiledFragment):
    """``declare_fused`` hook: fused runner for the conv2d shape."""
    if frag.key[0] == "hlscnn_conv2d":
        return _fused_conv2d(frag)
    return None


COSTS = CostModel("hlscnn", cycles_per_command=1.0)


@COSTS.op("hlscnn_conv2d")
def _cost_conv2d(attrs, shapes):
    """Analytic conv cost: weight SRAM load (setup) + per-sample activation
    stream over V lanes + the MAC volume retired V lanes per cycle."""
    (n, h, w, c), (kh, kw, ci, co) = shapes[0], shapes[1]
    (sh, sw) = attrs.get("strides", (1, 1))
    (ph, pw) = attrs.get("padding", (0, 0))
    hp, wp = h + 2 * ph, w + 2 * pw
    oh, ow = (hp - kh) // sh + 1, (wp - kw) // sw + 1
    setup = -(-kh * kw * ci * co // V) + 6
    data = n * (-(-hp * wp * c // V) + 4)
    macs = n * oh * ow * kh * kw * ci * co
    moved = 4 * (n * hp * wp * c + kh * kw * ci * co + n * oh * ow * co)
    return setup + data, moved, macs / V


TARGET.add_intrinsic(Intrinsic(
    "hlscnn_conv2d", planner=plan_conv2d, sample=_sample_conv2d,
    tol=0.05, options={"wgt_bits": 16},
    doc="non-grouped 2D convolution in 8/16-bit fixed point"))
TARGET.declare_fused(_fused_factory)
TARGET.add_rewrites(_rewrites)
TARGET.add_cost_model(COSTS)
TARGET.add_vt2_cases(_vt2)
TARGET.add_mapping_cases(_mapping_cases)
register_target(TARGET)
