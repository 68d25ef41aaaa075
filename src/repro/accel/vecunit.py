"""VecUnit: a small element-wise vector accelerator — the plugin-API proof.

A deliberately simple fourth backend (in the spirit of the paper's claim
that ILA + mappings are all a new prototype accelerator needs): a 16-lane
element-wise vector unit computing in **int16 block fixed point** — values
are quantized to a signed 16-bit grid whose power-of-two scale is configured
per invocation by the driver (``CFG_NUM``), the way FlexASR's driver sizes
AdaptivFloat exponent windows. Supported functions:

  EW_MUL      out = a * b          (element-wise product; swish/SE gating)
  EW_SIGMOID  out = sigmoid(a)

Architectural state: three row buffers (operands a/b, output) of
``MAX_ROWS x MAX_COLS`` values stored as V-lane words, plus geometry/mode/
scale registers. Instruction set (MMIO-style, one V-lane word per command):

  WR_A / WR_B   store one V-lane row into the operand buffers
  CFG           mode, n_rows, n_cols
  CFG_NUM       scale exponents (a, b, out)
  EW_START      run the configured element-wise function

Everything the compiler, executor and validation layers need is declared
through :mod:`repro.accel.target` and registered at the bottom of this file —
**no ``repro/core`` module mentions this backend**. Compiled programs pick it
up via flexible matching (EfficientNet's swish-family ``mul``/``sigmoid``
gating offloads here out of the box), and the registry-driven conformance
suite covers it with zero bespoke tests. ``docs/targets.md`` walks through
this file as the "add your accelerator in ~200 lines" example.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core import ir
from ..core.egraph import P, V as PV, Rewrite, shape_of
from ..core.ila import (
    ILA, BulkWrite, CompiledFragment, DataStream, PackedStream,
)
from .target import (
    AcceleratorTarget, CostModel, Intrinsic, SimJob, VT2Case, register_target,
)

V = 16              # interface lanes
MAX_COLS = 64       # row width in values (4 V-lane words)
MAX_ROWS = 64       # rows per invocation (driver chunks larger tensors)
QMAX = 2 ** 15 - 1  # int16 symmetric grid

WR_A = 0x10
WR_B = 0x11
CFG = 0x20
CFG_NUM = 0x21
EW_START = 0x30

MODE_MUL = 1
MODE_SIGMOID = 2

_WORDS = MAX_ROWS * MAX_COLS // V

vecunit = ILA("vecunit", vwidth=V)
# the driver's per-chunk scales: never shared across a batch (see
# ILA.per_stream), so a batch's runner does not depend on whether its
# chunks' scales happen to agree
vecunit.per_stream = frozenset({CFG_NUM})

TARGET = AcceleratorTarget(
    "vecunit",
    vecunit,
    display_name="VecUnit",
    capabilities={
        "max_rows": MAX_ROWS, "max_cols": MAX_COLS, "numerics": "int16-blockfp",
    },
    doc="element-wise vector unit (mul / sigmoid) in int16 block fixed point",
    # the abstract fragments are the *identical* fp32 expressions on both
    # sides — the VT2 bound is bit-exact, not the historical 1e-5 slack
    vt2_tol=0.0,
)
FRAGMENTS = TARGET.fragments
# unary ops (sigmoid) legitimately run with vec_b at its reset value, and
# sigmoid inputs are squashed well inside the block-scaled wrap point
TARGET.declare_lint(input_range=(-4.0, 4.0), reset_valid=("vec_b",))

vecunit.state("vec_a", lambda: jnp.zeros((_WORDS, V), jnp.float32))
vecunit.state("vec_b", lambda: jnp.zeros((_WORDS, V), jnp.float32))
vecunit.state("vec_out", lambda: jnp.zeros((_WORDS, V), jnp.float32))
for reg in ("mode", "n_rows", "n_cols", "exp_a", "exp_b", "exp_o"):
    vecunit.state(reg, (lambda: jnp.zeros((), jnp.float32)))


def _wr(buf):
    def update(st, addr, data):
        st = dict(st)
        st[buf] = jax.lax.dynamic_update_slice(st[buf], data[None, :], (addr, 0))
        return st

    return update


vecunit.instruction("wr_a", WR_A)(_wr("vec_a"))
vecunit.instruction("wr_b", WR_B)(_wr("vec_b"))


def _cfg(names):
    def update(st, addr, data):
        st = dict(st)
        for i, n in enumerate(names):
            st[n] = data[i]
        return st

    return update


vecunit.instruction("cfg", CFG)(_cfg(["mode", "n_rows", "n_cols"]))
vecunit.instruction("cfg_num", CFG_NUM)(_cfg(["exp_a", "exp_b", "exp_o"]))


def _q16(x, exp):
    """int16 block fixed point: round onto the 2^exp grid, saturate."""
    scale = jnp.exp2(exp)
    return jnp.clip(jnp.round(x / scale), -QMAX, QMAX) * scale


@vecunit.instruction("ew_start", EW_START, "run the configured element-wise fn")
def _ew_start(st, addr, data):
    A = st["vec_a"].reshape(MAX_ROWS, MAX_COLS)
    B = st["vec_b"].reshape(MAX_ROWS, MAX_COLS)
    mr = (jnp.arange(MAX_ROWS) < st["n_rows"]).astype(jnp.float32)
    mc = (jnp.arange(MAX_COLS) < st["n_cols"]).astype(jnp.float32)
    mask = mr[:, None] * mc[None, :]
    Aq = _q16(A, st["exp_a"]) * mask
    Bq = _q16(B, st["exp_b"]) * mask
    Y = jax.lax.switch(
        jnp.clip(st["mode"].astype(jnp.int32) - 1, 0, 1),
        [
            lambda ab: ab[0] * ab[1],
            lambda ab: 1.0 / (1.0 + jnp.exp(-ab[0])),
        ],
        (Aq, Bq),
    )
    Y = _q16(Y, st["exp_o"]) * mask
    st = dict(st)
    st["vec_out"] = Y.reshape(_WORDS, V)
    return st


# --------------------------------------------------------------------------
# Driver-side fragment builder (setup/data split; setup is empty — the whole
# invocation is a data stream, like VTA's vector-ALU fragments)
# --------------------------------------------------------------------------


def ew_fragment(kind: str, cache: bool = True) -> CompiledFragment:
    """No stationary operand: the setup stream is empty; the fragment exists
    to cache/batch same-kind invocations through one compiled runner."""
    assert kind in ("mul", "sigmoid")
    key = ("veu_ew", kind)

    def build():
        mode = MODE_MUL if kind == "mul" else MODE_SIGMOID
        return CompiledFragment(vecunit, key, PackedStream.empty(V), meta={"mode": mode})

    return FRAGMENTS.get(key, build) if cache else build()


def pack_ew_data(
    frag: CompiledFragment, a2: np.ndarray, b2: Optional[np.ndarray] = None
) -> DataStream:
    """Data stream for one (R, C) chunk: operand rows + geometry/scale
    config + trigger (see ``pack_ew_chunks``)."""
    a2 = np.asarray(a2, np.float32)
    assert a2.shape[0] <= MAX_ROWS and a2.shape[1] <= MAX_COLS
    ops = [a2]
    if frag.meta["mode"] == MODE_MUL:
        ops.append(np.asarray(b2, np.float32))
        assert ops[1].shape == a2.shape
    (data,) = pack_ew_chunks(frag, ops)
    return data


WORDS_PER_ROW = MAX_COLS // V


def _chunk_exps(x: np.ndarray, starts: np.ndarray) -> np.ndarray:
    """The driver-chosen power-of-two scale of each chunk of rows of ``x``
    beginning at ``starts``: the chunk's amax representable on the grid."""
    amax = np.maximum.reduceat(np.abs(x).max(axis=1), starts).astype(np.float64)
    with np.errstate(divide="ignore"):
        e = np.ceil(np.log2(amax / QMAX))
    return np.where(amax > 0.0, e, 0.0)


def pack_ew_chunks(frag: CompiledFragment, blocks) -> list:
    """The data stream of every MAX_ROWS-row chunk of the (R, C <=
    MAX_COLS) operand ``blocks``, one list entry each: operand rows (zero
    padded to MAX_COLS), then the geometry/scale config and the trigger.
    The driver sizes the output scale from the ideal fp32 result, as the
    FlexASR driver sizes AF exponent windows; every chunk's scales are
    found in one pass."""
    blocks = [np.asarray(x, np.float32) for x in blocks]
    R, C = blocks[0].shape
    if C < MAX_COLS:
        blocks = [np.pad(x, ((0, 0), (0, MAX_COLS - C))) for x in blocks]
    a = blocks[0]
    starts = np.arange(0, max(R, 1), MAX_ROWS)
    ea = _chunk_exps(a, starts)
    if frag.meta["mode"] == MODE_MUL:
        b = blocks[1]
        eb, eo = _chunk_exps(b, starts), _chunk_exps(a * b, starts)
    else:
        eb = np.zeros_like(ea)
        eo = np.full_like(ea, float(np.ceil(np.log2(1.0 / QMAX))))   # sigmoid range (0, 1)
    words = [np.ascontiguousarray(x).reshape(R * WORDS_PER_ROW, V) for x in blocks]
    ops = np.array([CFG, CFG_NUM, EW_START], np.int32)
    addrs = np.zeros((3,), np.int32)
    out = []
    for i, r0 in enumerate(starts):
        n = min(MAX_ROWS, R - r0)
        tail = np.zeros((3, V), np.float32)
        tail[0, :3] = (frag.meta["mode"], n, C)
        tail[1, :3] = (ea[i], eb[i], eo[i])
        w0, w1 = r0 * WORDS_PER_ROW, (r0 + n) * WORDS_PER_ROW
        bulk = [BulkWrite(buf, 0, w[w0:w1], op)
                for buf, w, op in zip(("vec_a", "vec_b"), words, (WR_A, WR_B))]
        out.append(DataStream(bulk, PackedStream(ops, addrs, tail)))
    return out


def read_full(st) -> jnp.ndarray:
    """Vmap-safe fixed-shape read of the whole output block."""
    return st["vec_out"].reshape(MAX_ROWS, MAX_COLS)


def build_ew_fragment(kind: str, a: np.ndarray, b: Optional[np.ndarray] = None):
    """One-shot builder (eager parity / VT cases): commands + read-out."""
    a2 = np.asarray(a, np.float32).reshape(-1, a.shape[-1]) if np.ndim(a) > 1 \
        else np.asarray(a, np.float32).reshape(1, -1)
    b2 = None if b is None else np.asarray(b, np.float32).reshape(a2.shape)
    R, C = a2.shape
    frag = ew_fragment(kind)
    cmds = frag.full_commands(pack_ew_data(frag, a2, b2))
    return cmds, lambda st: read_full(st)[:R, :C]


# --------------------------------------------------------------------------
# IR -> intrinsic rewrites + planner
# --------------------------------------------------------------------------


def _same_shape_guard(eg, cid, s):
    # element-wise only: no broadcasting semantics on the device
    return shape_of(eg, s["a"]) == shape_of(eg, s["b"])


def _rewrites():
    return [
        Rewrite(
            "veu-mul",
            P("mul", PV("a"), PV("b")),
            P("veu_mul", PV("a"), PV("b")),
            guard=_same_shape_guard,
        ),
        Rewrite(
            "veu-sigmoid",
            P("sigmoid", PV("x")),
            P("veu_sigmoid", PV("x")),
        ),
    ]


def plan_ew(ctx, x, args, kind):
    """Flatten the (arbitrary-rank) tensor into MAX_COLS-wide rows and chunk
    by MAX_ROWS — element-wise ops are fully driver-chunkable. Operands are
    host-broadcast first (the rewrite guard only admits equal shapes, but
    the intrinsic's declared semantics allow broadcasting)."""
    shape = np.broadcast_shapes(*[np.shape(t) for t in args])
    if not np.prod(shape):  # no rows routed here: nothing is invoked
        return [], lambda outs: np.zeros(shape, np.float32)
    args = [np.broadcast_to(np.asarray(t, np.float32), shape) for t in args]
    a = args[0]
    ideal = a * args[1] if kind == "mul" else 1.0 / (1.0 + np.exp(-a))
    n = a.size
    R_total = max(1, -(-n // MAX_COLS))
    padded = [np.zeros((R_total * MAX_COLS,), np.float32) for _ in args]
    for buf, t in zip(padded, args):
        buf[:n] = np.asarray(t, np.float32).ravel()
    blocks = [buf.reshape(R_total, MAX_COLS) for buf in padded]
    frag = ew_fragment(kind)
    jobs = [
        SimJob(frag, data, read_full, (slice(0, data.bulk[0].rows.shape[0] // WORDS_PER_ROW),
                                       slice(0, MAX_COLS)))
        for data in pack_ew_chunks(frag, blocks)
    ]

    def assemble(outs):
        out = np.concatenate(outs, axis=0).ravel()[:n].reshape(a.shape)
        ctx.record(f"veu_{kind}", "vecunit", out, ideal, ctx.ncmds(jobs))
        return out.astype(np.float32)

    return jobs, assemble


# --------------------------------------------------------------------------
# IR semantics (shape + ideal oracle) and validation declarations
# --------------------------------------------------------------------------


def _shape_mul(attrs, child_shapes):
    return tuple(np.broadcast_shapes(child_shapes[0], child_shapes[1]))


def _shape_unary(attrs, child_shapes):
    return tuple(child_shapes[0])


def _ideal_mul(attrs, args):
    return args[0] * args[1]


def _ideal_sigmoid(attrs, args):
    return 1.0 / (1.0 + jnp.exp(-args[0]))


def _sample_mul(r):
    if int(r.integers(2)):
        shape = (1, int(r.integers(2, 7)), int(r.integers(2, 7)), int(r.integers(1, 9)))
    else:
        shape = (int(r.integers(1, 30)), int(r.integers(1, 30)))
    return [
        r.standard_normal(shape).astype(np.float32),
        r.standard_normal(shape).astype(np.float32),
    ], {}


def _sample_sigmoid(r):
    shape = (int(r.integers(1, 30)), int(r.integers(1, 30)))
    return [(r.standard_normal(shape) * 2).astype(np.float32)], {}


def _vt2(dim_t, dim_d):
    a = ir.Var("a", (dim_t, dim_d))
    b = ir.Var("b", (dim_t, dim_d))
    return [
        VT2Case("ew-mul", ir.call("mul", a, b), ir.call("veu_mul", a, b),
                {"a": (dim_t, dim_d), "b": (dim_t, dim_d)}),
        VT2Case("ew-sigmoid", ir.call("sigmoid", a), ir.call("veu_sigmoid", a),
                {"a": (dim_t, dim_d)}),
    ]


def _mapping_cases(rng):
    def mul_case():
        a = rng.standard_normal((16, 48)).astype(np.float32)
        b = rng.standard_normal((16, 48)).astype(np.float32)
        cmds, rd = build_ew_fragment("mul", a, b)
        return a * b, rd(vecunit.simulate(cmds))

    def sigmoid_case():
        a = (rng.standard_normal((16, 48)) * 2).astype(np.float32)
        cmds, rd = build_ew_fragment("sigmoid", a)
        return 1.0 / (1.0 + np.exp(-a)), rd(vecunit.simulate(cmds))

    return [("EwMul", mul_case), ("Sigmoid", sigmoid_case)]


# Cost model: operand row streams + config tail per chunk; the 16-lane ALU
# retires V elements per cycle (sigmoid takes a few iterations per element).
COSTS = CostModel("vecunit", cycles_per_command=1.0)


def _cost_ew(n_operands):
    def cost(attrs, shapes):
        n = int(np.prod(np.broadcast_shapes(*shapes))) if shapes else 1
        rows = max(1, -(-n // MAX_COLS))
        chunks = -(-rows // MAX_ROWS)
        words = rows * (MAX_COLS // V)
        lanes = 1.0 if n_operands == 2 else 4.0   # sigmoid iterates per element
        return n_operands * words + 3 * chunks, 4 * (n_operands + 1) * n, lanes * n / V

    return cost


COSTS.op("veu_mul")(_cost_ew(2))
COSTS.op("veu_sigmoid")(_cost_ew(1))


TARGET.add_intrinsic(Intrinsic(
    "veu_mul", planner=lambda ctx, x, a: plan_ew(ctx, x, a, "mul"),
    shape=_shape_mul, ideal=_ideal_mul, sample=_sample_mul, tol=1e-3,
    doc="element-wise product in int16 block fixed point"))
TARGET.add_intrinsic(Intrinsic(
    "veu_sigmoid", planner=lambda ctx, x, a: plan_ew(ctx, x, a, "sigmoid"),
    shape=_shape_unary, ideal=_ideal_sigmoid, sample=_sample_sigmoid, tol=1e-3,
    doc="element-wise logistic sigmoid"))
TARGET.add_rewrites(_rewrites)
TARGET.add_cost_model(COSTS)
TARGET.add_vt2_cases(_vt2)
TARGET.add_mapping_cases(_mapping_cases)
register_target(TARGET)
