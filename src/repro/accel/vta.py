"""VTA accelerator ILA (Moreau et al., IEEE Micro'19) — JAX model.

Unlike FlexASR/HLSCNN, VTA is a *fine-grained programmable* accelerator with
an actual ISA: a processor-like design around a 16x16 int8 GEMM core with an
int32 accumulator register file, plus a vector ALU. "Operators" are sequences
of VTA instructions (Appendix A). We model the compute-relevant subset:

  LOAD_INP  dram -> inp SRAM   (int8 tile, 16x16)
  LOAD_WGT  dram -> wgt SRAM   (int8 tile, 16x16)
  LOAD_ACC  dram -> acc RF     (int32 tile — bias preload)
  GEMM      acc[d] += inp[i] @ wgt[w]^T   (int8 x int8 -> int32)
  ALU       acc[d] = op(acc[d], acc[s] | imm)   op in {add, max, shr, min}
  STORE     acc RF -> out dram (int8 narrowing with shift-based requant)

The ILA's "DRAM" is a host-visible array in the architectural state (the
paper models DMA through the accelerator interface the same way). GEMM
matches the real device: int8 operands, int32 accumulate, requantization via
arithmetic shift in the ALU — which makes the GEMM mapping *exact* for
integer inputs (Table 2 row 1: 0.00% error).
"""
from __future__ import annotations

import functools
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import ir
from ..core.egraph import P, V as PV, Rewrite, shape_of
from ..core.ila import (
    ILA, BulkWrite, Command, CompiledFragment, DataStream,
    PackedStream, fingerprint,
)
from . import numerics
from .target import (
    AcceleratorTarget, CostModel, Intrinsic, SimJob, VT2Case, register_target,
)

T = 16               # tile side (the 16x16 GEMM core)
N_INP = 64           # inp SRAM tiles
N_WGT = 64
N_ACC = 64
DRAM_TILES = 256     # host-visible scratch

LOAD_INP = 0x10
LOAD_WGT = 0x11
LOAD_ACC = 0x12
GEMM = 0x20
ALU = 0x21
STORE = 0x30
WR_DRAM = 0x40       # host writes a 16-value row into DRAM scratch

ALU_ADD = 0
ALU_MAX = 1
ALU_SHR = 2
ALU_MIN = 3

vta = ILA("vta", vwidth=T)

TARGET = AcceleratorTarget(
    "vta",
    vta,
    display_name="VTA",
    capabilities={
        "tile": T, "n_inp": N_INP, "n_wgt": N_WGT, "n_acc": N_ACC,
        "numerics": "int8xint8->int32",
    },
    doc="fine-grained programmable accelerator: 16x16 int8 GEMM core + vector ALU",
    # dense and vta_gemm interpret through the same fp32 matmul: bit-exact
    vt2_tol=0.0,
)
FRAGMENTS = TARGET.fragments
# dram rows carry pre-quantized int8-grid operands: |x| <= 127, inside the
# +/-128 fixed-range saturation point — wrap statically unreachable
TARGET.declare_lint(input_range=(-127.0, 127.0))

vta.state("dram", lambda: jnp.zeros((DRAM_TILES * T, T), jnp.float32))
vta.state("inp_sram", lambda: jnp.zeros((N_INP, T, T), jnp.float32))
vta.state("wgt_sram", lambda: jnp.zeros((N_WGT, T, T), jnp.float32))
vta.state("acc_rf", lambda: jnp.zeros((N_ACC, T, T), jnp.float32))


def _rd_tile(dram, tile_idx):
    return jax.lax.dynamic_slice(dram, (tile_idx * T, 0), (T, T))


@vta.instruction("wr_dram", WR_DRAM)
def _wr_dram(st, addr, data):
    st = dict(st)
    st["dram"] = jax.lax.dynamic_update_slice(st["dram"], data[None, :], (addr, 0))
    return st


def _load(buf):
    def update(st, addr, data):
        # data = (sram_idx, dram_tile)
        st = dict(st)
        sram_idx = data[0].astype(jnp.int32)
        tile = _rd_tile(st["dram"], data[1].astype(jnp.int32))
        if buf != "acc_rf":
            tile = jnp.clip(jnp.round(tile), -128, 127)  # int8 semantics
        st[buf] = jax.lax.dynamic_update_slice(st[buf], tile[None], (sram_idx, 0, 0))
        return st

    return update


vta.instruction("load_inp", LOAD_INP)(_load("inp_sram"))
vta.instruction("load_wgt", LOAD_WGT)(_load("wgt_sram"))
vta.instruction("load_acc", LOAD_ACC)(_load("acc_rf"))


@vta.instruction("gemm", GEMM, "acc[d] += inp[i] @ wgt[w]^T (int8 -> int32)")
def _gemm(st, addr, data):
    st = dict(st)
    d = data[0].astype(jnp.int32)
    i = data[1].astype(jnp.int32)
    w = data[2].astype(jnp.int32)
    inp = jax.lax.dynamic_slice(st["inp_sram"], (i, 0, 0), (1, T, T))[0]
    wgt = jax.lax.dynamic_slice(st["wgt_sram"], (w, 0, 0), (1, T, T))[0]
    acc = jax.lax.dynamic_slice(st["acc_rf"], (d, 0, 0), (1, T, T))[0]
    # int8 x int8 -> int32 exact in fp32 (|acc| < 2^24 for our tile counts)
    acc = acc + inp @ wgt.T
    st["acc_rf"] = jax.lax.dynamic_update_slice(st["acc_rf"], acc[None], (d, 0, 0))
    return st


@vta.instruction("alu", ALU, "acc[d] = op(acc[d], acc[s] or imm)")
def _alu(st, addr, data):
    st = dict(st)
    op = data[0].astype(jnp.int32)
    d = data[1].astype(jnp.int32)
    s = data[2].astype(jnp.int32)
    use_imm = data[3]
    imm = data[4]
    a = jax.lax.dynamic_slice(st["acc_rf"], (d, 0, 0), (1, T, T))[0]
    b_t = jax.lax.dynamic_slice(st["acc_rf"], (s, 0, 0), (1, T, T))[0]
    b = jnp.where(use_imm > 0, imm, b_t)
    out = jax.lax.switch(
        jnp.clip(op, 0, 3),
        [
            lambda ab: ab[0] + ab[1],
            lambda ab: jnp.maximum(ab[0], ab[1]),
            lambda ab: jnp.floor(ab[0] / jnp.exp2(ab[1])),   # arithmetic >>
            lambda ab: jnp.minimum(ab[0], ab[1]),
        ],
        (a, b),
    )
    st["acc_rf"] = jax.lax.dynamic_update_slice(st["acc_rf"], out[None], (d, 0, 0))
    return st


@vta.instruction("store", STORE, "acc[s] -> dram tile (optional int8 narrowing)")
def _store(st, addr, data):
    st = dict(st)
    s = data[0].astype(jnp.int32)
    dram_tile = data[1].astype(jnp.int32)
    narrow = data[2]
    acc = jax.lax.dynamic_slice(st["acc_rf"], (s, 0, 0), (1, T, T))[0]
    out = jnp.where(narrow > 0, jnp.clip(acc, -128, 127), acc)
    st["dram"] = jax.lax.dynamic_update_slice(st["dram"], out, (dram_tile * T, 0))
    return st


# ---------------------------------------------------------------------------
# Driver-side fragment builders — "operators are sequences of instructions".
#
# Split for the fragment-compiler fast path: the *setup* stream stages the
# stationary operand (weight tiles -> wgt SRAM) and zeroes the accumulators;
# the *data* stream DMAs the moving operand, issues the GEMM/ALU micro-ops,
# and stores results. DRAM scratch layout is fixed per fragment so data
# streams for every invocation hit the same addresses:
#
#   [0, nt*kt)                 weight tiles          (setup)
#   nt*kt                      always-zero tile      (setup; acc preload)
#   (nt*kt+1, +mt*kt)          input tiles           (data, bulk write)
#   (nt*kt+1+mt*kt, +mt*nt)    output tiles          (data, STORE)
# ---------------------------------------------------------------------------


def _tiles(m: np.ndarray) -> Tuple[np.ndarray, int, int]:
    """Pad (R, C) to tile multiples; return (tiles[rt, ct, T, T], rt, ct)."""
    r, c = m.shape
    rt, ct = (r + T - 1) // T, (c + T - 1) // T
    p = np.zeros((rt * T, ct * T), np.float32)
    p[:r, :c] = m
    return p.reshape(rt, T, ct, T).transpose(0, 2, 1, 3), rt, ct


def _write_dram_tile(cmds, tile_idx: int, tile: np.ndarray):
    for r in range(T):
        cmds.append(Command(WR_DRAM, tile_idx * T + r, tuple(tile[r])))


def _tile_rows(tiles: np.ndarray) -> np.ndarray:
    """(n, T, T) tile stack -> (n*T, T) contiguous DRAM rows."""
    return np.ascontiguousarray(tiles).reshape(-1, T)


def _cmd_stream(entries) -> PackedStream:
    """[(opcode, values), ...] -> PackedStream (addr unused by these ops)."""
    n = len(entries)
    ops = np.array([e[0] for e in entries], np.int32)
    addrs = np.zeros((n,), np.int32)
    data = np.zeros((n, T), np.float32)
    for i, (_, vals) in enumerate(entries):
        vals = np.asarray(vals, np.float32)
        data[i, : len(vals)] = vals
    return PackedStream(ops, addrs, data)


def gemm_fragment(b_int8: np.ndarray, mt: int, cache: bool = True) -> CompiledFragment:
    """Setup half of the GEMM mapping: weight tiles resident in wgt SRAM and
    ``mt * nt`` accumulators zeroed, for data chunks of up to ``mt`` row
    tiles. Cached per (weight chunk, layout)."""
    b_t, nt, kt = _tiles(np.asarray(b_int8, np.float32))
    assert mt * kt <= N_INP and nt * kt <= N_WGT and mt * nt <= N_ACC
    inp_base = nt * kt + 1
    out_base = inp_base + mt * kt
    assert (out_base + mt * nt) <= DRAM_TILES
    key = ("vta_gemm", mt, nt, kt, fingerprint(b_int8))

    def build():
        cmds: List[Command] = []
        for n in range(nt):
            for k in range(kt):
                _write_dram_tile(cmds, n * kt + k, b_t[n, k])
                cmds.append(Command(LOAD_WGT, 0, (n * kt + k, n * kt + k)))
        # zero accumulators: preload every acc tile from an always-zero tile
        zero_tile = nt * kt
        _write_dram_tile(cmds, zero_tile, np.zeros((T, T), np.float32))
        for m in range(mt):
            for n in range(nt):
                cmds.append(Command(LOAD_ACC, 0, (m * nt + n, zero_tile)))
        setup = PackedStream.from_commands(cmds, T)
        meta = {
            "mt": mt, "nt": nt, "kt": kt, "inp_base": inp_base,
            "out_base": out_base, "N": int(np.asarray(b_int8).shape[0]),
        }
        return CompiledFragment(vta, key, setup, meta=meta)

    return FRAGMENTS.get(key, build) if cache else build()


def pack_gemm_data(frag: CompiledFragment, a_int8: np.ndarray, requant_shift: int = 0) -> DataStream:
    """Data half: input tiles + GEMM/requant/STORE micro-ops for one chunk
    of up to ``mt`` row tiles."""
    m = frag.meta
    a_t, mt_c, kt = _tiles(np.asarray(a_int8, np.float32))
    assert kt == m["kt"] and mt_c <= m["mt"]
    nt, inp_base, out_base = m["nt"], m["inp_base"], m["out_base"]
    bulk = BulkWrite(
        "dram", inp_base * T, _tile_rows(a_t.reshape(mt_c * kt, T, T)), WR_DRAM
    )
    entries = []
    for i in range(mt_c):
        for k in range(kt):
            entries.append((LOAD_INP, (i * kt + k, inp_base + i * kt + k)))
    for mi in range(mt_c):
        for n in range(nt):
            for k in range(kt):
                entries.append((GEMM, (mi * nt + n, mi * kt + k, n * kt + k)))
    if requant_shift > 0:
        for mi in range(mt_c):
            for n in range(nt):
                entries.append((ALU, (ALU_SHR, mi * nt + n, 0, 1.0, float(requant_shift))))
    narrow = 1.0 if requant_shift > 0 else 0.0
    for mi in range(mt_c):
        for n in range(nt):
            entries.append((STORE, (mi * nt + n, out_base + mi * nt + n, narrow)))
    return DataStream([bulk], _cmd_stream(entries))


@functools.lru_cache(maxsize=None)
def _tile_reader(rows: int, cols: int, out_base: int):
    """The read of a ``rows x cols``-tile output region stored from DRAM
    tile ``out_base``, as one (rows*T, cols*T) array. One function object
    per geometry: the Executor keys its jitted reads by the function, so a
    per-call closure would compile a fresh read every invocation. The
    geometry space is finite (``rows * cols <= N_ACC``, ``out_base <
    DRAM_TILES``), so the cache needs no bound."""

    def read(st):
        region = st["dram"][out_base * T : (out_base + rows * cols) * T]
        return region.reshape(rows, cols, T, T).transpose(0, 2, 1, 3).reshape(rows * T, cols * T)

    return read


def read_gemm_full(frag: CompiledFragment):
    """Vmap-safe fixed-shape read of the whole output region: (mt*T, nt*T);
    callers slice the valid [:M, :N] window. The same function for every
    fragment of the same tile layout."""
    m = frag.meta
    return _tile_reader(m["mt"], m["nt"], m["out_base"])


def build_gemm_fragment(a_int8: np.ndarray, b_int8: np.ndarray, requant_shift: int = 0):
    """dense(a, b) (int8) -> VTA instruction sequence.

    a:(M,K) b:(N,K); returns int32 accum (or int8 after shift/narrow if
    requant_shift > 0). Tiled over the 16x16 GEMM core.
    """
    a = np.asarray(a_int8)
    mt = (a.shape[0] + T - 1) // T
    frag = gemm_fragment(b_int8, mt)
    cmds = frag.full_commands(pack_gemm_data(frag, a_int8, requant_shift))
    M, N = a.shape[0], np.asarray(b_int8).shape[0]
    read = read_gemm_full(frag)

    def read_out(st):
        return read(st)[:M, :N]

    return cmds, read_out


def alu_fragment(rt: int, ct: int, kind: str, cache: bool = True) -> CompiledFragment:
    """Vector-ALU ops have no stationary operand: the setup stream is empty
    and the whole invocation is a data stream. Cached per tile layout only
    (the fragment then exists to batch same-layout invocations).

    DRAM layout (``n = rt * ct`` tiles): a tiles [0, n), b tiles [n, 2n)
    (add only), outputs after the operand region.
    """
    n = rt * ct
    assert kind in ("add", "relu")
    n_ops = 2 * n if kind == "add" else n
    assert n_ops <= N_ACC and (n_ops + n) <= DRAM_TILES
    key = ("vta_alu", kind, rt, ct)

    def build():
        meta = {"rt": rt, "ct": ct, "kind": kind, "out_base": n_ops}
        return CompiledFragment(vta, key, PackedStream.empty(T), meta=meta)

    return FRAGMENTS.get(key, build) if cache else build()


def pack_alu_data(frag: CompiledFragment, a_int: np.ndarray, b_int=None) -> DataStream:
    m = frag.meta
    rt, ct, kind, out_base = m["rt"], m["ct"], m["kind"], m["out_base"]
    n = rt * ct
    a_t, rt2, ct2 = _tiles(np.asarray(a_int, np.float32))
    assert (rt2, ct2) == (rt, ct)
    bulk = [BulkWrite("dram", 0, _tile_rows(a_t.reshape(n, T, T)), WR_DRAM)]
    entries = [(LOAD_ACC, (i, i)) for i in range(n)]
    if kind == "add":
        b_t, _, _ = _tiles(np.asarray(b_int, np.float32))
        bulk.append(BulkWrite("dram", n * T, _tile_rows(b_t.reshape(n, T, T)), WR_DRAM))
        entries += [(LOAD_ACC, (n + i, n + i)) for i in range(n)]
        entries += [(ALU, (ALU_ADD, i, n + i, 0.0, 0.0)) for i in range(n)]
    else:
        entries += [(ALU, (ALU_MAX, i, 0, 1.0, 0.0)) for i in range(n)]
    entries += [(STORE, (i, out_base + i)) for i in range(n)]
    return DataStream(bulk, _cmd_stream(entries))


def read_alu_full(frag: CompiledFragment):
    """Vmap-safe read of the whole (rt*T, ct*T) output; slice [:R, :C].
    The same function for every fragment of the same tile layout."""
    m = frag.meta
    return _tile_reader(m["rt"], m["ct"], m["out_base"])


def _build_alu_fragment(kind, a_int, b_int=None):
    a = np.asarray(a_int)
    rt, ct = (a.shape[0] + T - 1) // T, (a.shape[1] + T - 1) // T
    frag = alu_fragment(rt, ct, kind)
    cmds = frag.full_commands(pack_alu_data(frag, a_int, b_int))
    R, C = a.shape
    read = read_alu_full(frag)

    def read_out(st):
        return read(st)[:R, :C]

    return cmds, read_out


def build_add_fragment(a_int: np.ndarray, b_int: np.ndarray):
    """elementwise add on the vector ALU (acc RF resident)."""
    return _build_alu_fragment("add", a_int, b_int)


def build_relu_fragment(a_int: np.ndarray):
    return _build_alu_fragment("relu", a_int)


# --------------------------------------------------------------------------
# Target declaration: rewrites, planners, validation cases, registration
# --------------------------------------------------------------------------


def _gemm_guard(eg, cid, s):
    """What ``gemm_fragment`` can hold: one row tile's K tiles fit the
    input SRAM (``kt <= N_INP``), so K <= T * N_INP; ``plan_gemm`` chunks
    rows and outputs to fit the rest."""
    a = shape_of(eg, s["a"])
    return a is not None and -(-a[-1] // T) <= N_INP


def _alu_cols(shape) -> int:
    """Column tiles ``plan_add``/``plan_relu`` cut the last axis into."""
    cols = shape[-1] if len(shape) > 1 else int(np.prod(shape))
    return -(-cols // T)


def _add_guard(eg, cid, s):
    """What ``alu_fragment`` can hold for one row tile of an add: both
    operands' tiles in the accumulators (``2 * ct <= N_ACC``)."""
    a, b = shape_of(eg, s["a"]), shape_of(eg, s["b"])
    if a is None or b is None:
        return False
    out = np.broadcast_shapes(a, b)
    return 2 * _alu_cols(out) <= N_ACC


def _relu_guard(eg, cid, s):
    """One row tile of a relu in the accumulators (``ct <= N_ACC``)."""
    x = shape_of(eg, s["x"])
    return x is not None and _alu_cols(x) <= N_ACC


def _rewrites():
    return [
        Rewrite("vta-gemm", P("dense", PV("a"), PV("b")), P("vta_gemm", PV("a"), PV("b")),
                guard=_gemm_guard),
        Rewrite("vta-add", P("add", PV("a"), PV("b")), P("vta_add", PV("a"), PV("b")),
                guard=_add_guard),
        Rewrite("vta-relu", P("relu", PV("x")), P("vta_relu", PV("x")),
                guard=_relu_guard),
    ]


def kernel_gemm(ctx, x, args):
    """Deployment fast path: the int8_gemm Pallas kernel."""
    from ..kernels import ops as kops

    a, b = args
    ideal = a @ b.T
    sa = np.abs(a).max() / 127.0 if np.abs(a).max() > 0 else 1.0
    sb = np.abs(b).max() / 127.0 if np.abs(b).max() > 0 else 1.0
    a8 = np.clip(np.round(a / sa), -127, 127)
    b8 = np.clip(np.round(b / sb), -127, 127)
    out32 = np.asarray(
        kops.int8_gemm(jnp.asarray(a8, jnp.int8), jnp.asarray(b8, jnp.int8))
    ).astype(np.float64)
    out = out32 * sa * sb
    ctx.record("vta_gemm", "vta-kernel", out, ideal, 0)
    return out.astype(np.float32)


def plan_gemm(ctx, x, args):
    a, b = args
    ideal = a @ b.T
    sa = np.abs(a).max() / 127.0 if np.abs(a).max() > 0 else 1.0
    sb = np.abs(b).max() / 127.0 if np.abs(b).max() > 0 else 1.0
    a8 = np.clip(np.round(a / sa), -127, 127)
    b8 = np.clip(np.round(b / sb), -127, 127)
    # tile rows so SRAM limits hold: mt*kt <= N_INP, nt*kt <= N_WGT, and
    # at most 8 x 8 tiles so mt*nt <= N_ACC and the DRAM layout fits
    kt = (a8.shape[1] + T - 1) // T
    max_m = max(1, min(N_INP // kt, 8)) * T
    max_n = max(1, min(N_WGT // kt, 8)) * T
    mt_layout = (min(max_m, a8.shape[0]) + T - 1) // T
    jobs, layout = [], []
    for mi in range(0, a8.shape[0], max_m):
        a_chunk = a8[mi : mi + max_m]
        row = []
        for nj in range(0, b8.shape[0], max_n):
            b_chunk = b8[nj : nj + max_n]
            frag = gemm_fragment(b_chunk, mt_layout)
            jobs.append(
                SimJob(frag, pack_gemm_data(frag, a_chunk), read_gemm_full(frag),
                       (slice(0, a_chunk.shape[0]), slice(0, b_chunk.shape[0])))
            )
            row.append(len(jobs) - 1)
        layout.append(row)

    def assemble(outs):
        out32 = np.concatenate(
            [np.concatenate([outs[i] for i in row], axis=1) for row in layout],
            axis=0,
        ).astype(np.float64)
        out = out32 * sa * sb
        ctx.record("vta_gemm", "vta", out, ideal, ctx.ncmds(jobs))
        return out.astype(np.float32)

    return jobs, assemble


def plan_add(ctx, x, args):
    a, b = args
    # elementwise adds stay in the accumulator's wide fixed point; the
    # driver scales both operands onto a shared int grid
    s = max(np.abs(a).max(), np.abs(b).max(), 1e-9) / (2 ** 20)
    ai = np.round(np.broadcast_to(a, np.broadcast_shapes(a.shape, b.shape)) / s)
    bi = np.round(np.broadcast_to(b, ai.shape) / s)
    a2 = ai.reshape(-1, ai.shape[-1]) if ai.ndim > 1 else ai.reshape(1, -1)
    b2 = bi.reshape(a2.shape)
    ct = (a2.shape[1] + T - 1) // T
    max_r = max(1, (N_ACC // 2) // ct) * T
    jobs = []
    for ri in range(0, a2.shape[0], max_r):
        ac, bc = a2[ri : ri + max_r], b2[ri : ri + max_r]
        rt = (ac.shape[0] + T - 1) // T
        frag = alu_fragment(rt, ct, "add")
        jobs.append(
            SimJob(frag, pack_alu_data(frag, ac, bc), read_alu_full(frag),
                   (slice(0, ac.shape[0]), slice(0, ac.shape[1])))
        )

    def assemble(outs):
        out = (np.concatenate(outs, axis=0) * s).reshape(ai.shape).astype(np.float32)
        ctx.record("vta_add", "vta", out, np.asarray(a) + np.asarray(b),
                   ctx.ncmds(jobs))
        return out

    return jobs, assemble


def plan_relu(ctx, x, args):
    (a,) = args
    s = max(np.abs(a).max(), 1e-9) / (2 ** 20)
    ai = np.round(a / s)
    a2 = ai.reshape(-1, ai.shape[-1]) if ai.ndim > 1 else ai.reshape(1, -1)
    ct = (a2.shape[1] + T - 1) // T
    max_r = max(1, (N_ACC // 2) // ct) * T
    jobs = []
    for ri in range(0, a2.shape[0], max_r):
        ac = a2[ri : ri + max_r]
        rt = (ac.shape[0] + T - 1) // T
        frag = alu_fragment(rt, ct, "relu")
        jobs.append(
            SimJob(frag, pack_alu_data(frag, ac), read_alu_full(frag),
                   (slice(0, ac.shape[0]), slice(0, ac.shape[1])))
        )

    def assemble(outs):
        out = (np.concatenate(outs, axis=0) * s).reshape(a.shape).astype(np.float32)
        ctx.record("vta_relu", "vta", out, np.maximum(a, 0), ctx.ncmds(jobs))
        return out

    return jobs, assemble


def _sample_gemm(r):
    M, K, N = int(r.integers(1, 21)), int(r.integers(1, 41)), int(r.integers(1, 21))
    return [
        r.integers(-120, 120, (M, K)).astype(np.float32),
        r.integers(-120, 120, (N, K)).astype(np.float32),
    ], {}


def _sample_add(r):
    R, C = int(r.integers(1, 21)), int(r.integers(1, 25))
    return [
        r.standard_normal((R, C)).astype(np.float32),
        r.standard_normal((R, C)).astype(np.float32),
    ], {}


def _sample_relu(r):
    R, C = int(r.integers(1, 21)), int(r.integers(1, 25))
    return [r.standard_normal((R, C)).astype(np.float32)], {}


def _vt2(dim_t, dim_d):
    a = ir.Var("a", (dim_t, dim_d))
    w = ir.Var("w", (dim_d, dim_d))
    return [
        VT2Case(
            "vta-gemm",
            ir.dense(a, w),
            ir.call("vta_gemm", a, w),
            {"a": (dim_t, dim_d), "w": (dim_d, dim_d)},
        ),
    ]


def _vt3_gemm(n: int = 3, seed: int = 0):
    """VTA ILA GEMM vs the int8_gemm Pallas kernel: exact equality."""
    from ..kernels import ops as kops

    rng = np.random.default_rng(seed)
    worst = 0.0
    for _ in range(n):
        a = rng.integers(-100, 100, (24, 48)).astype(np.float32)
        b = rng.integers(-100, 100, (20, 48)).astype(np.float32)
        cmds, rd = build_gemm_fragment(a, b)
        ila_out = np.asarray(rd(vta.simulate(cmds)))
        kern_out = np.asarray(
            kops.int8_gemm(jnp.asarray(a, jnp.int8), jnp.asarray(b, jnp.int8))
        ).astype(np.float32)
        worst = max(worst, float(np.abs(ila_out - kern_out).max()))
    return worst == 0.0, worst


def _mapping_cases(rng):
    def gemm_case():
        a = rng.integers(-100, 100, (16, 64)).astype(np.float32)
        b = rng.integers(-100, 100, (16, 64)).astype(np.float32)
        cmds, rd = build_gemm_fragment(a, b)
        out = rd(vta.simulate(cmds))
        return a @ b.T, out

    return [("GEMM", gemm_case)]


COSTS = CostModel("vta", cycles_per_command=1.0)


def _numel(shapes):
    return int(np.prod(np.broadcast_shapes(*shapes))) if shapes else 1


@COSTS.op("vta_gemm")
def _cost_gemm(attrs, shapes):
    (m, k), (n, _) = shapes[0], shapes[1]
    setup = -(-n * k // T) + 4          # weight tiles resident in wgt SRAM
    data = m * -(-k // T) + 4           # activation tile stream + launch
    moved = 4 * (m * k + n * k + m * n)
    return setup + data, moved, m * n * k / (T * T)


def _cost_alu(attrs, shapes):
    n = _numel(shapes)
    ops = len(shapes)                   # one tile stream per operand
    return ops * -(-n // T) + 4, 4 * (ops + 1) * n, n / T


COSTS.op("vta_add")(_cost_alu)
COSTS.op("vta_relu")(_cost_alu)


TARGET.add_intrinsic(Intrinsic(
    "vta_gemm", planner=plan_gemm, kernel=kernel_gemm, sample=_sample_gemm,
    tol=0.02, doc="tiled int8 GEMM on the 16x16 core"))
TARGET.add_intrinsic(Intrinsic(
    "vta_add", planner=plan_add, sample=_sample_add, tol=1e-4,
    doc="vector ALU elementwise add"))
TARGET.add_intrinsic(Intrinsic(
    "vta_relu", planner=plan_relu, sample=_sample_relu, tol=1e-4,
    doc="vector ALU relu (max with 0)"))
TARGET.add_rewrites(_rewrites)
TARGET.add_cost_model(COSTS)
TARGET.add_vt2_cases(_vt2)
TARGET.add_vt3_check("gemm_ila_vs_int8_gemm_kernel", _vt3_gemm)
TARGET.add_mapping_cases(_mapping_cases)
register_target(TARGET)
