"""Compiler IR for the D2A flow.

A small, pure (side-effect-free) tensor IR in the spirit of Relay/Glenside:
immutable expression trees with shape inference and a reference interpreter
(the "IR interpreter" used as the validation oracle in the paper, Section
4.4). Expressions are hashable so they can be hash-consed into the e-graph.

Op vocabulary (the subset the paper's mappings and rewrites need):

  dense(x, w)              -- x:(M,K) @ w:(N,K)^T -> (M,N)   (Relay nn.dense)
  bias_add(x, b)           -- broadcast add over last axis
  add / sub / mul / maximum
  relu / sigmoid / tanh / negative
  reshape(x; shape)        -- static target shape
  transpose(x; axes)
  conv2d(x, w; strides, padding)  -- NHWC x, HWIO w (HLSCNN layout)
  im2col(x; kh, kw, sh, sw)       -- NHWC -> (N*OH*OW, KH*KW*C) patches
  windows(x; wh, ww, sh, sw)      -- 2D sliding windows (Glenside `windows`)
  reduce_max(x; axis) / reduce_mean(x; axis) / reduce_sum(x; axis)
  layer_norm(x, g, b; eps)
  softmax(x; axis)
  zeros(; shape) / ones(; shape)
  concat(xs...; axis)
  split_time(x; t)         -- helper for LSTM unrolling patterns
  lstm_cell(x, h, c, wi, wh, b)   -- one LSTM time step (fused gates)
  lstm(x, wi, wh, b)       -- full LSTM over time (the coarse FlexASR op)
  attention(q, k, v; causal) -- scaled dot-product attention (FlexASR op);
                              ``causal`` masks keys after each query's row
  slice(x; axis, begin, end)      -- static slice of one axis
  rms_norm(x, g; eps)             -- x / sqrt(mean(x^2) + eps) * g, last axis
  rope(x; theta, interleaved)     -- rotary embedding of (T, d) at positions
                                     0..T-1 (DeepSeek-V3 de-interleaves first)
  embedding(table, ids)           -- rows of ``table`` at float token ids

Mixture-of-experts routing (host ops; one chip's share of the experts):

  moe_route(logits, bias; top_k, scale, held)
      sigmoid scores, top-k over every expert by score + ``bias``, the
      chosen scores normalised and scaled: the combine weights of the
      experts ``held = (lo, hi)``, (T, hi - lo), zero where not chosen
  moe_gather(x, w; expert, rows)  -- the rows of x routed to held expert
                               column ``expert``, zero rows appended up to a
                               multiple of ``rows`` (ragged: 0..T rows, so
                               the expert's operands take few shapes; shape
                               inference gives the capacity)
  moe_combine(w, y_0, ..., y_n-1) -- each expert's rows scattered back to
                               their tokens, weighted, summed: (T, D)

Accelerator ops (targets of IR-accelerator rewrites; opaque to IR rewrites):

  fasr_linear / fasr_lstm / fasr_maxpool / fasr_meanpool / fasr_layernorm /
  fasr_attention / fasr_store / fasr_load
  hlscnn_conv2d
  vta_gemm / vta_add / vta_relu

The vocabulary above is the *bundled* set. Plugin accelerator targets extend
it at registration time through :func:`register_accel_op`, which attaches a
shape rule and an ideal (fp32 oracle) evaluation rule for each new intrinsic
— shape inference, the interpreter, the e-graph shape analysis and
``accelerator_calls`` all consult the extension table, so a new backend never
needs to edit this module.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .telemetry import TELEMETRY

# --------------------------------------------------------------------------
# Accelerator-op extension registry (the plugin-target hook)
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class AccelOpSpec:
    """How the IR layer understands one plugin accelerator intrinsic.

    ``shape(attrs, child_shapes) -> shape`` and ``ideal(attrs, args) -> array``
    may be None for the bundled vocabulary (whose rules are built in below);
    ``counts`` is False for pass-through data-movement markers (store/load)
    that must not be tallied as accelerator invocations.
    """

    target: str
    shape: Optional[Callable] = None
    ideal: Optional[Callable] = None
    counts: bool = True


_ACCEL_EXT: Dict[str, AccelOpSpec] = {}


def register_accel_op(
    op: str,
    target: str,
    shape_fn: Optional[Callable] = None,
    eval_fn: Optional[Callable] = None,
    counts: bool = True,
) -> Optional[AccelOpSpec]:
    """Register an accelerator intrinsic op for ``target``.

    Makes the op a member of :data:`ACCEL_OPS` (cost model + Executor
    dispatch), attributes it to ``target`` in :func:`accelerator_calls`, and
    — when ``shape_fn``/``eval_fn`` are given — teaches shape inference and
    the ideal interpreter its semantics. Returns the spec this registration
    displaced (None for a first registration), so a transient re-registration
    — the fault campaign's mutant swap — can restore it exactly.
    """
    prev = _ACCEL_EXT.get(op)
    _ACCEL_EXT[op] = AccelOpSpec(target, shape_fn, eval_fn, counts)
    ACCEL_OPS.add(op)
    return prev


def unregister_accel_op(op: str) -> Optional[AccelOpSpec]:
    """Inverse of :func:`register_accel_op` (synthetic-target and mutant
    cleanup). Returns the removed spec (None if ``op`` was unknown) so the
    caller can later :func:`restore_accel_op` it, leaving the extension
    table bit-identical."""
    spec = _ACCEL_EXT.pop(op, None)
    if spec is not None:
        ACCEL_OPS.discard(op)
    return spec


def restore_accel_op(op: str, spec: Optional[AccelOpSpec]) -> None:
    """Reinstate the exact spec object a register/unregister displaced
    (``spec=None`` removes the op). With :func:`unregister_accel_op`'s
    return value this makes transient registrations — fault-campaign mutant
    swaps, synthetic test targets — leave the table bit-identical."""
    if spec is None:
        unregister_accel_op(op)
    else:
        _ACCEL_EXT[op] = spec
        ACCEL_OPS.add(op)


def accel_op_shape_fn(op: str) -> Optional[Callable]:
    spec = _ACCEL_EXT.get(op)
    return spec.shape if spec is not None else None


def accel_op_target(op: str) -> Optional[str]:
    """The target an intrinsic op invokes, or None for non-invoking ops."""
    spec = _ACCEL_EXT.get(op)
    if spec is not None:
        return spec.target if spec.counts else None
    return _BUILTIN_TRIGGER.get(op)


# --------------------------------------------------------------------------
# Expressions
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Expr:
    """Base class; all exprs are immutable and hashable."""


@dataclasses.dataclass(frozen=True)
class Var(Expr):
    name: str
    shape: Tuple[int, ...]
    dtype: str = "float32"

    def __repr__(self):
        return f"%{self.name}"


@dataclasses.dataclass(frozen=True)
class Const(Expr):
    """Scalar/small constant embedded in the program (by value)."""

    value: float

    def __repr__(self):
        return f"{self.value}"


@dataclasses.dataclass(frozen=True)
class Call(Expr):
    op: str
    args: Tuple[Expr, ...]
    attrs: Tuple[Tuple[str, Any], ...] = ()

    # A program is a DAG: a layer's output feeds the next layer several
    # times, so the generated recursive hash and equality would revisit
    # shared subtrees once per path, exponentially in depth. The hash is
    # computed once per node (not pickled: string hashes are salted per
    # process), and equality stops at identity or a hash mismatch.
    def __hash__(self):
        h = self.__dict__.get("_hash")
        if h is None:
            h = hash((self.op, self.args, self.attrs))
            object.__setattr__(self, "_hash", h)
        return h

    def __eq__(self, other):
        if self is other:
            return True
        if not isinstance(other, Call) or hash(self) != hash(other):
            return False
        return (self.op, self.args, self.attrs) == (other.op, other.args, other.attrs)

    def __getstate__(self):
        state = dict(self.__dict__)
        state.pop("_hash", None)
        return state

    def attr(self, key, default=None):
        for k, v in self.attrs:
            if k == key:
                return v
        return default

    def __repr__(self):
        a = " ".join(repr(x) for x in self.args)
        if self.attrs:
            kv = " ".join(f":{k} {v}" for k, v in self.attrs)
            return f"({self.op} {a} {kv})"
        return f"({self.op} {a})"


def call(op: str, *args: Expr, **attrs) -> Call:
    return Call(op, tuple(args), tuple(sorted(attrs.items())))


# Sugar constructors -------------------------------------------------------

def dense(x, w):
    return call("dense", x, w)


def bias_add(x, b):
    return call("bias_add", x, b)


def add(a, b):
    return call("add", a, b)


def mul(a, b):
    return call("mul", a, b)


def reshape(x, shape):
    return call("reshape", x, shape=tuple(shape))


def conv2d(x, w, strides=(1, 1), padding=(0, 0)):
    return call("conv2d", x, w, strides=tuple(strides), padding=tuple(padding))


# --------------------------------------------------------------------------
# Shape inference
# --------------------------------------------------------------------------


class ShapeError(Exception):
    pass


def _conv_out(h, k, s, p):
    return (h + 2 * p - k) // s + 1


def infer_shape(e: Expr, env: Optional[Dict[str, Tuple[int, ...]]] = None) -> Tuple[int, ...]:
    """Infer the output shape of ``e``. ``env`` overrides Var shapes."""
    memo: Dict[Expr, Tuple[int, ...]] = {}

    def rec(x: Expr) -> Tuple[int, ...]:
        if x in memo:
            return memo[x]
        s = _infer(x, rec, env)
        memo[x] = s
        return s

    return rec(e)


def call_shape(op: str, attrs: Dict[str, Any], child_shapes) -> Tuple[int, ...]:
    """The shape rule of ``op`` over raw operand shapes (the e-graph's
    shape analysis consults it for the ops it does not mirror)."""
    args = tuple(Var(f"_{i}", tuple(s)) for i, s in enumerate(child_shapes))
    return infer_shape(Call(op, args, tuple(sorted(attrs.items()))))


def check_expr(
    e: Expr, env: Optional[Dict[str, Tuple[int, ...]]] = None
) -> Tuple[int, ...]:
    """Pre-codegen static checker: validate shapes and dtypes of every
    sub-expression *before* any planner or simulator touches the program.

    Walks ``e`` in postorder, shape-checking each node (so the error names
    the innermost inconsistent call, with its operand shapes, instead of
    whatever downstream planner trips first) and verifying that every
    accelerator call targets a registered op and consumes float32 operands
    (the command-stream payload dtype). Returns the program's output shape;
    raises :class:`ShapeError` with per-node context on violation.
    """
    memo: Dict[Expr, Tuple[int, ...]] = {}

    def rec(x: Expr) -> Tuple[int, ...]:
        if x in memo:
            return memo[x]
        s = _infer(x, rec, env)
        memo[x] = s
        return s

    for x in postorder(e):
        if isinstance(x, Var) and x.dtype != "float32":
            raise ShapeError(
                f"check: var %{x.name} has dtype {x.dtype!r}; the IR "
                "carries float32 tensors only"
            )
        if not isinstance(x, Call):
            continue
        if x.op in ACCEL_OPS and accel_op_target(x.op) is None \
                and x.op not in ("fasr_store", "fasr_load"):
            raise ShapeError(
                f"check: accelerator op {x.op!r} has no registered target"
            )
        try:
            shape = rec(x)
        except ShapeError as err:
            arg_shapes = [rec(a) for a in x.args]
            raise ShapeError(
                f"check: {x.op}{tuple(arg_shapes)} "
                f"attrs={dict(x.attrs)}: {err}"
            ) from err
        if any(int(d) <= 0 for d in shape):
            raise ShapeError(
                f"check: {x.op} infers non-positive dimension in {shape}"
            )
    return rec(e)


def _infer(x: Expr, rec, env) -> Tuple[int, ...]:
    if isinstance(x, Var):
        if env and x.name in env:
            return tuple(env[x.name])
        return x.shape
    if isinstance(x, Const):
        return ()
    assert isinstance(x, Call)
    op, args = x.op, x.args
    if op in ("add", "sub", "mul", "maximum"):
        a, b = rec(args[0]), rec(args[1])
        return tuple(np.broadcast_shapes(a, b))
    if op in ("relu", "sigmoid", "tanh", "negative", "softmax"):
        return rec(args[0])
    if op == "dense":
        a, w = rec(args[0]), rec(args[1])
        if a[-1] != w[-1]:
            raise ShapeError(f"dense {a} x {w}")
        return a[:-1] + (w[0],)
    if op == "bias_add":
        return rec(args[0])
    if op == "reshape":
        tgt = tuple(x.attr("shape"))
        src = rec(args[0])
        if int(np.prod(tgt)) != int(np.prod(src)):
            raise ShapeError(f"reshape {src} -> {tgt}")
        return tgt
    if op == "transpose":
        src = rec(args[0])
        axes = x.attr("axes")
        return tuple(src[a] for a in axes)
    if op == "conv2d":
        n, h, w_, c = rec(args[0])
        kh, kw, ci, co = rec(args[1])
        (sh, sw), (ph, pw) = x.attr("strides"), x.attr("padding")
        if ci != c:
            raise ShapeError(f"conv2d channels {c} vs {ci}")
        return (n, _conv_out(h, kh, sh, ph), _conv_out(w_, kw, sw, pw), co)
    if op == "dw_conv2d":
        n, h, w_, c = rec(args[0])
        kh, kw, ci, _ = rec(args[1])
        (sh, sw), (ph, pw) = x.attr("strides"), x.attr("padding")
        return (n, _conv_out(h, kh, sh, ph), _conv_out(w_, kw, sw, pw), c)
    if op == "pad2d":
        n, h, w_, c = rec(args[0])
        ph, pw = x.attr("pad")
        return (n, h + 2 * ph, w_ + 2 * pw, c)
    if op == "im2col":
        n, h, w_, c = rec(args[0])
        kh, kw = x.attr("kh"), x.attr("kw")
        sh, sw = x.attr("sh"), x.attr("sw")
        oh, ow = _conv_out(h, kh, sh, 0), _conv_out(w_, kw, sw, 0)
        return (n * oh * ow, kh * kw * c)
    if op == "windows":
        h, w_ = rec(args[0])
        wh, ww = x.attr("wh"), x.attr("ww")
        sh, sw = x.attr("sh"), x.attr("sw")
        return (_conv_out(h, wh, sh, 0), _conv_out(w_, ww, sw, 0), wh, ww)
    if op in ("reduce_max", "reduce_mean", "reduce_sum"):
        src = rec(args[0])
        ax = x.attr("axis")
        axes = (ax,) if isinstance(ax, int) else tuple(ax)
        axes = tuple(a % len(src) for a in axes)
        return tuple(s for i, s in enumerate(src) if i not in axes)
    if op == "layer_norm":
        return rec(args[0])
    if op == "zeros" or op == "ones":
        return tuple(x.attr("shape"))
    if op == "concat":
        shapes = [rec(a) for a in args]
        ax = x.attr("axis")
        out = list(shapes[0])
        out[ax] = sum(s[ax] for s in shapes)
        return tuple(out)
    if op == "lstm_cell":
        xs, hs = rec(args[0]), rec(args[1])
        return hs
    if op == "lstm":
        xs = rec(args[0])  # (T, B, I)
        wh = rec(args[2])  # (4H, H)
        return (xs[0], xs[1], wh[1])
    if op == "attention":
        q, k, v = rec(args[0]), rec(args[1]), rec(args[2])
        return q[:-1] + (v[-1],)
    if op == "slice":
        src = list(rec(args[0]))
        ax = x.attr("axis") % len(src)
        b, e = x.attr("begin"), x.attr("end")
        if not 0 <= b < e <= src[ax]:
            raise ShapeError(f"slice [{b}:{e}] of axis {ax} of {tuple(src)}")
        src[ax] = e - b
        return tuple(src)
    if op in ("rms_norm", "rope"):
        return rec(args[0])
    if op == "embedding":
        table, ids = rec(args[0]), rec(args[1])
        return ids + table[1:]
    if op == "moe_route":
        logits, lo_hi = rec(args[0]), x.attr("held")
        return logits[:-1] + (lo_hi[1] - lo_hi[0],)
    if op == "moe_gather":
        src, pad = rec(args[0]), x.attr("rows", 1)
        return (-(-src[0] // pad) * pad,) + src[1:]  # capacity: every row
    if op == "moe_combine":
        w, ys = rec(args[0]), [rec(a) for a in args[1:]]
        if len(ys) != w[-1]:
            raise ShapeError(f"moe_combine: {len(ys)} experts, weights {w}")
        return w[:-1] + ys[0][-1:]
    if op == "flatten_window":
        # (OH, OW, WH, WW) -> (OH*OW, WH*WW)
        oh, ow, wh, ww = rec(args[0])
        return (oh * ow, wh * ww)
    # ---- accelerator ops: shapes follow their IR equivalents -------------
    if op == "fasr_linear":
        return _infer(call("bias_add", call("dense", args[0], args[1]), args[2]), rec, env)
    if op == "fasr_lstm":
        return _infer(call("lstm", *args), rec, env)
    if op in ("fasr_maxpool",):
        t = rec(args[0])  # (T, B) rows pooled pairwise over axis 0
        return (t[0] // 2,) + t[1:]
    if op in ("fasr_meanpool",):
        t = rec(args[0])
        return (t[0] // 2,) + t[1:]
    if op == "fasr_layernorm":
        return rec(args[0])
    if op == "fasr_attention":
        return _infer(call("attention", *args), rec, env)
    if op in ("fasr_store", "fasr_load", "vta_store", "vta_load"):
        return rec(args[0])
    if op == "hlscnn_conv2d":
        return _infer(
            call("conv2d", args[0], args[1], strides=x.attr("strides"), padding=x.attr("padding")),
            rec,
            env,
        )
    if op == "vta_gemm":
        return _infer(call("dense", args[0], args[1]), rec, env)
    if op in ("vta_add",):
        a, b = rec(args[0]), rec(args[1])
        return tuple(np.broadcast_shapes(a, b))
    if op in ("vta_relu",):
        return rec(args[0])
    spec = _ACCEL_EXT.get(op)
    if spec is not None and spec.shape is not None:
        return tuple(spec.shape(dict(x.attrs), [rec(a) for a in args]))
    raise ShapeError(f"unknown op {op}")


# --------------------------------------------------------------------------
# Reference interpreter (the "IR interpreter" oracle of Section 4.4)
# --------------------------------------------------------------------------


def _sigmoid(x):
    return 1.0 / (1.0 + jnp.exp(-x))


def _lstm_cell(x, h, c, wi, wh, b):
    """Fused-gate LSTM cell: gates = x@wi^T + h@wh^T + b, order i,f,g,o."""
    gates = x @ wi.T + h @ wh.T + b
    hdim = h.shape[-1]
    i = _sigmoid(gates[..., 0 * hdim : 1 * hdim])
    f = _sigmoid(gates[..., 1 * hdim : 2 * hdim])
    g = jnp.tanh(gates[..., 2 * hdim : 3 * hdim])
    o = _sigmoid(gates[..., 3 * hdim : 4 * hdim])
    c2 = f * c + i * g
    h2 = o * jnp.tanh(c2)
    return h2, c2


def _lstm(xs, wi, wh, b):
    T, B, _ = xs.shape
    H = wh.shape[1]
    h = jnp.zeros((B, H), xs.dtype)
    c = jnp.zeros((B, H), xs.dtype)
    outs = []
    for t in range(T):
        h, c = _lstm_cell(xs[t], h, c, wi, wh, b)
        outs.append(h)
    return jnp.stack(outs)


def _windows2d(x, wh, ww, sh, sw):
    H, W = x.shape
    oh, ow = (H - wh) // sh + 1, (W - ww) // sw + 1
    idx_h = jnp.arange(oh)[:, None, None, None] * sh + jnp.arange(wh)[None, None, :, None]
    idx_w = jnp.arange(ow)[None, :, None, None] * sw + jnp.arange(ww)[None, None, None, :]
    return x[idx_h, idx_w]  # (OH, OW, WH, WW)


def _im2col(x, kh, kw, sh, sw):
    N, H, W, C = x.shape
    oh, ow = (H - kh) // sh + 1, (W - kw) // sw + 1
    cols = []
    for i in range(kh):
        for j in range(kw):
            cols.append(x[:, i : i + oh * sh : sh, j : j + ow * sw : sw, :])
    # (N, OH, OW, KH*KW, C) -> (N*OH*OW, KH*KW*C)
    patches = jnp.stack(cols, axis=3)
    return patches.reshape(N * oh * ow, kh * kw * C)


def _conv2d(x, w, strides, padding):
    import jax.lax as lax

    return lax.conv_general_dilated(
        x,
        w,
        window_strides=strides,
        padding=[(padding[0], padding[0]), (padding[1], padding[1])],
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


def _attention(q, k, v):
    d = q.shape[-1]
    s = (q @ jnp.swapaxes(k, -1, -2)) / jnp.sqrt(jnp.asarray(d, q.dtype))
    p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    return p @ v


# The host ops of a transformer layer run as one compiled program each:
# eagerly, each would be a dozen device dispatches per call.
@jax.jit
def _attention_causal(q, k, v):
    d = q.shape[-1]
    s = (q @ jnp.swapaxes(k, -1, -2)) / jnp.sqrt(jnp.asarray(d, q.dtype))
    tq, tk = s.shape[-2], s.shape[-1]
    s = jnp.where(jnp.arange(tk)[None, :] <= jnp.arange(tq)[:, None], s, -jnp.inf)
    p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    return p @ v


@functools.partial(jax.jit, static_argnames="eps")
def _rms_norm(x, g, eps):
    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)) * g


@functools.partial(jax.jit, static_argnames=("theta", "interleaved"))
def _rope(x, theta, interleaved):
    """Rotary embedding of the rows of ``x`` (T, d) at positions 0..T-1, as
    HF DeepSeek-V3 applies it: with ``interleaved`` the d features are
    first de-interleaved (even ones, then odd ones); then ``x * cos +
    rotate_half(x) * sin`` with frequencies ``theta ** (-2i / d)``."""
    T, d = x.shape
    if interleaved:
        x = x.reshape(T, d // 2, 2).transpose(0, 2, 1).reshape(T, d)
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    f = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([f, f], axis=-1)
    rot = jnp.concatenate([-x[:, d // 2:], x[:, : d // 2]], axis=-1)
    return x * jnp.cos(ang) + rot * jnp.sin(ang)


@functools.partial(jax.jit, static_argnames=("top_k", "scale", "held"))
def _route(logits, bias, top_k, scale, held):
    s = _sigmoid(logits)
    _, top = jax.lax.top_k(s + bias, top_k)
    w = jnp.take_along_axis(s, top, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * scale
    rows = jnp.arange(s.shape[0])[:, None]
    full = jnp.zeros_like(s).at[rows, top].set(w)
    return full[:, held[0]:held[1]]


def _moe_route(logits, bias, top_k, scale, held):
    """DeepSeek-V3's ``noaux_tc`` router with one group: sigmoid scores;
    the experts chosen by top-k of score + correction bias; their scores
    normalised to sum 1 and multiplied by ``scale``. Returns the combine
    weights of the experts ``held``, zero where an expert was not chosen,
    on the host (the gathers and the scatter read them there)."""
    return np.asarray(_route(logits, bias, top_k, scale, tuple(held)))


def routed_rows(w, expert: int) -> np.ndarray:
    """Indices of the tokens routed to held expert column ``expert``: the
    rows whose combine weight there is not zero."""
    return np.flatnonzero(np.asarray(w)[:, expert])


# The row movement of MoE dispatch runs in numpy: its shapes follow the
# routing, and an eager JAX op would compile anew for every row count.


def _moe_gather(x, w, expert: int, pad_to: int) -> np.ndarray:
    """The rows of ``x`` routed to held expert column ``expert``, then zero
    rows up to a multiple of ``pad_to``."""
    rows = np.asarray(x, np.float32)[routed_rows(w, expert)]
    pad = -rows.shape[0] % pad_to
    if pad:
        rows = np.concatenate([rows, np.zeros((pad,) + rows.shape[1:], np.float32)])
    return rows


def _moe_combine(w, ys) -> np.ndarray:
    """Each held expert's rows (padding after them dropped) weighted by
    their combine weights and added at their tokens, expert by expert."""
    w = np.asarray(w, np.float32)
    out = np.zeros((w.shape[0], np.shape(ys[0])[-1]), np.float32)
    for e, y in enumerate(ys):
        idx = routed_rows(w, e)
        out[idx] += np.asarray(y, np.float32)[: len(idx)] * w[idx, e][:, None]
    return out


def _fasr_pool(x, kind):
    """FlexASR temporal pooling: pairwise reduce over axis 0 (window (2,1))."""
    T = x.shape[0]
    pairs = x[: T - T % 2].reshape(T // 2, 2, *x.shape[1:])
    if kind == "max":
        return jnp.max(pairs, axis=1)
    return jnp.mean(pairs, axis=1)


# Accelerator ops interpreted with *ideal* (fp32) semantics here; the
# bit-accurate custom-numerics execution lives in repro.accel.* and is
# compared against this oracle by the validation layer.
def interpret(e: Expr, env: Dict[str, Any], accel_exact: bool = True) -> Any:
    """Evaluate expression ``e`` with variable bindings ``env``.

    accel_exact: interpret accelerator ops with exact fp32 semantics
    (abstract-datatype view, as in the paper's VT2 proofs). The numerics-
    accurate path is provided by repro.core.codegen via the ILA simulators.
    """
    memo: Dict[Expr, Any] = {}

    def rec(x: Expr):
        if x in memo:
            return memo[x]
        v = _eval(x, rec, env)
        memo[x] = v
        return v

    return rec(e)


def _eval(x: Expr, rec, env):
    if isinstance(x, Var):
        if x.name not in env:
            raise KeyError(f"unbound var %{x.name}")
        return jnp.asarray(env[x.name])
    if isinstance(x, Const):
        return jnp.asarray(x.value)
    assert isinstance(x, Call)
    op = x.op
    a = [rec(arg) for arg in x.args]
    if op == "add" or op == "vta_add":
        return a[0] + a[1]
    if op == "sub":
        return a[0] - a[1]
    if op == "mul":
        return a[0] * a[1]
    if op == "maximum":
        return jnp.maximum(a[0], a[1])
    if op == "relu" or op == "vta_relu":
        return jnp.maximum(a[0], 0)
    if op == "sigmoid":
        return _sigmoid(a[0])
    if op == "tanh":
        return jnp.tanh(a[0])
    if op == "negative":
        return -a[0]
    if op == "softmax":
        ax = x.attr("axis", -1)
        e_ = jnp.exp(a[0] - jnp.max(a[0], axis=ax, keepdims=True))
        return e_ / jnp.sum(e_, axis=ax, keepdims=True)
    if op == "dense" or op == "vta_gemm":
        return a[0] @ a[1].T
    if op == "bias_add":
        return a[0] + a[1]
    if op == "reshape":
        return a[0].reshape(x.attr("shape"))
    if op == "transpose":
        return jnp.transpose(a[0], x.attr("axes"))
    if op == "conv2d":
        return _conv2d(a[0], a[1], x.attr("strides"), x.attr("padding"))
    if op == "pad2d":
        ph, pw = x.attr("pad")
        return jnp.pad(a[0], ((0, 0), (ph, ph), (pw, pw), (0, 0)))
    if op == "dw_conv2d":
        import jax.lax as lax

        c = a[0].shape[-1]
        p = x.attr("padding")
        # w: (kh, kw, C, 1) -> depthwise (HWIO with feature groups)
        w = jnp.transpose(a[1], (0, 1, 3, 2)).reshape(a[1].shape[0], a[1].shape[1], 1, c)
        return lax.conv_general_dilated(
            a[0], w, window_strides=x.attr("strides"),
            padding=[(p[0], p[0]), (p[1], p[1])],
            dimension_numbers=("NHWC", "HWIO", "NHWC"), feature_group_count=c,
        )
    if op == "hlscnn_conv2d":
        return _conv2d(a[0], a[1], x.attr("strides"), x.attr("padding"))
    if op == "im2col":
        return _im2col(a[0], x.attr("kh"), x.attr("kw"), x.attr("sh"), x.attr("sw"))
    if op == "windows":
        return _windows2d(a[0], x.attr("wh"), x.attr("ww"), x.attr("sh"), x.attr("sw"))
    if op == "flatten_window":
        oh, ow, wh, ww = a[0].shape
        return a[0].reshape(oh * ow, wh * ww)
    if op == "reduce_max":
        return jnp.max(a[0], axis=x.attr("axis"))
    if op == "reduce_mean":
        return jnp.mean(a[0], axis=x.attr("axis"))
    if op == "reduce_sum":
        return jnp.sum(a[0], axis=x.attr("axis"))
    if op == "layer_norm" or op == "fasr_layernorm":
        eps = x.attr("eps", 1e-5)
        xx = a[0]
        mu = jnp.mean(xx, axis=-1, keepdims=True)
        var = jnp.var(xx, axis=-1, keepdims=True)
        return (xx - mu) / jnp.sqrt(var + eps) * a[1] + a[2]
    if op == "zeros":
        return jnp.zeros(x.attr("shape"))
    if op == "ones":
        return jnp.ones(x.attr("shape"))
    if op == "concat":
        return jnp.concatenate(a, axis=x.attr("axis"))
    if op == "lstm_cell":
        return _lstm_cell(*a)[0]
    if op == "lstm" or op == "fasr_lstm":
        return _lstm(*a)
    if op == "attention" and x.attr("causal"):
        return _attention_causal(*a)
    if op == "attention" or op == "fasr_attention":
        return _attention(*a)
    if op == "slice":
        ax = x.attr("axis") % a[0].ndim
        idx = [slice(None)] * a[0].ndim
        idx[ax] = slice(x.attr("begin"), x.attr("end"))
        return a[0][tuple(idx)]
    if op == "rms_norm":
        return _rms_norm(a[0], a[1], x.attr("eps"))
    if op == "rope":
        return _rope(a[0], x.attr("theta"), x.attr("interleaved", False))
    if op == "embedding":
        return a[0][np.asarray(a[1]).astype(np.int32)]
    if op == "moe_route":
        with TELEMETRY.span("moe.route"):
            return _moe_route(a[0], a[1], x.attr("top_k"), x.attr("scale"),
                              x.attr("held"))
    if op == "moe_gather":
        with TELEMETRY.span("moe.dispatch", op=op):
            return _moe_gather(a[0], a[1], x.attr("expert"), x.attr("rows", 1))
    if op == "moe_combine":
        with TELEMETRY.span("moe.dispatch", op=op):
            return _moe_combine(a[0], a[1:])
    if op == "fasr_linear":
        return a[0] @ a[1].T + a[2]
    if op in ("fasr_store", "fasr_load", "vta_store", "vta_load"):
        return a[0]
    if op == "fasr_maxpool":
        return _fasr_pool(a[0], "max")
    if op == "fasr_meanpool":
        return _fasr_pool(a[0], "mean")
    spec = _ACCEL_EXT.get(op)
    if spec is not None and spec.ideal is not None:
        return spec.ideal(dict(x.attrs), a)
    raise ShapeError(f"interpret: unknown op {op}")


# --------------------------------------------------------------------------
# Traversal helpers
# --------------------------------------------------------------------------


def postorder(e: Expr):
    seen = set()
    out = []

    def rec(x):
        if id(x) in seen:
            return
        seen.add(id(x))
        if isinstance(x, Call):
            for a in x.args:
                rec(a)
        out.append(x)

    rec(e)
    return out


def count_ops(e: Expr, pred: Callable[[Call], bool] = lambda c: True) -> int:
    return sum(1 for x in postorder(e) if isinstance(x, Call) and pred(x))


def accelerator_calls(e: Expr) -> Dict[str, int]:
    """Count accelerator invocations by backend (Table 1 statistic).

    Keys cover every target known to the registry (bundled + plugins), so a
    target that received zero offloads still reports an explicit 0.
    """
    targets = set(_BUILTIN_TRIGGER.values())
    targets.update(s.target for s in _ACCEL_EXT.values())
    out: Dict[str, int] = {t: 0 for t in sorted(targets)}
    for x in postorder(e):
        if isinstance(x, Call):
            t = accel_op_target(x.op)
            if t is not None:
                out[t] += 1
    return out


#: host ops the Executor counts: op -> (counter name, fn(call, operand
#: values) -> the count for one sample)
ROW_COUNTERS: Dict[str, Tuple[str, Callable]] = {
    "moe_gather": ("moe.routed_rows",
                   lambda x, a: len(routed_rows(a[1], x.attr("expert")))),
}

# Bundled intrinsic -> target attribution (pass-through fasr_store/fasr_load
# deliberately absent: data movement is not an invocation).
_BUILTIN_TRIGGER: Dict[str, str] = {
    "fasr_linear": "flexasr",
    "fasr_lstm": "flexasr",
    "fasr_maxpool": "flexasr",
    "fasr_meanpool": "flexasr",
    "fasr_layernorm": "flexasr",
    "fasr_attention": "flexasr",
    "hlscnn_conv2d": "hlscnn",
    "vta_gemm": "vta",
    "vta_add": "vta",
    "vta_relu": "vta",
}

#: Mutable: plugin targets extend this via :func:`register_accel_op`.
ACCEL_OPS = set(_BUILTIN_TRIGGER) | {"fasr_store", "fasr_load"}
