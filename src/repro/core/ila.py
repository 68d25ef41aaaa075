"""Instruction-Level Abstraction (ILA) formalism in JAX.

Mirrors ILAng's model (Huang et al., TODAES'18; Figure 6 of the paper):

* an ILA has **architectural state** — named buffers/registers, here a dict
  of arrays (a pytree);
* each **instruction** corresponds to one command at the accelerator's
  interface (an MMIO write in the paper) and is given by a **decode**
  predicate over the command plus a **state-update function**;
* a **program fragment** is a sequence of commands; simulation folds the
  update functions over the fragment — exactly ILAng's auto-generated
  software simulator, but jit-able (``lax.scan`` + ``lax.switch``).

Commands are uniform records so fragments can be stacked into arrays:

    Command(opcode: int, addr: int, data: float32[V])

``V`` is the interface vector width (16 lanes for FlexASR, like the real
128-bit MMIO payload of Figure 1). Wide tensors are moved one V-lane row per
command — faithfully reproducing the granularity mismatch between IR tensors
and accelerator interface commands that D2A is designed to bridge.

Fragment-compiler fast path
---------------------------

Per-sample co-simulation is throughput-bound by three costs the paper's
compiled-simulator approach (ILAng generates C++ rather than interpreting)
avoids: re-deriving the command stream, host-side re-packing, and jit
retracing per distinct stream length. This module provides:

* ``PackedStream``    — a command stream as dense host arrays (no per-command
  Python objects on the hot path);
* a reserved ``NOP`` instruction, auto-registered on every ILA, so streams
  pad to power-of-two **length buckets** (bounding retraces to O(log max_len)
  per accelerator);
* ``ILA.simulate_packed`` / ``ILA.simulate_batch`` — bucketed single-stream
  and ``jax.vmap``-batched simulation over stacked command streams;
* ``CompiledFragment`` — a *setup* stream (weight/config load) simulated once
  and cached as architectural state, so steady-state invocations only pack
  and simulate the per-sample *data* stream;
* ``FragmentCache``   — an LRU keyed on (op, operand shapes, params
  fingerprint) holding compiled fragments across Executor invocations.

Pipelined execution support
---------------------------

The batched tiers are split into a **host half** (pure numpy: padding,
stacking, shared-payload detection — safe to run in a pack worker thread,
releases the GIL) and a **dispatch half** (jit lookup + the asynchronous JAX
call, main thread). ``CompiledFragment.prepare_batch``/``run_prepared``
expose the split to the Executor's pipelined engine, which packs group k+1
while group k simulates and materializes results only at assemble barriers.

Mesh sharding
-------------

``set_stream_mesh`` configures a ``jax.sharding.Mesh`` over the host's
devices; the dispatch halves then shard the stacked **batch axis** of
``simulate_batch``/``run_data_batch`` with a ``NamedSharding`` (setup state
stays replicated — the runner is a pure pytree-in/out vmap), co-simulating a
fleet of independent streams across all local devices. Batch dims are padded
to a multiple of the mesh size; sharding reorders *placement*, never
numerics, so results stay bit-exact.
"""
from __future__ import annotations

import dataclasses
import functools
import hashlib
import os
import threading
from collections import OrderedDict
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .telemetry import TELEMETRY

State = Dict[str, jnp.ndarray]

# Reserved opcode: identity state update, used only for bucket padding. No
# accelerator model may claim it (they all start their maps at 0x10).
NOP_OPCODE = 0

MIN_BUCKET = 16
MAX_DATA_RUNNERS = 128


def named(fn: Callable, name: str) -> Callable:
    """``fn`` under the Python name ``name``. ``jax.jit`` names the
    compiled module after it (``jit_<name>``), and a profile names every
    device operation by its module, so each jitted simulator says which
    ILA it runs and in which role (``flexasr_data_batch``, ``vta_read``)."""

    @functools.wraps(fn)
    def f(*args, **kwargs):
        return fn(*args, **kwargs)

    f.__name__ = f.__qualname__ = name
    return f


def bucket_length(n: int, min_len: int = MIN_BUCKET) -> int:
    """Next power-of-two >= max(n, min_len): the padded stream length."""
    n = max(int(n), min_len)
    return 1 << (n - 1).bit_length()


#: batch-axis bucket ladder for the vmapped simulators. "pow2" (default)
#: pads the batch dimension to the next power of two; "serving" adds the
#: 3/4-of-pow2 steps (1,2,3,4,6,8,12,16,24,32,...) so coalesced
#: cross-request batches — whose sizes are sums of small request batches,
#: rarely near a power of two — waste less replay padding, at the cost of
#: at most one extra trace per octave. Process-wide because traced batch
#: runners are cached per padded shape.
_BATCH_LADDER = "pow2"


def set_batch_ladder(mode: str = "pow2") -> str:
    """Select the batch-axis bucket ladder ("pow2" or "serving"); returns
    the previous mode so callers (the serving layer) can restore it.
    Padding replays the last stream and callers slice [:B], so the ladder
    never changes results — only padded shapes (and hence retraces)."""
    global _BATCH_LADDER
    assert mode in ("pow2", "serving"), f"unknown batch ladder {mode!r}"
    prev = _BATCH_LADDER
    _BATCH_LADDER = mode
    return prev


def batch_bucket(n: int) -> int:
    """Padded batch size for ``n`` streams under the active ladder."""
    p = bucket_length(n, min_len=1)
    if _BATCH_LADDER == "serving" and p >= 4 and n <= (3 * p) // 4:
        return (3 * p) // 4
    return p


# --------------------------------------------------------------------------
# Stream mesh: shard the stacked batch axis over the host's devices
# --------------------------------------------------------------------------

#: process-wide mesh over which batched simulation shards its stream axis
#: (None = single-device dispatch, the default)
_STREAM_MESH: Optional["jax.sharding.Mesh"] = None


def set_stream_mesh(spec: Any = "auto") -> Optional["jax.sharding.Mesh"]:
    """Configure batch-axis sharding for ``simulate_batch``/``run_data_batch``.

    ``spec`` is ``None``/``"off"`` (disable), ``"auto"`` (all local devices),
    an int (first N devices), or a 1-D ``jax.sharding.Mesh``. Returns the
    active mesh, or None when the host has a single device (sharding would
    be a no-op, so it is disabled rather than building a trivial mesh).
    Start the process with ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
    to expose N virtual devices on a CPU-only host.
    """
    global _STREAM_MESH
    if spec is None or spec == "off":
        _STREAM_MESH = None
        return None
    if isinstance(spec, jax.sharding.Mesh):
        _STREAM_MESH = spec
        return _STREAM_MESH
    devs = jax.devices()
    if spec != "auto":
        devs = devs[: int(spec)]
    if len(devs) <= 1:
        _STREAM_MESH = None
        return None
    _STREAM_MESH = jax.sharding.Mesh(np.array(devs), ("stream",))
    return _STREAM_MESH


def stream_mesh() -> Optional["jax.sharding.Mesh"]:
    return _STREAM_MESH


def mesh_pad(n: int) -> int:
    """Round a padded batch size up to a multiple of the stream mesh size
    (identity without a mesh), so the NamedSharding divides evenly."""
    if _STREAM_MESH is None:
        return n
    m = int(_STREAM_MESH.devices.size)
    return -(-n // m) * m


def _shard_batched(x: np.ndarray):
    """Device-put a batch-leading array with its axis 0 sharded over the
    stream mesh; plain ``jnp.asarray`` without a mesh."""
    if _STREAM_MESH is None:
        return jnp.asarray(x)
    sh = jax.sharding.NamedSharding(
        _STREAM_MESH, jax.sharding.PartitionSpec("stream")
    )
    return jax.device_put(x, sh)


def shard_streams(fn: Callable, n_batched: int, n_shared: int,
                  mesh: Optional["jax.sharding.Mesh"]) -> Callable:
    """Run ``fn`` — whose first ``n_batched`` operands are batch-leading
    and whose remaining ``n_shared`` are shared — on each device's slice of
    the batch under ``shard_map`` over ``mesh`` (identity for None). XLA
    partitions plain computations by itself, but cannot partition a Pallas
    (Mosaic) kernel: a runner built on one must be sharded this way.
    Per-sample work is independent, so results equal the unsharded ones."""
    if mesh is None:
        return fn
    P = jax.sharding.PartitionSpec
    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=(P("stream"),) * n_batched + (P(),) * n_shared,
        out_specs=P("stream"), check_vma=False,
    )


def _replicated(tree):
    """Replicate an unbatched pytree (setup state, shared payload rows)
    across the stream mesh; identity without a mesh."""
    if _STREAM_MESH is None:
        return tree
    sh = jax.sharding.NamedSharding(_STREAM_MESH, jax.sharding.PartitionSpec())
    return jax.device_put(tree, sh)


@dataclasses.dataclass(frozen=True)
class Command:
    opcode: int
    addr: int = 0
    data: Tuple[float, ...] = ()

    def as_arrays(self, vwidth: int):
        d = np.zeros((vwidth,), np.float32)
        d[: len(self.data)] = self.data
        return np.int32(self.opcode), np.int32(self.addr), d


@dataclasses.dataclass
class PackedStream:
    """A command stream as dense host arrays: ops (L,), addrs (L,),
    data (L, V). The hot-path representation — builders that pack tensors
    vectorize straight into these instead of materializing Command lists."""

    ops: np.ndarray
    addrs: np.ndarray
    data: np.ndarray

    def __len__(self) -> int:
        return int(self.ops.shape[0])

    @property
    def vwidth(self) -> int:
        return int(self.data.shape[1])

    @staticmethod
    def empty(vwidth: int) -> "PackedStream":
        return PackedStream(
            np.zeros((0,), np.int32), np.zeros((0,), np.int32),
            np.zeros((0, vwidth), np.float32),
        )

    @staticmethod
    def from_commands(cmds: Sequence[Command], vwidth: int) -> "PackedStream":
        ops = np.array([c.opcode for c in cmds], np.int32)
        addrs = np.array([c.addr for c in cmds], np.int32)
        data = np.zeros((len(cmds), vwidth), np.float32)
        for i, c in enumerate(cmds):
            data[i, : len(c.data)] = c.data
        return PackedStream(ops, addrs, data)

    @staticmethod
    def single(opcode: int, addr: int, values: Sequence[float], vwidth: int) -> "PackedStream":
        d = np.zeros((1, vwidth), np.float32)
        vals = np.asarray(values, np.float32)
        d[0, : len(vals)] = vals
        return PackedStream(np.array([opcode], np.int32), np.array([addr], np.int32), d)

    @staticmethod
    def concat(streams: Sequence["PackedStream"]) -> "PackedStream":
        streams = [s for s in streams if len(s)]
        if not streams:
            raise ValueError("concat of empty stream list")
        return PackedStream(
            np.concatenate([s.ops for s in streams]),
            np.concatenate([s.addrs for s in streams]),
            np.concatenate([s.data for s in streams], axis=0),
        )

    def to_commands(self) -> List[Command]:
        """Inverse of from_commands (compat path; not for the hot loop)."""
        return [
            Command(int(o), int(a), tuple(float(v) for v in d))
            for o, a, d in zip(self.ops, self.addrs, self.data)
        ]

    def sig(self) -> Tuple:
        """Batching signature: the command skeleton (opcodes + addresses as
        static values). Mirrors :meth:`DataStream.sig` so fully-packed
        streams — e.g. a fault-campaign mutant whose write instructions no
        longer satisfy the bulk slice-update lowering — group and batch
        through ``simulate_batch`` exactly like compiled data streams."""
        return (
            ("stream",),
            tuple(int(o) for o in self.ops),
            tuple(int(a) for a in self.addrs),
        )

    def padded(self, length: int, nop_opcode: int = NOP_OPCODE) -> "PackedStream":
        """Pad with NOPs to ``length`` (identity updates: semantics-free)."""
        n = len(self)
        if n == length:
            return self
        assert n < length, f"stream length {n} exceeds pad target {length}"
        ops = np.full((length,), nop_opcode, np.int32)
        addrs = np.zeros((length,), np.int32)
        data = np.zeros((length, self.vwidth), np.float32)
        ops[:n], addrs[:n], data[:n] = self.ops, self.addrs, self.data
        return PackedStream(ops, addrs, data)


@dataclasses.dataclass
class BulkWrite:
    """A run of row-write commands at contiguous addresses, targeting one
    state buffer: ``buf[base + i] = rows[i]``. Every data stream in our ILAs
    moves tensors this way (WRITE_V / WR_ACT / WR_DRAM), so the fragment
    compiler lowers the run to ONE ``dynamic_update_slice`` instead of
    scanning len(rows) commands — bit-identical, since contiguous row writes
    at distinct addresses compose to exactly that slice update."""

    buf: str
    base: int
    rows: np.ndarray  # (n, V)
    opcode: int       # the equivalent per-row instruction, for parity streams

    def to_stream(self) -> PackedStream:
        n = self.rows.shape[0]
        return PackedStream(
            np.full((n,), self.opcode, np.int32),
            np.arange(self.base, self.base + n, dtype=np.int32),
            np.asarray(self.rows, np.float32),
        )

    @property
    def sig(self) -> Tuple:
        return (self.buf, self.base, self.rows.shape)


@dataclasses.dataclass
class DataStream:
    """The per-invocation half of a compiled fragment: bulk tensor loads
    plus the irregular tail (config writes + FN_START trigger). The tail is
    scanned (NOP-bucketed); the bulk is applied as slice updates."""

    bulk: List[BulkWrite]
    tail: PackedStream

    def __len__(self) -> int:
        return sum(b.rows.shape[0] for b in self.bulk) + len(self.tail)

    def to_stream(self) -> PackedStream:
        """Full command-stream form (eager simulation / parity checks)."""
        return PackedStream.concat([b.to_stream() for b in self.bulk] + [self.tail])

    def sig(self) -> Tuple:
        """Compilation signature: bulk layout + the tail's *command skeleton*
        (opcodes + addresses as static values). Streams sharing a signature
        differ only in payloads and compile to one executor."""
        return (
            tuple(b.sig for b in self.bulk),
            tuple(int(o) for o in self.tail.ops),
            tuple(int(a) for a in self.tail.addrs),
        )


@dataclasses.dataclass(frozen=True)
class Instruction:
    """One ILA instruction: name + opcode + state-update semantics.

    ``update(state, addr, data) -> state`` must be pure & jit-able.
    ``decode`` defaults to opcode equality (address-map dispatch, like the
    MMIO address decode in Figure 6's ``SetDecode``).
    """

    name: str
    opcode: int
    update: Callable[[State, jnp.ndarray, jnp.ndarray], State]
    doc: str = ""


class ILA:
    """An accelerator (or compiler-IR) ILA model."""

    def __init__(self, name: str, vwidth: int = 16):
        self.name = name
        self.vwidth = vwidth
        self.instructions: List[Instruction] = []
        self._by_opcode: Dict[int, Instruction] = {}
        self._state_init: Dict[str, Callable[[], jnp.ndarray]] = {}
        # compiled-simulator bookkeeping: one trace per distinct bucketed
        # stream length (and per batch shape for the vmapped tier)
        self.n_traces_single = 0
        self.n_traces_batch = 0
        #: opcodes whose payload is per-stream data (a driver's numeric
        #: scales), batched even where a batch's rows happen to agree: the
        #: shared-payload mask keys the compiled runner, and rows that agree
        #: only by chance would compile a new variant mid-serving
        self.per_stream: frozenset = frozenset()
        self.instruction("nop", NOP_OPCODE, "identity update (bucket padding)")(
            lambda st, addr, data: st
        )

    # -- model construction ---------------------------------------------
    def state(self, name: str, init: Callable[[], jnp.ndarray]):
        self._state_init[name] = init

    def instruction(self, name: str, opcode: int, doc: str = ""):
        def deco(fn):
            ins = Instruction(name, opcode, fn, doc)
            self.instructions.append(ins)
            self._by_opcode[opcode] = ins
            return fn

        return deco

    def init_state(self) -> State:
        return {k: f() for k, f in self._state_init.items()}

    # -- simulation --------------------------------------------------------
    def simulate(self, commands: Sequence[Command], state: Optional[State] = None) -> State:
        """Reference (eager, per-command) simulation — the analogue of the
        ILAng-generated sequential C++ simulator."""
        st = dict(state) if state is not None else self.init_state()
        for i, cmd in enumerate(commands):
            ins = self._by_opcode.get(cmd.opcode)
            if ins is None:
                raise self._decode_error(i, cmd.opcode, len(commands))
            _, addr, data = cmd.as_arrays(self.vwidth)
            st = ins.update(st, jnp.asarray(addr), jnp.asarray(data))
        return st

    def _decode_error(self, index: int, opcode: int, n: int) -> RuntimeError:
        """Diagnostic for an undecodable command: names the ILA, the
        offending command's position and opcode, and the nearest registered
        opcodes — a stream-generation bug is debuggable instead of a bare
        KeyError."""
        nearest = sorted(
            self.instructions, key=lambda ins: abs(ins.opcode - opcode)
        )[:4]
        lines = [
            f"  candidate: {ins.name!r} = {ins.opcode:#x} "
            f"(distance {abs(ins.opcode - opcode)})"
            for ins in nearest
        ]
        return RuntimeError(
            f"{self.name}: no instruction decodes opcode {opcode:#x} "
            f"(command {index}/{n}).\n"
            f"  {len(self.instructions)} instructions registered; "
            "nearest opcodes:\n" + "\n".join(lines)
        )

    def pack_program(self, commands: Sequence[Command]):
        ops = np.array([c.opcode for c in commands], np.int32)
        addrs = np.array([c.addr for c in commands], np.int32)
        data = np.zeros((len(commands), self.vwidth), np.float32)
        for i, c in enumerate(commands):
            data[i, : len(c.data)] = c.data
        return jnp.asarray(ops), jnp.asarray(addrs), jnp.asarray(data)

    def _make_step(self):
        """The scan step: lax.switch dispatch on opcode over all updates."""
        instrs = sorted(self.instructions, key=lambda i: i.opcode)
        opcode_to_branch = {ins.opcode: b for b, ins in enumerate(instrs)}
        # dense opcode -> branch lookup table
        max_op = max(opcode_to_branch) + 1
        lut = np.zeros((max_op,), np.int32)
        for op, b in opcode_to_branch.items():
            lut[op] = b
        lut = jnp.asarray(lut)

        branches = []
        for ins in instrs:
            def mk(u):
                def br(operand):
                    st, addr, data = operand
                    return u(st, addr, data)

                return br

            branches.append(mk(ins.update))

        def step(st, cmd):
            op, addr, data = cmd
            st2 = jax.lax.switch(lut[op], branches, (st, addr, data))
            return st2, ()

        return step

    def make_jit_simulator(self):
        """Build a jit-compiled fragment simulator: lax.scan over the packed
        command stream with lax.switch dispatch on opcode.

        All instruction updates must preserve state shapes/dtypes (they do:
        ILA state is fixed architectural state, like hardware registers).
        """
        step = self._make_step()

        def run(state, ops, addrs, data):
            self.n_traces_single += 1  # python side effect: counts traces
            final, _ = jax.lax.scan(step, state, (ops, addrs, data))
            return final

        return jax.jit(named(run, f"{self.name}_stream_single"))

    def make_batch_simulator(self):
        """vmap the scanned simulator over stacked command streams, sharing
        one initial state across the batch (independent fragment sims)."""
        step = self._make_step()

        def run_one(state, ops, addrs, data):
            final, _ = jax.lax.scan(step, state, (ops, addrs, data))
            return final

        def run(state, ops, addrs, data):
            self.n_traces_batch += 1
            return jax.vmap(run_one, in_axes=(None, 0, 0, 0))(state, ops, addrs, data)

        return jax.jit(named(run, f"{self.name}_stream_batch"))

    def simulate_jit(self, commands: Sequence[Command], state: Optional[State] = None) -> State:
        """Jit-compiled simulation; the compiled scan is cached (jax.jit
        retraces only per distinct command-stream length)."""
        st = state if state is not None else self.init_state()
        if not hasattr(self, "_jit_run"):
            self._jit_run = self.make_jit_simulator()
        return self._jit_run(st, *self.pack_program(commands))

    # -- fragment-compiler fast path ------------------------------------
    def simulate_packed(
        self,
        stream: PackedStream,
        state: Optional[State] = None,
        bucket: bool = True,
    ) -> State:
        """Simulate a packed stream, NOP-padded to a power-of-two bucket so
        the jit scan retraces at most O(log max_len) times."""
        st = state if state is not None else self.init_state()
        if bucket:
            stream = stream.padded(bucket_length(len(stream)))
        if not hasattr(self, "_jit_run"):
            self._jit_run = self.make_jit_simulator()
        return self._jit_run(
            st, jnp.asarray(stream.ops), jnp.asarray(stream.addrs), jnp.asarray(stream.data)
        )

    def _host_stream_batch(self, streams: Sequence[PackedStream]):
        """Host half of :meth:`simulate_batch`: NOP-pad to the common length
        bucket, bucket the batch dim (replaying the last stream; a multiple
        of the stream mesh size when one is active) and stack to dense
        arrays. Pure numpy — safe in a pack worker thread."""
        assert streams, "simulate_batch needs at least one stream"
        L = bucket_length(max(len(s) for s in streams))
        B = len(streams)
        Bp = mesh_pad(batch_bucket(B))
        padded = [s.padded(L) for s in streams]
        padded += [padded[-1]] * (Bp - B)
        ops = np.stack([s.ops for s in padded])
        addrs = np.stack([s.addrs for s in padded])
        data = np.stack([s.data for s in padded])
        return ops, addrs, data

    def _dispatch_stream_batch(self, host, state: State) -> State:
        """Dispatch half: jit lookup + the (async) vmapped scan call, with
        the batch axis sharded over the stream mesh when one is active."""
        ops, addrs, data = host
        if not hasattr(self, "_jit_run_batch"):
            self._jit_run_batch = self.make_batch_simulator()
        return self._jit_run_batch(
            _replicated(state), _shard_batched(ops), _shard_batched(addrs),
            _shard_batched(data),
        )

    def simulate_batch(
        self,
        streams: Sequence[PackedStream],
        state: Optional[State] = None,
    ) -> State:
        """Simulate B independent streams (each from the same initial state)
        in one vmapped scan. Streams may have ragged true lengths: all are
        NOP-padded to the common bucket. The batch dimension is bucketed too
        (padding replays the last stream; callers slice [:B]).

        Returns the stacked final state pytree (leading axis = padded batch).
        """
        st = state if state is not None else self.init_state()
        return self._dispatch_stream_batch(self._host_stream_batch(streams), st)

    # -- compiled data-stream execution ---------------------------------
    def _data_runner(self, sig: Tuple, shared_mask: Tuple[bool, ...],
                     batched: bool) -> Callable:
        """Build the jitted executor for one data-stream signature: each
        bulk write lowers to ONE dynamic_update_slice, and the short tail
        *unrolls* with static opcodes — the command skeleton compiles away
        entirely (no per-step lax.switch), which is the compiled-simulator
        analogue of ILAng's generated C++ vs interpreting the command list.
        ``batched`` vmaps it over stacked streams sharing one state.

        ``shared_mask[i]`` marks tail payload rows that are identical across
        a batch: those stay unbatched under vmap, so values derived from
        them (mode/geometry registers) keep scalar batch status and
        FN_START's mode dispatch executes exactly one branch. A batched
        dispatch index would execute every branch at every position.
        """
        bulk_sig, tail_ops, tail_addrs = sig
        updates = [self._by_opcode[op].update for op in tail_ops]
        shared_pos = [i for i, s in enumerate(shared_mask) if s]
        batched_pos = [i for i, s in enumerate(shared_mask) if not s]
        row_src = {}  # position -> (which argument, index within it)
        for k, i in enumerate(shared_pos):
            row_src[i] = ("shared", k)
        for k, i in enumerate(batched_pos):
            row_src[i] = ("batched", k)

        def apply(state, rows_list, shared_data, batched_data):
            st = dict(state)
            for (buf, base, _shape), rows in zip(bulk_sig, rows_list):
                st[buf] = jax.lax.dynamic_update_slice(st[buf], rows, (base, 0))
            for i, (update, addr) in enumerate(zip(updates, tail_addrs)):
                which, k = row_src[i]
                row = shared_data[k] if which == "shared" else batched_data[k]
                st = update(st, jnp.int32(addr), row)
            return st

        if not batched:
            def run_single(state, rows_list, shared_data, batched_data):
                self.n_traces_single += 1
                return apply(state, rows_list, shared_data, batched_data)

            return jax.jit(named(run_single, f"{self.name}_data_single"))

        def run_batch(state, rows_list, shared_data, batched_data):
            self.n_traces_batch += 1
            return jax.vmap(apply, in_axes=(None, 0, None, 0))(
                state, rows_list, shared_data, batched_data
            )

        return jax.jit(named(run_batch, f"{self.name}_data_batch"))

    def _run_data_runner(self, sig: Tuple, shared_mask: Tuple[bool, ...],
                         batched: bool, *args) -> State:
        """Call the compiled executor for ``(sig, shared_mask, batched)``,
        building it on a miss. A miss's first call (trace, lower, compile,
        then the async dispatch) runs inside an ``executor.compile`` span."""
        if not hasattr(self, "_data_runners"):
            self._data_runners: "OrderedDict[Tuple, Callable]" = OrderedDict()
        key = (sig, shared_mask, batched)
        run = self._data_runners.get(key)
        if run is not None:
            self._data_runners.move_to_end(key)
            return run(*args)
        run = self._data_runners[key] = self._data_runner(sig, shared_mask, batched)
        # bound the compiled-executor cache: heavily ragged workloads (a
        # distinct operand shape per sample) would otherwise grow it without
        # limit; evicted signatures simply re-trace on next use
        while len(self._data_runners) > MAX_DATA_RUNNERS:
            self._data_runners.popitem(last=False)
        with TELEMETRY.span("executor.compile", kind="data_runner", ila=self.name):
            return run(*args)

    @staticmethod
    def _split_rows(tail_data: np.ndarray, shared_mask: Tuple[bool, ...]):
        shared = [tail_data[i] for i, s in enumerate(shared_mask) if s]
        batched = [tail_data[i] for i, s in enumerate(shared_mask) if not s]
        # keep fixed (possibly 0-length) shapes so the jit signature is stable
        V = tail_data.shape[1] if tail_data.ndim == 2 else 0
        sh = np.stack(shared) if shared else np.zeros((0, V), np.float32)
        ba = np.stack(batched) if batched else np.zeros((0, V), np.float32)
        return sh, ba

    def run_data(self, data: DataStream, state: Optional[State] = None) -> State:
        st = state if state is not None else self.init_state()
        mask = (True,) * len(data.tail)  # single stream: everything "shared"
        shared, batched = self._split_rows(data.tail.data, mask)
        return self._run_data_runner(
            data.sig(), mask, False, st,
            [jnp.asarray(b.rows) for b in data.bulk],
            jnp.asarray(shared), jnp.asarray(batched),
        )

    def _host_data_batch(self, datas: Sequence[DataStream]):
        """Host half of :meth:`run_data_batch`: signature check, batch
        bucketing (a multiple of the stream mesh size when one is active),
        shared-payload detection and payload stacking. Pure numpy — safe in
        a pack worker thread."""
        assert datas, "run_data_batch needs at least one stream"
        sig = datas[0].sig()
        assert all(d.sig() == sig for d in datas), "mixed signatures in one batch"
        B = len(datas)
        Bp = mesh_pad(batch_bucket(B))
        datas = list(datas) + [datas[-1]] * (Bp - B)
        tails = np.stack([d.tail.data for d in datas])          # (Bp, L, V)
        same = np.all(tails == tails[:1], axis=(0, 2))
        if self.per_stream:
            same &= ~np.isin(datas[0].tail.ops, list(self.per_stream))
        shared_mask = tuple(bool(v) for v in same)
        rows_list = [
            np.stack([d.bulk[i].rows for d in datas])
            for i in range(len(sig[0]))
        ]
        shared = tails[0][same]
        batched = tails[:, ~same]
        return sig, shared_mask, rows_list, shared, batched

    def _dispatch_data_batch(self, host, state: State) -> State:
        """Dispatch half: compiled-runner lookup + the (async) vmapped call.
        Batch-leading payloads shard over the stream mesh when one is
        active; setup state and batch-shared rows replicate."""
        sig, shared_mask, rows_list, shared, batched = host
        return self._run_data_runner(
            sig, shared_mask, True, _replicated(state),
            [_shard_batched(r) for r in rows_list],
            _replicated(jnp.asarray(shared)), _shard_batched(batched),
        )

    def run_data_batch(self, datas: Sequence[DataStream], state: Optional[State] = None) -> State:
        """Batched compiled execution of streams sharing one signature (same
        bulk layout and tail command skeleton; payloads differ). The batch
        dim is bucketed to a power of two by replaying the last stream
        (callers slice [:B]). Payload rows that are identical across the
        batch stay unbatched — see :meth:`_data_runner`."""
        st = state if state is not None else self.init_state()
        return self._dispatch_data_batch(self._host_data_batch(datas), st)

    def jit_cache_info(self) -> Dict[str, int]:
        return {
            "traces_single": self.n_traces_single,
            "traces_batch": self.n_traces_batch,
            "data_runners": len(getattr(self, "_data_runners", {})),
        }


# --------------------------------------------------------------------------
# Fragments & mappings (Section 2.1.3)
# --------------------------------------------------------------------------


@dataclasses.dataclass
class Fragment:
    """A program fragment: a sequence of ILA commands for one accelerator
    operation, plus how tensors marshal in/out of architectural state."""

    ila: ILA
    commands: List[Command]

    def __len__(self):
        return len(self.commands)


def fingerprint(*arrays, extra: Tuple = ()) -> str:
    """Content fingerprint of parameter tensors (+ static attrs) — the
    params half of a fragment-cache key. blake2b over dtype/shape/bytes."""
    h = hashlib.blake2b(digest_size=16)
    for a in arrays:
        a = np.ascontiguousarray(a)
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    if extra:
        h.update(repr(extra).encode())
    return h.hexdigest()


@dataclasses.dataclass
class CompiledFragment:
    """A fragment compiled for steady-state reuse.

    ``setup`` is the one-time stream (weight + static-config load) for one
    parameter set; its effect is simulated once and memoized as
    ``setup_state`` — architectural state with weights resident, exactly as
    a real driver leaves the device configured between invocations. Per
    invocation, callers pack only the *data* stream (activation load +
    FN_START) and run it from the cached setup state. ``meta`` carries
    builder-specific constants (exponent biases, layout dims) the data
    packer and read-out need.
    """

    ila: ILA
    key: Tuple
    setup: PackedStream
    meta: Dict[str, Any] = dataclasses.field(default_factory=dict)
    _setup_state: Optional[State] = dataclasses.field(default=None, repr=False)

    def setup_state(self) -> State:
        if self._setup_state is None:
            st = self.ila.init_state()
            if len(self.setup):
                st = self.ila.simulate_packed(self.setup, state=st)
            self._setup_state = st
        return self._setup_state

    def run(self, data: "DataStream | PackedStream") -> State:
        """One invocation: data stream from the cached post-setup state."""
        if isinstance(data, DataStream):
            return self.ila.run_data(data, state=self.setup_state())
        return self.ila.simulate_packed(data, state=self.setup_state())

    def run_batch(self, streams: Sequence["DataStream | PackedStream"]) -> State:
        """Batched invocations sharing this fragment's setup state; returns
        the stacked final state (leading axis covers the padded batch)."""
        return self.run_prepared(self.prepare_batch(streams))

    def prepare_batch(self, streams: Sequence["DataStream | PackedStream"]):
        """Host half of :meth:`run_batch` — padding, stacking and shared-
        payload detection in pure numpy. Safe to run in a pack worker
        thread; hand the result to :meth:`run_prepared` on the dispatch
        thread (the pipelined Executor's pack stage)."""
        if isinstance(streams[0], DataStream):
            return ("data", self.ila._host_data_batch(streams))
        return ("stream", self.ila._host_stream_batch(streams))

    def run_prepared(self, prepared) -> State:
        """Dispatch half of :meth:`run_batch`: resolve the setup state and
        issue the (async) vmapped simulator call for a prepared batch."""
        kind, host = prepared
        st = self.setup_state()
        if kind == "data":
            return self.ila._dispatch_data_batch(host, st)
        return self.ila._dispatch_stream_batch(host, st)

    def clone(self) -> "CompiledFragment":
        """A copy with its own (not yet simulated) setup state: a simulated
        device's instance of the fragment."""
        return CompiledFragment(self.ila, self.key, self.setup, dict(self.meta))

    def full_commands(self, data: "DataStream | PackedStream") -> List[Command]:
        """setup + data as one eager-simulable Command list (parity checks)."""
        stream = data.to_stream() if isinstance(data, DataStream) else data
        if len(self.setup) == 0:
            return stream.to_commands()
        return PackedStream.concat([self.setup, stream]).to_commands()


class FragmentCache:
    """LRU of CompiledFragments keyed by (op, shapes, params fingerprint).

    Thread-safe: the pipelined Executor's pack worker builds fragments while
    the dispatch thread resolves device-local copies, so lookup+insert (and
    the LRU reordering they imply) run under a lock.
    """

    def __init__(self, maxsize: int = 64):
        self.maxsize = maxsize
        self._entries: "OrderedDict[Tuple, CompiledFragment]" = OrderedDict()
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0

    def get(self, key: Tuple, build: Callable[[], CompiledFragment]) -> CompiledFragment:
        with self._lock:
            frag = self._entries.get(key)
            if frag is not None:
                self.hits += 1
                self._entries.move_to_end(key)
                return frag
            self.misses += 1
            frag = build()
            frag.key = key
            self._entries[key] = frag
            while len(self._entries) > self.maxsize:
                self._entries.popitem(last=False)
            return frag

    def clear(self):
        with self._lock:
            self._entries.clear()
            self.hits = 0
            self.misses = 0

    def __len__(self):
        return len(self._entries)

    def __contains__(self, key: Tuple) -> bool:
        return key in self._entries

    def info(self) -> Dict[str, int]:
        return {"size": len(self._entries), "hits": self.hits, "misses": self.misses}


# --------------------------------------------------------------------------
# Fused fast-path tier
# --------------------------------------------------------------------------


def default_platform() -> str:
    """The platform new arrays and computations land on: the
    ``jax.default_device`` in force (a device or a platform name), else the
    default backend. Kernels and fused runners read it when they are
    built, so a replay under ``jax.default_device(jax.devices("cpu")[0])``
    on a TPU host builds CPU lowerings."""
    dev = jax.config.jax_default_device
    if dev is None:
        return jax.default_backend()
    return dev if isinstance(dev, str) else dev.platform


def fused_lowering() -> str:
    """Which lowering a fused runner should build: ``"pallas"`` on the TPU
    (or where ``REPRO_FUSED_PALLAS=1`` forces the Pallas leg, interpret
    mode on the CPU), ``"xla"`` otherwise. ``REPRO_FUSED_FALLBACK=1``
    forces the XLA-fused fallback everywhere — the conformance suite uses
    it so the fallback leg is exercised even on hosts where Pallas lowers
    natively."""
    if os.environ.get("REPRO_FUSED_FALLBACK", "") == "1":
        return "xla"
    if os.environ.get("REPRO_FUSED_PALLAS", "") == "1":
        return "pallas"
    return "pallas" if default_platform() == "tpu" else "xla"


def fused_pad_streams(datas: Sequence["DataStream"]) -> List["DataStream"]:
    """Pad a fused batch exactly like :meth:`ILA._host_data_batch` pads the
    compiled tier's: bucket per the active batch ladder (times the
    stream-mesh size) by replaying the last stream. Keeping the two tiers'
    padding identical bounds retraces the same way and keeps ``[b]`` handle
    indexing aligned."""
    B = len(datas)
    Bp = mesh_pad(batch_bucket(B))
    return list(datas) + [datas[-1]] * (Bp - B)


@dataclasses.dataclass
class FusedRunner:
    """A target-registered fast path for one compiled-fragment family.

    The compiled tier simulates a ``DataStream`` through architectural
    state: ``dynamic_update_slice`` bulk writes into the state buffers, an
    unrolled config tail, the FN_START update, then a read-out slice. A
    ``FusedRunner`` lowers that whole round trip — bulk write + per-sample
    compute + read-out — into one fused computation on the stream payloads
    themselves, skipping state materialization entirely.

    Contract: ``dispatch(prepare(datas))`` must return the stacked
    full-region read of the fragment's output — element ``b`` equal (within
    the owning intrinsic's declared tolerance; bit-exact where the numerics
    round-trip exactly) to ``read(frag.run(datas[b]))`` for the planner's
    read function, for every ``b < len(datas)``. Entries past ``len(datas)``
    (bucket padding) are unconstrained. The compiled tier stays the
    bit-exactness oracle — conformance diffs the two on every intrinsic.

    ``prepare`` is the host half (pure numpy — safe on the pipelined
    engine's pack worker thread); ``dispatch`` is the device half and
    should return asynchronously (un-materialized jax arrays), sharding
    batch-leading payloads with :func:`_shard_batched` so ``set_stream_mesh``
    composes. ``read`` optionally pins the planner read function the runner
    fuses; the Executor falls back to the compiled tier when a job's read
    differs.
    """

    name: str
    prepare: Callable[[Sequence["DataStream"]], Any]
    dispatch: Callable[[Any], jnp.ndarray]
    read: Optional[Callable] = None
    lowering: str = "xla"
    #: the Pallas leg was built in interpret mode (CPU backend only)
    interpret: bool = False
    #: replicates the compiled tier's arithmetic step for step, so its
    #: outputs are bit-exact against the oracle rather than within tolerance
    exact: bool = False

    def run(self, datas: Sequence["DataStream"]) -> jnp.ndarray:
        return self.dispatch(self.prepare(datas))


# --------------------------------------------------------------------------
# Target registry (the AcceleratorTarget plugin surface)
# --------------------------------------------------------------------------


class TargetRegistry:
    """Process-wide registry of :class:`~repro.accel.target.AcceleratorTarget`
    plugins. The core compile/codegen/validate layers are written against
    this registry only — they never name a backend. Registering a target
    (``repro.accel.target.register_target``) is the whole integration step:
    its rewrites join flexible matching, its planners join the Executor, its
    declared validation cases join VT1–VT3 and the conformance suite.
    """

    def __init__(self):
        self._targets: "OrderedDict[str, Any]" = OrderedDict()
        self._by_op: Dict[str, Tuple[Any, Any]] = {}

    def register(self, target) -> None:
        for op in target.intrinsics:
            claimed = self._by_op.get(op)
            if claimed is not None and claimed[0].name != target.name:
                raise ValueError(
                    f"intrinsic {op!r} of target {target.name!r} is already "
                    f"claimed by target {claimed[0].name!r}; intrinsic op "
                    "names must be unique across targets"
                )
        self._targets[target.name] = target
        for op, intr in target.intrinsics.items():
            self._by_op[op] = (target, intr)

    def unregister(self, name: str):
        """Remove a registered target (inverse of :meth:`register`).
        Returns the removed target (None if ``name`` was not registered) so
        callers that must leave the registry bit-identical — the fault
        campaign, synthetic-target tests — can reinstate it."""
        target = self._targets.pop(name, None)
        if target is None:
            return None
        for op in target.intrinsics:
            claimed = self._by_op.get(op)
            if claimed is not None and claimed[0] is target:
                del self._by_op[op]
        return target

    def replace(self, target):
        """Swap ``target`` in under an existing registration of the same
        name, preserving registry order and requiring the same intrinsic op
        set (the fault campaign's mutant swap: same accelerator, mutated
        semantics). Returns the displaced target so the caller can swap it
        back, leaving the registry bit-identical."""
        old = self._targets.get(target.name)
        if old is None:
            raise KeyError(
                f"replace: no registered target named {target.name!r}"
            )
        if set(old.intrinsics) != set(target.intrinsics):
            raise ValueError(
                f"replace: target {target.name!r} intrinsic set changed "
                f"({sorted(set(old.intrinsics) ^ set(target.intrinsics))})"
            )
        self._targets[target.name] = target  # same key: order preserved
        for op, intr in target.intrinsics.items():
            self._by_op[op] = (target, intr)
        return old

    def names(self) -> List[str]:
        return list(self._targets)

    def get(self, name: str):
        if name not in self._targets:
            raise KeyError(
                f"unknown accelerator target {name!r}; registered: {self.names()}"
            )
        return self._targets[name]

    def all(self, names: Optional[Sequence[str]] = None) -> List[Any]:
        if names is None:
            return list(self._targets.values())
        return [self.get(n) for n in names]

    def intrinsic(self, op: str) -> Tuple[Any, Any]:
        """(target, intrinsic) owning intrinsic op ``op``; KeyError if none."""
        if op not in self._by_op:
            raise KeyError(f"no registered target declares intrinsic {op!r}")
        return self._by_op[op]

    def has_planner(self, op: str) -> bool:
        entry = self._by_op.get(op)
        return entry is not None and entry[1].planner is not None


#: the process-wide target registry; populated by importing ``repro.accel``
TARGETS = TargetRegistry()
